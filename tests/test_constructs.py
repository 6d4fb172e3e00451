"""Tests for RedN constructs: if (Fig 4), while (Fig 5/6), recycling (§3.4),
mov emulation (Appendix A), and Table 2 verb budgets."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import assembler, constructs, isa, machine


def build_if(x, y):
    """Fig 4 program: resp = 1 if x == y else 0 (default)."""
    p = assembler.Program(512)
    one = p.word(1)
    resp = p.word(0)
    mod = p.add_wq(4, managed=True, ordering=isa.ORD_DOORBELL)
    ctl = p.add_wq(8)
    refs = constructs.emit_if(ctl, mod, x=x, y=y, then_src=one,
                              then_dst=resp)
    return p, resp, refs


@pytest.mark.parametrize("x,y,want", [(3, 3, 1), (3, 4, 0), (0, 0, 1),
                                      (0xFFFFFF, 0xFFFFFF, 1),
                                      (0xFFFFFF, 0xFFFFFE, 0)])
def test_if_construct(x, y, want):
    p, resp, _ = build_if(x, y)
    spec, st0 = p.finalize()
    out = machine.run(spec, st0, 64)
    assert int(out.mem[resp]) == want


@settings(max_examples=25, deadline=None)
@given(x=st.integers(0, isa.ID_MASK), y=st.integers(0, isa.ID_MASK))
def test_if_matches_python_semantics(x, y):
    p, resp, _ = build_if(x, y)
    spec, st0 = p.finalize()
    out = machine.run(spec, st0, 64)
    assert int(out.mem[resp]) == (1 if x == y else 0)


def test_if_budget_matches_table2():
    """if = 1C + 1A + 3E: WAIT(input) + ENABLE + WAIT(before R3)."""
    p = assembler.Program(512)
    one = p.word(1)
    resp = p.word(0)
    inp = p.add_wq(2)
    trigger = inp.noop()                 # stands in for the input RECV
    mod = p.add_wq(4, managed=True, ordering=isa.ORD_DOORBELL)
    ctl = p.add_wq(8)
    refs = constructs.emit_if(ctl, mod, x=1, y=2, then_src=one,
                              then_dst=resp, wait_for=trigger)
    resp_wq = p.add_wq(4)
    resp_wq.wait_for(refs.cond_wr)       # E3: gate the return WR
    resp_wq.send(src=resp, ln=1, dst_region=resp, target_qp=-1)
    b = p.budget()
    # A: the CAS; E: WAIT(input)+ENABLE+WAIT(R3); C: cond NOOP + the
    # surrounding trigger NOOP and R3 SEND (scaffolding, not the if itself)
    assert b["A"] == 1 and b["E"] == 3 and b["C"] == 3


def search_outcome(keys, x, use_break, max_steps=2048):
    n = len(keys)
    p = assembler.Program(2048)
    resp = p.word(-1 & 0xFFFFFF)
    body = p.add_wq(2 * n + 2)
    ctl = p.add_wq(2 * n + 2)
    mod = p.add_wq(n + 2, managed=True, ordering=isa.ORD_DOORBELL)
    constructs.emit_while_search_unrolled(
        p, body, ctl, mod, n_iters=n, keys=keys, x=x, resp_region=resp,
        resp_payloads=list(range(n)), use_break=use_break)
    spec, st0 = p.finalize()
    out = machine.run(spec, st0, max_steps)
    return int(out.mem[resp]), out


@pytest.mark.parametrize("use_break", [False, True])
def test_while_search_finds_key(use_break):
    keys = [11, 22, 33, 44]
    for x, want in [(11, 0), (33, 2), (44, 3), (99, 16777215)]:
        got, _ = search_outcome(keys, x, use_break)
        assert got == want, (x, want, got, use_break)


def test_while_break_stops_subsequent_iterations():
    """With break, a hit at i stops CASes for i+2.. (Fig 6 semantics)."""
    keys = [5, 6, 7, 8, 9, 10]
    _, out_hit = search_outcome(keys, 6, use_break=True)
    _, out_miss = search_outcome(keys, 99, use_break=True)
    # fewer CAS verbs executed when breaking early
    assert int(out_hit.verb_counts[isa.CAS]) < int(
        out_miss.verb_counts[isa.CAS])


def test_while_nobreak_executes_all_iterations():
    keys = [5, 6, 7, 8, 9, 10]
    _, out = search_outcome(keys, 5, use_break=False)
    assert int(out.verb_counts[isa.CAS]) == len(keys)


@settings(max_examples=20, deadline=None)
@given(st.data())
def test_while_search_matches_python(data):
    n = data.draw(st.integers(1, 6))
    keys = data.draw(st.lists(st.integers(1, 1000), min_size=n, max_size=n,
                              unique=True))
    x = data.draw(st.sampled_from(keys + [1001]))
    use_break = data.draw(st.booleans())
    got, _ = search_outcome(keys, x, use_break)
    want = keys.index(x) if x in keys else 0xFFFFFF
    assert got == want


def test_while_unrolled_budget_matches_table2():
    """per-iteration: 1C + 1A + 3E (Table 2, while/unrolled row)."""
    n = 4
    p = assembler.Program(2048)
    resp = p.word(0)
    body = p.add_wq(2 * n + 2)
    ctl = p.add_wq(2 * n + 2)
    mod = p.add_wq(n + 2, managed=True, ordering=isa.ORD_DOORBELL)
    constructs.emit_while_search_unrolled(
        p, body, ctl, mod, n_iters=n, keys=[1, 2, 3, 4], x=9,
        resp_region=resp, resp_payloads=list(range(n)), use_break=False)
    b = p.budget()
    assert b["A"] == n                 # 1 CAS per iteration
    assert b["C"] == n                 # 1 conditional NOOP per iteration
    assert b["E"] == 3 * n - 1         # 3E per iteration (first gate elided)


def test_recycled_loop_fires_on_match_and_rearms():
    """§3.4: recycled predicate loop with no CPU involvement."""
    p = assembler.Program(1024)
    datum = p.word(7)
    marker = p.word(111)
    hits = p.word(0)
    loop = constructs.emit_recycled_predicate_loop(
        p, data_addr=datum, x=7, then_src=marker, then_dst=hits)
    # initial lap: cond id=0 (placeholder) != 7 packed -> first CAS misses;
    # the refetch READ loads mem[datum]=7 into the cond id, so lap 2 hits.
    spec, st0 = p.finalize()
    out = machine.run(spec, st0, max_steps=64)
    assert int(out.steps) == 64              # nontermination (fuel-bounded)
    assert int(out.mem[hits]) == 111         # the then-WRITE fired

    # change the datum -> predicate false -> then-WRITE stops firing
    st1 = out._replace(mem=out.mem.at[datum].set(8),
                       steps=jnp.zeros((), jnp.int32))
    st1 = machine.run(spec, st1, max_steps=16)   # flush the in-flight lap
    st1 = st1._replace(mem=st1.mem.at[hits].set(0),
                       steps=jnp.zeros((), jnp.int32))
    out2 = machine.run(spec, st1, max_steps=64)
    assert int(out2.steps) == 64                 # still looping...
    assert int(out2.mem[hits]) == 0              # ...but never firing


def test_recycled_budget():
    p = assembler.Program(1024)
    datum = p.word(7)
    constructs.emit_recycled_predicate_loop(
        p, data_addr=datum, x=7, then_src=datum, then_dst=datum)
    b = p.budget()
    # our adaptation: 3C + 2A + 1E (+2 pad NOOPs) per lap; paper: 3C+2A+4E.
    assert b["A"] == 2 and b["E"] == 1 and b["C"] == 3 + 2


# --- CAS-claim (§3.5) --------------------------------------------------------

def _build_claim(cell_value, expect=0, new=42):
    """resp = 1 iff the claim CAS won the cell (expect -> new)."""
    p = assembler.Program(512)
    one = p.word(1)
    resp = p.word(0)
    cell = p.word(cell_value)
    mod = p.add_wq(4, managed=True, ordering=isa.ORD_DOORBELL)
    ctl = p.add_wq(8)
    refs = constructs.emit_cas_claim(ctl, mod, cell=cell, expect=expect,
                                     new=new, then_src=one, then_dst=resp)
    ctl.enable(mod, upto=mod.n_posted)
    return p, resp, cell, refs


@pytest.mark.parametrize("cell_value,won", [(0, True), (7, False),
                                            (42, False)])
def test_cas_claim_branches_on_ownership(cell_value, won):
    """A winning claim swaps the cell and fires the then-branch; a losing
    one leaves both the cell and the conditional untouched."""
    p, resp, cell, _ = _build_claim(cell_value)
    spec, st0 = p.finalize()
    out = machine.run(spec, st0, 64)
    assert int(out.mem[resp]) == (1 if won else 0)
    assert int(out.mem[cell]) == (42 if won else cell_value)


def test_cas_claim_nonzero_expect():
    """expect != 0 also works: the return-old in the cond ctrl reads as
    pack(NOOP, old), which the test-CAS compares against pack(NOOP,
    expect)."""
    for cell_value, won in [(9, True), (10, False)]:
        p, resp, cell, _ = _build_claim(cell_value, expect=9, new=11)
        spec, st0 = p.finalize()
        out = machine.run(spec, st0, 64)
        assert int(out.mem[resp]) == (1 if won else 0)
        assert int(out.mem[cell]) == (11 if won else cell_value)


def test_cas_claim_patched_cell_and_value():
    """The hopscotch-writer usage: cell address and claim value arrive at
    run time through the patch addresses the refs expose."""
    p = assembler.Program(512)
    one = p.word(1)
    resp = p.word(0)
    cell = p.word(0)
    cell_addr_w = p.word(cell)                # "scattered" cell address
    key_w = p.word(1234)
    mod = p.add_wq(4, managed=True, ordering=isa.ORD_DOORBELL)
    drv = p.add_wq(4)
    ctl = p.add_wq(8)
    ctl.wait(drv, 2)                          # patches land first
    refs = constructs.emit_cas_claim(ctl, mod, then_src=one, then_dst=resp)
    ctl.enable(mod, upto=mod.n_posted)
    drv.write(src=cell_addr_w, dst=refs.cell_dst_addr)
    drv.write(src=key_w, dst=refs.new_opb_addr)
    spec, st0 = p.finalize()
    out = machine.run(spec, st0, 64)
    assert int(out.mem[resp]) == 1
    assert int(out.mem[cell]) == 1234


# --- mov emulation (Appendix A) ---------------------------------------------

def test_mov_immediate():
    p = assembler.Program(512)
    r = p.word(0)
    wq = p.add_wq(2)
    constructs.emit_mov_imm(wq, 77, r)
    spec, st0 = p.finalize()
    out = machine.run(spec, st0, 16)
    assert int(out.mem[r]) == 77


def test_mov_indirect():
    p = assembler.Program(512)
    cell = p.word(345)            # the pointee
    r_src = p.word(0)             # register holding &cell
    r_dst = p.word(0)
    p._data_init[r_src] = cell    # r_src := &cell
    mod = p.add_wq(4, managed=True, ordering=isa.ORD_DOORBELL)
    ctl = p.add_wq(4)
    constructs.emit_mov_indirect(ctl, mod, r_src, r_dst)
    spec, st0 = p.finalize()
    out = machine.run(spec, st0, 32)
    assert int(out.mem[r_dst]) == 345


def test_mov_indexed():
    p = assembler.Program(512)
    arr = p.alloc(4, [10, 20, 30, 40])
    r_src = p.word(arr)           # base address
    r_off = p.word(2)             # offset
    r_dst = p.word(0)
    mod = p.add_wq(4, managed=True, ordering=isa.ORD_DOORBELL)
    ctl = p.add_wq(8)
    constructs.emit_mov_indexed(ctl, mod, r_src, r_off, r_dst)
    spec, st0 = p.finalize()
    out = machine.run(spec, st0, 64)
    assert int(out.mem[r_dst]) == 30   # [r_src + r_off] = arr[2]


def test_mov_store_indirect():
    p = assembler.Program(512)
    cell = p.word(0)
    r_val = p.word(55)
    r_ptr = p.word(cell)
    mod = p.add_wq(4, managed=True, ordering=isa.ORD_DOORBELL)
    ctl = p.add_wq(4)
    constructs.emit_mov_store_indirect(ctl, mod, r_val, r_ptr)
    spec, st0 = p.finalize()
    out = machine.run(spec, st0, 32)
    assert int(out.mem[cell]) == 55


@settings(max_examples=15, deadline=None)
@given(vals=st.lists(st.integers(0, 1000), min_size=4, max_size=4),
       off=st.integers(0, 3))
def test_mov_indexed_matches_python(vals, off):
    p = assembler.Program(512)
    arr = p.alloc(4, vals)
    r_src = p.word(arr)
    r_off = p.word(off)
    r_dst = p.word(0)
    mod = p.add_wq(4, managed=True, ordering=isa.ORD_DOORBELL)
    ctl = p.add_wq(8)
    constructs.emit_mov_indexed(ctl, mod, r_src, r_off, r_dst)
    spec, st0 = p.finalize()
    out = machine.run(spec, st0, 64)
    assert int(out.mem[r_dst]) == vals[off]


# --- enable-branch (Calc-verb inequality conditional) -------------------------

def _build_branch(v, threshold):
    """if (v <= thr) then wq_a writes 1 else wq_b writes 2 into resp."""
    p = assembler.Program(512)
    v_w = p.word(v)
    one, two = p.word(1), p.word(2)
    resp = p.word(0)
    wq_a = p.add_wq(2, managed=True, ordering=isa.ORD_DOORBELL,
                    initial_enable=0)
    wq_b = p.add_wq(2, managed=True, ordering=isa.ORD_DOORBELL,
                    initial_enable=0)
    wq_a.write(src=one, dst=resp)
    wq_b.write(src=two, dst=resp)
    mod = p.add_wq(2, managed=True, ordering=isa.ORD_DOORBELL,
                   initial_enable=0)
    ctl = p.add_wq(10, managed=True, ordering=isa.ORD_DOORBELL,
                   initial_enable=99)

    def load(a_addr, b_addr):
        ctl.write(src=v_w, dst=a_addr)
        ctl.write(src=v_w, dst=b_addr)

    constructs.emit_enable_branch(
        ctl, mod, threshold=threshold, then_wq=wq_a.index, then_upto=2,
        else_wq=wq_b.index, else_upto=2, load=load)
    spec, st0 = p.finalize()
    out = machine.run(spec, st0, 64)
    return int(out.mem[resp])


@pytest.mark.parametrize("v,thr,want", [
    (0, 0, 1), (1, 0, 2), (3, 7, 1), (7, 7, 1), (8, 7, 2),
    (0xFFFFFE, 0xFFFFFE, 1), (0xFFFFFD, 3, 2)])
def test_enable_branch_selects_exactly_one_wq(v, thr, want):
    assert _build_branch(v, thr) == want


@settings(max_examples=25, deadline=None)
@given(v=st.integers(0, isa.ID_MASK - 1),
       thr=st.integers(0, isa.ID_MASK - 1))
def test_enable_branch_matches_python(v, thr):
    assert _build_branch(v, thr) == (1 if v <= thr else 2)


# --- displace-move (one hopscotch bubble step) --------------------------------

def test_displace_move_moves_vacates_and_zeroes():
    """One move step over the shared [key, pad, val_ptr] row layout:
    value row copied, key moved, mover CASed to EMPTY, stale row zeroed,
    carries advanced, next WQ released."""
    V, BW = 2, 3
    p = assembler.Program(1024)
    status = p.word(0)
    vals = p.alloc(4 * V, [11, 12, 21, 22, 31, 32, 0, 0], "vals")
    tbl_init = []
    for b, key in enumerate([101, 102, 103, 0]):
        tbl_init += [key, b, vals + b * V]
    table = p.alloc(4 * BW, tbl_init, "table")
    zeros = p.alloc(V, [0] * V)
    cand_w = p.word(table + 1 * BW)     # move bucket 1 ...
    free_w = p.word(table + 3 * BW)     # ... into (empty) bucket 3
    dist_w = p.word(5)
    nxt = p.add_wq(2, managed=True, ordering=isa.ORD_DOORBELL,
                   initial_enable=0)
    done = p.word(0)
    nxt.write_imm(dst=done, value=77)
    ctl = p.add_wq(24, managed=True, ordering=isa.ORD_DOORBELL,
                   initial_enable=99)
    refs = constructs.emit_displace_move(
        ctl, cand_w=cand_w, free_w=free_w, dist_w=dist_w, back=2,
        val_len=V, zeros=zeros, status_addr=status, status_val=4,
        next_wq=nxt.index, next_upto=2)
    spec, st0 = p.finalize()
    out = machine.run(spec, st0, 128)
    mem = np.asarray(out.mem)
    # key + value moved into the free bucket
    assert mem[table + 3 * BW] == 102
    assert mem[vals + 3 * V: vals + 3 * V + V].tolist() == [21, 22]
    # mover vacated, its value row zeroed
    assert mem[table + 1 * BW] == 0
    assert mem[vals + 1 * V: vals + 1 * V + V].tolist() == [0, 0]
    # other buckets untouched
    assert mem[table] == 101 and mem[table + 2 * BW] == 103
    assert mem[vals: vals + V].tolist() == [11, 12]
    # carries advanced, status recorded, next stage released
    assert mem[cand_w] == mem[free_w] == table + 1 * BW
    assert mem[dist_w] == 3
    assert mem[status] == 4
    assert mem[done] == 77
    assert refs.vacate.wq == ctl.index


def test_displace_move_vacate_cas_guards_raced_mover():
    """The vacate CAS re-reads its comparand from the bucket — if the
    resident changed under us the CAS must lose rather than clobber.
    (Single-writer serialization makes this unreachable in the store;
    the construct still guards it.)"""
    V, BW = 1, 3
    p = assembler.Program(512)
    status = p.word(0)
    vals = p.alloc(2 * V, [5, 0])
    table = p.alloc(2 * BW, [9, 0, vals, 0, 1, vals + 1])
    zeros = p.alloc(V, [0])
    cand_w = p.word(table)
    free_w = p.word(table + BW)
    dist_w = p.word(4)
    nxt = p.add_wq(1, managed=True, ordering=isa.ORD_DOORBELL,
                   initial_enable=0)
    nxt.noop()
    ctl = p.add_wq(24, managed=True, ordering=isa.ORD_DOORBELL,
                   initial_enable=99)
    constructs.emit_displace_move(
        ctl, cand_w=cand_w, free_w=free_w, dist_w=dist_w, back=1,
        val_len=V, zeros=zeros, status_addr=status, status_val=4,
        next_wq=nxt.index, next_upto=1)
    # sabotage: swap the resident key after build, before execution —
    # the comparand re-read makes the CAS observe the *new* key, so the
    # vacate still applies to what it read; emulate a racing writer by
    # changing the key between the comparand READ and the CAS instead:
    # overwrite the CAS's patched comparand post-hoc via a stale opa.
    spec, st0 = p.finalize()
    # run up to just after the comparand READ (12 WRs), then mutate
    s = st0
    for _ in range(13):
        s = machine.step(spec, s)
    s = s._replace(mem=s.mem.at[table].set(777))    # racing writer
    out = machine.run(spec, s, 128)
    mem = np.asarray(out.mem)
    # the CAS compared the *old* key against the new resident: no vacate
    assert mem[table] == 777


# --- bucket-vacate (the migrator's tail) --------------------------------------

def _build_vacate(bucket_keys, bucket_rows, target, *, repeats=1, V=2):
    """One doorbell-ordered ctl WQ running ``repeats`` back-to-back
    bucket-vacates of ``table[target]``; returns (spec, st0, probes)."""
    from repro.core import assembler
    BW = 3
    p = assembler.Program(1024)
    n = len(bucket_keys)
    flat = [w for row in bucket_rows for w in row]
    vals = p.alloc(n * V, flat, "vals")
    tbl_init = []
    for b, key in enumerate(bucket_keys):
        tbl_init += [key, b, vals + b * V]
    table = p.alloc(n * BW, tbl_init, "table")
    zeros = p.alloc(V, [0] * V)
    bucket_w = p.word(table + target * BW)
    ctl = p.add_wq(8 * repeats + 2, managed=True,
                   ordering=isa.ORD_DOORBELL, initial_enable=99)
    for _ in range(repeats):
        constructs.emit_bucket_vacate(ctl, bucket_w=bucket_w, val_len=V,
                                      zeros=zeros)
    spec, st0 = p.finalize()
    return spec, st0, (table, vals, BW, V, n)


def _vacate_outcome(spec, st0, backend, max_steps=64):
    from repro.core.engine import ChainEngine
    if backend == "interp":
        return np.asarray(machine.run(spec, st0, max_steps).mem)
    eng = ChainEngine.for_spec(spec, backend)
    batch = jax.tree_util.tree_map(lambda a: jnp.stack([a]), st0)
    return np.asarray(eng.run_batch(batch, max_steps).mem[0])


@pytest.mark.parametrize("backend", ["interp", "pallas-interpret"])
def test_bucket_vacate_already_empty_is_noop(backend):
    """Vacating an EMPTY bucket must leave keys AND value rows untouched:
    the CAS trivially retires 0 -> 0 and the row zeroing rewrites an
    already-zero row (the re-driven-lap idempotency recovery relies on)."""
    keys = [101, 0, 103]
    rows = [[11, 12], [0, 0], [31, 32]]
    spec, st0, (table, vals, BW, V, n) = _build_vacate(keys, rows, target=1)
    mem = _vacate_outcome(spec, st0, backend)
    for b in range(n):
        assert mem[table + b * BW] == keys[b], backend
        assert mem[vals + b * V: vals + (b + 1) * V].tolist() == rows[b]


@pytest.mark.parametrize("backend", ["interp", "pallas-interpret"])
def test_bucket_vacate_double_execution_idempotent(backend):
    """Two back-to-back vacates of a live bucket == one: the second pass
    lands on the EMPTY bucket and is a no-op on keys and value rows."""
    keys = [101, 102, 103]
    rows = [[11, 12], [21, 22], [31, 32]]
    once = _build_vacate(keys, rows, target=1, repeats=1)
    twice = _build_vacate(keys, rows, target=1, repeats=2)
    mem1 = _vacate_outcome(*once[:2], backend)
    mem2 = _vacate_outcome(*twice[:2], backend, max_steps=128)
    table, vals, BW, V, n = once[2]
    # the vacate itself: key retired, row zeroed, neighbours untouched
    assert mem1[table + 1 * BW] == 0
    assert mem1[vals + V: vals + 2 * V].tolist() == [0] * V
    assert mem1[table] == 101 and mem1[table + 2 * BW] == 103
    # second execution changed nothing in the data regions
    t2, v2, *_ = twice[2]
    for b in range(n):
        assert mem2[t2 + b * BW] == mem1[table + b * BW], backend
        np.testing.assert_array_equal(mem2[v2 + b * V: v2 + (b + 1) * V],
                                      mem1[vals + b * V: vals + (b + 1) * V])


@pytest.mark.parametrize("backend", ["interp", "pallas-interpret"])
def test_bucket_vacate_interp_pallas_parity(backend):
    """Both backends agree word-for-word on the whole image (not just the
    data regions) for the empty-bucket no-op run."""
    keys = [7, 0]
    rows = [[70, 71], [0, 0]]
    spec, st0, _ = _build_vacate(keys, rows, target=1)
    ref = _vacate_outcome(spec, st0, "interp")
    got = _vacate_outcome(spec, st0, backend)
    np.testing.assert_array_equal(got, ref)


def test_enable_branch_rejects_id_mask_threshold():
    """threshold+1 must stay inside the 24-bit id space: at ID_MASK the
    packed else-comparand would wrap to 0 and BOTH arms could convert."""
    p = assembler.Program(512)
    mod = p.add_wq(2, managed=True, ordering=isa.ORD_DOORBELL,
                   initial_enable=0)
    ctl = p.add_wq(10, managed=True, ordering=isa.ORD_DOORBELL,
                   initial_enable=99)
    with pytest.raises(ValueError, match="threshold"):
        constructs.emit_enable_branch(
            ctl, mod, threshold=isa.ID_MASK, then_wq=0, then_upto=1,
            else_wq=0, else_upto=1, load=lambda a, b: None)
