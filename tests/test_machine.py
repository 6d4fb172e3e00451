"""Unit tests for the RedN chain VM: verbs, ordering, self-modification."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import assembler, constructs, cost, isa, machine


def run_prog(prog, max_steps=512, before=None):
    spec, state = prog.finalize()
    if before is not None:
        state = before(state)
    out = machine.run(spec, state, max_steps)
    return spec, out


def test_write_imm_and_copy():
    p = assembler.Program(512)
    a = p.alloc(4, [11, 22, 33, 44])
    b = p.alloc(4)
    wq = p.add_wq(4)
    wq.write_imm(dst=b, value=7)
    wq.write(src=a, dst=b + 1, ln=3)
    _, out = run_prog(p)
    got = np.asarray(out.mem[b:b + 4])
    assert got.tolist() == [7, 11, 22, 33]
    assert int(out.steps) == 2


def test_read_and_atomics():
    p = assembler.Program(512)
    x = p.word(5)
    y = p.word(0)
    wq = p.add_wq(8)
    wq.read(src=x, dst=y)                    # y = 5
    wq.add(dst=y, addend=10)                 # y = 15
    wq.max_(dst=y, operand=100)              # y = 100
    wq.min_(dst=y, operand=64)               # y = 64
    wq.cas(dst=y, old=64, new=1)             # y = 1
    wq.cas(dst=y, old=64, new=2)             # fails, y = 1
    _, out = run_prog(p)
    assert int(out.mem[y]) == 1


def test_cas_returns_old_value():
    p = assembler.Program(512)
    x = p.word(42)
    ret = p.word(0)
    wq = p.add_wq(2)
    wq.cas(dst=x, old=42, new=9, ret=ret)
    _, out = run_prog(p)
    assert int(out.mem[x]) == 9 and int(out.mem[ret]) == 42


def test_wait_blocks_until_completion():
    """WQ1 waits for 2 completions on WQ0 before writing."""
    p = assembler.Program(512)
    flag = p.word(0)
    wq0 = p.add_wq(4)
    wq1 = p.add_wq(4)
    wq1.wait(wq0, 2)
    wq1.write_imm(dst=flag, value=1)
    wq0.noop()
    wq0.noop()
    _, out = run_prog(p)
    assert int(out.mem[flag]) == 1
    # WAIT synchronizes the waiter's clock with the producer's completion
    assert float(out.clock[1]) >= float(out.last_comp_time[0]) - 1e-6


def test_wait_never_satisfied_quiesces():
    p = assembler.Program(512)
    flag = p.word(0)
    wq0 = p.add_wq(4)
    wq1 = p.add_wq(4)
    wq1.wait(wq0, 5)           # wq0 only ever completes 1
    wq1.write_imm(dst=flag, value=1)
    wq0.noop()
    _, out = run_prog(p, max_steps=100)
    assert int(out.mem[flag]) == 0
    assert int(out.steps) < 100  # quiesced, not fuel-exhausted


def test_suppressed_completion_starves_wait():
    """The `break` primitive: a WR with SUPPRESS_COMPLETION doesn't count."""
    p = assembler.Program(512)
    flag = p.word(0)
    wq0 = p.add_wq(4)
    wq1 = p.add_wq(4)
    wq1.wait(wq0, 2)
    wq1.write_imm(dst=flag, value=1)
    wq0.noop()
    wq0.noop(signaled=False)
    _, out = run_prog(p)
    assert int(out.mem[flag]) == 0


def test_managed_wq_needs_enable():
    p = assembler.Program(512)
    flag = p.word(0)
    m = p.add_wq(4, managed=True, ordering=isa.ORD_DOORBELL)
    m.write_imm(dst=flag, value=1)
    _, out = run_prog(p)
    assert int(out.mem[flag]) == 0        # never enabled

    p2 = assembler.Program(512)
    flag2 = p2.word(0)
    m2 = p2.add_wq(4, managed=True, ordering=isa.ORD_DOORBELL)
    ctl = p2.add_wq(4)
    m2.write_imm(dst=flag2, value=1)
    ctl.enable(m2, upto=1)
    _, out2 = run_prog(p2)
    assert int(out2.mem[flag2]) == 1


def test_self_modifying_write_rewrites_opcode():
    """A WRITE that edits a later WR's control word (the §3.2 primitive)."""
    p = assembler.Program(512)
    flag = p.word(0)
    new_ctrl = p.word(isa.pack_ctrl(isa.WRITE_IMM, 0))
    mod = p.add_wq(4, managed=True, ordering=isa.ORD_DOORBELL)
    ctl = p.add_wq(4)
    target = mod.post(isa.NOOP, dst=flag, opa=99)   # latent WRITE_IMM 99
    ctl.write(src=new_ctrl, dst=target.ctrl_addr, ln=1)
    ctl.enable(mod, upto=1)
    _, out = run_prog(p)
    assert int(out.mem[flag]) == 99


def test_send_recv_scatter():
    """Client SEND triggers a pre-posted RECV that scatters the payload."""
    p = assembler.Program(512)
    a = p.word(0)
    b = p.word(0)
    tbl = p.scatter_table([a, b])
    wq = p.add_wq(4)
    wq.recv(scatter_table=tbl)
    spec, state = p.finalize()
    state = machine.deliver(state, 0, [123, 456])
    out = machine.run(spec, state, 64)
    assert int(out.mem[a]) == 123 and int(out.mem[b]) == 456


def test_recv_writes_only_the_words_its_table_lists():
    """A RECV writes the words its scatter table lists and no other,
    whatever the payload carries past them; batched, two contexts whose
    RECVs list two and four words each get their own count."""
    p = assembler.Program(512)
    words = [p.word(-1) for _ in range(4)]
    two = p.add_wq(2)
    two.recv(scatter_table=p.scatter_table(words[:2]))
    four = p.add_wq(2)
    four.recv(scatter_table=p.scatter_table(words))
    spec, state = p.finalize()
    payload = [11, 22, 33, 44, 55]
    st2 = machine.deliver(state, two.index, payload)
    st4 = machine.deliver(state, four.index, payload)
    out = machine.run(spec, st2, 64)
    assert [int(out.mem[w]) for w in words] == [11, 22, -1, -1]
    both = machine.run_batch(
        spec, jax.tree.map(lambda a, b: jnp.stack([a, b]), st2, st4), 64)
    assert [[int(m[w]) for w in words] for m in both.mem] == [
        [11, 22, -1, -1], [11, 22, 33, 44]]


def test_send_to_peer_qp():
    p = assembler.Program(512)
    payload = p.alloc(2, [7, 8])
    a = p.word(0)
    b = p.word(0)
    tbl = p.scatter_table([a, b])
    wq0 = p.add_wq(4)
    wq1 = p.add_wq(4)
    wq0.send(src=payload, ln=2, target_qp=1)
    wq1.recv(scatter_table=tbl)
    _, out = run_prog(p)
    assert int(out.mem[a]) == 7 and int(out.mem[b]) == 8


def test_response_send_to_client_region():
    p = assembler.Program(512)
    val = p.word(31337)
    resp = p.word(0)
    wq = p.add_wq(2)
    wq.send(src=val, ln=1, dst_region=resp, target_qp=-1)
    _, out = run_prog(p)
    assert int(out.mem[resp]) == 31337
    assert int(out.responses) == 1


def test_halt_pseudo_verb():
    p = assembler.Program(512)
    wq = p.add_wq(4)
    wq.halt()
    wq.noop()
    _, out = run_prog(p)
    assert bool(out.halted) and int(out.steps) == 1


def test_clock_matches_fig8_ordering_model():
    """Chain of k NOOPs: 1.21 + (k-1)*per-mode-fetch (paper Fig. 8)."""
    for mode, per in [(isa.ORD_WQ, 0.17), (isa.ORD_COMPLETION, 0.19),
                      (isa.ORD_DOORBELL, 0.54)]:
        p = assembler.Program(512)
        wq = p.add_wq(8, ordering=mode)
        for _ in range(5):
            wq.noop()
        _, out = run_prog(p)
        want = 1.21 + 4 * per
        np.testing.assert_allclose(float(out.clock[0]), want, rtol=1e-5)


def test_clock_matches_fig7_verb_latency():
    """Single WRITE = 1.60 us, single READ = 1.80 us (paper Fig. 7)."""
    for emit, want in [(lambda w, a, b: w.write(src=a, dst=b), 1.60),
                       (lambda w, a, b: w.read(src=a, dst=b), 1.80)]:
        p = assembler.Program(512)
        a, b = p.word(1), p.word(0)
        wq = p.add_wq(2)
        emit(wq, a, b)
        _, out = run_prog(p)
        np.testing.assert_allclose(float(out.clock[0]), want, rtol=1e-5)


def test_min_clock_scheduling_interleaves_pus():
    """Two independent WQs execute on parallel PU clocks, not serially."""
    p = assembler.Program(512)
    wq0 = p.add_wq(8)
    wq1 = p.add_wq(8)
    for _ in range(4):
        wq0.noop()
        wq1.noop()
    _, out = run_prog(p)
    t0, t1 = float(out.clock[0]), float(out.clock[1])
    serial = 2 * (1.21 + 3 * 0.17)
    assert max(t0, t1) < serial * 0.75   # parallel, not serial


def test_wq_recycling_wraps_around():
    """A recycled WQ re-executes its WRs (increment a counter many laps)."""
    p = assembler.Program(512)
    counter = p.word(0)
    wq = p.add_wq(2, recycled=True)
    wq.add(dst=counter, addend=1)
    wq.add(dst=counter, addend=1)
    spec, state = p.finalize()
    out = machine.run(spec, state, max_steps=100)
    assert int(out.steps) == 100           # fuel-bounded nontermination (T3)
    assert int(out.mem[counter]) == 100


def test_vmapped_batch_runs_independently():
    import jax
    p = assembler.Program(256)
    x = p.word(0)
    wq = p.add_wq(2)
    wq.add(dst=x, addend=1)
    spec, state = p.finalize()
    batch = jax.tree_util.tree_map(
        lambda a: jnp.stack([a] * 4), state)
    out = machine.run_batch(spec, batch, 16)
    assert np.asarray(out.mem[:, x]).tolist() == [1, 1, 1, 1]
