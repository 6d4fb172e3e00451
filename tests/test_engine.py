"""Tests for the batched ChainEngine: get_many/serve_many equivalence with
sequential gets, deliver_many, and the Pallas managed-WQ backend vs the
interpreter oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import isa, machine, programs
from repro.core.engine import ChainEngine


# --- deliver_many ------------------------------------------------------------

def test_deliver_many_matches_stacked_deliver():
    srv = programs.build_recycled_get_server(n_buckets=8, val_len=2)
    payloads = np.asarray([[k, srv.bucket_addr(srv.h1(k))]
                           for k in (1, 2, 3)], np.int32)
    batch = machine.deliver_many(srv.state, srv.loop_wq, payloads)
    for i, p in enumerate(payloads):
        ref = machine.deliver(srv.state, srv.loop_wq, list(p))
        for got, want in zip(jax.tree_util.tree_leaves(
                jax.tree_util.tree_map(lambda a: a[i], batch)),
                jax.tree_util.tree_leaves(ref)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_deliver_many_rejects_oversized_payload():
    srv = programs.build_recycled_get_server(n_buckets=8, val_len=2)
    bad = np.zeros((2, isa.MSG_WORDS + 1), np.int32)
    with pytest.raises(ValueError):
        machine.deliver_many(srv.state, srv.loop_wq, bad)


# --- get_many == N sequential get() -----------------------------------------

@pytest.mark.parametrize("parallel", [True, False])
def test_hash_get_many_matches_sequential(parallel):
    off = programs.build_hash_lookup(n_buckets=16, val_len=2,
                                     parallel=parallel)
    for k in (3, 5, 7, 7 + off.n_buckets):
        off.insert(k, [k * 10, k * 10 + 1])
    keys = [3, 4, 5, 7, 7 + off.n_buckets, 1000, 3]   # hits, misses, repeat
    seq = [off.get(k)[0].tolist() for k in keys]
    vals, out = off.get_many(keys)
    assert vals.tolist() == seq
    # every row ran an independent machine: response counters all advanced
    assert np.asarray(out.responses).shape == (len(keys),)


@pytest.mark.parametrize("use_break", [False, True])
def test_list_get_many_matches_sequential(use_break):
    off = programs.build_list_traversal(n_iters=6, val_len=2,
                                        use_break=use_break)
    off.set_list([(20 + i, [i, i * 3]) for i in range(6)])
    keys = [20, 23, 999, 25, 20]
    seq = [off.get(k)[0].tolist() for k in keys]
    vals, _ = off.get_many(keys)
    assert vals.tolist() == seq


def test_recycled_serve_many_matches_sequential_with_laps():
    """serve_many streams through *persistent* state: values AND on-chain
    lap counters must match N sequential serve() calls exactly."""
    a = programs.build_recycled_get_server(n_buckets=16, val_len=2)
    b = programs.build_recycled_get_server(n_buckets=16, val_len=2)
    for srv in (a, b):
        for k in range(1, 6):
            srv.insert(k, [k * 7, k * 7 + 1])
        srv.load()
    keys = [1, 9, 2, 3, 9, 5, 1]                      # mixed hit/miss
    seq = [a.serve(k).tolist() for k in keys]
    got = b.serve_many(keys).tolist()
    assert got == seq
    laps_a = int(np.asarray(a.state.mem)[a.laps_addr])
    laps_b = int(np.asarray(b.state.mem)[b.laps_addr])
    assert laps_a == laps_b == len(keys)
    np.testing.assert_array_equal(np.asarray(a.state.mem),
                                  np.asarray(b.state.mem))


def test_recycled_serve_many_then_serve_continues():
    """The batch leaves the loop re-armed: a later single serve works."""
    srv = programs.build_recycled_get_server(n_buckets=16, val_len=2)
    srv.insert(3, [33, 34])
    srv.load()
    assert srv.serve_many([5, 3, 6]).tolist() == [[0, 0], [33, 34], [0, 0]]
    assert srv.serve(3).tolist() == [33, 34]


# --- Pallas managed-WQ backend vs interpreter oracle ------------------------

def _recycled_batch(keys):
    srv = programs.build_recycled_get_server(n_buckets=16, val_len=2)
    for k in range(1, 8):
        srv.insert(k, [k * 9, k * 9 + 1])
    srv.load()
    payloads = [srv._payload(int(k)) for k in keys]
    return srv, payloads


def test_pallas_backend_matches_interpreter_recycled_server():
    keys = [1, 12, 3, 7, 15, 2]
    srv, payloads = _recycled_batch(keys)
    eng_i = ChainEngine.for_spec(srv.spec)
    eng_p = ChainEngine.for_spec(srv.spec, "pallas-interpret")
    out_i = eng_i.run_many(srv.state, srv.loop_wq, payloads, 64)
    out_p = eng_p.run_many(srv.state, srv.loop_wq, payloads, 64)
    np.testing.assert_array_equal(np.asarray(out_i.mem),
                                  np.asarray(out_p.mem))
    np.testing.assert_array_equal(np.asarray(out_i.head),
                                  np.asarray(out_p.head))
    np.testing.assert_array_equal(np.asarray(out_i.completions),
                                  np.asarray(out_p.completions))
    np.testing.assert_array_equal(np.asarray(out_i.enable_limit),
                                  np.asarray(out_p.enable_limit))
    np.testing.assert_array_equal(np.asarray(out_i.msg_head),
                                  np.asarray(out_p.msg_head))


def test_pallas_backend_matches_interpreter_straight_line():
    """Single plain WQ (non-managed) chain: atomics incl. return-old,
    plus a client-response SEND (responses counter parity)."""
    from repro.core import assembler
    p = assembler.Program(512)
    x = p.word(5)
    y = p.word(0)
    ret = p.word(0)
    resp = p.word(0)
    wq = p.add_wq(8)
    wq.read(src=x, dst=y)
    wq.add(dst=y, addend=10, ret=ret)
    wq.cas(dst=y, old=15, new=99)
    wq.max_(dst=y, operand=120)
    wq.min_(dst=y, operand=60)
    wq.send(src=y, ln=1, dst_region=resp, target_qp=-1)
    spec, st0 = p.finalize()

    out_i = machine.run(spec, st0, 16)
    eng_p = ChainEngine.for_spec(spec, "pallas-interpret")
    batch = jax.tree_util.tree_map(lambda a: jnp.stack([a] * 3), st0)
    out_p = eng_p.run_batch(batch, 16)
    for r in range(3):
        np.testing.assert_array_equal(np.asarray(out_p.mem[r]),
                                      np.asarray(out_i.mem))
        assert int(out_p.responses[r]) == int(out_i.responses) == 1
        assert int(out_p.steps[r]) == int(out_i.steps)
    assert int(np.asarray(out_i.mem)[ret]) == 5   # ADD returned old value
    assert int(np.asarray(out_i.mem)[resp]) == 60


def test_get_many_empty_batch():
    off = programs.build_hash_lookup(n_buckets=16, val_len=2)
    off.insert(3, [30, 31])
    vals, _ = off.get_many([])
    assert vals.shape == (0, 2)


def test_run_many_gives_fresh_fuel_to_reused_state():
    """A persistent state's cumulative steps counter must not starve a
    later batch (regression: run_many previously inherited it as fuel)."""
    srv = programs.build_recycled_get_server(n_buckets=16, val_len=2)
    for k in range(1, 4):
        srv.insert(k, [k * 9, k * 9 + 1])
    srv.load()
    assert srv.serve(3).tolist() == [27, 28]       # leaves steps > 0
    assert int(np.asarray(srv.state.steps)) > 0
    payloads = [srv._payload(k) for k in (1, 2, 3)]
    want = [[9, 10], [18, 19], [27, 28]]
    for backend in ("interp", "pallas-interpret"):
        out = ChainEngine.for_spec(srv.spec, backend).run_many(
            srv.state, srv.loop_wq, payloads, 16)
        got = np.asarray(out.mem[:, srv.resp_region:
                                 srv.resp_region + 2]).tolist()
        assert got == want, backend
        # steps counts executed WRs identically on both backends
        np.testing.assert_array_equal(np.asarray(out.steps),
                                      [12, 12, 12])


def test_run_batch_fuel_parity_across_backends():
    """run_batch must treat a state's cumulative steps as consumed fuel on
    both backends (regression: pallas granted fresh fori_loop fuel)."""
    srv = programs.build_recycled_get_server(n_buckets=16, val_len=2)
    for k in range(1, 4):
        srv.insert(k, [k * 9, k * 9 + 1])
    srv.load()
    srv.serve(3)                                   # state.steps becomes 12
    payloads = np.asarray([srv._payload(k) for k in (1, 2, 3)], np.int32)
    outs = {}
    for backend in ("interp", "pallas-interpret"):
        eng = ChainEngine.for_spec(srv.spec, backend)
        batch = eng.deliver_many(srv.state, srv.loop_wq, payloads)
        outs[backend] = eng.run_batch(batch, 16)   # only 4 WRs of fuel left
    np.testing.assert_array_equal(np.asarray(outs["interp"].mem),
                                  np.asarray(outs["pallas-interpret"].mem))
    np.testing.assert_array_equal(np.asarray(outs["interp"].steps),
                                  np.asarray(outs["pallas-interpret"].steps))
    assert np.asarray(outs["interp"].steps).tolist() == [16, 16, 16]


def test_run_many_zero_word_payloads_are_delivered():
    """(N, 0) payloads are N empty-message triggers, not an empty batch."""
    off = programs.build_hash_lookup(n_buckets=16, val_len=2)
    out = off.engine.run_many(off.materialize(), off.recv_wq,
                              np.zeros((3, 0), np.int32), 64)
    assert out.mem.shape[0] == 3
    assert np.asarray(out.msg_head[:, off.recv_wq]).tolist() == [1, 1, 1]


def test_pallas_backend_respects_pre_halted_state():
    """A HALTed machine must stay stopped on both backends (regression:
    pallas re-executed WRs and cleared the halted flag)."""
    from repro.core import assembler
    p = assembler.Program(256)
    v = p.word(1)
    wq = p.add_wq(4)
    wq.halt()
    wq.write_imm(dst=v, value=99)
    spec, st0 = p.finalize()
    halted = machine.run(spec, st0, 8)             # executes only HALT
    assert bool(halted.halted) and int(halted.mem[v]) == 1
    batch = jax.tree_util.tree_map(lambda a: jnp.stack([a] * 2), halted)
    for backend in ("interp", "pallas-interpret"):
        out = ChainEngine.for_spec(spec, backend).run_batch(batch, 8)
        assert np.asarray(out.mem[:, v]).tolist() == [1, 1], backend
        assert np.asarray(out.halted).tolist() == [True, True], backend


def test_recycled_get_many_returns_vals_and_state():
    srv = programs.build_recycled_get_server(n_buckets=8, val_len=2)
    srv.insert(2, [5, 6])
    srv.load()
    vals, state = srv.get_many([2, 7, 2])
    assert vals.tolist() == [[5, 6], [0, 0], [5, 6]]
    assert state is srv.state


def test_pallas_backend_rejects_inter_qp_send():
    from repro.core import assembler
    p = assembler.Program(256)
    v = p.word(42)
    wq = p.add_wq(2)
    wq.send(src=v, ln=1, target_qp=0)              # SEND to self
    spec, st0 = p.finalize()
    eng = ChainEngine.for_spec(spec, "pallas-interpret")
    batch = jax.tree_util.tree_map(lambda a: jnp.stack([a]), st0)
    with pytest.raises(ValueError, match="inter-QP SEND"):
        eng.run_batch(batch, 8)


def test_pallas_backend_raises_off_tpu():
    """backend='pallas' is the compiled TPU kernel: on any other backend it
    raises instead of quietly running the kernel in interpret mode."""
    assert jax.default_backend() != "tpu"
    srv = programs.build_recycled_get_server(n_buckets=8, val_len=2)
    with pytest.raises(ValueError, match="pallas-interpret"):
        ChainEngine(srv.spec, backend="pallas")


def test_pallas_backend_rejects_multi_wq_specs():
    off = programs.build_hash_lookup(n_buckets=16, val_len=2)
    with pytest.raises(ValueError):
        ChainEngine(off.spec, backend="pallas-interpret")


def test_engine_for_spec_is_cached():
    srv = programs.build_recycled_get_server(n_buckets=8, val_len=2)
    assert ChainEngine.for_spec(srv.spec) is ChainEngine.for_spec(srv.spec)


def test_pallas_send_validation_keyed_on_image(monkeypatch):
    """Engines are memoized per (spec, backend), so the inter-QP-SEND
    subset check must be keyed on the code-region *image*: after one valid
    image is validated, a different image with the same spec must still be
    scanned (regression: a one-shot boolean skipped it on the compiled TPU
    fast path, silently no-op'ing the SEND)."""
    from repro.core import assembler

    def build(bad):
        p = assembler.Program(320)
        v = p.word(42)
        d = p.word(0)
        wq = p.add_wq(2)
        if bad:
            wq.send(src=v, ln=1, target_qp=0)      # inter-QP SEND to self
        else:
            wq.write(src=v, dst=d)
        return p.finalize()

    spec_good, st_good = build(False)
    spec_bad, st_bad = build(True)
    assert spec_good == spec_bad                   # same spec, two images
    eng = ChainEngine(spec_good, backend="pallas-interpret")
    # simulate the compiled-TPU fast path the old one-shot flag guarded
    # (backend="pallas-interpret" keeps the kernel in interpret mode)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    batch = jax.tree_util.tree_map(lambda a: jnp.stack([a]), st_good)
    out = eng.run_batch(batch, 8)                  # validates the good image
    assert int(np.asarray(out.mem)[0, spec_good.mem_words - 2]) == 42

    bad_batch = jax.tree_util.tree_map(lambda a: jnp.stack([a]), st_bad)
    with pytest.raises(ValueError, match="inter-QP SEND"):
        eng.run_batch(bad_batch, 8)

    # the validated image still runs (the cache keeps keying correctly)
    eng.run_batch(batch, 8)


def test_run_many_accepts_traced_device_payloads():
    """run_many must work on jnp payloads without a host round-trip (the
    sharded serving path delivers traced arrays inside shard_map)."""
    off = programs.build_hash_lookup(n_buckets=16, val_len=2)
    off.insert(3, [30, 31])
    off.insert(5, [50, 51])
    st = off.materialize()
    pays_np = np.asarray([off._payload(k) for k in (3, 5, 9)], np.int32)
    want, _ = off.get_many([3, 5, 9])

    got_state = jax.jit(
        lambda s, p: off.engine.run_many(s, off.recv_wq, p, 256))(
            st, jnp.asarray(pays_np))
    got = np.asarray(got_state.mem[:, off.resp_region:
                                   off.resp_region + off.val_len])
    np.testing.assert_array_equal(got, want)
