"""Tests for the paper's offload programs (Figs. 3, 9, 12; §3.4 recycling)."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import isa, machine, programs


# --- Fig. 3: RPC offload -----------------------------------------------------

def test_rpc_echo_data_dependent():
    spec, state, info = programs.build_rpc_echo()
    for arg in [0, 7, 123456]:
        s = machine.deliver(state, info["recv_wq"], [arg])
        out = machine.run(spec, s, 64)
        assert int(out.mem[info["resp"]]) == info["bias"] + arg
        assert int(out.responses) == 1


# --- Fig. 9: hash lookup -----------------------------------------------------

@pytest.mark.parametrize("parallel", [True, False])
def test_hash_lookup_hit_first_bucket(parallel):
    off = programs.build_hash_lookup(n_buckets=16, val_len=4,
                                     parallel=parallel)
    off.insert(5, [50, 51, 52, 53])
    val, out = off.get(5)
    assert val.tolist() == [50, 51, 52, 53]


@pytest.mark.parametrize("parallel", [True, False])
def test_hash_lookup_hit_second_bucket(parallel):
    """Collision: key lands in its h2 bucket (Fig. 11's worst case)."""
    off = programs.build_hash_lookup(n_buckets=16, val_len=2,
                                     parallel=parallel)
    k = 7
    # occupy k's h1 bucket with a different key whose h1 also maps there
    blocker = k + off.n_buckets
    assert off.h1(blocker) == off.h1(k)
    off.insert(blocker, [1, 1])
    assert off.h1(k) in off.kv
    off.insert(k, [70, 71])
    val, _ = off.get(k)
    assert val.tolist() == [70, 71]
    val2, _ = off.get(blocker)
    assert val2.tolist() == [1, 1]


def test_hash_lookup_miss_returns_default():
    off = programs.build_hash_lookup(n_buckets=16, val_len=2)
    off.insert(3, [30, 31])
    val, _ = off.get(4)
    assert val.tolist() == [0, 0]


def test_hash_parallel_faster_than_seq_on_collision():
    """RedN-Parallel probes buckets on independent PUs (Fig. 11)."""
    lat = {}
    for parallel in (True, False):
        off = programs.build_hash_lookup(n_buckets=16, val_len=2,
                                         parallel=parallel)
        k = 7
        blocker = k + off.n_buckets
        off.insert(blocker, [1, 1])
        off.insert(k, [70, 71])        # forced into bucket 2
        val, out = off.get(k)
        assert val.tolist() == [70, 71]
        lat[parallel] = float(machine.total_time_us(out))
    assert lat[True] < lat[False]


@settings(max_examples=15, deadline=None)
@given(st.data())
def test_hash_lookup_matches_dict(data):
    off = programs.build_hash_lookup(n_buckets=32, val_len=2)
    keys = data.draw(st.lists(st.integers(1, 1 << 20), min_size=1,
                              max_size=8, unique=True))
    inserted = {}
    for k in keys:
        if off.insert(k, [k & 0xFFFF, (k >> 4) & 0xFFFF]):
            inserted[k] = [k & 0xFFFF, (k >> 4) & 0xFFFF]
    probe = data.draw(st.sampled_from(keys + [1 << 21]))
    val, _ = off.get(probe)
    want = inserted.get(probe, [0, 0])
    assert val.tolist() == want


# --- Fig. 12: list traversal -------------------------------------------------

@pytest.mark.parametrize("use_break", [False, True])
def test_list_traversal_finds_each_position(use_break):
    off = programs.build_list_traversal(n_iters=8, val_len=2,
                                        use_break=use_break)
    items = [(10 + i, [100 + i, 200 + i]) for i in range(8)]
    off.set_list(items)
    for pos in [0, 3, 7]:
        val, _ = off.get(10 + pos)
        assert val.tolist() == [100 + pos, 200 + pos], (pos, use_break)


@pytest.mark.parametrize("use_break", [False, True])
def test_list_traversal_miss(use_break):
    off = programs.build_list_traversal(n_iters=4, val_len=2,
                                        use_break=use_break)
    off.set_list([(10 + i, [i, i]) for i in range(4)])
    val, _ = off.get(999)
    assert val.tolist() == [0, 0]


def test_list_break_saves_work():
    """§5.3: break stops iterations after the hit (>= 65% fewer WRs when
    the key is early in a long list)."""
    counts = {}
    for use_break in (False, True):
        off = programs.build_list_traversal(n_iters=8, val_len=2,
                                            use_break=use_break)
        off.set_list([(10 + i, [i, i]) for i in range(8)])
        _, out = off.get(10)        # hit at position 0
        counts[use_break] = int(out.steps)
    assert counts[True] < counts[False]


def test_list_break_latency_overhead_on_full_walk():
    """Fig. 13: with the key at the end, +break costs extra latency."""
    lat = {}
    for use_break in (False, True):
        off = programs.build_list_traversal(n_iters=8, val_len=2,
                                            use_break=use_break)
        off.set_list([(10 + i, [i, i]) for i in range(8)])
        val, out = off.get(17)      # hit at last position
        assert val.tolist() == [7, 7]
        lat[use_break] = float(machine.total_time_us(out))
    assert lat[True] > lat[False]


@settings(max_examples=10, deadline=None)
@given(st.data())
def test_list_traversal_matches_python(data):
    n = data.draw(st.integers(2, 8))
    keys = data.draw(st.lists(st.integers(1, 10000), min_size=n, max_size=n,
                              unique=True))
    use_break = data.draw(st.booleans())
    off = programs.build_list_traversal(n_iters=n, val_len=2,
                                        use_break=use_break)
    items = [(k, [k % 97, k % 89]) for k in keys]
    off.set_list(items)
    probe = data.draw(st.sampled_from(keys + [20001]))
    val, _ = off.get(probe)
    want = next((v for k, v in items if k == probe), [0, 0])
    assert val.tolist() == want


# --- §3.4 recycled get server -------------------------------------------------

def test_recycled_server_serves_many_requests_without_rearming():
    srv = programs.build_recycled_get_server(n_buckets=16, val_len=2)
    for k in range(1, 9):
        srv.insert(k, [k * 10, k * 10 + 1])
    srv.load()
    for rounds in range(3):
        for k in range(1, 9):
            val = srv.serve(k)
            assert val.tolist() == [k * 10, k * 10 + 1], (rounds, k)
    # the loop really recycled (laps counted on-chain)
    assert int(np.asarray(srv.state.mem)[srv.laps_addr]) >= 24


def test_recycled_server_miss_then_hit():
    srv = programs.build_recycled_get_server(n_buckets=16, val_len=2)
    srv.insert(3, [33, 34])
    srv.load()
    assert srv.serve(5).tolist() == [0, 0]
    assert srv.serve(3).tolist() == [33, 34]
    assert srv.serve(5).tolist() == [0, 0]   # re-armed after the hit


# --- §5.2/§3.5 hopscotch shard server + writer --------------------------------

def test_hopscotch_server_query_zero_is_a_miss():
    """The get chain's found-flag rows are dynamic (keys != EMPTY): a
    query of 0 CAS-matches an empty bucket but must read back found=0 —
    the static flag-1 rows used to report a ghost hit."""
    import jax.numpy as jnp
    from repro.kvstore import hopscotch
    srv = programs.build_hopscotch_server(32, 2, 8)
    row = int(hopscotch.bucket_of(77, 32))
    keys = jnp.zeros((32,), jnp.int32).at[row].set(77)
    vals = jnp.zeros((32, 2), jnp.int32).at[row].set(jnp.asarray([9, 10]))
    q = jnp.asarray([0, 77, 3], jnp.int32)
    found, v = srv.get_many(keys, vals, q, hopscotch.bucket_of(q, 32))
    assert not bool(found[0]) and (np.asarray(v[0]) == 0).all()
    assert bool(found[1]) and v[1].tolist() == [9, 10]
    assert not bool(found[2])


def test_hopscotch_writer_zero_padded_request_is_inert():
    """A zero-padded receive-window slot (key 0, probe addrs 0) resolves
    against the null guard WQ, reports status 0, and commits nothing."""
    import jax.numpy as jnp
    w = programs.build_hopscotch_writer(32, 2, 8)
    keys = jnp.zeros((32,), jnp.int32).at[4].set(9)
    vals = jnp.zeros((32, 2), jnp.int32).at[4].set(jnp.asarray([1, 2]))
    pay = jnp.zeros((1 + 2 + 8,), jnp.int32)
    st = machine.deliver(w.device_state(keys, vals), w.recv_wq, pay)
    out = w.engine.run(st, 512)
    status, nk, nv = w.commit(out.mem, pay, keys, vals)
    assert int(status) == 0
    np.testing.assert_array_equal(np.asarray(nk), np.asarray(keys))
    np.testing.assert_array_equal(np.asarray(nv), np.asarray(vals))
    assert not bool(out.halted)          # quiesced, not fuel-capped
    assert int(out.steps) < 512


def test_hopscotch_writer_single_requests_all_outcomes():
    """One request per fresh context: update, first-empty claim, and the
    needs-displacement default, each via the response word + bucket addr."""
    import jax.numpy as jnp
    from repro.kvstore import hopscotch
    w = programs.build_hopscotch_writer(32, 2, 8)
    keys = jnp.zeros((32,), jnp.int32)
    vals = jnp.zeros((32, 2), jnp.int32)

    def one(k, v, tk, tv):
        pay = w.device_payloads(jnp.asarray([k], jnp.int32),
                                hopscotch.bucket_of(jnp.asarray([k]), 32),
                                jnp.asarray([v], jnp.int32))[0]
        st = machine.deliver(w.device_state(tk, tv), w.recv_wq, pay)
        out = w.engine.run(st, 512)
        return w.commit(out.mem, pay, tk, tv)

    s1, keys, vals = one(7, [70, 71], keys, vals)
    assert int(s1) == programs.SET_INSERTED
    s2, keys, vals = one(7, [72, 73], keys, vals)
    assert int(s2) == programs.SET_UPDATED
    home = int(hopscotch.bucket_of(7, 32))
    row = int(np.argmax(np.asarray(keys) == 7))
    assert (row - home) % 32 < 8
    np.testing.assert_array_equal(np.asarray(vals[row]), [72, 73])
