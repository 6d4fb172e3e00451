"""Tests: hopscotch/cuckoo tables, sharded store get paths (chain-VM redn
path vs oracle), capacity/drop semantics, isolation, failure resiliency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import Mesh

from repro.core import programs
from repro.kvstore import cuckoo, hopscotch, store
from repro.rdma import failure, isolation


# --- hopscotch ---------------------------------------------------------------

def test_hopscotch_insert_lookup_roundtrip():
    t = hopscotch.make_table(64, 2, neighborhood=8)
    for k in range(1, 40):
        assert t.insert(k, [k, k * 2])
    keys, vals = t.as_device()
    q = jnp.arange(1, 50, dtype=jnp.int32)
    found, v = hopscotch.lookup(keys, vals, q, 8)
    for i, k in enumerate(range(1, 50)):
        if k < 40:
            assert bool(found[i]) and v[i].tolist() == [k, k * 2]
        else:
            assert not bool(found[i]) and v[i].tolist() == [0, 0]


def test_hopscotch_update_in_place():
    t = hopscotch.make_table(32, 2)
    t.insert(5, [1, 1])
    t.insert(5, [9, 9])
    keys, vals = t.as_device()
    _, v = hopscotch.lookup(keys, vals, jnp.asarray([5], jnp.int32), 8)
    assert v[0].tolist() == [9, 9]


@settings(max_examples=20, deadline=None)
@given(keys=st.lists(st.integers(1, 1 << 24), min_size=1, max_size=48,
                     unique=True))
def test_hopscotch_matches_dict(keys):
    t = hopscotch.make_table(128, 1, neighborhood=8)
    ref = {}
    for k in keys:
        if t.insert(k, [k % 1009]):
            ref[k] = k % 1009
    dk, dv = t.as_device()
    q = jnp.asarray(keys + [1 << 25], jnp.int32)
    found, v = hopscotch.lookup(dk, dv, q, 8)
    for i, k in enumerate(keys + [1 << 25]):
        if k in ref:
            assert bool(found[i]) and int(v[i, 0]) == ref[k]
        else:
            assert not bool(found[i])


# --- cuckoo -------------------------------------------------------------------

def test_cuckoo_insert_lookup():
    t = cuckoo.make_table(32, 2, ways=4)
    for k in range(1, 60):
        assert t.insert(k, [k, k + 1]), k
    dk, dv = t.as_device()
    found, v = cuckoo.lookup(dk, dv, jnp.arange(1, 60, dtype=jnp.int32))
    assert bool(jnp.all(found))
    np.testing.assert_array_equal(np.asarray(v[:, 0]), np.arange(1, 60))


# --- shard_of: python-int path == device path ---------------------------------

@settings(max_examples=50, deadline=None)
@given(key=st.integers(-(1 << 31), (1 << 31) - 1),
       n_shards=st.integers(1, 64))
def test_shard_of_int_matches_device(key, n_shards):
    """Negative keys (and any int32 bit pattern) must route to the same
    shard whichever side hashes them."""
    dev = int(store.shard_of(jnp.asarray([key], jnp.int32), n_shards)[0])
    assert store.shard_of(key, n_shards) == dev


@settings(max_examples=20, deadline=None)
@given(key=st.integers(1 << 32, (1 << 34)), n_shards=st.integers(1, 16))
def test_shard_of_wide_int_matches_device(key, n_shards):
    """>= 2**32 python keys hash like their int32 truncation (what the
    device would see)."""
    trunc = np.int64(key).astype(np.int32)
    dev = int(store.shard_of(jnp.asarray([trunc], jnp.int32), n_shards)[0])
    assert store.shard_of(key, n_shards) == dev


def test_shard_of_cross_path_deterministic():
    """Seeded sweep (runs even without hypothesis): every int32 pattern —
    negative included — and >= 2**32 keys route identically on both
    paths."""
    rng = np.random.RandomState(3)
    ks = np.concatenate([
        rng.randint(-(1 << 31), (1 << 31) - 1, 200, dtype=np.int64),
        np.asarray([0, -1, 1 << 32 | 5, (1 << 33) - 1, 0xFFFFFFFF],
                   np.int64)])
    for k in ks.tolist():
        trunc = np.int64(k).astype(np.int32)
        for n in (1, 3, 8, 64):
            dev = int(store.shard_of(jnp.asarray([trunc], jnp.int32), n)[0])
            assert store.shard_of(k, n) == dev, (k, n)


# --- the per-shard chain program vs the jnp oracle -----------------------------

def test_hopscotch_server_bit_exact_with_oracle():
    t = hopscotch.make_table(64, 2, neighborhood=8)
    for k in range(1, 40):
        assert t.insert(k, [k, k * 2])
    keys, vals = t.as_device()
    srv = programs.build_hopscotch_server(64, 2, 8)
    # hits, misses, and query 0 — which must be a miss on both (the chain's
    # dynamic found-flag rows de-alias empty buckets from real hits)
    q = jnp.asarray(list(range(1, 50)) + [0], jnp.int32)
    found, v = srv.get_many(keys, vals, q, hopscotch.bucket_of(q, 64))
    rfound, rv = hopscotch.lookup(keys, vals, q, 8)
    np.testing.assert_array_equal(np.asarray(found), np.asarray(rfound))
    np.testing.assert_array_equal(np.asarray(v), np.asarray(rv))


# --- sharded store: the three get paths ---------------------------------------

@pytest.fixture(scope="module")
def kv_setup():
    kv = store.ShardedKV.build(n_shards=1, buckets_per_shard=128,
                               val_words=2)
    rng = np.random.RandomState(0)
    keys = rng.choice(np.arange(1, 1 << 16), size=60, replace=False)
    for k in keys:
        kv.set(int(k), [int(k) % 251, int(k) % 241])
    return kv, keys


@pytest.mark.parametrize("method", ["redn", "one_sided", "two_sided"])
def test_sharded_get_paths_agree_with_reference(kv_setup, method):
    kv, keys = kv_setup
    mesh = Mesh(np.array(jax.devices()[:1]), ("kv",))
    dk, dv = kv.device_arrays()
    rng = np.random.RandomState(1)
    probe = np.concatenate([rng.choice(keys, 20), [99999, 77777]])
    q = jnp.asarray(probe[None, :], jnp.int32)
    res = store.sharded_get(mesh, "kv", dk, dv, q, method=method)
    rfound, rvals = store.reference_get(kv, probe)
    np.testing.assert_array_equal(np.asarray(res.found[0]), rfound)
    np.testing.assert_array_equal(np.asarray(res.values[0]), rvals)
    assert bool(np.asarray(res.ok).all())
    assert int(res.dropped[0]) == 0 and int(res.deferred[0]) == 0


def test_get_paths_identical_across_methods(kv_setup):
    kv, keys = kv_setup
    mesh = Mesh(np.array(jax.devices()[:1]), ("kv",))
    dk, dv = kv.device_arrays()
    q = jnp.asarray(keys[None, :32], jnp.int32)
    outs = {m: store.sharded_get(mesh, "kv", dk, dv, q, method=m)
            for m in ("redn", "one_sided", "two_sided")}
    for m in ("one_sided", "two_sided"):
        np.testing.assert_array_equal(np.asarray(outs["redn"].values),
                                      np.asarray(outs[m].values))


@pytest.mark.parametrize("method", ["redn", "one_sided", "two_sided"])
def test_capacity_overflow_drops_are_flagged_not_missed(kv_setup, method):
    """All three paths: over-capacity requests come back ok=False (and
    counted in dropped); admitted rows still agree with the oracle.  A
    dropped hit must never read as found=False with ok silently True."""
    kv, keys = kv_setup
    mesh = Mesh(np.array(jax.devices()[:1]), ("kv",))
    dk, dv = kv.device_arrays()
    probe = keys[:24]                       # all hits -> drops would alias
    q = jnp.asarray(probe[None, :], jnp.int32)
    cap = 9
    res = store.sharded_get(mesh, "kv", dk, dv, q, method=method,
                            capacity=cap)
    ok = np.asarray(res.ok[0])
    assert ok.sum() == cap                  # one shard: first cap survive
    assert int(res.dropped[0]) == len(probe) - cap
    rfound, rvals = store.reference_get(kv, probe)
    np.testing.assert_array_equal(np.asarray(res.found[0])[ok], rfound[ok])
    np.testing.assert_array_equal(np.asarray(res.values[0])[ok], rvals[ok])
    # every dropped row is a *hit* in the table: ok=False is the only thing
    # separating it from a miss
    assert rfound[~ok].all()
    assert not np.asarray(res.found[0])[~ok].any()


@pytest.mark.parametrize("method", ["redn", "one_sided", "two_sided"])
def test_query_of_empty_key_is_a_miss_on_every_path(kv_setup, method):
    """Regression: key 0 is the EMPTY bucket marker — a query of 0 used to
    ghost-hit empty buckets and report found=True with garbage-zero
    values on all three get paths."""
    kv, keys = kv_setup
    mesh = Mesh(np.array(jax.devices()[:1]), ("kv",))
    dk, dv = kv.device_arrays()
    q = jnp.asarray(np.asarray([0, int(keys[0]), 0], np.int32)[None])
    res = store.sharded_get(mesh, "kv", dk, dv, q, method=method)
    assert bool(np.asarray(res.ok).all())
    found = np.asarray(res.found[0])
    assert not found[0] and not found[2]
    assert found[1]                         # real keys still hit
    np.testing.assert_array_equal(np.asarray(res.values[0][0]), [0, 0])


def test_query_zero_miss_in_lookup_and_reference_oracle(kv_setup):
    kv, _ = kv_setup
    dk, dv = kv.device_arrays()
    found, vals = hopscotch.lookup(dk[0], dv[0],
                                   jnp.asarray([0], jnp.int32), 8)
    assert not bool(found[0])
    rfound, rvals = store.reference_get(kv, np.asarray([0], np.int32))
    assert not rfound[0] and (rvals[0] == 0).all()


def test_capacity_zero_drops_everything(kv_setup):
    """Regression: ``capacity or b_local`` silently promoted an explicit
    capacity=0 to the default batch size; 0 is a legal drop-all limit."""
    kv, keys = kv_setup
    mesh = Mesh(np.array(jax.devices()[:1]), ("kv",))
    dk, dv = kv.device_arrays()
    q = jnp.asarray(keys[None, :16], jnp.int32)
    res = store.sharded_get(mesh, "kv", dk, dv, q, capacity=0)
    assert not np.asarray(res.ok).any()
    assert not np.asarray(res.found).any()
    assert int(res.dropped[0]) == 16 and int(res.deferred[0]) == 0
    sres, nk, nv = store.sharded_set(
        mesh, "kv", dk, dv, q, jnp.zeros(q.shape + (2,), jnp.int32),
        capacity=0)
    assert not np.asarray(sres.ok).any()
    assert int(sres.dropped[0]) == 16
    np.testing.assert_array_equal(np.asarray(nk), np.asarray(dk))


def test_rtt_model():
    assert store.RTTS["redn"] == 1
    assert store.RTTS["one_sided"] == 2
    assert store.HOST_SERVICE["two_sided"]
    assert not store.HOST_SERVICE["redn"]


def test_set_rejects_wide_keys():
    kv = store.ShardedKV.build(n_shards=1, buckets_per_shard=8, val_words=1)
    with pytest.raises(ValueError):
        kv.set(1 << 24, [1])
    with pytest.raises(ValueError):
        kv.set(0, [1])


# --- isolation ------------------------------------------------------------------

def test_token_bucket_limits_heavy_client():
    st0 = isolation.init(n_clients=2, burst=4.0)
    # client 0 fires 8 requests at t=0; client 1 fires 2
    clients = jnp.asarray([0] * 8 + [1] * 2, jnp.int32)
    st1, admitted = isolation.admit(st0, clients, 0.0, rate_per_us=0.001,
                                    burst=4.0)
    adm = np.asarray(admitted)
    assert adm[:4].all() and not adm[4:8].any()    # heavy client capped
    assert adm[8:].all()                           # light client unaffected

    # after enough time the bucket refills
    st2, admitted2 = isolation.admit(st1, jnp.asarray([0], jnp.int32),
                                     8000.0, rate_per_us=0.001, burst=4.0)
    assert bool(admitted2[0])


def test_sharded_get_isolated_defers_misbehaving_client(kv_setup):
    """§5.5 through the store: the flooder is deferred to its burst, the
    victims are all served by the owner chain and match the oracle."""
    kv, keys = kv_setup
    mesh = Mesh(np.array(jax.devices()[:1]), ("kv",))
    dk, dv = kv.device_arrays()
    flood, burst, cap = 20, 4.0, 12
    probe = np.concatenate([np.full(flood, keys[0]), keys[1:9]]).astype(
        np.int32)
    clients = np.asarray([0] * flood + list(range(1, 9)), np.int32)
    q = jnp.asarray(probe[None])
    bucket = isolation.init(n_clients=9, burst=burst)
    res, bucket = store.sharded_get_isolated(
        mesh, "kv", dk, dv, q, jnp.asarray(clients[None]), bucket,
        now_us=0.0, rate_per_us=0.001, burst=burst, capacity=cap)
    ok = np.asarray(res.ok[0])
    victim = clients > 0
    assert ok[victim].all()                     # victims all served
    assert ok[~victim].sum() == int(burst)      # flooder capped at burst
    assert int(res.deferred[0]) == flood - int(burst)
    assert int(res.dropped[0]) == 0             # admitted all fit capacity
    rfound, rvals = store.reference_get(kv, probe)
    np.testing.assert_array_equal(np.asarray(res.found[0])[ok], rfound[ok])
    np.testing.assert_array_equal(np.asarray(res.values[0])[ok], rvals[ok])
    # without admission, the flood occupies every slot: victims dropped
    res_off = store.sharded_get(mesh, "kv", dk, dv, q, capacity=cap)
    assert not np.asarray(res_off.ok[0])[victim].any()


# --- failure resiliency -----------------------------------------------------------

def test_service_survives_host_crash():
    items = [(k, [k * 3, k * 5]) for k in range(1, 9)]
    svc = failure.DeviceResidentService.start(items)
    assert svc.get(4).tolist() == [12, 20]
    svc.crash_host()                       # Memcached dies
    assert not svc.host_alive()
    for k in range(1, 9):                  # zero-interruption serving
        assert svc.get(k).tolist() == [k * 3, k * 5]
    svc.restart_host()
    assert svc.host_alive()
    assert svc.get(2).tolist() == [6, 10]
    assert svc.cold_restart_downtime_s() >= 2.0   # what vanilla would pay


def test_sharded_service_survives_host_crash():
    """§5.6 on the *sharded* store: kill the host driver and the sharded
    chain-VM gets — and the chain-offloaded fast-path sets — keep
    serving; only displacement needs the driver."""
    items = [(k, [k * 3, k * 5]) for k in range(1, 17)]
    svc = failure.ShardedKVService.start(items)
    q = np.arange(1, 21, dtype=np.int32)
    before = svc.get_many(q)
    svc.crash_host()
    assert not svc.host_alive()
    after = svc.get_many(q)                # zero-interruption serving
    np.testing.assert_array_equal(np.asarray(before.found),
                                  np.asarray(after.found))
    np.testing.assert_array_equal(np.asarray(before.values),
                                  np.asarray(after.values))
    assert bool(np.asarray(after.ok).all())
    assert np.asarray(after.found[0])[:16].all()
    assert not np.asarray(after.found[0])[16:].any()
    # the writer chain needs no host: update and insert serve driver-dead
    assert svc.set(99, [1, 2])             # in-neighborhood insert
    assert svc.set(4, [40, 41])            # update
    got = svc.get_many(np.asarray([99, 4], np.int32))
    assert bool(got.found[0][0]) and bool(got.found[0][1])
    np.testing.assert_array_equal(np.asarray(got.values[0]),
                                  [[1, 2], [40, 41]])
    svc.restart_host()
    assert svc.set(99, [7, 8])
    np.testing.assert_array_equal(
        np.asarray(svc.get_many(np.asarray([99], np.int32)).values[0][0]),
        [7, 8])
