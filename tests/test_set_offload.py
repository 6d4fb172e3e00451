"""The chain-offloaded SET path: writer program vs the host insert oracle,
sharded_set through the mesh, cross-path visibility, and the §5.6
driver-dead fast-path set story."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import programs
from repro.core.engine import ChainEngine
from repro.kvstore import hopscotch, store
from repro.rdma import failure

NB = 64


def _keys_with_home(bucket, count, n_buckets=NB, start=1, n_shards=None):
    """Brute-force 24-bit keys whose home bucket is `bucket` (optionally
    pinned to shard 0, for service-level displacement scenarios)."""
    return store.keys_homed_at(bucket, count, n_buckets, start=start,
                               n_shards=n_shards)


def test_set_status_codes_match_across_layers():
    """The chain writer's response codes and the host oracle's constants
    are defined in two modules (core must not import kvstore) — they must
    stay numerically identical."""
    assert hopscotch.SET_UPDATED == programs.SET_UPDATED
    assert hopscotch.SET_INSERTED == programs.SET_INSERTED
    assert (hopscotch.SET_NEEDS_DISPLACEMENT
            == programs.SET_NEEDS_DISPLACEMENT)


# --- the writer program vs the host oracle -----------------------------------

@pytest.fixture(scope="module")
def seeded():
    t = hopscotch.make_table(NB, 2, neighborhood=8)
    for k in range(1, 25):
        assert t.insert(k, [k, k * 2])
    return t


def test_writer_chain_bit_exact_with_insert_oracle(seeded):
    """Updates, in-neighborhood inserts, and repeated writes to the same
    key: statuses and the full (keys, values) arrays match the batched
    host oracle applied in the same order."""
    t = seeded
    keys0, vals0 = t.as_device()
    w = programs.build_hopscotch_writer(NB, 2, 8)
    reqs = np.asarray([5, 70001, 5, 70002, 70001, 19], np.int32)
    vals = np.stack([reqs % 97, reqs % 89], axis=1).astype(np.int32)
    st, nk, nv = w.set_many(keys0, vals0, jnp.asarray(reqs),
                            hopscotch.bucket_of(jnp.asarray(reqs), NB),
                            jnp.asarray(vals))
    ref_t = hopscotch.HopscotchTable(t.keys.copy(), t.values.copy(), 8)
    ref_status = hopscotch.insert_many(ref_t, reqs, vals)
    np.testing.assert_array_equal(np.asarray(st), ref_status)
    np.testing.assert_array_equal(np.asarray(nk), ref_t.keys)
    np.testing.assert_array_equal(np.asarray(nv), ref_t.values)
    assert int(st[0]) == programs.SET_UPDATED
    assert int(st[1]) == programs.SET_INSERTED
    assert int(st[2]) == programs.SET_UPDATED    # second write = update
    assert int(st[4]) == programs.SET_UPDATED    # insert then update


def test_writer_chain_reports_needs_displacement_without_mutation():
    """A neighborhood-full insert answers SET_NEEDS_DISPLACEMENT and
    leaves the table bit-identical — the host slow path's cue; an update
    inside the full neighborhood still works."""
    t = hopscotch.make_table(NB, 2, neighborhood=8)
    cluster = _keys_with_home(7, 9)
    for k in cluster[:8]:
        assert t.insert(k, [k, k + 1])
    keys0, vals0 = t.as_device()
    w = programs.build_hopscotch_writer(NB, 2, 8)
    reqs = np.asarray([cluster[8], cluster[3]], np.int32)
    vals = np.asarray([[1, 2], [77, 78]], np.int32)
    st, nk, nv = w.set_many(keys0, vals0, jnp.asarray(reqs),
                            hopscotch.bucket_of(jnp.asarray(reqs), NB),
                            jnp.asarray(vals))
    ref_status = hopscotch.insert_many(t, reqs, vals)
    np.testing.assert_array_equal(np.asarray(st), ref_status)
    assert int(st[0]) == programs.SET_NEEDS_DISPLACEMENT
    assert int(st[1]) == programs.SET_UPDATED
    np.testing.assert_array_equal(np.asarray(nk), t.keys)
    np.testing.assert_array_equal(np.asarray(nv), t.values)


def test_writer_sequentializes_conflicting_inserts(seeded):
    """Two fresh keys with the same home bucket in one batch must claim
    *different* buckets (request i observes writes 0..i-1)."""
    t = seeded
    keys0, vals0 = t.as_device()
    w = programs.build_hopscotch_writer(NB, 2, 8)
    a, b = _keys_with_home(33, 2, start=100000)
    reqs = np.asarray([a, b], np.int32)
    vals = np.asarray([[1, 1], [2, 2]], np.int32)
    st, nk, nv = w.set_many(keys0, vals0, jnp.asarray(reqs),
                            hopscotch.bucket_of(jnp.asarray(reqs), NB),
                            jnp.asarray(vals))
    assert (np.asarray(st) == programs.SET_INSERTED).all()
    found, got = hopscotch.lookup(nk, nv, jnp.asarray(reqs), 8)
    assert bool(found.all())
    np.testing.assert_array_equal(np.asarray(got), vals)


def test_writer_rejected_on_pallas_backend_interp_serves():
    """The writer is a multi-WQ program: the single-WQ pallas backend must
    reject it explicitly, and the interp fallback must serve it."""
    w = programs.build_hopscotch_writer(32, 2, 4)
    with pytest.raises(ValueError, match="single-WQ"):
        ChainEngine.for_spec(w.spec, "pallas")
    with pytest.raises(ValueError, match="single-WQ"):
        ChainEngine.for_spec(w.spec, "pallas-interpret")
    assert w.engine.backend == "interp"
    zk = jnp.zeros((32,), jnp.int32)
    zv = jnp.zeros((32, 2), jnp.int32)
    st, nk, nv = w.set_many(zk, zv, jnp.asarray([9], jnp.int32),
                            hopscotch.bucket_of(jnp.asarray([9]), 32),
                            jnp.asarray([[4, 5]], jnp.int32))
    assert int(st[0]) == programs.SET_INSERTED
    f, v = hopscotch.lookup(nk, nv, jnp.asarray([9], jnp.int32), 4)
    assert bool(f[0]) and v[0].tolist() == [4, 5]


def test_writer_request_budget_enforced():
    """1 + val_len + neighborhood must fit one 16-word SEND/RECV."""
    with pytest.raises(ValueError):
        programs.build_hopscotch_writer(32, 8, 8)
    programs.build_hopscotch_writer(32, 7, 8)     # the boundary fits


# --- sharded_set through the mesh --------------------------------------------

@pytest.fixture(scope="module")
def mesh_kv():
    kv = store.ShardedKV.build(n_shards=1, buckets_per_shard=128,
                               val_words=2)
    rng = np.random.RandomState(2)
    keys = rng.choice(np.arange(1, 1 << 16), size=48, replace=False)
    for k in keys:
        kv.set(int(k), [int(k) % 251, int(k) % 241])
    mesh = Mesh(np.array(jax.devices()[:1]), ("kv",))
    return kv, keys, mesh


def test_sharded_set_bit_exact_and_visible_on_all_get_paths(mesh_kv):
    """The acceptance scenario: a routed batch of updates + inserts
    executes as writer chains at the owner shard, matches the host oracle
    bit-for-bit, and the new values are visible through redn, one_sided,
    and two_sided gets (update-after-insert included)."""
    kv, keys, mesh = mesh_kv
    dk, dv = kv.device_arrays()
    upd = keys[:5].astype(np.int32)
    new = np.asarray([80001, 80002, 80003], np.int32)
    sk = np.concatenate([upd, new, new[:1]])      # re-set 80001: update
    sv = np.stack([sk % 61, sk % 53], axis=1).astype(np.int32)
    res, nk, nv = store.sharded_set(mesh, "kv", dk, dv,
                                    jnp.asarray(sk[None]),
                                    jnp.asarray(sv[None]))
    assert bool(np.asarray(res.ok).all())
    assert bool(np.asarray(res.applied).all())
    assert int(res.dropped[0]) == 0

    ref_t = hopscotch.HopscotchTable(kv.tables[0].keys.copy(),
                                     kv.tables[0].values.copy(), 8)
    ref_status = hopscotch.insert_many(ref_t, sk, sv)
    np.testing.assert_array_equal(np.asarray(res.status[0]), ref_status)
    np.testing.assert_array_equal(np.asarray(nk[0]), ref_t.keys)
    np.testing.assert_array_equal(np.asarray(nv[0]), ref_t.values)

    probe = np.concatenate([sk[:-1], [0, 99991]]).astype(np.int32)
    q = jnp.asarray(probe[None])
    outs = {}
    for m in ("redn", "one_sided", "two_sided"):
        r = store.sharded_get(mesh, "kv", nk, nv, q, method=m)
        f, v = np.asarray(r.found[0]), np.asarray(r.values[0])
        assert f[:len(sk) - 1].all(), m
        np.testing.assert_array_equal(v[:5], sv[:5])
        np.testing.assert_array_equal(v[5], sv[-1])   # update-after-insert
        assert not f[-2], (m, "query-0 ghost hit")    # never-inserted + 0
        assert not f[-1], m
        outs[m] = (f, v)
    for m in ("one_sided", "two_sided"):
        np.testing.assert_array_equal(outs["redn"][1], outs[m][1])


def test_sharded_set_padding_slots_are_inert(mesh_kv):
    """Key-0 (unused) slots must not occupy dispatch capacity, evict real
    writes, report ok=True, or inflate the drop/defer counters."""
    kv, keys, mesh = mesh_kv
    dk, dv = kv.device_arrays()
    sk = np.asarray([0, 91001], np.int32)     # padding ahead of a real set
    sv = np.asarray([[0, 0], [6, 7]], np.int32)
    res, nk, nv = store.sharded_set(mesh, "kv", dk, dv,
                                    jnp.asarray(sk[None]),
                                    jnp.asarray(sv[None]), capacity=1)
    ok = np.asarray(res.ok[0])
    assert not ok[0] and ok[1]                # real write got the slot
    assert int(res.status[0][1]) == programs.SET_INSERTED
    assert int(res.dropped[0]) == 0 and int(res.deferred[0]) == 0
    f, v = hopscotch.lookup(nk[0], nv[0], jnp.asarray(sk[1:]), 8)
    assert bool(f[0]) and v[0].tolist() == [6, 7]


def test_sharded_set_capacity_drops_are_not_acks(mesh_kv):
    """Over-capacity SETs come back ok=False/applied=False and leave the
    store untouched — a dropped write must never look acknowledged."""
    kv, keys, mesh = mesh_kv
    dk, dv = kv.device_arrays()
    sk = np.asarray([90001, 90002, 90003, 90004], np.int32)
    sv = np.stack([sk % 7, sk % 11], axis=1).astype(np.int32)
    cap = 2
    res, nk, nv = store.sharded_set(mesh, "kv", dk, dv,
                                    jnp.asarray(sk[None]),
                                    jnp.asarray(sv[None]), capacity=cap)
    ok = np.asarray(res.ok[0])
    assert ok.sum() == cap and int(res.dropped[0]) == len(sk) - cap
    assert not np.asarray(res.applied[0])[~ok].any()
    assert (np.asarray(res.status[0])[~ok] == 0).all()
    # only the admitted writes landed
    f, _ = hopscotch.lookup(nk[0], nv[0], jnp.asarray(sk), 8)
    np.testing.assert_array_equal(np.asarray(f), ok)


# --- §5.6: displacement is chain-served too (no host role left) ---------------

def test_service_displacement_serves_with_driver_dead():
    """The acceptance scenario: a neighborhood-full insert — the one SET
    path that used to fall back to the host — completes through the
    displacer chain with the driver crashed, and every key (including
    the displaced one) is served by the chain get path afterwards."""
    nb, home = 128, 40
    staggered = [_keys_with_home((home + d) % nb, 1, n_buckets=nb,
                                 start=200 + 97 * d, n_shards=1)[0]
                 for d in range(8)]
    svc = failure.ShardedKVService.start(
        [(k, [k % 7, k % 11]) for k in staggered])
    # overwrite one value through the chain so any stale host copy would
    # be caught: displacement must move the *device* truth around
    assert svc.set(staggered[2], [42, 43])
    z = _keys_with_home(home, 1, n_buckets=nb, start=50000, n_shards=1)[0]
    svc.crash_host()
    assert not svc.host_alive()
    assert svc.set(z, [9, 9])          # displacement, host driver dead
    r = svc.get_many(np.asarray(staggered + [z], np.int32))
    assert np.asarray(r.found[0]).all()
    want = [[k % 7, k % 11] for k in staggered] + [[9, 9]]
    want[2] = [42, 43]
    np.testing.assert_array_equal(np.asarray(r.values[0]), want)
    # bit-exact with the bounded host oracle replayed over the same story
    ref = hopscotch.make_table(nb, 2, neighborhood=8)
    for k in staggered:
        assert ref.set_full(k, [k % 7, k % 11]) == hopscotch.SET_INSERTED
    assert ref.set_full(staggered[2], [42, 43]) == hopscotch.SET_UPDATED
    assert ref.set_full(z, [9, 9]) == hopscotch.SET_DISPLACED
    np.testing.assert_array_equal(np.asarray(svc.keys[0]), ref.keys)
    np.testing.assert_array_equal(np.asarray(svc.vals[0]), ref.values)


def test_service_set_many_batched(mesh_kv):
    """The batched service entry point: a driver-dead batch of mixed
    updates/inserts is fully applied and acked."""
    items = [(k, [k, k + 1]) for k in range(1, 9)]
    svc = failure.ShardedKVService.start(items)
    svc.crash_host()
    sk = np.asarray([3, 801, 5, 802], np.int32)
    sv = np.stack([sk * 2, sk * 3], axis=1).astype(np.int32)
    res = svc.set_many(sk, sv)
    assert bool(np.asarray(res.applied).all())
    st = np.asarray(res.status[0])
    np.testing.assert_array_equal(st, [programs.SET_UPDATED,
                                       programs.SET_INSERTED,
                                       programs.SET_UPDATED,
                                       programs.SET_INSERTED])
    r = svc.get_many(sk)
    assert np.asarray(r.found[0]).all()
    np.testing.assert_array_equal(np.asarray(r.values[0]), sv)


def test_service_start_needs_one_device_per_shard():
    """Two shards on a one-device host must not quietly share a chip."""
    assert len(jax.devices()) == 1
    with pytest.raises(ValueError, match="one device per shard"):
        failure.ShardedKVService.start([(5, [1, 2])], n_shards=2)


def test_service_state_is_placed_on_its_mesh():
    svc = failure.ShardedKVService.start([(5, [1, 2])], ttl=True)
    want = NamedSharding(svc.mesh, P(svc.axis))
    for arr in (svc.keys, svc.vals, svc.exp, svc.sweep_hand):
        assert arr.sharding.is_equivalent_to(want, arr.ndim)
