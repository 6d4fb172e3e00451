"""The chain-offloaded hopscotch displacement: the displacer program vs
the bounded `set_full` host oracle, the sharded_set escalation stage,
and the completed §5.6 story (every SET path serves with the driver
dead).  Also the writer/oracle parity bugfixes that ride along: zero-
filled value tails on shrink updates and zeroed vacated value rows."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import Mesh

from repro.core import programs
from repro.kvstore import hopscotch, store
from repro.rdma import failure

NB, H, S, M, V = 64, 4, 8, 4, 2


def _keys_with_home(bucket, count, n_buckets=NB, start=1, n_shards=None):
    return store.keys_homed_at(bucket, count, n_buckets, start=start,
                               n_shards=n_shards)


def test_status_codes_match_across_layers():
    assert hopscotch.SET_DISPLACED == programs.SET_DISPLACED
    assert hopscotch.SET_NEEDS_RESIZE == programs.SET_NEEDS_RESIZE


def test_bucket_home_matches_kvstore_hash():
    """core.programs derives per-bucket home distances with its own copy
    of the multiplicative hash (core must not import kvstore) — the two
    must stay numerically identical."""
    ks = jnp.asarray([1, 2, 12345, 0xFFFFFF, 999983], jnp.int32)
    for n in (7, 64, 128, 1000):
        np.testing.assert_array_equal(
            np.asarray(programs.bucket_home(ks, n)),
            np.asarray(hopscotch.bucket_of(ks, n)))


# --- the displacer program vs the bounded host oracle -------------------------

@pytest.fixture(scope="module")
def displacer():
    return programs.build_hopscotch_displacer(NB, V, H, S, M)


def _run_one(disp, table, key, value, max_steps=4096):
    """One request through the chain; returns (status, keys, vals)."""
    row = np.zeros(V, np.int32)
    row[:len(value)] = value
    keys0, vals0 = table.as_device()
    pay = disp.device_payloads(
        jnp.asarray([key], jnp.int32),
        hopscotch.bucket_of(jnp.asarray([key], jnp.int32), NB),
        jnp.asarray([row], jnp.int32))
    return disp.run_one(keys0, vals0, pay[0], max_steps)


def _assert_matches_oracle(disp, table, key, value, want_status):
    ref = hopscotch.HopscotchTable(table.keys.copy(), table.values.copy(),
                                  H)
    ref_status = ref.set_full(key, value, disp.max_search, disp.max_moves)
    st_, nk, nv = _run_one(disp, table, key, value)
    assert int(st_) == ref_status == want_status
    np.testing.assert_array_equal(np.asarray(nk), ref.keys)
    np.testing.assert_array_equal(np.asarray(nv), ref.values)
    return np.asarray(nk), np.asarray(nv)


def _staggered_full_neighborhood(home):
    """Fill [home, home+H) with keys homed *at* their own bucket (pad 0
    each), so the bubble can move any of them one window forward."""
    t = hopscotch.make_table(NB, V, neighborhood=H)
    for d in range(H):
        k = _keys_with_home((home + d) % NB, 1, start=200 + 97 * d)[0]
        assert t.insert(k, [k % 7, k % 11])
    return t


def test_displacer_one_move_bit_exact(displacer):
    t = _staggered_full_neighborhood(10)
    z = _keys_with_home(10, 1, start=50000)[0]
    nk, nv = _assert_matches_oracle(displacer, t, z, [9, 9],
                                    hopscotch.SET_DISPLACED)
    f, v = hopscotch.lookup(jnp.asarray(nk), jnp.asarray(nv),
                            jnp.asarray([z], jnp.int32), H)
    assert bool(f[0]) and v[0].tolist() == [9, 9]
    # vacated buckets must not leak value words (the zero-row bugfix)
    assert (nv[nk == hopscotch.EMPTY] == 0).all()


def test_displacer_wraparound_window(displacer):
    """Home near the end of the table: the unwrapped mirror rows carry
    the window across the wrap."""
    t = _staggered_full_neighborhood(NB - 2)
    z = _keys_with_home(NB - 2, 1, start=60000)[0]
    _assert_matches_oracle(displacer, t, z, [8, 8],
                           hopscotch.SET_DISPLACED)


def test_displacer_multi_move_ladder(displacer):
    """A pad-2 ladder permits only back=1 moves: the bubble must take
    several laps, each choosing the same window offset."""
    t = hopscotch.make_table(NB, V, neighborhood=H)
    home = 10
    for pos in range(home, home + 6):
        k = _keys_with_home((pos - 2) % NB, 1, start=300 + 13 * pos)[0]
        t.keys[pos] = k
        t.values[pos] = [k % 7, k % 11]
    z = _keys_with_home(home, 1, start=70000)[0]
    nk, nv = _assert_matches_oracle(displacer, t, z, [3, 4],
                                    hopscotch.SET_DISPLACED)
    assert (nv[nk == hopscotch.EMPTY] == 0).all()


def test_displacer_update_and_plain_insert(displacer):
    t = _staggered_full_neighborhood(10)
    upd = int(t.keys[11])
    _assert_matches_oracle(displacer, t, upd, [1], hopscotch.SET_UPDATED)
    t2 = hopscotch.make_table(NB, V, neighborhood=H)
    k0 = _keys_with_home(10, 1)[0]
    assert t2.insert(k0, [5, 5])
    z = _keys_with_home(10, 1, start=90000)[0]
    _assert_matches_oracle(displacer, t2, z, [6, 6],
                           hopscotch.SET_INSERTED)


def test_displacer_stuck_window_needs_resize(displacer):
    """Keys homed at the requester's own bucket fill the neighborhood;
    nothing in any window can move forward — both the chain and the
    bounded oracle answer SET_NEEDS_RESIZE and leave the table
    bit-identical (no partial moves)."""
    t = hopscotch.make_table(NB, V, neighborhood=H)
    cluster = _keys_with_home(10, H + 1)
    for k in cluster[:H]:
        assert t.insert(k, [k % 7, k % 11])
    # occupy the next buckets with immovable (pad-0) residents so the
    # first window contains a movable key but later windows do not
    for d in range(H, H + 2):
        k = _keys_with_home((10 + d) % NB, 1, start=500 + d)[0]
        assert t.insert(k, [k % 7, k % 11])
    keys_before = t.keys.copy()
    vals_before = t.values.copy()
    _assert_matches_oracle(displacer, t, cluster[H], [1, 2],
                           hopscotch.SET_NEEDS_RESIZE)
    np.testing.assert_array_equal(t.keys, keys_before)
    np.testing.assert_array_equal(t.values, vals_before)


def test_displacer_move_budget_honored():
    """max_moves=1 on a ladder that needs several laps: needs-resize,
    with the table untouched on both sides."""
    d1 = programs.build_hopscotch_displacer(NB, V, H, S, 1)
    t = hopscotch.make_table(NB, V, neighborhood=H)
    home = 10
    for pos in range(home, home + 6):
        k = _keys_with_home((pos - 2) % NB, 1, start=300 + 13 * pos)[0]
        t.keys[pos] = k
        t.values[pos] = [k % 7, k % 11]
    z = _keys_with_home(home, 1, start=70000)[0]
    _assert_matches_oracle(d1, t, z, [3, 4], hopscotch.SET_NEEDS_RESIZE)


def test_displacer_search_window_honored(displacer):
    """No EMPTY bucket within max_search probes of home: needs-resize."""
    t = hopscotch.make_table(NB, V, neighborhood=H)
    home = 20
    for pos in range(home, home + S):
        k = _keys_with_home(pos % NB, 1, start=400 + 17 * pos)[0]
        t.keys[pos % NB] = k
        t.values[pos % NB] = [k % 7, k % 11]
    z = _keys_with_home(home, 1, start=80000)[0]
    _assert_matches_oracle(displacer, t, z, [2, 2],
                           hopscotch.SET_NEEDS_RESIZE)


def test_displacer_zero_padded_request_is_inert(displacer):
    """A transport padding slot (all-zero payload) quiesces against the
    null guard: status 0, arrays untouched."""
    t = _staggered_full_neighborhood(10)
    keys0, vals0 = t.as_device()
    st_, nk, nv = displacer.run_one(keys0, vals0,
                                    jnp.zeros(V + 2, jnp.int32), 4096)
    assert int(st_) == 0
    np.testing.assert_array_equal(np.asarray(nk), t.keys)
    np.testing.assert_array_equal(np.asarray(nv), t.values)


def test_displacer_build_bounds():
    with pytest.raises(ValueError, match="neighborhood"):
        programs.build_hopscotch_displacer(NB, V, 1, S, M)
    with pytest.raises(ValueError, match="max_search"):
        programs.build_hopscotch_displacer(NB, V, H, NB + 1, M)
    with pytest.raises(ValueError, match="max_moves"):
        programs.build_hopscotch_displacer(NB, V, H, S, 0)
    with pytest.raises(ValueError, match="request budget"):
        programs.build_hopscotch_displacer(NB, 15, H, S, M)


# --- the sharded_set escalation stage -----------------------------------------

@pytest.fixture(scope="module")
def mesh1():
    return Mesh(np.array(jax.devices()[:1]), ("kv",))


def test_sharded_set_escalates_displacement_bit_exact(mesh1):
    """A mixed batch (update, inserts, a displacement-requiring insert,
    a duplicate of it that must become an update): the two-stage chain
    pipeline matches the two-pass host oracle bit-for-bit, and the new
    values are visible on every get path."""
    nb = 128
    kv = store.ShardedKV.build(1, nb, V)
    home = 40
    staggered = [_keys_with_home((home + d) % nb, 1, n_buckets=nb,
                                 start=200 + 97 * d, n_shards=1)[0]
                 for d in range(8)]
    for k in staggered:
        kv.set(k, [k % 7, k % 11])
    dk, dv = kv.device_arrays()
    z = _keys_with_home(home, 1, n_buckets=nb, start=50000, n_shards=1)[0]
    sk = np.asarray([staggered[3], z, 77001, z], np.int32)
    sv = np.stack([sk % 61, sk % 53], axis=1).astype(np.int32)
    res, nk, nv = store.sharded_set(mesh1, "kv", dk, dv,
                                    jnp.asarray(sk[None]),
                                    jnp.asarray(sv[None]))
    ref = hopscotch.HopscotchTable(kv.tables[0].keys.copy(),
                                   kv.tables[0].values.copy(), 8)
    ref_st = hopscotch.insert_many_displaced(ref, sk, sv)
    np.testing.assert_array_equal(np.asarray(res.status[0]), ref_st)
    assert int(res.status[0][1]) == programs.SET_DISPLACED
    assert int(res.status[0][3]) == programs.SET_UPDATED  # dup -> update
    assert bool(np.asarray(res.applied[0]).all())
    assert bool(np.asarray(res.ok[0]).all())
    np.testing.assert_array_equal(np.asarray(nk[0]), ref.keys)
    np.testing.assert_array_equal(np.asarray(nv[0]), ref.values)
    q = jnp.asarray(sk[None])
    for m in ("redn", "one_sided", "two_sided"):
        g = store.sharded_get(mesh1, "kv", nk, nv, q, method=m)
        assert np.asarray(g.found[0]).all(), m
        np.testing.assert_array_equal(np.asarray(g.values[0][1]), sv[3])


def test_sharded_set_resize_rows_not_acked(mesh1):
    """A genuinely unplaceable insert (stuck window) reports
    SET_NEEDS_RESIZE, applied=False, and leaves the arrays untouched."""
    nb = 128
    kv = store.ShardedKV.build(1, nb, V)
    cluster = _keys_with_home(7, 9, n_buckets=nb, start=1000, n_shards=1)
    for k in cluster[:8]:
        kv.set(k, [k % 5 + 1, k % 3 + 1])
    dk, dv = kv.device_arrays()
    sk = np.asarray([cluster[8]], np.int32)
    sv = np.asarray([[1, 2]], np.int32)
    res, nk, nv = store.sharded_set(mesh1, "kv", dk, dv,
                                    jnp.asarray(sk[None]),
                                    jnp.asarray(sv[None]))
    assert int(res.status[0][0]) == programs.SET_NEEDS_RESIZE
    assert not bool(np.asarray(res.applied[0]).any())
    assert bool(np.asarray(res.ok[0]).all())   # answered, not dropped
    np.testing.assert_array_equal(np.asarray(nk), np.asarray(dk))
    np.testing.assert_array_equal(np.asarray(nv), np.asarray(dv))


# --- oracle parity under load (the hypothesis sweep) --------------------------

@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2 ** 31 - 1))
def test_displacer_oracle_parity_random_high_load(seed):
    """Random SET batches against a table at load factor ~0.85+, applied
    through the writer + displacer pipeline, replayed on the bounded host
    oracle; interleaved gets check the store serves exactly the oracle's
    table state."""
    _random_parity_round(seed)


def test_displacer_oracle_parity_seeded():
    """Deterministic instances of the same property (runs without
    hypothesis)."""
    for seed in (0, 7, 1234):
        _random_parity_round(seed)


def _random_parity_round(seed):
    rng = np.random.RandomState(seed)
    mesh = Mesh(np.array(jax.devices()[:1]), ("kv",))
    t = hopscotch.make_table(NB, V, neighborhood=H)
    # fill to load factor ~0.85 with the bounded host insert
    n_target = int(NB * 0.85)
    k = 1 + int(rng.randint(1 << 20))
    while (t.keys != hopscotch.EMPTY).sum() < n_target:
        t.insert(int(k), [int(k) % 97, int(k) % 89],
                 max_search=S, max_moves=M)
        k += 1 + int(rng.randint(50))
    dk, dv = t.as_device()
    dk, dv = dk[None], dv[None]          # (S=1, B), (S=1, B, V)
    ref = hopscotch.HopscotchTable(t.keys.copy(), t.values.copy(), H)

    for _ in range(2):
        live = t.keys[t.keys != hopscotch.EMPTY]
        upd = rng.choice(live, size=3).astype(np.int64)
        new = 1 + rng.randint(0, 1 << 22, size=3).astype(np.int64)
        sk = np.concatenate([upd, new]).astype(np.int32)
        rng.shuffle(sk)
        sv = np.stack([sk % 251, sk % 241], axis=1).astype(np.int32)
        res, dk, dv = store.sharded_set(
            mesh, "kv", dk, dv, jnp.asarray(sk[None]),
            jnp.asarray(sv[None]), neighborhood=H, max_search=S,
            max_moves=M)
        ref_st = hopscotch.insert_many_displaced(ref, sk, sv, S, M)
        np.testing.assert_array_equal(np.asarray(res.status[0]), ref_st)
        np.testing.assert_array_equal(np.asarray(dk[0]), ref.keys)
        np.testing.assert_array_equal(np.asarray(dv[0]), ref.values)
        # interleaved gets: the chain get serves the oracle's exact state
        q = np.concatenate([sk, [0]]).astype(np.int32)
        g = store.sharded_get(mesh, "kv", dk, dv, jnp.asarray(q[None]),
                              method="redn", neighborhood=H)
        rf, rv = hopscotch.lookup(*ref.as_device(),
                                  jnp.asarray(q, jnp.int32), H)
        np.testing.assert_array_equal(np.asarray(g.found[0]),
                                      np.asarray(rf))
        np.testing.assert_array_equal(np.asarray(g.values[0]),
                                      np.asarray(rv))


# --- satellite: shrink-update parity (stale value tails) ----------------------

def test_update_with_shorter_value_zero_fills_tail(mesh1):
    """Re-setting a key with a shorter value must zero the trailing
    words on *every* path — host set_fast/set_full and the chain writer
    all write full val_words rows now."""
    t = hopscotch.make_table(NB, V, neighborhood=H)
    k = 17
    assert t.insert(k, [7, 8])
    assert t.set_fast(k, [5]) == hopscotch.SET_UPDATED
    np.testing.assert_array_equal(
        t.values[np.where(t.keys == k)[0][0]], [5, 0])

    t2 = hopscotch.make_table(NB, V, neighborhood=H)
    assert t2.insert(k, [7, 8])
    assert t2.set_full(k, [5]) == hopscotch.SET_UPDATED
    np.testing.assert_array_equal(
        t2.values[np.where(t2.keys == k)[0][0]], [5, 0])


def test_chain_vs_insert_many_shrink_update_parity(mesh1):
    """The regression the bug caused: chain writer and host oracle used
    to diverge on an update with a shorter value (the chain writes the
    full zero-padded row; the host left the stale tail)."""
    kv = store.ShardedKV.build(1, 128, V)
    kv.set(23, [7, 8])
    dk, dv = kv.device_arrays()
    sk = np.asarray([23], np.int32)
    sv = np.asarray([[5, 0]], np.int32)      # "shorter" value, zero-padded
    res, nk, nv = store.sharded_set(mesh1, "kv", dk, dv,
                                    jnp.asarray(sk[None]),
                                    jnp.asarray(sv[None]))
    ref = hopscotch.HopscotchTable(kv.tables[0].keys.copy(),
                                   kv.tables[0].values.copy(), 8)
    ref_st = hopscotch.insert_many(ref, sk, [[5]])   # short host-side form
    np.testing.assert_array_equal(np.asarray(res.status[0]), ref_st)
    np.testing.assert_array_equal(np.asarray(nk[0]), ref.keys)
    np.testing.assert_array_equal(np.asarray(nv[0]), ref.values)
    g = store.sharded_get(mesh1, "kv", nk, nv,
                          jnp.asarray(sk[None]), method="redn")
    np.testing.assert_array_equal(np.asarray(g.values[0][0]), [5, 0])


# --- satellite: 24-bit key bound on the batched paths -------------------------

def test_batched_paths_reject_wide_keys(mesh1):
    kv = store.ShardedKV.build(1, 128, V)
    dk, dv = kv.device_arrays()
    wide = jnp.asarray([[0x1000000]], jnp.int32)
    neg = jnp.asarray([[-5]], jnp.int32)
    with pytest.raises(ValueError, match="24-bit"):
        store.sharded_get(mesh1, "kv", dk, dv, wide)
    with pytest.raises(ValueError, match="24-bit"):
        store.sharded_get(mesh1, "kv", dk, dv, neg)
    sv = jnp.zeros((1, 1, V), jnp.int32)
    with pytest.raises(ValueError, match="24-bit"):
        store.sharded_set(mesh1, "kv", dk, dv, wide, sv)
    with pytest.raises(ValueError, match="24-bit"):
        store.sharded_set(mesh1, "kv", dk, dv, neg, sv)


def test_service_batched_paths_reject_wide_keys():
    svc = failure.ShardedKVService.start([(5, [1, 2])])
    with pytest.raises(ValueError, match="24-bit"):
        svc.get_many(np.asarray([1 << 24], np.int64))
    with pytest.raises(ValueError, match="24-bit"):
        svc.set_many(np.asarray([1 << 24], np.int64),
                     np.asarray([[1, 2]], np.int64))
    # in-range keys still served; 0 stays a legal always-miss query
    g = svc.get_many(np.asarray([5, 0], np.int32))
    assert bool(g.found[0][0]) and not bool(g.found[0][1])


# --- satellite: serving caches keyed on mesh geometry -------------------------

def test_same_geometry_meshes_share_one_compiled_step():
    """Two same-geometry meshes must hit one cache entry — and the cache
    key must be a plain tuple of the geometry (axis names, shape, device
    ids), never the Mesh object, so the serving cache cannot grow with
    (or pin) per-call Mesh/device handles beyond one closure per
    distinct geometry."""
    m1 = Mesh(np.array(jax.devices()[:1]), ("kv",))
    m2 = Mesh(np.array(jax.devices()[:1]), ("kv",))
    g1 = store._mapped_get(m1, "kv", "redn", 1, 4, 8, 2)
    n_entries = len(store._MAPPED_CACHE)
    g2 = store._mapped_get(m2, "kv", "redn", 1, 4, 8, 2)
    assert g1 is g2
    assert len(store._MAPPED_CACHE) == n_entries   # no second entry
    s1 = store._mapped_set(m1, "kv", 1, 4, 8, 2, 512, 16, 8)
    s2 = store._mapped_set(m2, "kv", 1, 4, 8, 2, 512, 16, 8)
    assert s1 is s2
    for key in store._MAPPED_CACHE:
        assert not any(isinstance(part, Mesh) for part in key)
        hash(key)                                  # geometry is hashable
    # and the shared step serves both meshes' calls identically
    kv = store.ShardedKV.build(1, 128, 2)
    kv.set(9, [3, 4])
    dk, dv = kv.device_arrays()
    q = jnp.asarray([[9, 10, 0, 9]], jnp.int32)
    r1 = store.sharded_get(m1, "kv", dk, dv, q, capacity=4)
    r2 = store.sharded_get(m2, "kv", dk, dv, q, capacity=4)
    np.testing.assert_array_equal(np.asarray(r1.found),
                                  np.asarray(r2.found))
    np.testing.assert_array_equal(np.asarray(r1.values),
                                  np.asarray(r2.values))


def test_escalation_fuel_covers_large_unrolls(mesh1):
    """Regression: the displacer stage's step budget must scale with the
    unroll (`HopscotchShardWriter.fuel`), not a fixed multiple of
    max_steps — a 16-move ladder under max_steps=256 used to exhaust
    fuel mid-bubble and misreport a placeable key as needs-resize."""
    nb, h = 128, 8
    s_bound, m_bound = 24, 16
    t = hopscotch.make_table(nb, V, neighborhood=h)
    home = 30
    for pos in range(home, home + 23):       # pad-6 ladder: back=1 only
        k = _keys_with_home((pos - 6) % nb, 1, n_buckets=nb,
                            start=500 + 29 * pos, n_shards=1)[0]
        t.keys[pos % nb] = k
        t.values[pos % nb] = [k % 7, k % 11]
    z = _keys_with_home(home, 1, n_buckets=nb, start=60000, n_shards=1)[0]
    disp = programs.build_hopscotch_displacer(nb, V, h, s_bound, m_bound)
    assert disp.fuel > 8 * 256               # the old heuristic budget
    dk, dv = t.as_device()
    sk = np.asarray([z], np.int32)
    sv = np.asarray([[5, 6]], np.int32)
    res, nk, nv = store.sharded_set(
        mesh1, "kv", dk[None], dv[None], jnp.asarray(sk[None]),
        jnp.asarray(sv[None]), neighborhood=h, max_steps=256,
        max_search=s_bound, max_moves=m_bound)
    ref = hopscotch.HopscotchTable(t.keys.copy(), t.values.copy(), h)
    assert ref.set_full(z, [5, 6], s_bound, m_bound) \
        == hopscotch.SET_DISPLACED
    assert int(res.status[0][0]) == programs.SET_DISPLACED
    np.testing.assert_array_equal(np.asarray(nk[0]), ref.keys)
    np.testing.assert_array_equal(np.asarray(nv[0]), ref.values)


def test_live_masked_rows_may_hold_sentinel_keys(mesh1):
    """Rows an admission stage masked dead (live=False) are never
    dispatched, so out-of-range sentinels there must not raise — only
    live rows are validated."""
    kv = store.ShardedKV.build(1, 128, V)
    kv.set(9, [3, 4])
    dk, dv = kv.device_arrays()
    q = jnp.asarray([[9, -1]], jnp.int32)          # -1 sentinel, masked
    live = jnp.asarray([[True, False]])
    r = store.sharded_get(mesh1, "kv", dk, dv, q, live=live)
    assert bool(r.found[0][0]) and not bool(r.ok[0][1])
    with pytest.raises(ValueError, match="24-bit"):
        store.sharded_get(mesh1, "kv", dk, dv, q)  # unmasked: rejected


def test_sharded_set_on_tiny_shard_serves_writer_only(mesh1):
    """A shard smaller than the neighborhood cannot build a displacer
    (its unroll needs >= H probes) — the set path must still serve, with
    escalated rows resolving to SET_NEEDS_RESIZE exactly as the bounded
    oracle answers (a full wrap-covered table has nothing to bubble)."""
    nb = 4
    kv = store.ShardedKV.build(1, nb, V)
    dk, dv = kv.device_arrays()
    sk = np.asarray([11, 12, 13, 14, 15], np.int32)
    sv = np.stack([sk % 7, sk % 5], axis=1).astype(np.int32)
    res, nk, nv = store.sharded_set(mesh1, "kv", dk, dv,
                                    jnp.asarray(sk[None]),
                                    jnp.asarray(sv[None]))
    ref = hopscotch.HopscotchTable(kv.tables[0].keys.copy(),
                                   kv.tables[0].values.copy(), 8)
    ref_st = hopscotch.insert_many_displaced(ref, sk, sv,
                                             max_search=nb)
    np.testing.assert_array_equal(np.asarray(res.status[0]), ref_st)
    # 4 buckets absorb 4 inserts; the 5th is a genuine needs-resize
    assert sorted(np.asarray(res.status[0]).tolist()) == [2, 2, 2, 2, 5]
    np.testing.assert_array_equal(np.asarray(nk[0]), ref.keys)
    np.testing.assert_array_equal(np.asarray(nv[0]), ref.values)


def test_service_start_rejects_overfull_bootstrap():
    """Bootstrap items the bounded host insert cannot place must raise,
    not silently vanish into a later unexplained miss."""
    cl = _keys_with_home(3, 10, n_buckets=16, start=100)
    items = [(k, [1, 2]) for k in cl]
    with pytest.raises(ValueError, match="resize"):
        failure.ShardedKVService.start(items, buckets_per_shard=16)


def test_sharded_set_neighborhood_one_still_serves(mesh1):
    """H=1 (a degenerate single-bucket neighborhood) cannot build a
    displacer — its bubble window is empty — but the set path must keep
    serving: updates/inserts via the writer, escalated rows resolved to
    SET_NEEDS_RESIZE exactly as the bounded oracle answers."""
    nb = 64
    kv = store.ShardedKV.build(1, nb, V, neighborhood=1)
    dk, dv = kv.device_arrays()
    a = _keys_with_home(5, 1, n_buckets=nb)[0]
    b = _keys_with_home(5, 2, n_buckets=nb, start=a + 1)[1]
    sk = np.asarray([a, a, b], np.int32)   # insert, update, bucket-full
    sv = np.stack([sk % 7 + 1, sk % 5 + 1], axis=1).astype(np.int32)
    res, nk, nv = store.sharded_set(mesh1, "kv", dk, dv,
                                    jnp.asarray(sk[None]),
                                    jnp.asarray(sv[None]), neighborhood=1)
    ref = hopscotch.HopscotchTable(kv.tables[0].keys.copy(),
                                   kv.tables[0].values.copy(), 1)
    ref_st = hopscotch.insert_many_displaced(ref, sk, sv)
    np.testing.assert_array_equal(np.asarray(res.status[0]), ref_st)
    np.testing.assert_array_equal(
        np.asarray(res.status[0]),
        [programs.SET_INSERTED, programs.SET_UPDATED,
         programs.SET_NEEDS_RESIZE])
    np.testing.assert_array_equal(np.asarray(nk[0]), ref.keys)
    np.testing.assert_array_equal(np.asarray(nv[0]), ref.values)
