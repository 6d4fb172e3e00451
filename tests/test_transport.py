"""Property tests for the RDMA-over-mesh transport invariants."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.rdma import transport


def _rank_quadratic(dest, live=None):
    """The O(B^2) reference formulation the sort/segment-cumsum replaced."""
    b = dest.shape[0]
    same = dest[None, :] == dest[:, None]
    earlier = jnp.tril(jnp.ones((b, b), jnp.bool_), k=-1)
    if live is not None:
        same = same & live[None, :]
    return jnp.sum(same & earlier, axis=1).astype(jnp.int32)


@settings(max_examples=30, deadline=None)
@given(st.data())
def test_rank_within_dest_is_a_valid_slotting(data):
    n = data.draw(st.integers(1, 24))
    dests = data.draw(st.lists(st.integers(0, 3), min_size=n, max_size=n))
    pos = np.asarray(transport.rank_within_dest(
        jnp.asarray(dests, jnp.int32)))
    # (dest, pos) pairs are unique and dense per destination
    seen = {}
    for d, p in zip(dests, pos):
        seen.setdefault(d, []).append(int(p))
    for d, ps in seen.items():
        assert sorted(ps) == list(range(len(ps))), (d, ps)


@settings(max_examples=40, deadline=None)
@given(st.data())
def test_rank_within_dest_matches_quadratic(data):
    """The sort/segment-cumsum formulation == the B x B mask version,
    with and without a live mask."""
    n = data.draw(st.integers(1, 48))
    dests = jnp.asarray(
        data.draw(st.lists(st.integers(0, 5), min_size=n, max_size=n)),
        jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(transport.rank_within_dest(dests)),
        np.asarray(_rank_quadratic(dests)))
    live = jnp.asarray(
        data.draw(st.lists(st.booleans(), min_size=n, max_size=n)))
    got = np.asarray(transport.rank_within_dest(dests, live))
    want = np.asarray(_rank_quadratic(dests, live))
    # live rows must agree exactly; non-live rows consume no slot, so only
    # their *live* successors' ranks are contractual
    np.testing.assert_array_equal(got[np.asarray(live)],
                                  want[np.asarray(live)])


def test_rank_within_dest_matches_quadratic_deterministic():
    """Seeded equivalence sweep (runs even without hypothesis)."""
    rng = np.random.RandomState(7)
    for _ in range(50):
        n = rng.randint(1, 64)
        dests = jnp.asarray(rng.randint(0, 6, n), jnp.int32)
        live = jnp.asarray(rng.rand(n) < 0.6)
        np.testing.assert_array_equal(
            np.asarray(transport.rank_within_dest(dests)),
            np.asarray(_rank_quadratic(dests)))
        np.testing.assert_array_equal(
            np.asarray(transport.rank_within_dest(dests, live)),
            np.asarray(_rank_quadratic(dests, live)))


def test_rank_within_dest_large_batch():
    """Batch 4096 (the scale the O(B log B) formulation exists for)."""
    rng = np.random.RandomState(0)
    dest = jnp.asarray(rng.randint(0, 64, size=4096), jnp.int32)
    pos = np.asarray(transport.rank_within_dest(dest))
    d = np.asarray(dest)
    for s in range(64):
        grp = pos[d == s]
        assert sorted(grp.tolist()) == list(range(len(grp)))


@settings(max_examples=20, deadline=None)
@given(st.data())
def test_dispatch_combine_roundtrip_identity(data):
    """On a 1-shard mesh: combine(f(dispatch(x))) == f(x) for elementwise f,
    with the over-capacity tail per destination flagged not-ok (a drop is
    reported, never silently aliased with a zero response)."""
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:1]), ("kv",))
    n = data.draw(st.integers(1, 16))
    cap = data.draw(st.integers(1, 16))
    vals = data.draw(st.lists(st.integers(1, 1000), min_size=n, max_size=n))
    payload = jnp.asarray(vals, jnp.int32)[:, None]
    dest = jnp.zeros((n,), jnp.int32)

    def body(p, d):
        recv, pos, ok = transport.dispatch(p, d, 1, cap, "kv")
        resp = recv * 2                      # the "offload chain"
        out = transport.combine(resp.reshape(1, cap, -1), d, pos, ok, "kv")
        return out, ok

    f = jax.shard_map(body, mesh=mesh,
                      in_specs=(jax.sharding.PartitionSpec(),) * 2,
                      out_specs=(jax.sharding.PartitionSpec(),) * 2,
                      check_vma=False)
    out, ok = f(payload, dest)
    out = np.asarray(out)[:, 0]
    ok = np.asarray(ok)
    for i, v in enumerate(vals):
        if i < cap:
            assert ok[i] and out[i] == 2 * v
        else:
            assert not ok[i]                 # dropped -> flagged, not missed


def test_dispatch_live_mask_frees_slots():
    """Deferred (not-live) requests consume no capacity slot: with the
    first half of a same-destination batch deferred, the second half all
    fits in a half-sized capacity window."""
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:1]), ("kv",))
    n, cap = 8, 4
    payload = jnp.arange(1, n + 1, dtype=jnp.int32)[:, None]
    dest = jnp.zeros((n,), jnp.int32)
    live = jnp.asarray([False] * 4 + [True] * 4)

    def body(p, d, lv):
        recv, pos, ok = transport.dispatch(p, d, 1, cap, "kv", lv)
        out = transport.combine(recv.reshape(1, cap, -1), d, pos, ok, "kv")
        return out, ok

    f = jax.shard_map(body, mesh=mesh,
                      in_specs=(jax.sharding.PartitionSpec(),) * 3,
                      out_specs=(jax.sharding.PartitionSpec(),) * 2,
                      check_vma=False)
    out, ok = f(payload, dest, live)
    assert not np.asarray(ok)[:4].any()
    assert np.asarray(ok)[4:].all()
    np.testing.assert_array_equal(np.asarray(out)[4:, 0],
                                  np.arange(5, n + 1))


def test_triggered_chain_stateful_serializes_and_threads_carry():
    """The SET wire pattern: the owner scans its receive window through a
    stateful step — each request observes every earlier one's writes, and
    over-capacity rows are dropped (ok=False) without touching state."""
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:1]), ("kv",))
    n, cap = 6, 4
    payload = jnp.arange(1, n + 1, dtype=jnp.int32)[:, None]
    dest = jnp.zeros((n,), jnp.int32)

    def step(carry, req):
        # "chain": append req to a running sum; respond with the sum so
        # far (request i sees requests 0..i) — zero-padded slots inert
        carry = carry + req[0]
        return carry, carry[None]

    def body(p, d):
        resp, ok, carry = transport.triggered_chain_stateful(
            step, jnp.zeros((), jnp.int32), p, d, 1, cap, "kv", 1)
        return resp, ok, carry[None]

    spec = jax.sharding.PartitionSpec()
    f = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec),
                      out_specs=(spec, spec, spec), check_vma=False)
    resp, ok, carry = f(payload, dest)
    assert np.asarray(ok)[:cap].all() and not np.asarray(ok)[cap:].any()
    # prefix sums prove sequential execution over the shared carry
    np.testing.assert_array_equal(np.asarray(resp)[:cap, 0],
                                  np.cumsum(np.arange(1, cap + 1)))
    # dropped rows: zeroed response, and their payloads never reached step
    assert (np.asarray(resp)[cap:] == 0).all()
    assert int(carry[0]) == np.arange(1, cap + 1).sum()


@settings(max_examples=40, deadline=None)
@given(st.data())
def test_escalation_subset_never_drops(data):
    """Two-stage dispatch (the SET path's displacement escalation): any
    subset of stage-1's admitted rows, re-ranked at the same capacity,
    stays within capacity — the escalation stage cannot introduce new
    drops, so stage-2 `ok` covers every escalated row."""
    n = data.draw(st.integers(1, 40))
    cap = data.draw(st.integers(1, 8))
    dests = jnp.asarray(
        data.draw(st.lists(st.integers(0, 3), min_size=n, max_size=n)),
        jnp.int32)
    live1 = jnp.asarray(
        data.draw(st.lists(st.booleans(), min_size=n, max_size=n)))
    ok1 = (transport.rank_within_dest(dests, live1) < cap) & live1
    subset = jnp.asarray(
        data.draw(st.lists(st.booleans(), min_size=n, max_size=n)))
    live2 = ok1 & subset
    pos2 = transport.rank_within_dest(dests, live2)
    ok2 = (pos2 < cap) & live2
    np.testing.assert_array_equal(np.asarray(ok2), np.asarray(live2))


def test_escalation_subset_never_drops_deterministic():
    """Seeded sweep of the same invariant (runs without hypothesis)."""
    rng = np.random.RandomState(11)
    for _ in range(50):
        n = rng.randint(1, 40)
        cap = rng.randint(1, 8)
        dests = jnp.asarray(rng.randint(0, 4, size=n), jnp.int32)
        live1 = jnp.asarray(rng.rand(n) < 0.7)
        ok1 = (transport.rank_within_dest(dests, live1) < cap) & live1
        live2 = ok1 & jnp.asarray(rng.rand(n) < 0.5)
        ok2 = (transport.rank_within_dest(dests, live2) < cap) & live2
        np.testing.assert_array_equal(np.asarray(ok2), np.asarray(live2))
