"""The SET writer scan that carries the writer's image through a call.

The steady-state sharded SET builds the writer's whole image once per
call and per owner, and each request resets only the words outside the
table and value rows (``HopscotchShardWriter.run_carried``).  The
whole-image step (``run_one``: rebuild, run, commit) stays as the oracle.
These tests hold the carried scan to that oracle and to
``hopscotch.insert_many`` bit for bit, over seeded batches of updates,
inserts, neighborhood-full inserts, duplicate keys and key-0 padding; on
the sharded path they add the displacer stage, on one device and on four;
and they count the images each path builds.
"""
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro import obs
from repro.core import faults as faults_mod
from repro.core import programs
from repro.kvstore import hopscotch, store
from repro.rdma import failure

NB, H, V = 256, 8, 7
HERE = pathlib.Path(__file__).parent


def _table(rng, n_buckets=NB, n_shards=1, shard=0):
    """A half-full table with one full neighborhood (home ``full``): one
    resident homed at each of its H buckets, placed before the rest.
    Returns ``(table, full, items)``, ``items`` in the order placed."""
    t = hopscotch.make_table(n_buckets, V, H)
    full = int(rng.integers(0, n_buckets))
    items = []

    def place(k):
        v = rng.integers(-2**31, 2**31, V).tolist()
        if t.insert(k, v):
            items.append((k, v))

    for d in range(H):
        place(store.keys_homed_at((full + d) % n_buckets, 1, n_buckets,
                                  start=int(rng.integers(1, 1 << 20)),
                                  n_shards=n_shards, shard=shard)[0])
    assert len(items) == H
    while len(items) < n_buckets // 2:
        k = int(rng.integers(1, 1 << 24))
        if n_shards == 1 or int(store.shard_of(k, n_shards)) == shard:
            place(k)
    return t, full, items


def _batch(rng, t, full, width, n_buckets=NB, n_shards=1, shard=0):
    """``width`` rows: updates, inserts, two neighborhood-full inserts at
    ``full``, a duplicate of each kind, and key-0 padding."""
    resident = t.keys[t.keys != 0]
    fresh = [k for k in rng.integers(1, 1 << 24, 4 * width).tolist()
             if k not in set(resident.tolist())
             and (n_shards == 1 or int(store.shard_of(k, n_shards)) == shard)]
    stuck = store.keys_homed_at(full, 2, n_buckets, start=1 << 22,
                                n_shards=n_shards, shard=shard)
    upd = rng.choice(resident, 3, replace=False).tolist()
    ins = fresh[:3]
    keys = upd + ins + stuck + [upd[0], ins[0], stuck[0]]
    keys += [0] * (width - len(keys))
    keys = np.asarray(keys, np.int32)
    rng.shuffle(keys)
    vals = rng.integers(-2**31, 2**31, (width, V)).astype(np.int32)
    vals[keys == 0] = 0
    return keys, vals


@jax.jit
def _carried(keys, vals, payloads):
    """The carried scan of the sharded path, without the routing."""
    w = programs.build_hopscotch_writer(keys.shape[0], V, H)
    step = store._guarded_step(w.run_carried, 512)
    (image,), status = jax.lax.scan(step, (w.device_image(keys, vals),),
                                    payloads)
    return (status[:, 0], *w.image_arrays(image))


@pytest.mark.parametrize("seed", [11, 2654435769, 3141592653])
def test_carried_scan_matches_the_per_row_scan_and_the_oracle(seed):
    rng = np.random.default_rng(seed)
    t, full, _ = _table(rng)
    sk, sv = _batch(rng, t, full, 24)
    keys, vals = t.as_device()
    w = programs.build_hopscotch_writer(NB, V, H)
    q = jnp.asarray(sk)
    payloads = w.device_payloads(q, hopscotch.bucket_of(q, NB),
                                 jnp.asarray(sv))
    got = [np.asarray(a) for a in _carried(keys, vals, payloads)]
    per_row = [np.asarray(a) for a in w.set_many(
        keys, vals, q, hopscotch.bucket_of(q, NB), jnp.asarray(sv))]
    for a, b in zip(got, per_row):
        np.testing.assert_array_equal(a, b)

    live = sk != 0
    want = hopscotch.insert_many(t, sk[live], sv[live])
    np.testing.assert_array_equal(got[0][live], want)
    assert (got[0][~live] == 0).all()
    np.testing.assert_array_equal(got[1], t.keys)
    np.testing.assert_array_equal(got[2], t.values)
    kinds = set(want.tolist())
    assert {programs.SET_UPDATED, programs.SET_INSERTED,
            programs.SET_NEEDS_DISPLACEMENT} <= kinds, kinds


@pytest.mark.parametrize("seed", [5, 4294967291])
def test_a_row_changes_the_rows_only_where_it_applied(seed):
    """Row by row: a row the writer does not apply (needs displacement,
    key 0) leaves the table and value rows of the image bit-identical;
    an applied row changes its bucket's key word (insert) and value row,
    and no other word of the range."""
    rng = np.random.default_rng(seed)
    t, full, _ = _table(rng)
    sk, sv = _batch(rng, t, full, 16)
    keys, vals = t.as_device()
    w = programs.build_hopscotch_writer(NB, V, H)
    lo, hi = w.rows
    q = jnp.asarray(sk)
    payloads = w.device_payloads(q, hopscotch.bucket_of(q, NB),
                                 jnp.asarray(sv))
    run = jax.jit(lambda m, p: w.run_carried(m, p, 512))
    image = w.device_image(keys, vals)
    np.testing.assert_array_equal(np.asarray(image),
                                  np.asarray(w.device_state(keys, vals).mem))
    seen = set()
    for i in range(len(sk)):
        status, after = run(image, payloads[i])
        status = int(status)
        before_rows = np.asarray(image[lo:hi])
        changed = np.flatnonzero(np.asarray(after[lo:hi]) != before_rows)
        if status in (programs.SET_UPDATED, programs.SET_INSERTED):
            k, v = (np.asarray(a) for a in w.image_arrays(after))
            (row,) = np.flatnonzero(k == sk[i])
            np.testing.assert_array_equal(v[row], sv[i])
            vrow = w.values_base - lo + row * V
            allowed = set(range(vrow, vrow + V))
            if status == programs.SET_INSERTED:
                allowed.add(row * programs.BUCKET_WORDS)
            assert set(changed.tolist()) <= allowed, (i, status)
        else:
            assert changed.size == 0, (i, status)
        seen.add(status)
        image = after
    assert {0, programs.SET_UPDATED, programs.SET_INSERTED,
            programs.SET_NEEDS_DISPLACEMENT} <= seen, seen
    # a zero payload run directly (not skipped) touches no row either
    _, after = run(image, jnp.zeros_like(payloads[0]))
    np.testing.assert_array_equal(np.asarray(after[lo:hi]),
                                  np.asarray(image[lo:hi]))


@pytest.fixture(scope="module")
def mesh1():
    return Mesh(np.array(jax.devices()[:1]), ("kv",))


@pytest.mark.parametrize("seed", [3141592653, 2971215073])
def test_sharded_set_matches_the_per_row_path_and_the_oracle(mesh1, seed):
    """One owner: the carried scan plus the displacer stage against the
    whole-image path (the faulted path with no fault armed, row for row
    ``run_one``) and ``insert_many_displaced``.  On these seeds the first
    neighborhood-full insert finds a free bucket to bubble home."""
    rng = np.random.default_rng(seed)
    t, full, _ = _table(rng)
    sk, sv = _batch(rng, t, full, 16)
    dk, dv = t.as_device()
    args = (mesh1, "kv", dk[None], dv[None], jnp.asarray(sk[None]),
            jnp.asarray(sv[None]))
    res, nk, nv = store.sharded_set(*args)
    per_row, pk, pv = store.sharded_set(
        *args, faults=faults_mod.FaultPlan.none(sk[None].shape))
    np.testing.assert_array_equal(np.asarray(res.status),
                                  np.asarray(per_row.status))
    np.testing.assert_array_equal(np.asarray(nk), np.asarray(pk))
    np.testing.assert_array_equal(np.asarray(nv), np.asarray(pv))

    live = sk != 0
    want = hopscotch.insert_many_displaced(t, sk[live], sv[live])
    assert np.asarray(res.ok[0])[live].all()
    np.testing.assert_array_equal(np.asarray(res.status[0])[live], want)
    np.testing.assert_array_equal(np.asarray(nk[0]), t.keys)
    np.testing.assert_array_equal(np.asarray(nv[0]), t.values)
    assert (want == programs.SET_DISPLACED).sum() >= 1
    assert int(res.escalated[0]) == 3       # two stuck keys and a repeat


def test_sharded_set_four_devices_matches_the_oracle():
    """Four owners on four host devices, every source sending to every
    owner (a fresh process: the device count is fixed when JAX starts)."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(HERE.parent / "src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    r = subprocess.run(
        [sys.executable, str(HERE / "multidevice" / "set_carried_main.py")],
        capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    got = json.loads(r.stdout.strip().splitlines()[-1])
    assert got["devices"] == 4
    assert got["status_mismatches"] == got["table_mismatches"] == 0
    assert got["per_row_mismatches"] == 0
    assert got["served"] == got["live"] == 32
    assert min(got["escalated"]) >= 1
    assert got["image_builds"] == [1 + e for e in got["escalated"]]


# --- the image_builds counter ------------------------------------------------

@pytest.mark.parametrize("path", ["carried", "faulted", "racing"])
def test_image_builds_count_the_images_each_path_built(mesh1, path):
    """1 + escalated where the writer scan carries its image; one per live
    row where each row builds its own (the faulted path); one per lap of
    the racing writers; each plus one per displacer row."""
    rng = np.random.default_rng(99)
    t, full, _ = _table(rng)
    sk, sv = _batch(rng, t, full, 16)
    dk, dv = t.as_device()
    kwargs = {"carried": {},
              "faulted": {"faults": faults_mod.FaultPlan.none((1, 16))},
              "racing": {"n_writers": 2}}[path]
    res, _, _ = store.sharded_set(mesh1, "kv", dk[None], dv[None],
                                  jnp.asarray(sk[None]),
                                  jnp.asarray(sv[None]), **kwargs)
    scanned, escalated = int(res.scanned[0]), int(res.escalated[0])
    assert scanned == int((sk != 0).sum()) and escalated >= 1
    first = {"carried": 1, "faulted": scanned, "racing": 16 // 2}[path]
    assert np.asarray(res.image_builds).tolist() == [first + escalated]
    assert np.asarray(res.image_builds).dtype == np.int32


def test_traced_set_counters_carry_the_image_builds(monkeypatch, tmp_path):
    """While tracing, a SET call's ``kv.counters`` span reads the images
    the owner built: one for the writer scan and one per displacer row."""
    rng = np.random.default_rng(3)
    t, full, items = _table(rng)
    sk, sv = _batch(rng, t, full, 16)
    svc = failure.ShardedKVService.start(items, n_shards=1,
                                         buckets_per_shard=NB, val_words=V)
    np.testing.assert_array_equal(svc.kv.tables[0].keys, t.keys)
    marks = []
    monkeypatch.setattr(obs, "mark", lambda name, **a: marks.append(a))
    jax.profiler.start_trace(str(tmp_path))
    try:
        res = svc.set_many(sk[None], sv[None])
        svc.get_many(np.asarray([[int(sk[sk != 0][0])]], np.int32))
    finally:
        jax.profiler.stop_trace()
    (got,) = [m for m in marks if m.get("kind") == "set"]
    assert got["escalated"] == int(res.escalated[0]) >= 1
    assert got["image_builds"] == 1 + got["escalated"]
    assert got["scanned"] == int((sk != 0).sum())


def test_the_displacer_refuses_a_carried_image():
    """A failed bubble leaves moved rows in its image, so the displacer,
    which inherits the writer's layout, must not run on a carried one."""
    disp = programs.build_hopscotch_displacer(NB, V, H, 16, 8)
    with pytest.raises(NotImplementedError, match="rebuilt image"):
        disp.run_carried(disp.state0.mem, jnp.zeros((1 + V + 1,), jnp.int32))
