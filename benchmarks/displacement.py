"""Displaced-insert serving (§3.5 + §5.6): the bubble on-chain vs the
host slow path it replaced.

Before this PR a neighborhood-full insert fell back to the host: sync
the full shard table from device, bubble on the CPU, push the touched
rows back — the one SET path that died with the driver.  Now it runs as
the *displacer chain* (``programs.build_hopscotch_displacer``) at the
owner shard, escalated automatically by ``store.sharded_set``.  This
benchmark measures both patterns on the same workloads:

* **displaced-insert latency** — a single neighborhood-full insert
  through (a) the chain pipeline (writer stage + displacer stage) and
  (b) a faithful replay of the old host slow path (device->host sync,
  host bubble, per-row push-back).
* **load-factor sweep** — batches of fresh inserts against tables filled
  to ~0.5-0.9: displaced fraction, needs-resize fraction, and both
  patterns' wall-clock per batch.

Self-checks recorded into ``BENCH_chains.json``: every round is
bit-exact with the bounded host oracle
(``hopscotch.insert_many_displaced``), vacated buckets' value rows are
zeroed, needs-resize rows leave the arrays untouched, and the
engineered displacement round actually displaces.

Run: PYTHONPATH=src python -m benchmarks.displacement        (smoke)
     PYTHONPATH=src python -m benchmarks.displacement --long
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np

from benchmarks import common
from repro import compile_cache

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_chains.json")

N_BUCKETS = 128
VAL_WORDS = 2
H = 8


def _keys_with_home(bucket, count, n_buckets=N_BUCKETS, start=1,
                    n_shards=1):
    from repro.kvstore import store
    return store.keys_homed_at(bucket, count, n_buckets, start=start,
                               n_shards=n_shards)


def _host_slow_path(keys_dev, vals_dev, sk, sv):
    """The pattern this PR deleted from ``failure.ShardedKVService.set``:
    full device->host sync, host bubble, per-row ``.at[].set`` push-back.
    Returns the updated device arrays (for timing parity with the chain
    path, which also returns new arrays)."""
    import jax.numpy as jnp

    from repro.kvstore import hopscotch

    t = hopscotch.HopscotchTable(np.asarray(keys_dev)[0].copy(),
                                 np.asarray(vals_dev)[0].copy(), H)
    touched = set()
    for k, v in zip(sk.tolist(), sv.tolist()):
        kb, vb = t.keys.copy(), t.values.copy()
        if t.set_full(int(k), v) != hopscotch.SET_NEEDS_RESIZE:
            touched.update(np.where((t.keys != kb)
                                    | (t.values != vb).any(1))[0].tolist())
    rows = np.asarray(sorted(touched), np.int32)
    if len(rows):
        keys_dev = keys_dev.at[0, rows].set(jnp.asarray(t.keys[rows]))
        vals_dev = vals_dev.at[0, rows].set(jnp.asarray(t.values[rows]))
    return keys_dev, vals_dev


def run_round(load_factor: float, batch: int, seed: int = 0) -> dict:
    """One load-factor point: fresh-insert batch, chain vs host."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from repro.kvstore import hopscotch, store

    rng = np.random.RandomState(seed)
    t = hopscotch.make_table(N_BUCKETS, VAL_WORDS, neighborhood=H)
    k, attempts = 1, 0
    while (t.keys != hopscotch.EMPTY).sum() < int(N_BUCKETS * load_factor):
        attempts += 1
        if attempts > 64 * N_BUCKETS:
            # bounded insert can dead-end near full occupancy; make the
            # stall visible instead of spinning on the key stream
            raise RuntimeError(
                f"table fill stalled at load factor "
                f"{(t.keys != hopscotch.EMPTY).sum() / N_BUCKETS:.2f} "
                f"(target {load_factor}) — needs-resize territory")
        t.insert(int(k), [int(k) % 97, int(k) % 89])
        k += 1 + int(rng.randint(64))
    mesh = Mesh(np.array(jax.devices()[:1]), ("kv",))
    dk, dv = t.as_device()
    dk, dv = dk[None], dv[None]

    sk = (1 + rng.randint(1 << 16, 1 << 22, size=batch)).astype(np.int32)
    sv = np.stack([sk % 251, sk % 241], axis=1).astype(np.int32)
    skj, svj = jnp.asarray(sk[None]), jnp.asarray(sv[None])

    def chain_round():
        res, nk, nv = store.sharded_set(mesh, "kv", dk, dv, skj, svj)
        jax.block_until_ready((res.status, nk, nv))
        return res, nk, nv

    chain_us = common.timeit_us(chain_round, n=3, warmup=1)
    res, nk, nv = chain_round()

    def host_round():
        jax.block_until_ready(_host_slow_path(dk, dv, sk, sv))

    host_us = common.timeit_us(host_round, n=3, warmup=1)

    # --- self-checks -----------------------------------------------------
    ref = hopscotch.HopscotchTable(t.keys.copy(), t.values.copy(), H)
    ref_st = hopscotch.insert_many_displaced(ref, sk, sv)
    st = np.asarray(res.status[0])
    bit_exact = bool((st == ref_st).all()
                     and np.array_equal(np.asarray(nk[0]), ref.keys)
                     and np.array_equal(np.asarray(nv[0]), ref.values))
    nk0, nv0 = np.asarray(nk[0]), np.asarray(nv[0])
    vacated_zeroed = bool((nv0[nk0 == hopscotch.EMPTY] == 0).all())

    return {
        "load_factor": float((t.keys != hopscotch.EMPTY).sum()
                             / N_BUCKETS),
        "batch": batch,
        "chain_us_per_batch": float(chain_us),
        "host_slow_path_us_per_batch": float(host_us),
        "displaced": int((st == hopscotch.SET_DISPLACED).sum()),
        "inserted": int((st == hopscotch.SET_INSERTED).sum()),
        "updated": int((st == hopscotch.SET_UPDATED).sum()),
        "needs_resize": int((st == hopscotch.SET_NEEDS_RESIZE).sum()),
        "bit_exact": bit_exact,
        "vacated_rows_zeroed": vacated_zeroed,
    }


def run_single_displaced_insert() -> dict:
    """The engineered latency point: one neighborhood-full insert."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from repro.kvstore import hopscotch, store

    t = hopscotch.make_table(N_BUCKETS, VAL_WORDS, neighborhood=H)
    home = 40
    for d in range(H):
        kk = _keys_with_home((home + d) % N_BUCKETS, 1,
                             start=200 + 97 * d)[0]
        assert t.insert(kk, [kk % 7, kk % 11])
    z = _keys_with_home(home, 1, start=50000)[0]
    mesh = Mesh(np.array(jax.devices()[:1]), ("kv",))
    dk, dv = t.as_device()
    dk, dv = dk[None], dv[None]
    skj = jnp.asarray(np.asarray([[z]], np.int32))
    svj = jnp.asarray(np.asarray([[[9, 9]]], np.int32))
    sk = np.asarray([z], np.int32)
    sv = np.asarray([[9, 9]], np.int32)

    def chain_one():
        res, nk, nv = store.sharded_set(mesh, "kv", dk, dv, skj, svj)
        jax.block_until_ready((res.status, nk, nv))
        return res, nk, nv

    chain_us = common.timeit_us(chain_one, n=5, warmup=1)
    res, nk, nv = chain_one()

    def host_one():
        jax.block_until_ready(_host_slow_path(dk, dv, sk, sv))

    host_us = common.timeit_us(host_one, n=5, warmup=1)

    ref = hopscotch.HopscotchTable(t.keys.copy(), t.values.copy(), H)
    ref_status = ref.set_full(z, [9, 9])
    return {
        "chain_us": float(chain_us),
        "host_slow_path_us": float(host_us),
        "status": int(np.asarray(res.status)[0, 0]),
        "displaced": bool(int(np.asarray(res.status)[0, 0])
                          == hopscotch.SET_DISPLACED == ref_status),
        "bit_exact": bool(
            np.array_equal(np.asarray(nk[0]), ref.keys)
            and np.array_equal(np.asarray(nv[0]), ref.values)),
    }


def main(out_path: str = OUT_PATH, long: bool = False):
    import jax

    lfs = (0.5, 0.7, 0.85, 0.9) if long else (0.7, 0.9)
    batch = 32 if long else 12
    sweep = {f"{lf:.2f}": run_round(lf, batch, seed=int(lf * 100))
             for lf in lfs}
    single = run_single_displaced_insert()

    results = {}
    if os.path.exists(out_path):
        with open(out_path) as f:
            results = json.load(f)
    results["displacement"] = {
        "backend": jax.default_backend(),
        "single_displaced_insert": single,
        "load_factor_sweep": sweep,
    }
    checks = results.setdefault("checks", {})
    checks["displacement_single_displaced"] = bool(single["displaced"])
    checks["displacement_single_bit_exact"] = bool(single["bit_exact"])
    for name, r in sweep.items():
        checks[f"displacement_lf{name}_bit_exact"] = bool(r["bit_exact"])
        checks[f"displacement_lf{name}_vacated_zeroed"] = bool(
            r["vacated_rows_zeroed"])
    # at the top of the sweep the bubble must actually be exercised
    top = sweep[f"{max(lfs):.2f}"]
    checks["displacement_sweep_exercises_bubble"] = bool(
        top["displaced"] + top["needs_resize"] > 0)
    with open(out_path, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)

    rows = [("displacement/single_chain", single["chain_us"],
             "writer+displacer stages, 1 request"),
            ("displacement/single_host_slow_path",
             single["host_slow_path_us"],
             "device->host sync + host bubble + row push-back")]
    for name, r in sweep.items():
        rows.append((f"displacement/lf{name}_chain",
                     r["chain_us_per_batch"],
                     f"batch={r['batch']};displaced={r['displaced']};"
                     f"resize={r['needs_resize']}"))
        rows.append((f"displacement/lf{name}_host",
                     r["host_slow_path_us_per_batch"],
                     f"batch={r['batch']}"))
    common.emit(rows)
    for name, ok in checks.items():
        if name.startswith("displacement"):
            print(f"check,{name},{'PASS' if ok else 'FAIL'}")
    return results


if __name__ == "__main__":
    compile_cache.enable()
    main(long="--long" in sys.argv)
