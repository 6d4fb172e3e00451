"""Online resize while serving (§5.6 extension): what a get costs while
the table is growing, and that growth actually completes under load.

The migrating store serves from a *double frame*: gets probe the doubled
frame first and fall back to the old one (gated per request on the
owner's migration watermark), so a mid-resize get pays up to a second
chain stage — the price of never pausing the service.  This benchmark
measures that price and pins the correctness claims that make it
meaningful:

* **get latency** — the same query batch through (a) the quiesced
  single-frame store, (b) the double-frame store at half-migrated
  watermark, (c) the post-cutover doubled store.
* **growth under load** — the full migration driven quantum by quantum
  with a get batch interleaved after *every* quantum: per-quantum
  serving stays authoritative (``ok`` everywhere) and bit-exact with the
  two-frame oracle, and the final cutover table equals
  ``HopscotchTable.grow(step=quantum)`` exactly.
* **forced growth** — the §5.6 scenario: an insert the bounded bubble
  cannot place auto-escalates into an incremental resize on the service
  (driver *crashed* first) and still lands.

Self-checks recorded into ``BENCH_chains.json`` (``resize`` section).

Run: PYTHONPATH=src python -m benchmarks.resize          (smoke)
     PYTHONPATH=src python -m benchmarks.resize --long
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np

from benchmarks import common
from repro import compile_cache

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_chains.json")

N_BUCKETS = 64
VAL_WORDS = 2
H = 8


def _filled_table(n_keys, seed=0):
    from repro.kvstore import hopscotch

    t = hopscotch.make_table(N_BUCKETS, VAL_WORDS, neighborhood=H)
    rng = np.random.RandomState(seed)
    ks, k = [], 1
    while len(ks) < n_keys:
        if t.insert(k, [k % 97 + 1, k % 89 + 1]):
            ks.append(k)
        k += 1 + int(rng.randint(8))
    return t, ks


def _oracle_double_get(rs, q):
    import jax.numpy as jnp

    from repro.kvstore import hopscotch

    fn, vn = hopscotch.lookup(rs.new_keys[0], rs.new_vals[0],
                              jnp.asarray(q, jnp.int32), H)
    fo, vo = hopscotch.lookup(rs.keys[0], rs.vals[0],
                              jnp.asarray(q, jnp.int32), H)
    f = np.asarray(fn) | np.asarray(fo)
    v = np.where(np.asarray(fn)[:, None], np.asarray(vn), np.asarray(vo))
    return f, v


def run_get_latency(batch: int, seed: int = 0) -> dict:
    """Gets during migration vs the quiesced baseline."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from repro.kvstore import store

    t, ks = _filled_table(int(N_BUCKETS * 0.45), seed=seed)
    mesh = Mesh(np.array(jax.devices()[:1]), ("kv",))
    dk, dv = t.as_device()
    dk, dv = dk[None], dv[None]
    rng = np.random.RandomState(seed + 1)
    q = np.asarray(rng.choice(ks, size=batch), np.int32)
    qj = jnp.asarray(q[None])

    def quiesced():
        g = store.sharded_get(mesh, "kv", dk, dv, qj, neighborhood=H)
        jax.block_until_ready(g.values)
        return g

    base_us = common.timeit_us(quiesced, n=10, warmup=2)

    rs = store.begin_resize(dk, dv)
    while int(np.asarray(rs.watermark)[0]) < N_BUCKETS // 2:
        rs, _ = store.sharded_resize(mesh, "kv", rs, step=8,
                                     neighborhood=H)

    def migrating():
        g = store.sharded_get_migrating(mesh, "kv", rs, qj, neighborhood=H)
        jax.block_until_ready(g.values)
        return g

    mig_us = common.timeit_us(migrating, n=10, warmup=2)
    g = migrating()
    f_ref, v_ref = _oracle_double_get(rs, q)
    mid_bit_exact = bool(
        np.array_equal(np.asarray(g.found[0]), f_ref)
        and np.array_equal(np.asarray(g.values[0]), v_ref)
        and np.asarray(g.ok[0]).all())

    while not store.resize_done(rs):
        rs, _ = store.sharded_resize(mesh, "kv", rs, step=8,
                                     neighborhood=H)
    nk, nv = store.finish_resize(rs)

    def cutover():
        g = store.sharded_get(mesh, "kv", nk, nv, qj, neighborhood=H)
        jax.block_until_ready(g.values)
        return g

    cut_us = common.timeit_us(cutover, n=10, warmup=2)
    g2 = cutover()
    post_ok = bool(np.asarray(g2.found[0]).all())

    return {
        "batch": batch,
        "quiesced_us_per_batch": float(base_us),
        "migrating_us_per_batch": float(mig_us),
        "post_cutover_us_per_batch": float(cut_us),
        "migrating_overhead_x": float(mig_us / base_us),
        "mid_resize_bit_exact": mid_bit_exact,
        "post_cutover_all_found": post_ok,
    }


def run_growth_under_load(step: int = 8, seed: int = 3) -> dict:
    """Drive a full migration with a get batch after every quantum."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from repro.kvstore import hopscotch, store

    t, ks = _filled_table(int(N_BUCKETS * 0.5), seed=seed)
    ref = hopscotch.HopscotchTable(t.keys.copy(), t.values.copy(), H)
    mesh = Mesh(np.array(jax.devices()[:1]), ("kv",))
    dk, dv = t.as_device()
    rs = store.begin_resize(dk[None], dv[None])
    q = np.asarray(ks + [0, 999983], np.int32)
    qj = jnp.asarray(q[None])

    quanta = 0
    served_ok = True
    bit_exact = True
    moved = discarded = escalated = 0
    while not store.resize_done(rs):
        rs, rep = store.sharded_resize(mesh, "kv", rs, step=step,
                                       neighborhood=H)
        quanta += 1
        moved += int(np.asarray(rep.moved).sum())
        discarded += int(np.asarray(rep.discarded).sum())
        escalated += int(np.asarray(rep.escalated).sum())
        g = store.sharded_get_migrating(mesh, "kv", rs, qj,
                                        neighborhood=H)
        served_ok &= bool(np.asarray(g.ok[0]).all())
        f_ref, v_ref = _oracle_double_get(rs, q)
        bit_exact &= bool(
            np.array_equal(np.asarray(g.found[0]), f_ref)
            and np.array_equal(np.asarray(g.values[0]), v_ref))

    nk, nv = store.finish_resize(rs)
    grown = ref.grow(step=step)
    cutover_exact = bool(
        np.array_equal(np.asarray(nk[0]), grown.keys)
        and np.array_equal(np.asarray(nv[0]), grown.values))
    return {
        "step": step,
        "quanta": quanta,
        "moved": moved,
        "discarded": discarded,
        "escalated": escalated,
        "serving_never_stopped": served_ok,
        "mid_resize_bit_exact": bit_exact,
        "cutover_bit_exact": cutover_exact,
    }


def run_forced_growth() -> dict:
    """§5.6: the growth-forcing insert, host driver dead, timed."""
    from repro.kvstore import store as kv_store
    from repro.rdma import failure

    cl = kv_store.keys_homed_at(7, 9, N_BUCKETS, start=1, n_shards=1)
    items = [(k, [k % 9 + 1, k % 5 + 1]) for k in cl[:8]]
    for d in range(H, H + 24):
        kk = kv_store.keys_homed_at((7 + d) % N_BUCKETS, 1, N_BUCKETS,
                                    start=3000 + 7 * d, n_shards=1)[0]
        items.append((kk, [kk % 9 + 1, kk % 5 + 1]))
    svc = failure.ShardedKVService.start(items,
                                         buckets_per_shard=N_BUCKETS)
    svc.resize_quantum = 16
    svc.crash_host()
    z = cl[8]
    t0 = common.time.perf_counter()
    landed = svc.set(z, [42, 43])
    grow_us = (common.time.perf_counter() - t0) * 1e6
    svc.drive_resize()
    g = svc.get_many(np.asarray([z], np.int32))
    return {
        "forced_insert_us": float(grow_us),
        "landed": bool(landed),
        "resized_while_dead": bool(svc.resizes_completed == 1
                                   and not svc.host_alive()),
        "value_served_post_cutover": bool(
            np.asarray(g.found[0])[0]
            and np.asarray(g.values[0][0]).tolist() == [42, 43]),
    }


def main(out_path: str = OUT_PATH, long: bool = False):
    import jax

    batch = 32 if long else 12
    lat = run_get_latency(batch)
    load = run_growth_under_load(step=8 if not long else 4)
    forced = run_forced_growth()

    results = {}
    if os.path.exists(out_path):
        with open(out_path) as f:
            results = json.load(f)
    results["resize"] = {
        "backend": jax.default_backend(),
        "get_latency": lat,
        "growth_under_load": load,
        "forced_growth": forced,
    }
    checks = results.setdefault("checks", {})
    checks["resize_mid_get_bit_exact"] = bool(
        lat["mid_resize_bit_exact"] and load["mid_resize_bit_exact"])
    checks["resize_serving_never_stops"] = bool(
        load["serving_never_stopped"])
    checks["resize_cutover_matches_grow_oracle"] = bool(
        load["cutover_bit_exact"] and lat["post_cutover_all_found"])
    checks["resize_forced_growth_lands_driver_dead"] = bool(
        forced["landed"] and forced["resized_while_dead"]
        and forced["value_served_post_cutover"])
    with open(out_path, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)

    rows = [
        ("resize/get_quiesced", lat["quiesced_us_per_batch"],
         f"batch={lat['batch']};single frame"),
        ("resize/get_migrating", lat["migrating_us_per_batch"],
         f"batch={lat['batch']};double frame at w=n/2;"
         f"overhead={lat['migrating_overhead_x']:.2f}x"),
        ("resize/get_post_cutover", lat["post_cutover_us_per_batch"],
         f"batch={lat['batch']};doubled frame"),
        ("resize/forced_growth_insert", forced["forced_insert_us"],
         "begin_resize + re-issued insert, driver dead"),
    ]
    common.emit(rows)
    for name, ok in checks.items():
        if name.startswith("resize"):
            print(f"check,{name},{'PASS' if ok else 'FAIL'}")
    return results


if __name__ == "__main__":
    compile_cache.enable()
    main(long="--long" in sys.argv)
