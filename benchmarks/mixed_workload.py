"""Mixed get/set serving (§4–5): the read-write workload the chain SET opens.

Memcached-style traffic is not read-only: the paper's integration keeps
the device-resident structure the *source of truth* while clients both
query and populate it.  This benchmark drives the sharded store with mixed
batches at two ratios — 95/5 (cache-like) and 50/50 (write-heavy) — on two
configurations:

* **redn** — gets execute the hopscotch *server* chain, sets the hopscotch
  *writer* chain (`store.sharded_set`), both at the owner shards against
  the authoritative device arrays: 1 RTT each, no host in either path.
* **two_sided baseline** — gets are host RPCs (`method="two_sided"`); sets
  run the pre-offload pattern this PR replaced: host-table insert plus a
  full ``(S, B)``/``(S, B, V)`` device re-upload per batch.

Every round's self-checks (recorded into ``BENCH_chains.json``):
the chain SET statuses are bit-exact with the batched host oracle
(`hopscotch.insert_many_displaced` — the writer + displacer escalation
replay), both configurations end with identical device
arrays, all live keys read back with their latest values on both get
paths, and a query of key 0 stays a miss (the ghost-hit regression).

Run: PYTHONPATH=src python -m benchmarks.mixed_workload        (smoke)
     PYTHONPATH=src python -m benchmarks.mixed_workload --long
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
KEY_SPACE = (1, 1 << 16)


def _value_of(key: int, round_: int) -> list:
    return [int(key) % 251 + round_, int(key) % 241]


def run_mixed(get_ratio: float, batch: int, rounds: int,
              seed: int = 0) -> dict:
    """Drive `rounds` mixed batches; returns measurements + self-checks."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from repro.kvstore import hopscotch, store

    rng = np.random.RandomState(seed)
    n_get = max(1, int(round(batch * get_ratio)))
    n_set = max(1, batch - n_get)

    kv = store.ShardedKV.build(1, N_BUCKETS, VAL_WORDS)
    seed_keys = rng.choice(np.arange(*KEY_SPACE), size=48, replace=False)
    for k in seed_keys:
        if not kv.set(int(k), _value_of(k, 0)):
            raise RuntimeError(f"seeding key {k} needs a resize")
    mesh = Mesh(np.array(jax.devices()[:1]), ("kv",))
    dk, dv = kv.device_arrays()

    # the two_sided baseline's host-side mirror (old pattern: host insert
    # + full device re-upload per batch)
    base_kv = store.ShardedKV.build(1, N_BUCKETS, VAL_WORDS)
    for k in seed_keys:
        if not base_kv.set(int(k), _value_of(k, 0)):
            raise RuntimeError(f"seeding key {k} needs a resize")
    bdk, bdv = base_kv.device_arrays()

    # the chain-set oracle mirror (checks only, not timed)
    oracle = hopscotch.HopscotchTable(kv.tables[0].keys.copy(),
                                      kv.tables[0].values.copy(), 8)

    latest = {int(k): _value_of(k, 0) for k in seed_keys}
    checks = dict(sets_bit_exact=True, arrays_agree=True,
                  reads_serve_latest=True, paths_agree=True,
                  query0_misses=True)
    redn_us, base_us = [], []
    statuses = np.zeros(6, np.int64)     # histogram of SET outcomes

    # the store compile-caches its shard_map serving steps per geometry,
    # so rounds after the first measure execution, not tracing
    def redn_round(dk, dv, gq, sk, sv):
        g = store.sharded_get(mesh, "kv", dk, dv, gq, method="redn")
        s, nk, nv = store.sharded_set(mesh, "kv", dk, dv, sk, sv)
        return g, s, nk, nv

    def base_get(bdk, bdv, gq):
        return store.sharded_get(mesh, "kv", bdk, bdv, gq,
                                 method="two_sided")

    for r in range(1, rounds + 1):
        known = np.asarray(sorted(latest), np.int32)
        get_q = rng.choice(known, size=n_get)
        set_upd = rng.choice(known, size=max(1, n_set // 2))
        set_new = rng.choice(np.arange(*KEY_SPACE), size=n_set
                             - len(set_upd))
        set_k = np.concatenate([set_upd, set_new]).astype(np.int32)
        set_v = np.asarray([_value_of(k, r) for k in set_k], np.int32)
        gq = jnp.asarray(get_q[None])
        sk, sv = jnp.asarray(set_k[None]), jnp.asarray(set_v[None])

        # --- redn: chain get + chain set, all device-resident ------------
        redn_us.append(common.timeit_us(
            lambda: jax.block_until_ready(redn_round(dk, dv, gq, sk, sv)),
            n=3, warmup=1))
        gres, sres, dk, dv = redn_round(dk, dv, gq, sk, sv)

        # --- baseline: host RPC get + host set with full re-upload -------
        def base_round(bdk=bdk, bdv=bdv, gq=gq):
            g = jax.block_until_ready(base_get(bdk, bdv, gq))
            # same two-pass order as the chain pipeline (fast pass, then
            # displacements) — an inline-displacing order can disagree
            # about which keys fit once the table is tight
            hopscotch.insert_many_displaced(base_kv.tables[0], set_k,
                                            set_v)
            nk, nv = base_kv.device_arrays()     # the old O(table) upload
            jax.block_until_ready((nk, nv))
            return g, nk, nv

        base_us.append(common.timeit_us(base_round, n=3, warmup=1))
        bres, bdk, bdv = base_round()

        # --- self-checks (gets ran against the pre-set-round state) -----
        gf = np.asarray(gres.found[0])
        gv = np.asarray(gres.values[0])
        bf = np.asarray(bres.found[0])
        want = np.asarray([latest[int(k)] for k in get_q], np.int32)
        checks["reads_serve_latest"] &= bool(gf.all()
                                             and (gv == want).all())
        checks["paths_agree"] &= bool((gf == bf).all()
                                      and (gv == np.asarray(
                                          bres.values[0])).all())

        st = np.asarray(sres.status[0])
        # the chain pipeline escalates needs-displacement rows to the
        # displacer stage, so the oracle replays both passes
        ref = hopscotch.insert_many_displaced(oracle, set_k, set_v)
        checks["sets_bit_exact"] &= bool((st == ref).all())
        checks["arrays_agree"] &= bool(
            np.array_equal(np.asarray(dk[0]), oracle.keys)
            and np.array_equal(np.asarray(dv[0]), oracle.values))
        np.add.at(statuses, np.clip(st, 0, 5), 1)
        for k, v, s in zip(set_k.tolist(), set_v.tolist(), st.tolist()):
            if s in (hopscotch.SET_UPDATED, hopscotch.SET_INSERTED,
                     hopscotch.SET_DISPLACED):
                latest[int(k)] = v

    q0 = store.sharded_get(mesh, "kv", dk, dv,
                           jnp.asarray(np.asarray([[0]], np.int32)))
    checks["query0_misses"] = not bool(np.asarray(q0.found).any())

    return {
        "get_ratio": get_ratio,
        "batch": batch,
        "rounds": rounds,
        "gets_per_round": n_get,
        "sets_per_round": int(n_set),
        "redn_us_per_round": float(np.mean(redn_us)),
        "baseline_us_per_round": float(np.mean(base_us)),
        "set_status_histogram": {
            "dropped": int(statuses[0]),
            "updated": int(statuses[1]),
            "inserted": int(statuses[2]),
            "needs_displacement": int(statuses[3]),   # always 0: escalated
            "displaced": int(statuses[4]),
            "needs_resize": int(statuses[5]),
        },
        "checks": checks,
    }


def main(out_path: str = OUT_PATH, long: bool = False):
    import jax

    batch, rounds = (96, 6) if long else (24, 3)
    mixes = {"95_5": run_mixed(0.95, batch, rounds, seed=1),
             "50_50": run_mixed(0.50, batch, rounds, seed=2)}

    results = {}
    if os.path.exists(out_path):
        with open(out_path) as f:
            results = json.load(f)
    results["mixed_workload"] = {
        "backend": jax.default_backend(),
        **mixes,
    }
    checks = results.setdefault("checks", {})
    for name, m in mixes.items():
        for c, ok in m["checks"].items():
            checks[f"mixed_{name}_{c}"] = bool(ok)
        checks[f"mixed_{name}_sets_applied"] = (
            m["set_status_histogram"]["updated"]
            + m["set_status_histogram"]["inserted"] > 0)

    rows = []
    for name, m in mixes.items():
        rows.append((f"mixed/{name}_redn", m["redn_us_per_round"],
                     f"chain get+set, batch={m['batch']}"))
        rows.append((f"mixed/{name}_two_sided_baseline",
                     m["baseline_us_per_round"],
                     "host RPC get + host set w/ full re-upload"))
    common.emit(rows)
    for name, ok in checks.items():
        if name.startswith("mixed"):
            print(f"check,{name},{'PASS' if ok else 'FAIL'}")

    with open(out_path, "w") as f:
        json.dump(results, f, indent=2)
    print(f"# wrote {os.path.abspath(out_path)}")
    return results


if __name__ == "__main__":
    compile_cache.enable()
    main(long="--long" in sys.argv[1:])
