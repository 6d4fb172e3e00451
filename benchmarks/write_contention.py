"""Multi-writer write contention: racing writer QPs over one shard table.

The §3.5/§5.5 write-side scaling question: when N pre-posted writer
chains race their claim CASes against ONE shared hopscotch table
(`programs.build_multi_writer_group`), what does contention cost, and
does a fair scheduler actually keep the writers fair?

Two workloads, swept over 1/2/4/8 writers:

* **hot-key hammer** — every writer inserts a distinct key homed at the
  SAME bucket, so all claim CASes fight over one neighborhood; losers
  re-probe at farther slots (the §3.5 claim-or-starve idiom), which is
  exactly where unfairness would show up.
* **uniform** — writers insert into disjoint neighborhoods; the no-
  contention baseline the hammer is priced against.

Writers advance under token-bucket fair quotas
(`isolation.fair_quotas`, equal rates — the §5.5 rate limiter applied
between writer lanes), and every run is priced with the VM's own cost
clock, so the numbers are deterministic and CI-gateable.  The recorded
headline is **fairness**: the best/worst ratio of per-writer completion
clocks under the hammer must stay <= 2x (the acceptance gate) — a
starved lane would blow this immediately.  Correctness rides along:
every status terminal, final tables fsck-clean (the bit-exact
linearizability proof is the cut-point sweep in tests/test_faults.py).

Run: PYTHONPATH=src python -m benchmarks.write_contention
"""
from __future__ import annotations

import json
import os

import numpy as np

from repro import compile_cache

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_chains.json")

N_BUCKETS = 32
VAL_WORDS = 2
NEIGHBORHOOD = 8
WRITER_COUNTS = (1, 2, 4, 8)
FAIRNESS_GATE = 2.0

TERMINAL = (1, 2, 4)     # SET_UPDATED / SET_INSERTED / SET_DISPLACED


def _workload(n_writers: int, hot: bool):
    from repro.kvstore import store

    if hot:
        return store.keys_homed_at(3, n_writers, N_BUCKETS)
    return [store.keys_homed_at((4 * w) % N_BUCKETS, 1, N_BUCKETS)[0]
            for w in range(n_writers)]


def _run(n_writers: int, hot: bool) -> dict:
    import jax.numpy as jnp

    from repro.core import machine, programs
    from repro.kvstore import fsck, hopscotch
    from repro.rdma import isolation

    g = programs.build_multi_writer_group(
        N_BUCKETS, VAL_WORDS, neighborhood=NEIGHBORHOOD,
        n_writers=n_writers)
    qs = _workload(n_writers, hot)
    pay = g.device_payloads(
        jnp.asarray(qs, jnp.int32),
        jnp.asarray([hopscotch.bucket_of(q, N_BUCKETS) for q in qs],
                    jnp.int32),
        jnp.asarray([[q & 0xFF, q >> 4] for q in qs], jnp.int32))

    st = g.device_state(jnp.zeros((N_BUCKETS,), jnp.int32),
                        jnp.zeros((N_BUCKETS, VAL_WORDS), jnp.int32))
    for w, (recv_wq, _) in enumerate(g.lanes):
        st = machine.deliver(st, recv_wq, pay[w])
    sched = isolation.fair_quotas([8.0] * n_writers, n_rounds=48)
    out = machine.run_scheduled(g.spec, st, sched, g.writer_slices, g.fuel)

    status = [int(out.mem[resp]) for _, resp in g.lanes]
    finish = [float(jnp.max(out.last_comp_time[lo:hi]))
              for lo, hi in g.writer_slices]
    rows = np.arange(N_BUCKETS)
    keys_out = np.asarray(
        out.mem[g.table_base + rows * programs.BUCKET_WORDS])
    cols = np.arange(VAL_WORDS)[None, :]
    vals_out = np.asarray(
        out.mem[g.values_base + rows[:, None] * VAL_WORDS + cols])
    clean = bool(fsck.check_invariants(
        keys_out[None], vals_out[None], neighborhood=NEIGHBORHOOD).clean)
    committed = sorted(int(k) for k in keys_out if k)

    total_us = float(machine.total_time_us(out))
    return {
        "n_writers": n_writers,
        "workload": "hot" if hot else "uniform",
        "statuses": status,
        "all_terminal": all(s in TERMINAL for s in status),
        "all_committed": committed == sorted(int(q) for q in qs),
        "fsck_clean": clean,
        "per_writer_finish_us": [round(f, 3) for f in finish],
        "fairness_ratio": (round(max(finish) / min(finish), 4)
                           if n_writers > 1 else 1.0),
        "total_us": round(total_us, 3),
        "us_per_op": round(total_us / n_writers, 3),
    }


def main(out_path: str = OUT_PATH):
    import jax

    runs = [_run(w, hot) for w in WRITER_COUNTS for hot in (True, False)]
    hot = [r for r in runs if r["workload"] == "hot"]
    uniform = [r for r in runs if r["workload"] == "uniform"]

    results = {}
    if os.path.exists(out_path):
        with open(out_path) as f:
            results = json.load(f)
    results["contention_write"] = {
        "backend": jax.default_backend(),
        "n_buckets": N_BUCKETS,
        "neighborhood": NEIGHBORHOOD,
        "fairness_gate": FAIRNESS_GATE,
        "hot": hot,
        "uniform": uniform,
    }
    checks = results.setdefault("checks", {})
    checks["contention_write_fairness_2x"] = all(
        r["fairness_ratio"] <= FAIRNESS_GATE for r in hot
        if r["n_writers"] > 1)
    checks["contention_write_all_terminal_and_committed"] = all(
        r["all_terminal"] and r["all_committed"] for r in runs)
    checks["contention_write_tables_fsck_clean"] = all(
        r["fsck_clean"] for r in runs)

    print("name,us_per_op,derived")
    for r in runs:
        print(f"contention_write/{r['workload']}_w{r['n_writers']},"
              f"{r['us_per_op']:.2f},"
              f"fairness={r['fairness_ratio']:.2f} "
              f"total={r['total_us']:.1f}us")
    for name, ok in checks.items():
        if name.startswith("contention_write"):
            print(f"check,{name},{'PASS' if ok else 'FAIL'}")

    with open(out_path, "w") as f:
        json.dump(results, f, indent=2)
    print(f"# wrote {os.path.abspath(out_path)}")
    return results


if __name__ == "__main__":
    compile_cache.enable()
    main()
