"""Chain-serving throughput: single-get vs batched get_many vs Pallas.

Measures gets/sec on this host for the paper's offload programs across
batch sizes {1, 16, 64, 256}:

* ``single``   — the seed-era API: one ``machine.run`` + numpy round-trip
  per key (N independent ``get()`` calls).
* ``get_many`` — the ChainEngine fast path: one ``materialize()``, one
  ``deliver_many``, one vmapped run for the whole batch.
* ``pallas``   — the managed-WQ chain kernel (interpret mode on CPU; the
  same call compiles on TPU), run as a grid of recycled-get-server client
  contexts, with bit-exactness vs the interpreter verified in-line.

Writes machine-readable ``BENCH_chains.json`` (repo root by default) so the
perf trajectory of later PRs has a baseline, and prints the usual
``name,us_per_call,derived`` rows.

Run: PYTHONPATH=src python -m benchmarks.throughput
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from repro.core import programs
from repro.core.engine import ChainEngine
from repro import compile_cache

BATCHES = (1, 16, 64, 256)
OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_chains.json")


def _time_us(fn, n: int, warmup: int = 1) -> float:
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e6


def _mixed_keys(batch: int, live, miss_every: int = 4):
    """Deterministic mixed hit/miss key batch."""
    live = list(live)
    keys = []
    for i in range(batch):
        if i % miss_every == miss_every - 1:
            keys.append(1_000_000 + i)            # miss
        else:
            keys.append(live[i % len(live)])      # hit
    return keys


def bench_hash_lookup(results: dict):
    off = programs.build_hash_lookup(n_buckets=64, val_len=4)
    live = []
    for k in range(1, 33):
        if off.insert(k, [k, k * 2, k * 3, k * 5]):
            live.append(k)
    out = results["hash_lookup"] = {}
    for batch in BATCHES:
        keys = _mixed_keys(batch, live)

        def run_single():
            return [off.get(k)[0] for k in keys]

        def run_many():
            return off.get_many(keys)[0]

        # correctness before timing: the two paths must agree
        seq_vals = [v.tolist() for v in run_single()]
        many_vals = run_many().tolist()
        assert many_vals == seq_vals, f"get_many mismatch at batch {batch}"

        reps_single = 3 if batch <= 64 else 2
        t_single = _time_us(run_single, reps_single)
        t_many = _time_us(run_many, 5)
        out[str(batch)] = {
            "single_us": t_single,
            "get_many_us": t_many,
            "single_gets_per_sec": batch / (t_single * 1e-6),
            "get_many_gets_per_sec": batch / (t_many * 1e-6),
            "speedup": t_single / t_many,
        }
    return out


def bench_recycled_pallas(results: dict):
    """Recycled get server as a grid of client contexts: interpreter vs the
    Pallas managed-WQ kernel (interpret mode on CPU), bit-exact."""
    srv = programs.build_recycled_get_server(n_buckets=32, val_len=2)
    live = list(range(1, 17))
    for k in live:
        srv.insert(k, [k * 11, k * 11 + 1])
    srv.load()
    eng_i = ChainEngine.for_spec(srv.spec)
    eng_p = ChainEngine.for_spec(srv.spec, "pallas-interpret")

    out = results["recycled_server"] = {}
    exact = True
    for batch in BATCHES:
        keys = _mixed_keys(batch, live)
        payloads = np.asarray([srv._payload(k) for k in keys], np.int32)

        def run_interp():
            return eng_i.run_many(srv.state, srv.loop_wq, payloads, 64)

        def run_pallas():
            return eng_p.run_many(srv.state, srv.loop_wq, payloads, 64)

        mem_i = np.asarray(run_interp().mem)
        mem_p = np.asarray(run_pallas().mem)
        exact &= bool(np.array_equal(mem_i, mem_p))

        t_i = _time_us(lambda: np.asarray(run_interp().mem), 3)
        t_p = _time_us(lambda: np.asarray(run_pallas().mem), 3)
        out[str(batch)] = {
            "interp_us": t_i,
            "pallas_interpret_us": t_p,
            "interp_gets_per_sec": batch / (t_i * 1e-6),
            "pallas_gets_per_sec": batch / (t_p * 1e-6),
        }
    out["pallas_matches_interpreter"] = exact
    return out


def main(out_path: str = OUT_PATH):
    import jax

    # merge into the existing json: the contention / mixed-workload
    # benchmarks share this file, and a fresh dict would clobber their
    # sections and checks
    results = {}
    if os.path.exists(out_path):
        with open(out_path) as f:
            results = json.load(f)
    results["meta"] = {
        "backend": jax.default_backend(),
        "batches": list(BATCHES),
        "note": "wall-clock on this host; pallas runs in interpret mode "
                "off-TPU",
    }
    bench_hash_lookup(results)
    bench_recycled_pallas(results)

    print("name,us_per_call,derived")
    for batch in BATCHES:
        h = results["hash_lookup"][str(batch)]
        print(f"throughput/hash_single_b{batch},{h['single_us']:.1f},"
              f"{h['single_gets_per_sec']:.0f} gets/s")
        print(f"throughput/hash_get_many_b{batch},{h['get_many_us']:.1f},"
              f"{h['get_many_gets_per_sec']:.0f} gets/s "
              f"({h['speedup']:.1f}x)")
        r = results["recycled_server"][str(batch)]
        print(f"throughput/recycled_pallas_b{batch},"
              f"{r['pallas_interpret_us']:.1f},"
              f"{r['pallas_gets_per_sec']:.0f} gets/s")

    big = str(max(BATCHES))
    checks = {
        "get_many_10x_at_256":
            results["hash_lookup"][big]["speedup"] >= 10.0,
        "pallas_bit_exact":
            results["recycled_server"]["pallas_matches_interpreter"],
    }
    results.setdefault("checks", {}).update(checks)
    for name, ok in checks.items():
        print(f"check,throughput.{name},{'PASS' if ok else 'FAIL'}")

    with open(out_path, "w") as f:
        json.dump(results, f, indent=2)
    print(f"# wrote {os.path.abspath(out_path)}")
    return results


if __name__ == "__main__":
    compile_cache.enable()
    main()
