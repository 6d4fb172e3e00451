"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows for every fidelity benchmark
(Tables 1/3/5, Figs. 7/8/10/11/13/14/15/16), followed by claim checks and
the roofline summary (when dry-run results exist).
"""
from __future__ import annotations

import json
import sys

import numpy as np

from repro import compile_cache

from . import fidelity, roofline
from .common import emit


def main() -> None:
    print("name,us_per_call,derived")
    all_checks = {}
    for bench in fidelity.ALL:
        rows, checks = bench()
        emit(rows)
        all_checks[bench.__name__] = checks

    rl_rows, _ = roofline.rows()
    if rl_rows:
        emit(rl_rows)
    # hillclimb profiles (EXPERIMENTS.md §Perf), where present
    opt_rows = [r for r in roofline.load_records()
                if r.get("opt_profile") != "baseline"
                and r.get("status") == "ok"]
    for r in opt_rows:
        ro = r["roofline"]
        dom = max(ro["compute_s"], ro["memory_s"], ro["collective_s"])
        print(f"perf/{r['arch']}/{r['shape']}/{r['opt_profile']},"
              f"{dom*1e6:.3f},dom={ro['bottleneck']};"
              f"C={ro['compute_s']:.2e};M={ro['memory_s']:.2e};"
              f"X={ro['collective_s']:.2e}")

    print("\n# claim checks (paper-fidelity assertions)")
    failed = 0
    for bench, checks in all_checks.items():
        for name, val in checks.items():
            if isinstance(val, (bool, np.bool_)):
                status = "PASS" if val else "FAIL"
                failed += 0 if val else 1
                print(f"check,{bench}.{name},{status}")
            else:
                print(f"info,{bench}.{name},{json.dumps(val, default=str)}")
    if failed:
        print(f"\n# {failed} claim check(s) FAILED", file=sys.stderr)
        sys.exit(1)
    print("\n# all claim checks passed")


if __name__ == "__main__":
    compile_cache.enable()
    main()
