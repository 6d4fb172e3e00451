"""Static-verification certificates for every shipped chain program.

Runs the `core.analysis` registry sweep and records, per builder, the
verdict (clean-or-waivered) plus the static certificates — posted-WR
bound, engine fuel, Table-2 verb budget, and the static chain-latency
estimate — into the ``verification`` section of ``BENCH_chains.json``.

Two modes:

* default — re-run the sweep and (re)record the section; exits 1 if any
  builder has a non-waived finding, so a regression can never be
  *recorded* as passing.
* ``--check`` — the drift gate: re-run the sweep and compare against the
  recorded certificates without writing.  Any difference (a builder
  added/removed, a WR-bound or latency change, a new waiver) exits 1 —
  certificate changes must land as an explicit re-record in the same PR
  that caused them.

Run: PYTHONPATH=src python -m benchmarks.verify_programs
     PYTHONPATH=src python -m benchmarks.verify_programs --check
"""
from __future__ import annotations

import argparse
import json
import os
import sys

from repro import compile_cache

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_chains.json")


def collect() -> dict:
    from repro.core import analysis

    programs = {}
    all_ok = True
    fuel_ok = True
    for name, rep in analysis.verify_all().items():
        c = rep.certificates
        programs[name] = {
            "ok": rep.ok(),
            "errors": len(rep.errors),
            "warnings": len(rep.warnings),
            "waived": len(rep.waived),
            "n_wqs": c["n_wqs"],
            "n_posted": c["n_posted"],
            "static_wr_bound": c["static_wr_bound"],
            "recycled_wqs": c["recycled_wqs"],
            "budget": c["budget"],
            "serial_latency_us": c["serial_latency_us"],
        }
        if "fuel" in c:
            programs[name]["fuel"] = c["fuel"]
            bound = c["static_wr_bound"]
            if bound is None or bound >= c["fuel"]:
                fuel_ok = False
        all_ok &= rep.ok()
    return {
        "programs": programs,
        "checks": {
            "verification_sweep_clean_or_waivered": all_ok,
            "verification_fuel_bounds_hold": fuel_ok,
        },
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="benchmarks.verify_programs",
        description="Record/check static-verification certificates.")
    ap.add_argument("--check", action="store_true",
                    help="compare against recorded certificates; exit 1 "
                         "on drift (writes nothing)")
    ap.add_argument("--out", default=OUT_PATH, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    section = collect()
    failed = [k for k, v in section["checks"].items() if not v]

    if args.check:
        recorded = None
        if os.path.exists(args.out):
            with open(args.out) as f:
                recorded = json.load(f).get("verification")
        if recorded is None:
            print("verification: no recorded section "
                  f"(run `python -m benchmarks.verify_programs` first)",
                  file=sys.stderr)
            return 1
        if failed:
            print(f"verification: checks FAILED: {failed}", file=sys.stderr)
            return 1
        if recorded != section:
            drift = sorted(
                set(recorded["programs"]) ^ set(section["programs"])) or [
                n for n, p in section["programs"].items()
                if recorded["programs"].get(n) != p]
            print(f"verification: certificate drift in {drift} — re-record "
                  "with `python -m benchmarks.verify_programs`",
                  file=sys.stderr)
            return 1
        print(f"verification: {len(section['programs'])} program "
              "certificates match the recorded ones")
        return 0

    results = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    results["verification"] = section
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
    for name, p in sorted(section["programs"].items()):
        bound = p["static_wr_bound"]
        print(f"{name}: ok={p['ok']} wr_bound="
              f"{'unbounded' if bound is None else bound} "
              f"latency={p['serial_latency_us']}us waived={p['waived']}")
    if failed:
        print(f"verification checks FAILED: {failed}", file=sys.stderr)
        return 1
    print(f"recorded {len(section['programs'])} program certificates "
          f"-> {os.path.relpath(args.out)}")
    return 0


if __name__ == "__main__":
    compile_cache.enable()
    sys.exit(main())
