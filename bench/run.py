#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  Earlier lines on standard error record
the run; the last line of standard output is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and,
with ``--trace 1``, ``breakdown``), and last the numbers compared with
their limits under ``checks``.  Exits non-zero, with no result line,
when JAX finds no TPU, fewer chips than the cell asks for, a device kind
missing from ``bench/peaks.json``, or no program under ``src/``.
"""
import time

T_PROCESS = time.perf_counter()

import argparse                                          # noqa: E402
import json                                              # noqa: E402
import pathlib                                           # noqa: E402
import sys                                               # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
TRACE_DIR = ROOT / ".bench_trace"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, roofline

    cell = harness.load_cell(args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        harness.log(f"run: no program under {ROOT / 'src'}")
        return 2

    import jax

    devices = jax.devices()
    d0 = devices[0]
    harness.log(f"device: platform={d0.platform} kind={d0.device_kind} "
                f"count={len(devices)}")
    if d0.platform != "tpu":
        harness.log(f"run: needs a TPU, JAX found {d0.platform!r}")
        return 2
    if len(devices) < cell.chips:
        harness.log(f"run: {cell.name} needs {cell.chips} chips, JAX found "
                    f"{len(devices)}")
        return 2
    try:
        peak_row = roofline.peak(d0.device_kind)
    except KeyError as e:
        harness.log(f"run: {e}")
        return 2

    from repro import compile_cache

    harness.log(f"compile cache: {compile_cache.enable()}")
    # every program, however quick to compile, is kept, so a run after
    # the first compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    out = harness.execute(
        cell, args.seed, args.seconds, t_process=T_PROCESS,
        trace_dir=TRACE_DIR / cell.name if args.trace else None,
        peak_row=peak_row)
    for line in out.lines:
        harness.log(line)
    for name, (value, limit) in out.checks.items():
        harness.log(f"check {name}={value} limit={limit}")
    print(json.dumps(out.result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
