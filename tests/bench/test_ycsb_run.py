"""Whole runs of the harness on the CPU at 2^10 buckets: a correct store
comes out correct, the control and each planted fault do not, and the
command refuses to run without a TPU or outside a checkout."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import ycsb_cells  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEED = 2**31 + 2**20 + 5          # past 32 signed bits


@pytest.mark.parametrize("cell", ["ycsb-c.1chip", "ycsb-a.1chip"])
def test_a_run_of_the_store_is_correct(cell):
    out = ycsb_cells.run(cell, SEED, 1.5)
    r = out.result
    assert r["correct"], out.lines
    assert r["attempted"] > 0 and r["failed"] == 0
    want = {"ops_per_s", "get_p95_ms", "setup_s"}
    if cell.startswith("ycsb-a"):
        want.add("update_p95_ms")
    assert set(r["metrics"]) == want
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert list(r)[-1] == "checks"
    assert all(c["value"] == 0 for c in r["checks"].values())
    assert any("compiles_in_window=0" in line for line in out.lines)


@pytest.mark.parametrize("cell,fault,caught_by", [
    ("ycsb-a.1chip", "control", "get_mismatches"),
    ("ycsb-a.1chip", "state_unchanged", "readback_mismatches"),
    ("ycsb-c.1chip", "half_batch", "get_mismatches"),
    ("ycsb-c.1chip", "altered_answer", "get_mismatches"),
])
def test_the_control_and_each_fault_come_out_not_correct(cell, fault,
                                                         caught_by):
    r = ycsb_cells.run(cell, SEED + 1, 1.0, fault=fault).result
    assert r["correct"] is False
    assert r["checks"][caught_by]["value"] > r["checks"][caught_by]["limit"]


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ycsb-c.1chip",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    r = _run_cli(ROOT)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    assert r.stdout.strip() == ""


def test_run_refuses_outside_a_checkout(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for path in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run_cli(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
