"""Multi-device integration tests (subprocess with forced host devices).

XLA locks the device count at first jax init, so these run in fresh
subprocesses with XLA_FLAGS set — never in this process or conftest.
"""
import os
import pathlib
import subprocess
import sys

import pytest

HERE = pathlib.Path(__file__).parent
SRC = str(HERE.parent / "src")


def run_sub(script: str, n_devices: int = 8, timeout: int = 600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_devices}")
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, str(HERE / "multidevice" / script)],
        capture_output=True, text=True, timeout=timeout, env=env)


@pytest.mark.slow
def test_kv_sharded_get_8dev():
    r = run_sub("kv_multidevice_main.py")
    assert "MULTIDEVICE_KV_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_gpipe_pipeline_4stage():
    r = run_sub("pipeline_main.py")
    assert "PIPELINE_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_elastic_remesh_restore():
    r = run_sub("elastic_main.py")
    assert "ELASTIC_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_chip_smoke_four_shard_phase_4dev():
    r = run_sub("chip_smoke_main.py", n_devices=4)
    assert "CHIP_SMOKE_4DEV_OK" in r.stdout, r.stdout + r.stderr
