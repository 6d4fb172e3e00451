"""The four-shard configuration's routed path on four host devices, at
2^10 buckets a shard."""
import json
import os
import pathlib
import subprocess
import sys

HERE = pathlib.Path(__file__).resolve().parent


def test_four_shard_run_is_correct_and_no_exchange_is_not():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, str(HERE / "four_shards_main.py")],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    good, bad = out["good"], out["no_exchange"]
    assert good["correct"], good
    assert good["device"]["count"] == 4
    assert good["attempted"] > 0 and good["failed"] == 0
    assert "update_p95_ms" not in good["metrics"]
    assert bad["correct"] is False
    assert bad["checks"]["get_mismatches"]["value"] > 0
