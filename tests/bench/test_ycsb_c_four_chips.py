"""The ``ycsb-c.4chip`` cell on four host devices at 2^10 buckets a shard:
correct through the routed path, not correct with the exchange left out,
each owner's routed-slot counter equal to a count on the host, the
traced window's live-slot readers, and every record on the shard that
owns its key."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent


@pytest.fixture(scope="module")
def out(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, str(HERE / "four_chips_c_main.py"),
                        str(tmp_path_factory.mktemp("trace"))],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_routed_run_is_correct_and_no_exchange_is_not(out):
    good, bad = out["good"], out["no_exchange"]
    assert good["correct"], good
    assert good["device"]["count"] == 4
    assert good["attempted"] > 0 and good["failed"] == 0
    assert all(c["value"] == 0 for c in good["checks"].values())
    assert "update_p95_ms" not in good["metrics"]
    assert bad["correct"] is False
    assert bad["checks"]["get_mismatches"]["value"] > 0


def test_traced_window_reads_every_call_full(out):
    """256 clients fill every 256-wide call: 64 rows from each source,
    256 live of four owners' 4 x 64 slots."""
    m = out["good"]["metrics"]
    assert m["get_live_slot_share"]["value"] == pytest.approx(25.0)
    assert 25.0 <= m["get_owner_max_share"]["value"] <= 100.0


def test_routed_counter_matches_a_host_count_per_owner(out):
    assert out["all_ok"]
    assert out["routed"] == out["host_routed"]
    assert sum(out["routed"]) == out["live_rows"] > 0


def test_every_record_lies_on_the_shard_that_owns_its_key(out):
    assert all(n > 0 for n in out["occupied"])
    assert out["misplaced"] == 0
