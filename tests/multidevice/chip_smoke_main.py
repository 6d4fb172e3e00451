"""Subprocess body: chip_smoke.py's four-chip phase on 4 host devices at
2^12 buckets per shard (a rehearsal of ``chip_smoke.py --four-chips``),
after a one-shard phase of the same geometry in the same process: the
cached chain programs must serve meshes of both sizes.

Run with XLA_FLAGS=--xla_force_host_platform_device_count=4 (the parent
test sets it).
"""
import importlib.util
import os
import pathlib

assert "--xla_force_host_platform_device_count=4" in os.environ.get(
    "XLA_FLAGS", ""), "parent must set XLA_FLAGS"

import jax                                      # noqa: E402

assert len(jax.devices()) == 4, jax.devices()

ROOT = pathlib.Path(__file__).resolve().parents[2]
spec = importlib.util.spec_from_file_location("chip_smoke",
                                              ROOT / "chip_smoke.py")
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)

clock = smoke.CompileClock()
for n_shards in (1, 4):
    tally = smoke.Tally(clock)
    rec = smoke.kv_phase(tally, n_shards=n_shards, buckets=1 << 12, seed=0,
                         rounds=1, phase=f"kv{n_shards}")
    for line in tally.lines(rec["phase"]):
        print(line)
    assert len(rec["devices"]) == n_shards, rec
    assert rec["get_capacity"] == smoke.GET_BATCH // n_shards, rec
    assert tally.mismatches == 0
    assert tally.ops["table_buckets"] == n_shards << 12
print("CHIP_SMOKE_4DEV_OK")
