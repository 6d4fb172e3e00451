"""Subprocess body: the four-shard configuration (``ycsb-4x2p20``) under
the ``ycsb-b`` mix at 2^10 buckets a shard on four host devices, served
through the routed (all-to-all) path; then the same run with the
exchange between shards left out, which must come out not correct.  The parent test sets
XLA_FLAGS=--xla_force_host_platform_device_count=4."""
import json
import pathlib
import sys

import jax

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import ycsb_cells  # noqa: E402

assert len(jax.devices()) == 4, jax.devices()
SEED = 2**32 + 17
cell = ycsb_cells.tiny("ycsb-b.4chip", "ycsb-4x2p20", "ycsb-b")
good = ycsb_cells.run(cell, SEED, 2.0).result
bad = ycsb_cells.run(cell, SEED, 1.0, fault="no_exchange").result
print(json.dumps({"good": good, "no_exchange": bad}))
