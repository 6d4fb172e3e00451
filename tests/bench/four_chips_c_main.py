"""Subprocess body: the ``ycsb-c.4chip`` cell at 2^10 buckets a shard on
four host devices, served through the routed (all-to-all) path, traced,
then with the exchange left out, then one GET call of the cell's width
against the service the first run left, and its final table.  Prints one
JSON line.  The parent test sets
XLA_FLAGS=--xla_force_host_platform_device_count=4 and the trace
directory as the first argument."""
import json
import pathlib
import sys

import jax
import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import ycsb_cells  # noqa: E402
from bench import harness  # noqa: E402
from bench.metrics import _program  # noqa: E402

assert len(jax.devices()) == 4, jax.devices()
SEED = 2**33 + 29


def owner(keys, n_shards: int) -> np.ndarray:
    """The owner shard of each key, written out here apart from the
    store's ``shard_of``: the low 32 bits xor-shifted right by 13, times
    the golden-ratio constant 0x9E3779B1 modulo 2^32, modulo the shard
    count."""
    k = np.asarray(keys, np.uint64) & np.uint64(0xFFFFFFFF)
    k ^= k >> np.uint64(13)
    return ((k * np.uint64(0x9E3779B1)) & np.uint64(0xFFFFFFFF)) \
        % np.uint64(n_shards)


trace_dir = pathlib.Path(sys.argv[1])
_program.TRACE_DIR = trace_dir
cell = ycsb_cells.tiny("ycsb-c.4chip")
kept = {}


def keep(svc, keys, vals):
    kept["svc"] = svc
    return svc


good = harness.execute(cell, SEED, 2.0, wrap=keep,
                       trace_dir=trace_dir / cell.name).result
bad = ycsb_cells.run(cell, SEED, 1.0, fault="no_exchange").result

svc = kept["svc"]
table = np.asarray(jax.device_get(svc.keys))
s_n, width = cell.config.n_shards, cell.config.get_width // 4
rng = np.random.default_rng(SEED)
q = rng.choice(table[table != 0], (s_n, width)).astype(np.int32)
q[rng.random(q.shape) < 0.3] = 0
res = svc.get_many(q)
homed = [owner(row[row != 0], s_n).tolist() for row in table]
print(json.dumps({
    "good": good, "no_exchange": bad,
    "routed": np.asarray(res.routed).tolist(),
    "host_routed": [int(((q != 0) & (owner(q, s_n) == s)).sum())
                    for s in range(s_n)],
    "live_rows": int((q != 0).sum()),
    "all_ok": bool(np.asarray(res.ok).all()),
    "occupied": [len(h) for h in homed],
    "misplaced": sum(int(o != s) for s, h in enumerate(homed) for o in h),
}))
