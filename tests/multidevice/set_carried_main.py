"""Subprocess body: four shards of 256 buckets on four host devices, one
SET call from every source shard routed to its owners, whose writer scans
carry the writer's image.  Each owner's answers and table are checked
against ``hopscotch.insert_many_displaced`` applied in the owner's
receive-window order (source shard, then batch order), and against the
whole-image path (the faulted path with no fault armed).  The parent test
sets XLA_FLAGS=--xla_force_host_platform_device_count=4 and prints nothing
else on the last line."""
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import faults as faults_mod
from repro.kvstore import hopscotch, store

N_SHARDS, NB, H, V, WIDTH = 4, 256, 8, 7, 16
assert len(jax.devices()) == N_SHARDS, jax.devices()

rng = np.random.default_rng(8128)
kv = store.ShardedKV.build(N_SHARDS, NB, V, H)
fulls = []
for s in range(N_SHARDS):
    # one full neighborhood per owner: a resident homed at each bucket
    full = int(rng.integers(0, NB))
    for d in range(H):
        k = store.keys_homed_at((full + d) % NB, 1, NB,
                                start=int(rng.integers(1, 1 << 20)),
                                n_shards=N_SHARDS, shard=s)[0]
        assert kv.set(k, rng.integers(-2**31, 2**31, V).tolist())
    fulls.append(full)
while sum(int((t.keys != 0).sum()) for t in kv.tables) < N_SHARDS * NB // 2:
    kv.set(int(rng.integers(1, 1 << 24)),
           rng.integers(-2**31, 2**31, V).tolist())

# each owner gets two updates, two inserts, a neighborhood-full insert and
# a repeat of each kind; every source sends a quarter of each owner's rows
resident = np.concatenate([t.keys[t.keys != 0] for t in kv.tables])
rows = []
for s in range(N_SHARDS):
    mine = resident[store.shard_of(jnp.asarray(resident), N_SHARDS) == s]
    upd = rng.choice(np.asarray(mine), 2, replace=False).tolist()
    ins = store.keys_homed_at(int(rng.integers(0, NB)), 2, NB,
                              start=int(rng.integers(1 << 21, 1 << 22)),
                              n_shards=N_SHARDS, shard=s)
    stuck = store.keys_homed_at(fulls[s], 1, NB, start=1 << 22,
                                n_shards=N_SHARDS, shard=s)
    rows += upd + ins + stuck + [upd[0], ins[0], stuck[0]]
rows = np.asarray(rows, np.int32)
rng.shuffle(rows)
sk = np.zeros((N_SHARDS, WIDTH), np.int32)
sk[:, :len(rows) // N_SHARDS] = rows.reshape(N_SHARDS, -1)
sv = rng.integers(-2**31, 2**31, (N_SHARDS, WIDTH, V)).astype(np.int32)
sv[sk == 0] = 0

mesh = store.serving_mesh(N_SHARDS)
sharding = NamedSharding(mesh, P("kv"))
dk, dv = kv.device_arrays(sharding)
args = (mesh, "kv", dk, dv, jax.device_put(sk, sharding),
        jax.device_put(sv, sharding))
res, nk, nv = store.sharded_set(*args)
per_row, pk, pv = store.sharded_set(
    *args, faults=faults_mod.FaultPlan.none(sk.shape))

status = np.asarray(res.status)
owner = np.asarray(store.shard_of(jnp.asarray(sk), N_SHARDS))
status_mismatches = table_mismatches = 0
for s, t in enumerate(kv.tables):
    mine = (owner == s) & (sk != 0)         # source-major: window order
    want = hopscotch.insert_many_displaced(t, sk[mine], sv[mine])
    status_mismatches += int((status[mine] != want).sum())
    table_mismatches += int((np.asarray(nk[s]) != t.keys).sum()
                            + (np.asarray(nv[s]) != t.values).any(1).sum())
per_row_mismatches = int(
    (status != np.asarray(per_row.status)).sum()
    + (np.asarray(nk) != np.asarray(pk)).sum()
    + (np.asarray(nv) != np.asarray(pv)).sum())
print(json.dumps({
    "devices": len(jax.devices()), "live": int((sk != 0).sum()),
    "served": int(np.asarray(res.ok).sum()),
    "status_mismatches": status_mismatches,
    "table_mismatches": table_mismatches,
    "per_row_mismatches": per_row_mismatches,
    "escalated": np.asarray(res.escalated).tolist(),
    "image_builds": np.asarray(res.image_builds).tolist()}))
