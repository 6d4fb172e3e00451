"""Subprocess body: four shards of 2^10 buckets on four host devices,
GETs from every source shard routed to their owners, whose GET bodies
each read their own shard's read-only segment; every answer against the
``HopscotchTable`` oracle.  The parent test sets
XLA_FLAGS=--xla_force_host_platform_device_count=4 and prints nothing
else on the last line."""
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.kvstore import store

N_SHARDS, NB, V = 4, 1 << 10, 7
assert len(jax.devices()) == N_SHARDS, jax.devices()

rng = np.random.default_rng(4242)
kv = store.ShardedKV.build(N_SHARDS, NB, V)
keys = rng.choice(np.arange(1, 1 << 24), 1600, replace=False)
placed = [int(k) for k in keys
          if kv.set(int(k), rng.integers(-2**31, 2**31, V).tolist())]
mesh = store.serving_mesh(N_SHARDS)
dk, dv = kv.device_arrays(NamedSharding(mesh, P("kv")))

q = np.concatenate([rng.choice(placed, 112), [0] * 4,
                    rng.integers(1, 1 << 24, 12)]).astype(np.int32)
rng.shuffle(q)
q = q.reshape(N_SHARDS, -1)
res = store.sharded_get(mesh, "kv", dk, dv, jnp.asarray(q),
                        capacity=q.shape[1])
found, values = store.reference_get(kv, q)
ok = np.asarray(res.ok).reshape(-1)
mismatches = int((np.asarray(res.found).reshape(-1) != found)[ok].sum()
                 + (np.asarray(res.values).reshape(-1, V)
                    != values)[ok].any(axis=1).sum())
print(json.dumps({"devices": len(jax.devices()), "queries": int(q.size),
                  "served": int(ok.sum()), "hits": int(found.sum()),
                  "mismatches": mismatches,
                  "breached": int(np.asarray(res.breached).sum()),
                  "image_words": np.asarray(res.image_words).tolist()}))
