"""Memcached with RedN offload (paper §5.4–§5.6) — the flagship use case.

A sharded KV store serves zipf-distributed gets through the paper's three
paths (redn / one-sided / two-sided), then demonstrates the two systems
properties RedN buys: per-tenant isolation and host-crash survival.

Run: PYTHONPATH=src python examples/memcached_offload.py
"""
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.data.pipeline import kv_request_stream
from repro.kvstore import store as kv_store
from repro.rdma import failure, isolation


def main():
    print("== populate (host set path, like the paper) ==")
    kv = kv_store.ShardedKV.build(n_shards=1, buckets_per_shard=1024,
                                  val_words=4)
    n_keys = 400
    for k in range(1, n_keys + 1):
        if not kv.set(k, [k, k * 2, k * 3, k * 5]):
            raise RuntimeError(f"seeding key {k} needs a resize")
    mesh = kv_store.serving_mesh(kv.n_shards, "kv")
    dk, dv = kv.device_arrays(NamedSharding(mesh, P("kv")))

    print("== serve 4 batches of 64 zipf gets via each path ==")
    stream = kv_request_stream(n_keys, 64, seed=1)
    for method in ("redn", "one_sided", "two_sided"):
        hits = 0
        for _ in range(4):
            _, keys = next(stream)
            q = jnp.asarray(keys[None])
            res = kv_store.sharded_get(mesh, "kv", dk, dv, q, method=method)
            hits += int(jnp.sum(res.found))
        print(f"  {method:10s}: {hits}/256 hits, "
              f"{kv_store.RTTS[method]} RTT"
              f"{' + host CPU' if kv_store.HOST_SERVICE[method] else ''}")

    print("== isolation (§5.5): a greedy tenant cannot starve others ==")
    buckets = isolation.init(n_clients=2, burst=8.0)
    greedy = jnp.zeros(32, jnp.int32)            # tenant 0: 32 requests
    polite = jnp.ones(4, jnp.int32)              # tenant 1: 4 requests
    buckets, ok_greedy = isolation.admit(buckets, greedy, 0.0, 0.01, 8.0)
    buckets, ok_polite = isolation.admit(buckets, polite, 0.0, 0.01, 8.0)
    print(f"  greedy tenant admitted {int(ok_greedy.sum())}/32, "
          f"polite tenant admitted {int(ok_polite.sum())}/4")

    print("== failure resiliency (§5.6): kill the host, keep serving ==")
    svc = failure.DeviceResidentService.start(
        [(k, [k, k + 1]) for k in range(1, 9)])
    print(f"  get(3) = {svc.get(3).tolist()}  (host alive: "
          f"{svc.host_alive()})")
    svc.crash_host()
    print(f"  get(5) = {svc.get(5).tolist()}  (host alive: "
          f"{svc.host_alive()})  <- zero-interruption")
    batch = svc.get_many([1, 2, 3, 4]).tolist()
    print(f"  get_many([1..4]) = {batch}  <- one device call, host dead")
    svc.restart_host()
    print(f"  vanilla Memcached would have been down "
          f"{svc.cold_restart_downtime_s():.2f}s")


if __name__ == "__main__":
    main()
