"""§5.5 contention/isolation: a misbehaving client vs per-QP rate limits.

The paper's scenario: one tenant floods the serving engine with requests
(a non-terminating/greedy chain in §5.5); without isolation the victims'
gets queue behind the flood — RedN's per-WQ (ConnectX rate-limiter) token
buckets cap the flooder, restoring the victims' ~1-RTT latency (the paper
reports a ~35x latency reduction).

Two layers, both recorded into ``BENCH_chains.json``:

* **Real execution** — the sharded chain-serving path
  (`store.sharded_get_isolated`): a flooder bursts ahead of 8 victim
  clients into a capacity-bounded transport.  Without admission the
  flooder occupies every dispatch slot and the victims are *dropped*
  (reported via the per-request ``ok`` mask — never as misses); with the
  token bucket the flooder is deferred to its rate and every victim is
  served by the owner-shard chain program, bit-exact with the hopscotch
  oracle.
* **Latency model** — queue-position pricing at batch 4096 (the scale the
  O(B log B) rank formulation exists for): victim latency =
  (service-queue position) x chain service time + 1 RTT, with the chain
  service time taken from the VM's own cost clock for one hopscotch-server
  get.  The isolation-off/on ratio is the recorded headline.

Run: PYTHONPATH=src python -m benchmarks.contention        (smoke scale)
     PYTHONPATH=src python -m benchmarks.contention --long (batch 4096)
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np

from repro.core import cost, machine, programs
from repro import compile_cache

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_chains.json")

N_VICTIMS = 8            # polite clients, 2 requests each
VICTIM_REQS = 2
BURST = 8.0              # flooder's token bucket depth
RATE_PER_US = 0.01


def chain_service_us(n_buckets: int = 128, val_len: int = 2) -> float:
    """Price one hopscotch-server get with the VM's own latency clock."""
    import jax.numpy as jnp

    srv = programs.build_hopscotch_server(n_buckets, val_len)
    keys = jnp.zeros((n_buckets,), jnp.int32).at[5].set(77)
    vals = jnp.zeros((n_buckets, val_len), jnp.int32).at[5, 0].set(9)
    st = srv.device_state(keys, vals)
    home = jnp.asarray([5], jnp.int32)
    out = srv.engine.run_many(
        st, srv.recv_wq, srv.device_payloads(jnp.asarray([77], jnp.int32),
                                             home), 96)
    return float(machine.total_time_us(
        machine.VMState(*[leaf[0] for leaf in out])))


def _contention_batch(flood: int):
    """Arrival batch: the flooder's burst lands ahead of the victims."""
    clients = np.concatenate([
        np.zeros(flood, np.int32),
        (1 + np.arange(N_VICTIMS, dtype=np.int32)).repeat(VICTIM_REQS)])
    return clients.astype(np.int32)


def latency_model(flood: int, svc_us: float) -> dict:
    """Queue-position latency for the victims, isolation off vs on."""
    import jax.numpy as jnp

    from repro.rdma import isolation, transport

    clients = jnp.asarray(_contention_batch(flood))
    b = clients.shape[0]
    dest = jnp.zeros((b,), jnp.int32)          # one owner shard: worst case
    victim = np.asarray(clients) > 0

    def victim_lat(live):
        pos = np.asarray(transport.rank_within_dest(dest, live))
        lat = (pos + 1) * svc_us + 2 * cost.NET_ONE_WAY
        lv = np.ones(b, bool) if live is None else np.asarray(live)
        served = victim & lv
        return float(lat[served].mean()), float(
            np.percentile(lat[served], 99))

    off_mean, off_p99 = victim_lat(None)
    bucket = isolation.init(n_clients=N_VICTIMS + 1, burst=BURST)
    _, admitted = isolation.admit(bucket, clients, 0.0, RATE_PER_US, BURST)
    on_mean, on_p99 = victim_lat(admitted)
    deferred = int(b - int(np.asarray(admitted).sum()))
    return {
        "batch": b,
        "flood_requests": flood,
        "victim_requests": int(victim.sum()),
        "chain_service_us": svc_us,
        "victim_mean_us_isolation_off": off_mean,
        "victim_mean_us_isolation_on": on_mean,
        "victim_p99_us_isolation_off": off_p99,
        "victim_p99_us_isolation_on": on_p99,
        "deferred_flood_requests": deferred,
        "isolation_latency_ratio": off_mean / on_mean,
    }


def real_isolated_serving(flood: int = 48, capacity: int = 24) -> dict:
    """Run the actual sharded chain-serving path under contention.

    Capacity is sized so the flooder alone can exhaust it: without
    admission every victim request is dropped (ok=False — reported, not
    mistaken for a miss); with the token bucket the flooder defers to its
    burst and every victim is served, bit-exact with the hopscotch oracle.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from repro.kvstore import store
    from repro.rdma import isolation

    kv = store.ShardedKV.build(n_shards=1, buckets_per_shard=128,
                               val_words=2)
    victim_keys = np.arange(101, 101 + N_VICTIMS * VICTIM_REQS)
    hot_key = 7
    for k in [hot_key, *victim_keys]:
        if not kv.set(int(k), [int(k) % 251, int(k) % 241]):
            raise RuntimeError(f"seeding key {k} needs a resize")
    mesh = Mesh(np.array(jax.devices()[:1]), ("kv",))
    dk, dv = kv.device_arrays()

    clients = _contention_batch(flood)
    queries = np.concatenate([np.full(flood, hot_key, np.int32),
                              victim_keys.astype(np.int32)])
    q = jnp.asarray(queries[None])
    victim = clients > 0
    rfound, rvals = store.reference_get(kv, queries)

    res_off = store.sharded_get(mesh, "kv", dk, dv, q, capacity=capacity)
    ok_off = np.asarray(res_off.ok[0])

    bucket = isolation.init(n_clients=N_VICTIMS + 1, burst=BURST)
    res_on, _ = store.sharded_get_isolated(
        mesh, "kv", dk, dv, q, jnp.asarray(clients[None]), bucket,
        now_us=0.0, rate_per_us=RATE_PER_US, burst=BURST, capacity=capacity)
    ok_on = np.asarray(res_on.ok[0])

    victims_exact = bool(
        np.array_equal(np.asarray(res_on.found[0])[victim & ok_on],
                       rfound[victim & ok_on])
        and np.array_equal(np.asarray(res_on.values[0])[victim & ok_on],
                           rvals[victim & ok_on]))
    return {
        "flood_requests": flood,
        "capacity": capacity,
        "victims_served_isolation_off": int(ok_off[victim].sum()),
        "victims_served_isolation_on": int(ok_on[victim].sum()),
        "victims_total": int(victim.sum()),
        "dropped_isolation_off": int(res_off.dropped[0]),
        "deferred_isolation_on": int(res_on.deferred[0]),
        "victims_bit_exact_with_oracle": victims_exact,
        "all_victims_served_on": bool(ok_on[victim].all()),
        "no_victim_served_off": bool(~ok_off[victim].any()),
    }


def main(out_path: str = OUT_PATH, long: bool = False):
    import jax

    svc = chain_service_us()
    flood = 4096 - N_VICTIMS * VICTIM_REQS if long else 1024
    model = latency_model(flood, svc)
    real = real_isolated_serving()

    results = {}
    if os.path.exists(out_path):
        with open(out_path) as f:
            results = json.load(f)
    results["contention"] = {
        "backend": jax.default_backend(),
        "model": model,
        "real_serving": real,
    }
    checks = results.setdefault("checks", {})
    checks["contention_isolation_ratio_10x"] = (
        model["isolation_latency_ratio"] >= 10.0)
    checks["contention_victims_bit_exact"] = (
        real["victims_bit_exact_with_oracle"] and
        real["all_victims_served_on"])
    checks["contention_flood_starves_without_isolation"] = (
        real["no_victim_served_off"])

    print("name,us_per_call,derived")
    print(f"contention/victim_isolation_off,"
          f"{model['victim_mean_us_isolation_off']:.2f},"
          f"p99={model['victim_p99_us_isolation_off']:.2f} "
          f"(flood={model['flood_requests']})")
    print(f"contention/victim_isolation_on,"
          f"{model['victim_mean_us_isolation_on']:.2f},"
          f"p99={model['victim_p99_us_isolation_on']:.2f} "
          f"(deferred={model['deferred_flood_requests']})")
    print(f"contention/isolation_latency_ratio,"
          f"{model['isolation_latency_ratio']:.1f},paper reports ~35x")
    print(f"contention/real_victims_served,"
          f"{real['victims_served_isolation_on']},"
          f"of {real['victims_total']} (off: "
          f"{real['victims_served_isolation_off']})")
    for name, ok in checks.items():
        if name.startswith("contention"):
            print(f"check,{name},{'PASS' if ok else 'FAIL'}")

    with open(out_path, "w") as f:
        json.dump(results, f, indent=2)
    print(f"# wrote {os.path.abspath(out_path)}")
    return results


if __name__ == "__main__":
    compile_cache.enable()
    main(long="--long" in sys.argv[1:])
