"""Fault injection + crash-consistent recovery (robustness layer).

RedN's §5.6 resiliency benchmarks kill the *host driver*; these kill the
*chains themselves* mid-flight and price what recovery costs:

* **cut-point sweeps** — every step of a displacement bubble and of a
  migration lap is killed once (traced fault parameters: one compile
  serves every cut); each torn state must be fsck-classified, repaired,
  and re-driven to the host oracle's bit-exact answer.
* **recovery drill** — ``set_reliable`` against each fault kind (host
  crash, NIC WQE drop, raced atomic, lost doorbell): attempts taken,
  recovery latency, store fsck-clean afterwards.
* **availability under storm** — a seeded storm (``FAULT_SEED`` rotates
  it in CI) of faulted SETs through the retry/fsck/backoff loop: the
  fraction that land within the retry budget is the availability claim.

Self-checks recorded into ``BENCH_chains.json`` (``faults`` section):
``faults_cutpoint_sweep_converges``, ``faults_fsck_clean_after_recovery``,
``faults_service_availability_under_storm``.

Run: PYTHONPATH=src python -m benchmarks.faults          (smoke)
     PYTHONPATH=src python -m benchmarks.faults --long   (full sweeps)
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

from benchmarks import common
from repro import compile_cache

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_chains.json")

TERMINAL_SET = (1, 2, 4)        # SET_UPDATED / SET_INSERTED / SET_DISPLACED
TERMINAL_MIG = (6, 7)           # MIG_MOVED / MIG_DISCARDED


def _displacer_scenario():
    """n=16, H=4 neighborhood [3..6] full; bucket 6's resident is movable,
    so the clean outcome is one bubble move + SET_DISPLACED."""
    from repro.core import programs
    from repro.kvstore import store

    n, v, h = 16, 2, 4
    d = programs.build_hopscotch_displacer(n, v, neighborhood=h,
                                           max_search=16, max_moves=8)
    homed3 = store.keys_homed_at(3, 4, n)
    homed6 = store.keys_homed_at(6, 1, n)
    keys0 = np.zeros(n, np.int32)
    vals0 = np.zeros((n, v), np.int32)
    for b, k in zip((3, 4, 5), homed3[:3]):
        keys0[b], vals0[b] = k, [k & 0xFF, b]
    keys0[6], vals0[6] = homed6[0], [homed6[0] & 0xFF, 6]
    return d, h, keys0, vals0, homed3[3], [91, 92]


def run_displacement_sweep(stride: int = 1) -> dict:
    """Kill the displacement chain at every ``stride``-th step; fsck +
    repair + re-issue must converge bit-exactly to the oracle."""
    import jax
    import jax.numpy as jnp

    from repro.kvstore import fsck, hopscotch

    prog, h, keys0, vals0, q, qval = _displacer_scenario()
    oracle = hopscotch.HopscotchTable(keys0.copy(), vals0.copy(), h)
    hopscotch.insert_many_displaced(oracle, [q], [np.asarray(qval)],
                                    max_search=16, max_moves=8)
    payload = prog.device_payloads(
        jnp.asarray([q]), jnp.asarray([hopscotch.bucket_of(q, len(keys0))]),
        jnp.asarray([qval]))[0]
    fuel = prog.fuel
    from repro.core import faults as faults_mod
    faulted = jax.jit(prog.run_one_faulted, static_argnames=("max_steps",))
    clean = jax.jit(prog.run_one, static_argnames=("max_steps",))
    k0, v0 = jnp.asarray(keys0), jnp.asarray(vals0)

    cuts = sorted(set(list(range(0, fuel + 1, stride)) + [fuel]))
    torn = diverged = 0
    t_first = None
    t0 = time.perf_counter()
    for i, cut in enumerate(cuts):
        plan = faults_mod.FaultPlan.kill_at(jnp.int32(cut))
        _, tk, tv = faulted(k0, v0, payload, max_steps=fuel, faults=plan)
        tk, tv = tk[None], tv[None]
        rep = fsck.check_invariants(tk, tv, neighborhood=h)
        if not rep.clean:
            torn += 1
            tk, tv, _ = fsck.repair(tk, tv, rep, neighborhood=h)
        _, rk, rv = clean(tk[0], tv[0], payload, max_steps=fuel)
        if not (np.array_equal(np.asarray(rk), oracle.keys)
                and np.array_equal(np.asarray(rv), oracle.values)):
            diverged += 1
        if i == 0:
            t_first = time.perf_counter() - t0
    total_s = time.perf_counter() - t0
    rest_us = ((total_s - t_first) / max(len(cuts) - 1, 1)) * 1e6
    return {
        "fuel": fuel,
        "cuts_swept": len(cuts),
        "torn_states": torn,
        "diverged": diverged,
        "first_cut_us": float(t_first * 1e6),     # includes the one compile
        "per_cut_us": float(rest_us),             # traced faults: no recompile
    }


def run_migration_sweep(stride: int = 1) -> dict:
    """Kill a migration lap at every ``stride``-th step; repair re-drives
    while the source bucket is live (a terminal status is *not* proof of
    completion — the response WR lands before the copy/vacate tail)."""
    import jax
    import jax.numpy as jnp

    from repro.core import faults as faults_mod
    from repro.core import programs
    from repro.kvstore import fsck, hopscotch, store

    n, v, h = 8, 2, 4
    m = programs.build_hopscotch_migrator(n, v, neighborhood=h)
    k2 = store.keys_homed_at(2, 1, n)[0]
    k5 = store.keys_homed_at(5, 1, n)[0]
    ok0 = np.zeros(n, np.int32)
    ov0 = np.zeros((n, v), np.int32)
    ok0[2], ov0[2] = k2, [21, 22]
    ok0[5], ov0[5] = k5, [51, 52]
    to = hopscotch.HopscotchTable(ok0.copy(), ov0.copy(), h)
    tn = hopscotch.make_table(2 * n, v, h)
    to.migrate_bucket(tn, 2)

    nk0 = jnp.zeros((2 * n,), jnp.int32)
    nv0 = jnp.zeros((2 * n, v), jnp.int32)
    fuel = m.fuel
    faulted = jax.jit(m.run_one_faulted, static_argnames=("max_steps",))
    clean = jax.jit(m.run_one, static_argnames=("max_steps",))
    ok0j, ov0j = jnp.asarray(ok0), jnp.asarray(ov0)
    pay0 = m.device_payloads(jnp.asarray([2]), ok0j)[0]

    cuts = sorted(set(list(range(0, fuel + 1, stride)) + [fuel]))
    torn = diverged = 0
    for cut in cuts:
        plan = faults_mod.FaultPlan.kill_at(jnp.int32(cut))
        _, ok, ov, nk, nv = faulted(ok0j, ov0j, nk0, nv0, pay0,
                                    max_steps=fuel, faults=plan)
        rs = store.ResizeState(ok[None], ov[None], nk[None], nv[None],
                               jnp.zeros((1,), jnp.int32))
        rep = fsck.check_invariants(resize=rs, neighborhood=h)
        if not rep.clean:
            torn += 1
            rs, _ = fsck.repair_resize(rs, rep, neighborhood=h)
        rok, rov = rs.keys[0], rs.vals[0]
        rnk, rnv = rs.new_keys[0], rs.new_vals[0]
        if int(np.asarray(rok)[2]) != hopscotch.EMPTY:
            pay = m.device_payloads(jnp.asarray([2]), rok)[0]
            _, rok, rov, rnk, rnv = clean(rok, rov, rnk, rnv, pay,
                                          max_steps=fuel)
        if not (np.array_equal(np.asarray(rok), to.keys)
                and np.array_equal(np.asarray(rov), to.values)
                and np.array_equal(np.asarray(rnk), tn.keys)
                and np.array_equal(np.asarray(rnv), tn.values)):
            diverged += 1
    return {
        "fuel": fuel,
        "cuts_swept": len(cuts),
        "torn_states": torn,
        "diverged": diverged,
    }


def run_recovery_drill() -> dict:
    """``set_reliable`` against each fault kind: attempts + latency +
    fsck-clean afterwards."""
    from repro.core import faults as faults_mod
    from repro.rdma import failure

    svc = failure.ShardedKVService.start(
        [(k, [k * 2, k * 2 + 1]) for k in range(1, 7)],
        n_shards=1, buckets_per_shard=64, val_words=2)
    kinds = {
        "kill": faults_mod.FaultPlan.kill_at(10),
        "suppress": faults_mod.FaultPlan.suppress_at(5),
        "cas": faults_mod.FaultPlan.cas_fail_at(0),
        "enable": faults_mod.FaultPlan.enable_zero_at(0),
    }
    out = {}
    all_ok = True
    for i, (name, plan) in enumerate(kinds.items()):
        key = 0x3000 + i
        t0 = time.perf_counter()
        status, attempts = svc.set_reliable(key, [i + 1, i + 2],
                                            faults=plan)
        us = (time.perf_counter() - t0) * 1e6
        g = svc.get_many([key])
        served = bool(np.asarray(g.found)[0, 0])
        all_ok &= (status in TERMINAL_SET) and served
        out[name] = {"status": int(status), "attempts": int(attempts),
                     "recovery_us": float(us), "served": served}
    report = svc.fsck_and_repair()
    return {
        "kinds": out,
        "all_recovered": bool(all_ok),
        "fsck_clean_after": bool(report.clean),
        "repairs_applied": int(svc.repairs_applied),
    }


def run_storm_availability(n_requests: int = 24,
                           p_fault: float = 0.4) -> dict:
    """A seeded storm of faulted SETs through the retry loop: the landed
    fraction is the availability claim, and the store must end clean."""
    from repro.core import faults as faults_mod
    from repro.rdma import failure

    seed = faults_mod.storm_seed()
    svc = failure.ShardedKVService.start(
        [(k, [k * 2, k * 2 + 1]) for k in range(1, 9)],
        n_shards=1, buckets_per_shard=128, val_words=2)
    storm = np.asarray(faults_mod.storm(
        n_requests, p_fault=p_fault, max_step=120, seed=seed).as_rows())

    landed = 0
    attempts_hist: dict = {}
    faulted_us, clean_us = [], []
    for i in range(n_requests):
        key = 0x5000 + 13 * i
        row = storm[i]
        plan = (faults_mod.FaultPlan.from_row(row)
                if (row >= 0).any() else None)
        t0 = time.perf_counter()
        try:
            _, attempts = svc.set_reliable(key, [i + 1, i + 2],
                                           faults=plan)
            landed += 1
        except failure.ChainInterrupted:
            attempts = svc.retry_budget + 1
        us = (time.perf_counter() - t0) * 1e6
        (faulted_us if plan is not None else clean_us).append(us)
        attempts_hist[attempts] = attempts_hist.get(attempts, 0) + 1

    queries = np.asarray([0x5000 + 13 * i for i in range(n_requests)],
                         np.int32)
    g = svc.get_many(queries)
    served = int(np.asarray(g.found).sum())
    report = svc.fsck_and_repair()
    return {
        "seed": int(seed),
        "requests": n_requests,
        "faulted_requests": int((storm >= 0).any(axis=1).sum()),
        "landed": landed,
        "availability": float(landed / n_requests),
        "served_after": served,
        "attempts_hist": {str(k): v
                          for k, v in sorted(attempts_hist.items())},
        "mean_clean_us": float(np.mean(clean_us)) if clean_us else 0.0,
        "mean_faulted_us": (float(np.mean(faulted_us))
                            if faulted_us else 0.0),
        "fsck_clean_after": bool(report.clean),
        "repairs_applied": int(svc.repairs_applied),
    }


def main(out_path: str = OUT_PATH, long: bool = False):
    import jax

    disp = run_displacement_sweep(stride=1 if long else 17)
    mig = run_migration_sweep(stride=1 if long else 3)
    drill = run_recovery_drill()
    storm = run_storm_availability(n_requests=64 if long else 24)

    results = {}
    if os.path.exists(out_path):
        with open(out_path) as f:
            results = json.load(f)
    results["faults"] = {
        "backend": jax.default_backend(),
        "displacement_sweep": disp,
        "migration_sweep": mig,
        "recovery_drill": drill,
        "storm": storm,
    }
    checks = results.setdefault("checks", {})
    checks["faults_cutpoint_sweep_converges"] = bool(
        disp["diverged"] == 0 and mig["diverged"] == 0
        and disp["torn_states"] > 0 and mig["torn_states"] > 0)
    checks["faults_fsck_clean_after_recovery"] = bool(
        drill["all_recovered"] and drill["fsck_clean_after"]
        and storm["fsck_clean_after"])
    checks["faults_service_availability_under_storm"] = bool(
        storm["availability"] == 1.0
        and storm["served_after"] == storm["requests"])
    with open(out_path, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)

    rows = [
        ("faults/displacement_cut", disp["per_cut_us"],
         f"cuts={disp['cuts_swept']}/{disp['fuel'] + 1};"
         f"torn={disp['torn_states']};diverged={disp['diverged']};"
         f"first_cut_us={disp['first_cut_us']:.0f} (one compile)"),
        ("faults/migration_sweep", 0.0,
         f"cuts={mig['cuts_swept']}/{mig['fuel'] + 1};"
         f"torn={mig['torn_states']};diverged={mig['diverged']}"),
        ("faults/recovery_kill", drill["kinds"]["kill"]["recovery_us"],
         f"attempts={drill['kinds']['kill']['attempts']}"),
        ("faults/recovery_suppress",
         drill["kinds"]["suppress"]["recovery_us"],
         f"attempts={drill['kinds']['suppress']['attempts']}"),
        ("faults/recovery_cas", drill["kinds"]["cas"]["recovery_us"],
         f"attempts={drill['kinds']['cas']['attempts']}"),
        ("faults/recovery_enable",
         drill["kinds"]["enable"]["recovery_us"],
         f"attempts={drill['kinds']['enable']['attempts']}"),
        ("faults/storm_set_faulted", storm["mean_faulted_us"],
         f"seed={storm['seed']};availability={storm['availability']:.3f};"
         f"clean_us={storm['mean_clean_us']:.0f};"
         f"repairs={storm['repairs_applied']}"),
    ]
    common.emit(rows)
    for name, ok in checks.items():
        if name.startswith("faults"):
            print(f"check,{name},{'PASS' if ok else 'FAIL'}")
    return results


if __name__ == "__main__":
    compile_cache.enable()
    main(long="--long" in sys.argv)
