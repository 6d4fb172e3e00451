#!/usr/bin/env python3
"""Smoke test: the key-value store's serving path on a TPU.

Run from the root of a checkout, on a machine whose JAX sees a TPU:

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips, the sharded path only

One chip: a ``ShardedKVService`` holding one 2^20-bucket shard, about half
full of seeded items (24-bit keys, 4-word values), serves rounds of
GET / SET / DELETE / GET through its own methods; then the chain-VM Pallas
kernel serves the recycled get server against the interpreter.  Four
chips: four such shards, one per chip, served through the routed
(all-to-all) path.  Every answer, and at the end every word of the device
table, is compared with the host ``HopscotchTable`` oracle.

Earlier lines record the run: sizes, compile and serve seconds, ops
compared, mismatches, peak device memory.  The last line is one JSON
object, ``{"ok": true, "device": {...}}``, printed only when every phase
passed.  The script exits non-zero without that line when JAX finds no
TPU, when the repository's ``src/`` is missing, or when any answer is
wrong.  It runs in one process and starts none.
"""
from __future__ import annotations

import argparse
import collections
import copy
import functools
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

VAL_WORDS = 4
BUCKETS = 1 << 20
LOAD = 0.5
GET_BATCH = 64        # GETs per call, split evenly over the source shards
SET_BATCH = 16        # SETs per call
DEL_BATCH = 16        # DELETEs per call
ROUNDS = 3            # rounds of GET, SET, DELETE, GET
KEY_SPACE = 1 << 24   # keys are 24-bit chain ids; 0 is the empty marker


class CompileClock:
    """Seconds XLA spends compiling, and how many programs it compiled,
    read from JAX's monitoring events (one listener per process)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.count += 1


class Tally:
    """What a phase recorded: for every timed call its wall seconds and
    the compiles inside it, and for every compared quantity the ops
    compared and the mismatches."""

    def __init__(self, clock: CompileClock):
        self.clock = clock
        self.ops = collections.Counter()
        self.bad = collections.Counter()
        self.calls = collections.defaultdict(list)

    def timed(self, op, fn):
        c0, n0, t0 = self.clock.seconds, self.clock.count, time.perf_counter()
        out = fn()
        wall = time.perf_counter() - t0
        self.calls[op].append((wall, self.clock.seconds - c0,
                               self.clock.count - n0))
        return out

    def compare(self, op, got, want, ok):
        """Count rows of ``got`` vs ``want`` (leading dim = ops); a row
        the store did not serve (``ok`` False) is a mismatch too."""
        got, want = np.asarray(got), np.asarray(want)
        diff = (got != want).reshape(len(got), -1).any(axis=1)
        self.ops[op] += len(got)
        self.bad[op] += int((diff | ~np.asarray(ok, bool)).sum())

    def lines(self, phase):
        for op, calls in self.calls.items():
            (wall0, comp0, n0), rest = calls[0], calls[1:]
            yield (f"{phase}: call={op} calls={len(calls)} "
                   f"first_call_s={wall0} first_call_compile_s={comp0} "
                   f"first_call_compiles={n0} "
                   f"later_calls_s={[w for w, _, _ in rest]} "
                   f"later_call_compiles={sum(n for _, _, n in rest)}")
        for op in self.ops:
            yield (f"{phase}: compare={op} ops={self.ops[op]} "
                   f"mismatches={self.bad[op]}")

    @property
    def mismatches(self):
        return sum(self.bad.values())


# --- the oracle ------------------------------------------------------------

def seed_store(rng, n_shards, buckets, val_words, load=LOAD):
    """Seeded items filling ``load`` of every shard, inserted in order into
    a host oracle.  An item the bounded insert cannot place (it would need
    a resize) is left out, so the service's own bootstrap of the kept
    items succeeds.  Returns ``(oracle, keys, vals, n_left_out)``."""
    from repro.kvstore import store

    n = int(n_shards * buckets * load)
    keys = rng.choice(KEY_SPACE - 1, n, replace=False).astype(np.int32) + 1
    vals = rng.integers(-2**31, 2**31, (n, val_words), dtype=np.int32)
    oracle = store.ShardedKV.build(n_shards, buckets, val_words)
    kept = np.fromiter((oracle.set(k, v) for k, v in
                        zip(keys.tolist(), vals)), bool, n)
    return oracle, keys[kept], vals[kept], int(n - kept.sum())


def _by_owner(oracle, flat_keys):
    from repro.kvstore import store

    owner = np.asarray(store.shard_of(flat_keys, oracle.n_shards))
    for s in range(oracle.n_shards):
        yield s, np.flatnonzero((owner == s) & (flat_keys != 0))


def oracle_set(oracle, qk, qv):
    """Statuses of an (S, B) SET batch applied to the oracle: each owner
    applies its requests in source-major order, fast writer first, then
    the displacer for the rows that need it (the store's two stages)."""
    from repro.kvstore import hopscotch

    flat_k, flat_v = qk.reshape(-1), qv.reshape(-1, qv.shape[-1])
    status = np.zeros(flat_k.shape, np.int32)
    for s, idx in _by_owner(oracle, flat_k):
        status[idx] = hopscotch.insert_many_displaced(
            oracle.tables[s], flat_k[idx], flat_v[idx])
    return status.reshape(qk.shape)


def oracle_delete(oracle, qk):
    from repro.kvstore import hopscotch

    flat = qk.reshape(-1)
    status = np.zeros(flat.shape, np.int32)
    for s, idx in _by_owner(oracle, flat):
        status[idx] = hopscotch.delete_many(oracle.tables[s], flat[idx])
    return status.reshape(qk.shape)


# --- traffic ---------------------------------------------------------------

def _absent(rng, oracle, count):
    """``count`` fresh keys absent from the oracle."""
    present = np.concatenate([t.keys for t in oracle.tables])
    out = np.zeros(0, np.int32)
    while len(out) < count:
        c = rng.integers(1, KEY_SPACE, 4 * count + 64).astype(np.int32)
        c = c[~np.isin(c, present) & ~np.isin(c, out)]
        out = np.concatenate([out, np.unique(c)])
    return rng.permutation(out)[:count]


def _needs_displacer(rng, oracle, shard):
    """Fresh keys on ``shard`` whose home neighborhood is full, so a SET
    of one must go through the displacer chain."""
    from repro.kvstore import hopscotch, store

    t = oracle.tables[shard]
    occ = (t.keys != hopscotch.EMPTY).astype(np.int32)
    n, h = len(occ), t.neighborhood
    window = sum(np.roll(occ, -d) for d in range(h))       # [b, b + h)
    full = np.flatnonzero(window == h)
    c = rng.integers(1, KEY_SPACE, 1 << 20).astype(np.int32)
    home = np.asarray(hopscotch.bucket_of(c, n))
    c = c[np.isin(home, full) & ~np.isin(c, t.keys)]
    c = c[np.asarray(store.shard_of(c, oracle.n_shards)) == shard]
    return rng.permutation(np.unique(c))


def set_batch(rng, oracle, live, n_shards, per_source, val_words):
    """An (S, per_source) SET batch of updates, fresh inserts and at least
    one insert that needs the displacer, with its oracle statuses.  A
    candidate the oracle answers SET_NEEDS_RESIZE is swapped out: growth
    is not this smoke's path.  Returns ``(keys, vals, statuses)`` and
    leaves the oracle updated."""
    from repro.kvstore import hopscotch

    total = n_shards * per_source
    n_upd = total // 2
    disp = list(_needs_displacer(rng, oracle, 0))
    for _ in range(64):
        ks = np.concatenate([
            rng.choice(live, n_upd, replace=False),
            _absent(rng, oracle, total - n_upd - 1),
            [disp.pop(0)]]).astype(np.int32)
        ks = rng.permutation(ks).reshape(n_shards, per_source)
        vs = rng.integers(-2**31, 2**31, ks.shape + (val_words,),
                          dtype=np.int32)
        trial = copy.deepcopy(oracle)
        st = oracle_set(trial, ks, vs)
        if (st != hopscotch.SET_NEEDS_RESIZE).all() and \
                (st == hopscotch.SET_DISPLACED).any():
            oracle.tables[:] = trial.tables
            return ks, vs, st
    raise RuntimeError("no SET batch with a displacement and no resize "
                       "found in 64 draws")


def get_batch(rng, live, gone, n_shards, per_source):
    """(S, per_source) GET keys: live keys, deleted keys, fresh keys, and
    key 0 (the empty marker, always a miss)."""
    total = n_shards * per_source
    n_gone = min(len(gone), total // 8)
    q = np.concatenate([
        [0],
        rng.choice(gone, n_gone, replace=False) if n_gone else [],
        rng.integers(1, KEY_SPACE, total // 8),
        rng.choice(live, total - 1 - n_gone - total // 8, replace=False),
    ]).astype(np.int32)
    return rng.permutation(q).reshape(n_shards, per_source)


def del_batch(rng, oracle, live, fresh, n_shards, per_source):
    """(S, per_source) DELETE keys: keys just set, other live keys, and
    keys that are not there."""
    total = n_shards * per_source
    n_fresh = min(len(fresh), total // 4)
    q = np.concatenate([
        rng.choice(fresh, n_fresh, replace=False),
        _absent(rng, oracle, total // 4),
        rng.choice(live, total - n_fresh - total // 4, replace=False),
    ]).astype(np.int32)
    return rng.permutation(q).reshape(n_shards, per_source)


# --- phases ----------------------------------------------------------------

def kv_phase(tally, *, n_shards, buckets, seed, rounds=ROUNDS,
             val_words=VAL_WORDS, phase="kv"):
    """Seed a ``ShardedKVService`` and serve ``rounds`` rounds of GET,
    SET, DELETE, GET through its methods, comparing every answer with the
    oracle; finally compare the whole device table.  Returns a dict of
    what the run recorded (prints nothing)."""
    import jax

    from repro.kvstore import store
    from repro.rdma.failure import ShardedKVService

    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    oracle, keys, vals, left_out = seed_store(rng, n_shards, buckets,
                                              val_words)
    svc = ShardedKVService.start(zip(keys.tolist(), vals), n_shards=n_shards,
                                 buckets_per_shard=buckets,
                                 val_words=val_words)
    jax.block_until_ready((svc.keys, svc.vals))
    setup_s = time.perf_counter() - t0
    devices = svc.keys.sharding.device_set
    if len(devices) != n_shards:
        raise RuntimeError(f"{n_shards} shards placed on {len(devices)} "
                           "device(s)")

    state = {"live": keys, "gone": np.zeros(0, np.int32)}
    g_src = GET_BATCH // n_shards
    s_src, d_src = SET_BATCH // n_shards, DEL_BATCH // n_shards

    def serve_get():
        q = get_batch(rng, state["live"], state["gone"], n_shards, g_src)
        res = tally.timed("get", lambda: jax.device_get(svc.get_many(q)))
        want_f, want_v = store.reference_get(oracle, q.reshape(-1))
        got = np.concatenate([res.found.reshape(-1, 1),
                              res.values.reshape(len(want_v), -1)], axis=1)
        want = np.concatenate([want_f[:, None], want_v], axis=1)
        tally.compare("get", got, want, res.ok.reshape(-1))

    def serve_set_delete():
        qk, qv, want = set_batch(rng, oracle, state["live"], n_shards,
                                 s_src, val_words)
        res = tally.timed("set", lambda: jax.device_get(
            svc.set_many(qk, qv)))
        tally.compare("set", res.status.reshape(-1), want.reshape(-1),
                      res.ok.reshape(-1))
        live = np.union1d(state["live"], qk.reshape(-1))
        qd = del_batch(rng, oracle, live, qk.reshape(-1), n_shards, d_src)
        want = oracle_delete(oracle, qd)
        res = tally.timed("delete", lambda: jax.device_get(
            svc.delete_many(qd)))
        tally.compare("delete", res.status.reshape(-1), want.reshape(-1),
                      res.ok.reshape(-1))
        state["gone"] = np.union1d(state["gone"], qd.reshape(-1))
        state["live"] = np.setdiff1d(live, state["gone"])

    for _ in range(rounds):
        serve_get()
        serve_set_delete()
        serve_get()

    # the device table is authoritative: every bucket, key and value row,
    # must equal the oracle's
    dk, dv = jax.device_get((svc.keys, svc.vals))
    tally.compare("table_buckets",
                  np.concatenate([dk.reshape(-1, 1),
                                  dv.reshape(dk.size, -1)], axis=1),
                  np.concatenate([np.concatenate([t.keys for t in
                                                  oracle.tables])[:, None],
                                  np.concatenate([t.values for t in
                                                  oracle.tables])], axis=1),
                  np.ones(dk.size, bool))
    return {
        "phase": phase, "n_shards": n_shards, "buckets_per_shard": buckets,
        "val_words": val_words, "items": int(len(keys)),
        "load": len(keys) / (n_shards * buckets), "left_out": left_out,
        "get_capacity": g_src, "setup_s": setup_s,
        "devices": sorted(str(d) for d in devices),
    }


def pallas_phase(tally, *, seed, backend="pallas"):
    """The chain-VM Pallas kernel serves the recycled get server at its
    default image, one grid cell per request, against the interpreter."""
    import jax

    from repro.core import programs
    from repro.core.engine import ChainEngine

    rng = np.random.default_rng(seed)
    srv = programs.build_recycled_get_server()
    keys = rng.choice(np.arange(1, 1 << 16), srv.n_buckets, replace=False)
    for k in keys[: srv.n_buckets // 2]:
        srv.insert(int(k), rng.integers(-2**31, 2**31, srv.val_len).tolist())
    srv.load()
    probe = rng.choice(keys, GET_BATCH)
    payloads = np.asarray([[k, srv.bucket_addr(srv.h1(int(k)))]
                           for k in probe], np.int32)
    runs = {}
    for name in ("interp", backend):
        eng = ChainEngine.for_spec(srv.spec, name)
        runs[name] = tally.timed(name, lambda: jax.device_get(eng.run_many(
            srv.state, srv.loop_wq, payloads, 64)))
    ref, got = runs["interp"], runs[backend]
    ok = np.ones(len(probe), bool)
    for field in ("mem", "head", "enable_limit", "completions", "msg_head",
                  "halted", "responses", "steps"):
        tally.compare(f"{backend}_vs_interp.{field}", getattr(got, field),
                      getattr(ref, field), ok)
    resp = got.mem[:, srv.resp_region:srv.resp_region + srv.val_len]
    hits = [(i, srv.kv[srv.h1(int(k))][1]) for i, k in enumerate(probe)
            if srv.kv.get(srv.h1(int(k)), (None,))[0] == int(k)]
    tally.compare(f"{backend}.hit_values", resp[[i for i, _ in hits]],
                  np.asarray([v for _, v in hits]).reshape(len(hits), -1),
                  ok[:len(hits)])
    return {"phase": backend, "image_words": int(srv.spec.mem_words),
            "requests": len(probe), "hits": len(hits)}


def _peak_bytes(devices):
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append(stats.get("peak_bytes_in_use"))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-shard routed path, one shard per "
                         "chip of a 4-chip host")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    d0 = devices[0]
    print(f"device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devices)}", flush=True)
    if d0.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {d0.platform!r}",
              file=sys.stderr)
        return 2
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    n_chips = 4 if args.four_chips else 1
    if len(devices) < n_chips:
        print(f"chip_smoke: needs {n_chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2

    from repro import compile_cache
    print(f"compile cache: {compile_cache.enable()}", flush=True)
    clock = CompileClock()

    phases = [functools.partial(kv_phase, n_shards=n_chips, buckets=BUCKETS,
                                seed=args.seed, phase=f"kv{n_chips}")]
    if not args.four_chips:
        phases.append(functools.partial(pallas_phase, seed=args.seed))
    failed = 0
    for run_phase in phases:
        tally = Tally(clock)
        rec = run_phase(tally)
        print(json.dumps(rec), flush=True)
        for line in tally.lines(rec["phase"]):
            print(line, flush=True)
        print(f"{rec['phase']}: mismatches={tally.mismatches} "
              f"peak_bytes_in_use={_peak_bytes(devices[:n_chips])}",
              flush=True)
        failed += tally.mismatches
    if failed:
        print(f"chip_smoke: FAILED with {failed} mismatches",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
