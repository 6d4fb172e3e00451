"""One run of one cell: set-up, the measured window, the checks against
the reference, and the result line.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its deployment in ``bench/configs/<config>.json``, its mix in
``bench/traffic/<mix>.json`` and each per-layer metric's reader in
``bench/metrics/<metric>.py``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import re
import sys
import time
from typing import Callable, Optional

import numpy as np

from . import loop, reference, ycsb

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
MANIFEST = ROOT / "BENCHMARK.json"
_PERCENTILE = re.compile(r"^(get|update)_p(\d+)_ms$")
_OPS = {"get": ycsb.READ, "update": ycsb.UPDATE}


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


class CompileClock:
    """Programs XLA compiled and the seconds it spent, read from JAX's
    monitoring events (one listener per process)."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.count += 1


@dataclasses.dataclass(frozen=True)
class Config:
    """A deployment, as its file under ``bench/configs/`` states it."""
    name: str
    n_shards: int
    buckets_per_shard: int
    neighborhood: int
    val_words: int
    recordcount: int
    get_width: int
    set_width: int

    @classmethod
    def from_dict(cls, spec: dict) -> "Config":
        return cls(**{f.name: spec[f.name] for f in dataclasses.fields(cls)})

    @property
    def shape(self) -> loop.Shape:
        return loop.Shape(self.n_shards, self.get_width, self.set_width,
                          self.val_words)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Config
    mix: ycsb.Mix
    end_to_end: tuple          # metric entries of BENCHMARK.json
    per_layer: tuple


def _for_cell(metrics, cell: str) -> tuple:
    return tuple(m for m in metrics
                 if "workloads" not in m or cell in m["workloads"])


def load_cell(name: str, manifest: pathlib.Path = MANIFEST) -> Cell:
    spec = json.loads(manifest.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {manifest.name}; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg_file = manifest.parent / configs[w["config"]]["file"]
    return Cell(name=name, chips=w["chips"],
                config=Config.from_dict(json.loads(cfg_file.read_text())),
                mix=ycsb.Mix.load(w["traffic"]),
                end_to_end=_for_cell(spec["end_to_end"], name),
                per_layer=_for_cell(spec["per_layer"], name))


def reader(metric: str) -> Callable:
    """The ``reduce`` function of ``bench/metrics/<metric>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{metric}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.reduce


# -- set-up ------------------------------------------------------------------

def start_service(cfg: Config, keys: np.ndarray, vals: np.ndarray, split):
    """Bootstrap the records through the store's own host insert, place
    the tables one shard per chip, and wrap them in the service.  Items
    the bounded insert refuses were never acknowledged and are left out.
    Returns ``(service, placed mask)``."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.kvstore import store
    from repro.rdma import failure

    t = time.perf_counter()
    kv = store.ShardedKV.build(cfg.n_shards, cfg.buckets_per_shard,
                               cfg.val_words, cfg.neighborhood)
    placed = np.fromiter((kv.set(k, v) for k, v in
                          zip(keys.tolist(), vals.tolist())), bool, len(keys))
    split["bootstrap"] = time.perf_counter() - t

    t = time.perf_counter()
    axis = "kv"
    mesh = store.serving_mesh(cfg.n_shards, axis)
    dev_keys, dev_vals = kv.device_arrays(
        NamedSharding(mesh, PartitionSpec(axis)))
    jax.block_until_ready((dev_keys, dev_vals))
    svc = failure.ShardedKVService(kv=kv, mesh=mesh, axis=axis,
                                   keys=dev_keys, vals=dev_vals,
                                   driver=failure.HostDriver())
    split["placement"] = time.perf_counter() - t
    return svc, placed


def _rows(cfg: Config, width: int, keys: np.ndarray) -> np.ndarray:
    """``keys`` laid out as one call: (shards, width / shards), padded
    with key 0."""
    out = np.zeros(width, np.int32)
    out[:len(keys)] = keys
    return out.reshape(cfg.n_shards, width // cfg.n_shards)


def warm_up(svc, cfg: Config, mix: ycsb.Mix, keys, vals, split, clock):
    """Run each call shape the cell's traffic uses twice: the first call
    compiles or loads from the cache, the second runs warm.  SET calls
    rewrite records with the values they hold, so the table is as the
    bootstrap left it."""
    shapes = [("get", lambda: loop.get_call(
        svc, _rows(cfg, cfg.get_width, keys[:cfg.get_width])))]
    if mix.update_proportion > 0:
        k = _rows(cfg, cfg.set_width, keys[:cfg.set_width])
        v = np.zeros(k.shape + (cfg.val_words,), np.int32)
        v.reshape(-1, cfg.val_words)[:cfg.set_width] = vals[:cfg.set_width]
        shapes.append(("set", lambda: loop.set_call(svc, k, v)))
    for name, call in shapes:
        for phase in ("first_call", "warm_call"):
            c0, n0, t = clock.seconds, clock.count, time.perf_counter()
            call()
            split[f"{name}_{phase}"] = time.perf_counter() - t
            split[f"{name}_{phase}_compiles"] = clock.count - n0
            split[f"{name}_{phase}_compile_s"] = clock.seconds - c0


# -- after the window ----------------------------------------------------------

def read_back(svc, cfg: Config, keys: list) -> list:
    """GET every key in ``keys`` through the service, in calls of the
    window's GET width; rows it does not serve are asked again."""
    calls, todo = [], list(keys)
    for _ in range(1000):
        if not todo:
            break
        batch, todo = todo[:cfg.get_width], todo[cfg.get_width:]
        q = _rows(cfg, cfg.get_width, np.asarray(batch, np.int32))
        t = time.perf_counter()
        found, values, ok = loop.get_call(svc, q)
        call = loop.Call(ycsb.READ, q, None, t, time.perf_counter(), False,
                         ok=np.asarray(ok), found=np.asarray(found),
                         values=np.asarray(values))
        calls.append(call)
        todo = [int(k) for k in q[(q != 0) & ~call.ok]] + todo
    return calls


def peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# -- the run -------------------------------------------------------------------

@dataclasses.dataclass
class Outcome:
    result: dict                 # the contract's last line
    checks: dict                 # name -> (value, limit)
    lines: list                  # earlier lines, for the record


#: every compared number must be exactly 0: the reference is exact
LIMITS = {"get_mismatches": 0, "update_mismatches": 0,
          "readback_mismatches": 0, "table_mismatches": 0, "failed": 0}


def execute(cell: Cell, seed: int, seconds: float, *, trace_dir=None,
            t_process: Optional[float] = None, wrap: Callable = None,
            peak_row: Optional[dict] = None) -> Outcome:
    """Set up ``cell`` from ``seed``, serve the window, check every answer
    and the final table against the reference.  ``wrap(service, keys,
    values)``, given the placed records, returns what serves in the
    service's place: the control (``bench/control.py``) or, in tests, a
    broken service."""
    import jax

    t_process = time.perf_counter() if t_process is None else t_process
    cfg, mix = cell.config, cell.mix
    clock = CompileClock()
    lines, split = [], {}

    t = time.perf_counter()
    keys, vals = ycsb.records(seed, cfg.recordcount, cfg.val_words)
    split["seeding"] = time.perf_counter() - t
    svc, placed = start_service(cfg, keys, vals, split)
    rec_keys, rec_vals = keys[placed], vals[placed]
    if wrap is not None:
        svc = wrap(svc, rec_keys, rec_vals)
    warm_up(svc, cfg, mix, rec_keys, rec_vals, split, clock)

    stream = ycsb.OpStream(mix, len(rec_keys), cfg.val_words, seed)
    closed = loop.ClosedLoop(svc, cfg.shape, stream, rec_keys, mix.clients,
                             annotate=trace_dir is not None)
    tracer = None
    if trace_dir is not None:
        from . import trace
        tracer = trace.Tracer(trace_dir)
        tracer.start()
    setup_s = time.perf_counter() - t_process
    n0 = clock.count
    if tracer is not None:
        with jax.profiler.TraceAnnotation("bench.window"):
            closed.serve_window(seconds)
    else:
        closed.serve_window(seconds)
    window_compiles = clock.count - n0
    if tracer is not None:
        tracer.stop()
    devices = jax.devices()[:cfg.n_shards]
    mem_peak = peak_bytes(devices)
    closed.drain()

    # -- checks, outside the timing ---------------------------------------
    ref = reference.Reference(rec_keys, rec_vals)
    tally = reference.Tally()
    updated = reference.replay(ref, closed.calls, tally)
    reference.read_back(ref, read_back(svc, cfg, sorted(updated)), tally)
    table_keys, table_vals = jax.device_get((svc.keys, svc.vals))
    reference.compare_table(ref, table_keys, table_vals, tally)

    metrics = {}
    for m in cell.end_to_end:
        value = _end_to_end(m["name"], closed, setup_s)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    d0 = jax.devices()[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": jax.device_count(), "memory_peak_bytes": mem_peak}
    result = {"correct": None, "attempted": closed.attempted,
              "failed": closed.failed, "metrics": metrics, "device": device}

    if tracer is not None:
        from . import trace
        traced = trace.load(tracer.xplane(), peak_row, cfg.neighborhood,
                            cfg.val_words)
        metrics.clear()
        for m in cell.per_layer:
            value = reader(m["name"])(traced)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = traced.busy_s()
        device["window_s"] = traced.window_s
        result["breakdown"] = traced.breakdown()
        tracer.remove()

    checks = {"get_mismatches": tally["get_mismatches"],
              "update_mismatches": tally["update_mismatches"],
              "readback_mismatches": tally["readback_mismatches"],
              "table_mismatches": tally["table_mismatches"],
              "failed": closed.failed}
    checks = {k: (v, LIMITS[k]) for k, v in checks.items()}
    result["correct"] = all(v <= lim for v, lim in checks.values())
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}

    n_get = len(closed.latency[ycsb.READ])
    n_upd = len(closed.latency[ycsb.UPDATE])
    lines += [
        f"device: platform={d0.platform} kind={d0.device_kind} "
        f"count={jax.device_count()} shards_on={[str(d) for d in devices]}",
        f"records: requested={cfg.recordcount} placed={len(rec_keys)} "
        f"left_out={cfg.recordcount - len(rec_keys)} "
        f"load={len(rec_keys) / (cfg.n_shards * cfg.buckets_per_shard)}",
        f"setup_s={setup_s} split={json.dumps(split)}",
        f"window: seconds={closed.seconds} calls="
        f"{sum(c.in_window for c in closed.calls)} "
        f"compiles_in_window={window_compiles}",
        f"generator: mean_gap_between_calls_ms={closed.mean_gap_ms()}",
        f"requests: attempted={closed.attempted} "
        f"answered_in_window={closed.answered_in_window} "
        f"reissued={closed.reissued} failed={closed.failed}",
        f"samples: get={n_get} update={n_upd} (p95 needs 200 for 10 "
        f"beyond it)",
        f"compared: {json.dumps(dict(sorted(tally.items())))}",
        f"memory: peak_bytes_in_use="
        f"{[(d.memory_stats() or {}).get('peak_bytes_in_use') for d in devices]}",
    ]
    return Outcome(result, checks, lines)


def _end_to_end(name: str, closed: loop.ClosedLoop, setup_s: float):
    if name == "setup_s":
        return setup_s
    if name == "ops_per_s":
        return closed.ops_per_s()
    m = _PERCENTILE.match(name)
    if m:
        return closed.percentile_ms(_OPS[m.group(1)], float(m.group(2)))
    raise KeyError(f"no end-to-end metric {name!r}")
