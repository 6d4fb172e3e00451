"""Cells cut to 2^10 buckets a shard, and faults planted under the timed
path, for the tests of the harness."""
import dataclasses
import json
import pathlib
import sys
from types import SimpleNamespace

import numpy as np

sys.path[:0] = [str(pathlib.Path(__file__).resolve().parents[2]),
                str(pathlib.Path(__file__).resolve().parents[2] / "src")]

from bench import harness, ycsb  # noqa: E402

BUCKETS = 1 << 10


def tiny(cell_name: str, config: str = None, traffic: str = None):
    """A cell of ``BENCHMARK.json`` at 2^10 buckets a shard; or, given a
    configuration and a mix under ``bench/``, a cell made of them that the
    manifest does not list yet, reporting every end-to-end metric that is
    not restricted to named cells."""
    if config is None:
        cell = harness.load_cell(cell_name)
    else:
        spec = json.loads(harness.MANIFEST.read_text())
        cfg = harness.Config.from_dict(json.loads(
            (harness.BENCH / "configs" / f"{config}.json").read_text()))
        cell = harness.Cell(
            cell_name, cfg.n_shards, cfg, ycsb.Mix.load(traffic),
            tuple(m for m in spec["end_to_end"] if "workloads" not in m),
            ())
    cfg = dataclasses.replace(
        cell.config, buckets_per_shard=BUCKETS,
        recordcount=cell.config.n_shards * BUCKETS // 2)
    return dataclasses.replace(cell, config=cfg)


class Fault:
    """The real service, with one fault planted where its answers are
    produced."""

    def __init__(self, svc):
        self.svc = svc

    def __getattr__(self, name):
        return getattr(self.svc, name)

    def get_many(self, q):
        return self.svc.get_many(q)

    def set_many(self, k, v):
        return self.svc.set_many(k, v)


class StateUnchanged(Fault):
    """A SET step acknowledges its rows and returns the table unchanged."""

    def set_many(self, k, v):
        keys, vals = self.svc.keys, self.svc.vals
        res = self.svc.set_many(k, v)
        self.svc.keys, self.svc.vals = keys, vals
        return res


class HalfBatch(Fault):
    """GET serves the first half of each call and answers the rest as
    misses."""

    def get_many(self, q):
        res = self.svc.get_many(q)
        found, values = np.array(res.found), np.array(res.values)
        flat = found.reshape(-1)
        flat[flat.size // 2:] = False
        values.reshape(flat.size, -1)[flat.size // 2:] = 0
        return SimpleNamespace(found=found, values=values, ok=res.ok)


class AlteredAnswer(Fault):
    """One word of one GET answer is altered where it is produced."""

    def get_many(self, q):
        res = self.svc.get_many(q)
        values = np.array(res.values)
        values.reshape(-1)[0] ^= 1
        return SimpleNamespace(found=res.found, values=values, ok=res.ok)


class NoExchange(Fault):
    """The all-to-all left out: each source shard answers from its own
    shard only, so a key another shard owns reads as a miss."""

    def get_many(self, q):
        from repro.kvstore import store

        res = self.svc.get_many(q)
        q = np.asarray(q)
        owner = np.asarray(store.shard_of(q, q.shape[0]))
        remote = owner != np.arange(q.shape[0])[:, None]
        found = np.array(res.found) & ~remote
        values = np.where(remote[..., None], 0, np.array(res.values))
        return SimpleNamespace(found=found, values=values, ok=res.ok)


FAULTS = {"state_unchanged": StateUnchanged, "half_batch": HalfBatch,
          "altered_answer": AlteredAnswer, "no_exchange": NoExchange}


def run(cell, seed: int, seconds: float, fault: str = None):
    """Execute ``cell`` (a name in ``BENCHMARK.json``, or a
    :func:`tiny` cell) with ``fault`` planted, if one is named."""
    wrap = None
    if fault == "control":
        from bench import control
        wrap = lambda svc, k, v: control.NarrowValues(k, v)  # noqa: E731
    elif fault is not None:
        wrap = lambda svc, k, v: FAULTS[fault](svc)  # noqa: E731
    if isinstance(cell, str):
        cell = tiny(cell)
    return harness.execute(cell, seed, seconds, wrap=wrap)
