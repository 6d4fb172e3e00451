"""The profiler trace of a run, reduced to what the per-layer metrics
read: the benchmark's own host spans and the device's programs and ops,
on one clock.

The harness wraps the window in a ``bench.window`` span, each service
call in ``bench.get`` or ``bench.set``, and after each call records a
zero-length ``bench.answers`` span whose arguments carry the call's kind,
live rows and hits, so a trace alone holds what a reader needs.  A chip is
busy while a program runs on it: the union of its ``XLA Modules``
intervals.  Of its ``XLA Ops`` only the top-level ops (for the breakdown)
and the all-to-all ops are kept: ops inside a loop body repeat once per
iteration and number hundreds of thousands a second.
"""
from __future__ import annotations

import collections
import dataclasses
import pathlib
import shutil
from typing import Optional

import numpy as np

DEVICE_PLANE = "/device:TPU:"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


class Tracer:
    """The JAX profiler, on for the window, writing under ``path``."""

    def __init__(self, path):
        self.path = pathlib.Path(path)

    def start(self):
        import jax

        shutil.rmtree(self.path, ignore_errors=True)
        self.path.mkdir(parents=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # the host spans are our own
        jax.profiler.start_trace(str(self.path), profiler_options=opts)

    def stop(self):
        import jax

        jax.profiler.stop_trace()

    def xplane(self) -> pathlib.Path:
        found = sorted(self.path.glob("plugins/profile/*/*.xplane.pb"))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {self.path}")
        return found[-1]

    def remove(self):
        shutil.rmtree(self.path, ignore_errors=True)


@dataclasses.dataclass
class Span:
    name: str
    start: float           # ns
    end: float
    args: dict


def _merge(starts: np.ndarray, ends: np.ndarray) -> np.ndarray:
    """Union of intervals, as an (n, 2) array of disjoint sorted ones."""
    if len(starts) == 0:
        return np.zeros((0, 2))
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > reach[:-1]
    idx = np.flatnonzero(new)
    return np.stack([s[idx], np.maximum.reduceat(e, idx)], axis=1)


def _covered(merged: np.ndarray, a: float, b: float) -> float:
    """Length of ``[a, b]`` that the disjoint intervals cover."""
    if len(merged) == 0 or b <= a:
        return 0.0
    lo = np.clip(merged[:, 0], a, b)
    hi = np.clip(merged[:, 1], a, b)
    return float(np.sum(hi - lo))


@dataclasses.dataclass
class Traced:
    """One traced window: the host spans, and for each chip the programs
    it ran (busy time), its top-level ops and its all-to-all ops."""
    spans: list                  # [Span], in start order
    modules: dict                # chip -> (starts, ends) of programs
    ops: dict                    # chip -> (names, starts, ends), top level
    a2a: dict                    # chip -> (starts, ends) of all-to-alls
    peak: Optional[dict] = None  # row of bench/peaks.json
    neighborhood: int = 8
    val_words: int = 4

    def __post_init__(self):
        self._busy = {c: _merge(s, e) for c, (s, e) in self.modules.items()}
        win = [s for s in self.spans if s.name == "bench.window"]
        self.window = ((win[0].start, win[0].end) if win else
                       (min(s.start for s in self.spans),
                        max(s.end for s in self.spans)))

    @property
    def n_chips(self) -> int:
        return len(self.modules)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def calls(self, name: str) -> list:
        """Spans of ``bench.get`` or ``bench.set`` inside the window."""
        a, b = self.window
        return [s for s in self.spans
                if s.name == name and s.start >= a and s.end <= b]

    def answers(self, kind: str) -> list:
        a, b = self.window
        return [s.args for s in self.spans
                if s.name == "bench.answers" and s.args.get("kind") == kind
                and a <= s.start <= b]

    def busy_in(self, a: float, b: float) -> float:
        """ns in ``[a, b]`` during which a program ran, mean over chips."""
        if not self._busy:
            return 0.0
        return float(np.mean([_covered(m, a, b)
                              for m in self._busy.values()]))

    def a2a_in(self, a: float, b: float) -> float:
        """ns in ``[a, b]`` during which an all-to-all ran, mean over
        chips."""
        if not self.a2a:
            return 0.0
        return float(np.mean([_covered(_merge(s, e), a, b)
                              for s, e in self.a2a.values()]))

    def busy_s(self) -> float:
        return self.busy_in(*self.window) * 1e-9

    def breakdown(self, top: int = 10) -> dict:
        """The top-level device ops that took most time (seconds, mean over
        chips) and the longest idle gaps, named by the host span open in
        them."""
        a, b = self.window
        per_op = collections.Counter()
        for names, s, e in self.ops.values():
            inside = np.flatnonzero((s >= a) & (e <= b))
            for i in inside:
                per_op[names[i]] += float(e[i] - s[i])
        device_ops = [[n, t * 1e-9 / max(self.n_chips, 1)]
                      for n, t in per_op.most_common(top)]
        calls = [s for s in self.spans
                 if s.name in ("bench.get", "bench.set")]
        gaps = []
        for chip, merged in self._busy.items():
            inner = merged[(merged[:, 1] > a) & (merged[:, 0] < b)]
            edges = np.concatenate([[a], np.clip(inner, a, b).reshape(-1),
                                    [b]])
            for g0, g1 in zip(edges[0::2], edges[1::2]):
                if g1 > g0:
                    mid = (g0 + g1) / 2
                    host = next((s.name for s in calls
                                 if s.start <= mid <= s.end),
                                "bench.loop (between calls)")
                    gaps.append((float(g1 - g0), f"chip{chip}: {host}"))
        gaps.sort(reverse=True)
        return {"device_ops": device_ops,
                "idle_gaps": [[n, g * 1e-9] for g, n in gaps[:top]]}

    # -- a compact copy, for fixtures --------------------------------------
    def to_json(self) -> dict:
        arr = lambda x: np.asarray(x).tolist()  # noqa: E731
        return {
            "spans": [dataclasses.asdict(s) for s in self.spans],
            "modules": {c: [arr(s), arr(e)] for c, (s, e)
                        in self.modules.items()},
            "ops": {c: [list(n), arr(s), arr(e)] for c, (n, s, e)
                    in self.ops.items()},
            "a2a": {c: [arr(s), arr(e)] for c, (s, e) in self.a2a.items()},
        }

    @classmethod
    def from_json(cls, doc: dict, peak=None, neighborhood=8, val_words=4):
        f = lambda x: np.asarray(x, float)  # noqa: E731
        return cls([Span(**s) for s in doc["spans"]],
                   {int(c): (f(s), f(e)) for c, (s, e)
                    in doc["modules"].items()},
                   {int(c): (list(n), f(s), f(e)) for c, (n, s, e)
                    in doc["ops"].items()},
                   {int(c): (f(s), f(e)) for c, (s, e)
                    in doc["a2a"].items()},
                   peak, neighborhood, val_words)


def _stats(event) -> dict:
    return {k: v for k, v in event.stats}


def _short(op: str) -> str:
    """``%while.186 = (s32[...]) while(...)`` -> ``while.186``."""
    return op.split(" = ", 1)[0].lstrip("%")


def _device_line(line, keep_all: bool, pick=None):
    """Events of one device line as arrays; with ``keep_all`` False only
    the top-level ones (not inside an earlier event's interval) and those
    ``pick`` selects by name."""
    names, starts, ends, pick_s, pick_e = [], [], [], [], []
    reach = -np.inf
    for ev in line.events:
        s0 = ev.start_ns
        s1 = s0 + ev.duration_ns
        if pick is not None and pick(ev.name):
            pick_s.append(s0)
            pick_e.append(s1)
        if keep_all or s0 >= reach:
            names.append(ev.name)
            starts.append(s0)
            ends.append(s1)
        reach = max(reach, s1)
    f = lambda x: np.asarray(x, float)  # noqa: E731
    return names, f(starts), f(ends), f(pick_s), f(pick_e)


def load(xplane, peak: Optional[dict] = None, neighborhood: int = 8,
         val_words: int = 4) -> Traced:
    """Read an ``.xplane.pb``: the ``bench.*`` host spans, and of every
    TPU chip its ``XLA Modules`` (programs run) and ``XLA Ops``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(xplane))
    spans, modules, ops, a2a = [], {}, {}, {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            chip = int(plane.name[len(DEVICE_PLANE):].split()[0])
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    _, s, e, _, _ = _device_line(line, True)
                    modules[chip] = (s, e)
                elif line.name == OPS_LINE:
                    n, s, e, ps, pe = _device_line(
                        line, False, lambda name: "all-to-all" in name)
                    ops[chip] = ([_short(x) for x in n], s, e)
                    a2a[chip] = (ps, pe)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(Span(ev.name, ev.start_ns,
                                          ev.start_ns + ev.duration_ns,
                                          _stats(ev)))
    spans.sort(key=lambda s: s.start)
    return Traced(spans, modules, ops, a2a, peak, neighborhood, val_words)
