"""The closed loop: C clients, each with one request outstanding, served
through a key-value service's ``get_many``/``set_many`` at fixed call
widths.

One dispatcher thread serves them in steps: a GET call takes the oldest
pending reads, then a SET call the oldest pending updates.  A client
whose answer comes back issues its next operation at once, so an update
issued when a GET call returns can join the SET call that follows.  A
call ends when its answers are on the host.  A row the service did not
serve (``ok`` False: dropped at the transport's capacity or deferred) is
re-issued first in the next call of its kind, and its latency still runs
from the client's first issue.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Optional

import numpy as np

from . import ycsb

#: how long after the window's close a request the service has not
#: answered is re-issued before it counts as failed
DRAIN_S = 60.0


@dataclasses.dataclass
class Request:
    client: int
    kind: int                       # ycsb.READ or ycsb.UPDATE
    key: int
    value: Optional[np.ndarray]     # (V,) int32 for an update
    t_issue: float
    attempts: int = 0


@dataclasses.dataclass
class Call:
    """One service call and its answers, as the host received them."""
    kind: int
    keys: np.ndarray                # (S, w) int32, 0 = empty slot
    vals: Optional[np.ndarray]      # (S, w, V) int32 for a SET call
    t_start: float
    t_end: float
    in_window: bool
    ok: np.ndarray = None           # (S, w) bool
    found: np.ndarray = None        # GET: (S, w) bool
    values: np.ndarray = None       # GET: (S, w, V) int32
    applied: np.ndarray = None      # SET: (S, w) bool

    @property
    def live(self) -> int:
        return int((self.keys != 0).sum())


@dataclasses.dataclass
class Shape:
    """What the loop needs of a deployment: shards and call widths."""
    n_shards: int
    get_width: int
    set_width: int
    val_words: int


def get_call(service, keys: np.ndarray):
    """One GET call; returns ``(found, values, ok)`` on the host."""
    import jax

    res = service.get_many(keys)
    return jax.device_get((res.found, res.values, res.ok))


def set_call(service, keys: np.ndarray, vals: np.ndarray):
    """One SET call; returns ``(applied, ok)`` on the host."""
    import jax

    res = service.set_many(keys, vals)
    return jax.device_get((res.applied, res.ok))


class ClosedLoop:
    def __init__(self, service, shape: Shape, stream: ycsb.OpStream,
                 record_keys: np.ndarray, clients: int, *,
                 clock=time.perf_counter, annotate: bool = False):
        for width in (shape.get_width, shape.set_width):
            if width % shape.n_shards:
                raise ValueError(f"call width {width} does not split over "
                                 f"{shape.n_shards} shards")
        self.service = service
        self.shape = shape
        self.stream = stream
        self.record_keys = record_keys
        self.clients = clients
        self.clock = clock
        self.annotate = annotate
        self.pending = {k: [collections.deque()
                            for _ in range(shape.n_shards)]
                        for k in (ycsb.READ, ycsb.UPDATE)}
        self.calls: list[Call] = []
        self.latency = {ycsb.READ: [], ycsb.UPDATE: []}
        self.attempted = 0
        self.answered_in_window = 0
        self.reissued = 0
        self.failed = 0
        self.window = (None, None)

    # -- clients -----------------------------------------------------------
    def _issue(self, client: int, now: float):
        kind, rec, value = self.stream.next()
        req = Request(client, kind, int(self.record_keys[rec]),
                      value if kind == ycsb.UPDATE else None, now)
        self.pending[kind][client % self.shape.n_shards].append(req)

    # -- one call ----------------------------------------------------------
    def _call(self, kind: int, in_window: bool, issue: bool):
        s_n = self.shape.n_shards
        width = (self.shape.get_width if kind == ycsb.READ
                 else self.shape.set_width) // s_n
        keys = np.zeros((s_n, width), np.int32)
        vals = (np.zeros((s_n, width, self.shape.val_words), np.int32)
                if kind == ycsb.UPDATE else None)
        rows = []
        for s, queue in enumerate(self.pending[kind]):
            for j in range(min(width, len(queue))):
                req = queue.popleft()
                if req.attempts == 0 and in_window:
                    self.attempted += 1
                req.attempts += 1
                keys[s, j] = req.key
                if vals is not None:
                    vals[s, j] = req.value
                rows.append((s, j, req))
        name = "bench.get" if kind == ycsb.READ else "bench.set"
        span = contextlib.nullcontext()
        if self.annotate:
            import jax
            span = jax.profiler.TraceAnnotation(name, live=len(rows))
        t0 = self.clock()
        with span:
            if kind == ycsb.READ:
                found, values, ok = get_call(self.service, keys)
            else:
                applied, ok = set_call(self.service, keys, vals)
        t1 = self.clock()
        call = Call(kind, keys, vals, t0, t1, in_window, ok=np.asarray(ok))
        if kind == ycsb.READ:
            call.found, call.values = np.asarray(found), np.asarray(values)
            hits = call.found & call.ok
        else:
            call.applied = np.asarray(applied)
            hits = call.applied & call.ok
        if self.annotate:
            import jax
            with jax.profiler.TraceAnnotation(
                    "bench.answers", kind=name[len("bench."):],
                    live=len(rows), hits=int(hits.sum())):
                pass
        self.calls.append(call)
        retry = collections.defaultdict(list)
        for s, j, req in rows:
            if not call.ok[s, j]:
                self.reissued += 1
                retry[s].append(req)
                continue
            self.latency[kind].append(t1 - req.t_issue)
            if in_window:
                self.answered_in_window += 1
            if issue:
                self._issue(req.client, t1)
        for s, reqs in retry.items():
            self.pending[kind][s].extendleft(reversed(reqs))
        return call

    def _has(self, kind: int) -> bool:
        return any(self.pending[kind])

    # -- the run -----------------------------------------------------------
    def serve_window(self, seconds: float) -> "ClosedLoop":
        """Serve the window: steps begin until ``seconds`` have passed; the
        window ends when the last call begun in it has its answers."""
        t0 = self.clock()
        for c in range(self.clients):
            self._issue(c, t0)
        deadline = t0 + seconds
        t_end = t0
        open_ = True
        while open_:
            for kind in (ycsb.READ, ycsb.UPDATE):
                if not self._has(kind):
                    continue
                if self.clock() >= deadline:
                    open_ = False
                    break
                t_end = self._call(kind, True, True).t_end
        self.window = (t0, t_end)
        return self

    def drain(self, limit_s: float = DRAIN_S) -> "ClosedLoop":
        """Re-issue what the service left unanswered in the window, for up
        to ``limit_s``; what is still unanswered then has failed.  Requests
        that clients issued but that were never dispatched were not
        attempted, and are dropped."""
        for kind in (ycsb.READ, ycsb.UPDATE):
            for queue in self.pending[kind]:
                kept = [r for r in queue if r.attempts]
                queue.clear()
                queue.extend(kept)
        until = self.clock() + limit_s
        while self.clock() < until:
            kinds = [k for k in (ycsb.READ, ycsb.UPDATE) if self._has(k)]
            if not kinds:
                break
            for kind in kinds:
                self._call(kind, False, False)
        self.failed = sum(len(q) for k in self.pending.values() for q in k)
        return self

    # -- what the window measured -------------------------------------------
    @property
    def seconds(self) -> float:
        return self.window[1] - self.window[0]

    def ops_per_s(self) -> float:
        return self.answered_in_window / self.seconds

    def mean_gap_ms(self) -> Optional[float]:
        """Host time of the load generator between one window call's
        answers and the next call: how late it issued."""
        calls = [c for c in self.calls if c.in_window]
        gaps = [b.t_start - a.t_end for a, b in zip(calls, calls[1:])]
        return sum(gaps) / len(gaps) * 1e3 if gaps else None

    def percentile_ms(self, kind: int, q: float) -> Optional[float]:
        lat = self.latency[kind]
        if not lat:
            return None
        return float(np.percentile(np.asarray(lat), q)) * 1e3
