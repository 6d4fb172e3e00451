"""The program's own instrumentation (``repro.obs``) in a traced window,
for the readers of its spans, scopes and counters.

:func:`bench.trace.load` keeps the benchmark's ``bench.*`` host spans and
each chip's device ops under XLA's names.  This reads the same
``.xplane.pb`` for what the program adds beside them: its host spans
(``kv.*``, ``host.*``) in :attr:`Program.spans`, and for each chip the
top-level ops that ran inside one of its ``kv.*`` device scopes, with that
scope, in :attr:`Program.scoped`.  A reader gets it with :func:`of`.  A
program that does not trace itself leaves both empty, and every reader of
them then gives None.
"""
from __future__ import annotations

import dataclasses
import pathlib
from typing import Optional

import numpy as np

from bench import trace
from bench.run import TRACE_DIR

PROGRAM_PREFIXES = ("kv.", "host.")
SCOPE_PREFIX = "kv."
ATTR = "_program"      # where :func:`attach` keeps a window's Program


@dataclasses.dataclass
class Program:
    spans: list    # [trace.Span] of the program, in start order
    scoped: dict   # chip -> (scopes, starts, ends) of top-level ops

    def spans_in(self, name: str, a: float, b: float) -> list:
        """The program's spans called ``name`` that lie in ``[a, b]``."""
        return [s for s in self.spans
                if s.name == name and s.start >= a and s.end <= b]

    def calls(self, a: float, b: float) -> list:
        """``kv.get_many``/``kv.set_many`` spans in ``[a, b]``."""
        return (self.spans_in("kv.get_many", a, b)
                + self.spans_in("kv.set_many", a, b))

    def counters(self, kind: str) -> dict:
        """seq -> arguments of the ``kv.counters`` spans of ``kind``."""
        return {s.args["seq"]: s.args for s in self.spans
                if s.name == "kv.counters" and s.args.get("kind") == kind}

    def call_seq(self, call: trace.Span, name: str) -> Optional[int]:
        """``seq`` of the program span ``name`` inside a ``bench.*`` call."""
        inner = self.spans_in(name, call.start, call.end)
        return inner[0].args.get("seq") if inner else None

    def scoped_in(self, scope: str, a: float, b: float) -> float:
        """ns in ``[a, b]`` during which an op of device scope ``scope``
        ran, mean over chips."""
        if not self.scoped:
            return 0.0
        covered = []
        for scopes, s, e in self.scoped.values():
            pick = np.asarray([x == scope for x in scopes], bool)
            covered.append(trace._covered(trace._merge(s[pick], e[pick]),
                                          a, b) if pick.any() else 0.0)
        return float(np.mean(covered))

    # -- a compact copy, for fixtures --------------------------------------
    def to_json(self) -> dict:
        arr = lambda x: np.asarray(x).tolist()  # noqa: E731
        return {"program": [dataclasses.asdict(s) for s in self.spans],
                "scoped": {c: [list(n), arr(s), arr(e)] for c, (n, s, e)
                           in self.scoped.items()}}

    @classmethod
    def from_json(cls, doc: dict) -> "Program":
        f = lambda x: np.asarray(x, float)  # noqa: E731
        return cls([trace.Span(**s) for s in doc.get("program", [])],
                   {int(c): (list(n), f(s), f(e)) for c, (n, s, e)
                    in doc.get("scoped", {}).items()})


def attach(traced: trace.Traced, program: Program) -> trace.Traced:
    """Give ``traced`` the program's instrumentation of its window."""
    setattr(traced, ATTR, program)
    return traced


def of(traced: trace.Traced) -> Program:
    """The program's instrumentation in ``traced``'s window: what
    :func:`attach` gave it, else what the newest trace under
    ``bench/run.py``'s ``TRACE_DIR`` holds whose ``bench.*`` spans make
    the same window (the run's own, still on disk while its metrics are
    reduced), else nothing."""
    found = getattr(traced, ATTR, None)
    if found is None:
        files = sorted(TRACE_DIR.glob("*/plugins/profile/*/*.xplane.pb"),
                       key=lambda p: p.stat().st_mtime, reverse=True)
        found = next(filter(None, (load(x, traced) for x in files)),
                     Program([], {}))
        attach(traced, found)
    return found


def load(xplane, traced: trace.Traced) -> Optional[Program]:
    """The program's spans and scoped ops in ``xplane``, None unless its
    ``bench.*`` spans make ``traced``'s window.  ``traced.ops`` gives the
    top-level ops; the chips' ``XLA Modules`` give the program each ran in,
    whose HLO gives the op's scope."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(xplane))
    bench, spans, programs = [], [], {}
    for plane in data.planes:
        if plane.name.startswith(trace.DEVICE_PLANE):
            chip = int(plane.name[len(trace.DEVICE_PLANE):].split()[0])
            for line in plane.lines:
                if line.name == trace.MODULES_LINE:
                    n, s, e, _, _ = trace._device_line(line, True)
                    programs[chip] = (n, s, e)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith((trace.SPAN_PREFIX,)
                                          + PROGRAM_PREFIXES):
                        span = trace.Span(ev.name, ev.start_ns,
                                          ev.start_ns + ev.duration_ns,
                                          trace._stats(ev))
                        (bench if ev.name.startswith(trace.SPAN_PREFIX)
                         else spans).append(span)
    if not bench or trace.Traced(bench, {}, {}, {}).window != traced.window:
        return None
    spans.sort(key=lambda s: s.start)
    hlo = program_scopes(xplane) if traced.ops else {}
    scoped = {}
    for chip, ops in traced.ops.items():
        found = _scoped_ops(ops, programs.get(chip, ([], [], [])), hlo)
        if found[0]:
            scoped[chip] = found
    return Program(spans, scoped)


# -- the scopes of the programs' ops, from their HLO --------------------------
# ``ProfileData`` gives neither an op's own metadata nor the programs' HLO,
# where the op's scope path lives (``OpMetadata.op_name``:
# ``jit(body)/kv.route/sort``), so these read the HLO from the file's
# protobuf wire format.  The ``/host:metadata`` plane holds an event
# metadata per program, named as its ``XLA Modules`` events, whose stat
# ``Hlo Proto`` is the program's HloProto.  Field numbers, from
# tsl/profiler/protobuf/xplane.proto: XSpace.planes 1; XPlane.name 2,
# .event_metadata 4, .stat_metadata 5 (map entries: key 1, value 2);
# XEventMetadata.name 2, .stats 5; XStatMetadata.name 2;
# XStat.metadata_id 1, .bytes_value 6.  From xla/service/hlo.proto:
# HloProto.hlo_module 1; HloModuleProto.entry_computation_name 2,
# .computations 3; HloComputationProto.name 1, .instructions 2;
# HloInstructionProto.name 1, .metadata 7; OpMetadata.op_name 2.
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"


def _varint(buf, i: int):
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf, start: int, end: int):
    """``(field, value)`` of one message: an int for a varint, a
    ``(start, end)`` span for a length-delimited value, None for a fixed
    width one."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _innermost(path: str) -> Optional[str]:
    inner = [p for p in path.split("/") if p.startswith(SCOPE_PREFIX)]
    return inner[-1] if inner else None


def _entry_scopes(buf, hlo_proto) -> dict:
    """instruction -> innermost ``kv.*`` scope, over the entry computation
    of one HloProto (its top-level ops)."""
    module = dict(_fields(buf, *hlo_proto)).get(1)
    fields = list(_fields(buf, *module)) if module else []
    entry = next((_text(buf, v) for f, v in fields if f == 2), None)
    out = {}
    for f, comp in fields:
        body = list(_fields(buf, *comp)) if f == 3 else []
        name = next((_text(buf, v) for g, v in body if g == 1), None)
        if entry is not None and name != entry:
            continue
        for g, ins in body:
            d = dict(_fields(buf, *ins)) if g == 2 else {}
            meta = dict(_fields(buf, *d[7])) if 7 in d else {}
            scope = _innermost(_text(buf, meta[2])) if 2 in meta else None
            if scope is not None:
                out[_text(buf, d[1])] = scope
    return out


def program_scopes(xplane) -> dict:
    """``{program: {top-level instruction: kv.* scope}}`` for every
    program whose HLO the trace holds."""
    buf = memoryview(pathlib.Path(xplane).read_bytes())
    out = {}
    for f, plane in _fields(buf, 0, len(buf)):
        fields = list(_fields(buf, *plane)) if f == 1 else []
        if not any(g == 2 and _text(buf, v) == METADATA_PLANE
                   for g, v in fields):
            continue
        entries = [(g, dict(_fields(buf, *v))) for g, v in fields
                   if g in (4, 5)]
        hlo_ids = {e[1] for g, e in entries if g == 5 and 2 in e
                   and _text(buf, dict(_fields(buf, *e[2])).get(2, (0, 0)))
                   == HLO_STAT}
        for g, e in entries:
            meta = list(_fields(buf, *e[2])) if g == 4 and 2 in e else []
            name = next((_text(buf, v) for h, v in meta if h == 2), "")
            for h, v in meta:
                st = dict(_fields(buf, *v)) if h == 5 else {}
                if st.get(1) in hlo_ids and 6 in st:
                    out[name] = _entry_scopes(buf, st[6])
    return out


def _scoped_ops(ops, programs, scopes: dict):
    """(scopes, starts, ends) of the top-level ops ``ops`` that lie in a
    ``kv.*`` scope of the program (``programs``: the chip's ``XLA
    Modules`` names, starts, ends) running at their start."""
    names, s, e = ops
    p_names, p_starts, p_ends = programs
    at = np.searchsorted(p_starts, s, side="right") - 1
    out = ([], [], [])
    for n, s0, e0, i in zip(names, s, e, at):
        scope = (scopes.get(p_names[i], {}).get(n)
                 if i >= 0 and s0 < p_ends[i] else None)
        if scope is not None:
            for col, x in zip(out, (scope, s0, e0)):
                col.append(x)
    return out[0], np.asarray(out[1], float), np.asarray(out[2], float)
