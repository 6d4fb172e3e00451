"""Spans, scopes and counters of the serving path, on the profiler's clock.

A thin layer over the JAX profiler, not a second tracing system: host
spans are :class:`jax.profiler.TraceAnnotation` events and device scopes
are :func:`jax.named_scope`, so both land in the same profiler session as
the device's own events and share their clock.

Tracing is on exactly while a profiler trace is active
(:func:`enabled`); there is no flag.  With no trace active a span is the
profiler's no-op annotation, a scope costs nothing at run time (it only
names ops in the compiled program's metadata), and the service reads
nothing back from the device for its counters.

Names (PERF.md section 3 lists each with the metric that reads it):

* host spans ``kv.get_many``, ``kv.set_many``, ``kv.sync``,
  ``kv.counters`` (:class:`repro.rdma.failure.ShardedKVService`) and
  ``host.gc`` (:func:`install_gc_spans`);
* device scopes ``kv.route`` (:mod:`repro.rdma.transport` dispatch and
  combine), ``kv.get.vm`` (the GET chain VM) and ``kv.set.scan`` (the SET
  path's serial scan).
"""
from __future__ import annotations

import gc

import jax
from jax._src.lib import _profiler

span = jax.profiler.TraceAnnotation
scope = jax.named_scope


def enabled() -> bool:
    """True while a profiler trace is being recorded."""
    return _profiler.TraceMe.is_enabled()


def mark(name: str, **args) -> None:
    """A zero-length span carrying ``args``, recorded only while tracing."""
    if enabled():
        with span(name, **args):
            pass


def _gc_spans(phase: str, info: dict) -> None:
    """``gc.callbacks`` hook: a ``host.gc`` span from a collection's start
    to its stop.  Collections do not nest, so one open span suffices."""
    if phase == "start":
        if enabled():
            _gc_spans.open = span("host.gc", generation=info["generation"])
            _gc_spans.open.__enter__()
    elif _gc_spans.open is not None:
        _gc_spans.open.set_metadata(collected=info["collected"])
        _gc_spans.open.__exit__(None, None, None)
        _gc_spans.open = None


_gc_spans.open = None


def install_gc_spans() -> None:
    """Record each collection of the interpreter's heap as a ``host.gc``
    span while tracing; installed once per process, idle otherwise."""
    if _gc_spans not in gc.callbacks:
        gc.callbacks.append(_gc_spans)
