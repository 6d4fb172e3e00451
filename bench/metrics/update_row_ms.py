"""Device time of the SET path's serial scan per row: the time of the
``kv.set.scan`` scope inside a ``bench.set`` call (mean over chips) over
the largest number of live rows any owner's scan ran in that call (its
``kv.counters`` span).  Averaged over the window's SET calls whose
counters were emitted, that scanned a row, and whose ``kv.set.scan`` ops
the trace holds."""
from bench.metrics import _program


def reduce(traced):
    program = _program.of(traced)
    if not program.scoped:
        return None
    counters = program.counters("set")
    per_row = []
    for call in traced.calls("bench.set"):
        c = counters.get(program.call_seq(call, "kv.set_many"))
        scan = program.scoped_in("kv.set.scan", call.start, call.end)
        if c and c["scanned_max"] > 0 and scan > 0:
            per_row.append(scan / c["scanned_max"])
    if not per_row:
        return None
    return sum(per_row) / len(per_row) * 1e-6
