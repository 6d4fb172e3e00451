"""Device-idle time inside each ``bench.get`` call after its
``kv.get_many`` span closed: the program has been launched, and the host
waits for the answers' copy to the host.  Mean over the window's GET
calls that hold a ``kv.get_many`` span and whose ``kv.get.vm`` ops the
trace holds (a call whose program the trace missed would read the
trace's gap as idle time)."""
from bench.metrics import _program


def reduce(traced):
    program = _program.of(traced)
    if not traced.modules or not program.scoped:
        return None
    idle = []
    for call in traced.calls("bench.get"):
        inner = program.spans_in("kv.get_many", call.start, call.end)
        if inner and program.scoped_in("kv.get.vm", call.start,
                                       call.end):
            a = inner[-1].end
            idle.append((call.end - a) - traced.busy_in(a, call.end))
    if not idle:
        return None
    return sum(idle) / len(idle) * 1e-6
