"""Device time of one trip of the GET body's vmapped chain-VM loop: the
time of the ``kv.get.vm`` scope inside a ``bench.get`` call (mean over
chips) over that call's trips, the largest ``vm_steps`` of any context of
any owner (its ``kv.counters`` span).  Averaged over the window's GET
calls whose counters were emitted and whose ``kv.get.vm`` ops the trace
holds."""
from bench.metrics import _program


def reduce(traced):
    program = _program.of(traced)
    if not program.scoped:
        return None
    counters = program.counters("get")
    per_trip = []
    for call in traced.calls("bench.get"):
        c = counters.get(program.call_seq(call, "kv.get_many"))
        vm = program.scoped_in("kv.get.vm", call.start, call.end)
        if c and c["trips"] > 0 and vm > 0:
            per_trip.append(vm / c["trips"])
    if not per_trip:
        return None
    return sum(per_trip) / len(per_trip) * 1e-6
