"""Device time of the ops in the ``kv.route`` scope (the transport's
dispatch and combine: rank sort, scatter, all-to-all and gather) inside
each ``bench.get`` call, mean over chips, averaged over the window's GET
calls whose ``kv.get.vm`` ops the trace holds."""
from bench.metrics import _program


def reduce(traced):
    program = _program.of(traced)
    if not program.scoped:
        return None
    t = [program.scoped_in("kv.route", s.start, s.end)
         for s in traced.calls("bench.get")
         if program.scoped_in("kv.get.vm", s.start, s.end)]
    if not t:
        return None
    return sum(t) / len(t) * 1e-6
