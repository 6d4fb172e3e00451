"""Host time of the service per call, read from the program's own spans:
each ``kv.get_many``/``kv.set_many`` span in the window less the
``kv.sync`` spans inside it (host reads that wait on the device), averaged
over the window's calls.  It holds argument conversion, key checks, the
compile-cache lookup and the launch of the jitted body."""
from bench.metrics import _program


def reduce(traced):
    program = _program.of(traced)
    calls = program.calls(*traced.window)
    if not calls:
        return None
    host = [(c.end - c.start)
            - sum(s.end - s.start
                  for s in program.spans_in("kv.sync", c.start, c.end))
            for c in calls]
    return sum(host) / len(host) * 1e-6
