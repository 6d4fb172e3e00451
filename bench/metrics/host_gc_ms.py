"""Time the interpreter spent collecting its heap (``host.gc`` spans)
inside the window, per window call of the service.  Read only where the
program traces its calls: without ``kv.*_many`` spans there is nothing to
read."""
from bench.metrics import _program


def reduce(traced):
    program = _program.of(traced)
    a, b = traced.window
    calls = traced.calls("bench.get") + traced.calls("bench.set")
    if not calls or not program.calls(a, b):
        return None
    gc = sum(min(s.end, b) - max(s.start, a) for s in program.spans
             if s.name == "host.gc" and s.end > a and s.start < b)
    return gc / len(calls) * 1e-6
