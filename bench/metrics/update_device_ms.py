"""Device-busy time per SET call: the union of op intervals inside each
``bench.set`` span (mean over chips), averaged over the window's SET
calls."""


def reduce(traced):
    calls = traced.calls("bench.set")
    if not calls or not traced.modules:
        return None
    busy = [traced.busy_in(s.start, s.end) for s in calls]
    return sum(busy) / len(busy) * 1e-6
