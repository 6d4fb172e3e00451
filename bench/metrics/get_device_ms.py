"""Device-busy time per GET call: the union of op intervals inside each
``bench.get`` span (mean over chips), averaged over the window's GET
calls.  It holds whatever programs the call launches."""


def reduce(traced):
    calls = traced.calls("bench.get")
    if not calls or not traced.modules:
        return None
    busy = [traced.busy_in(s.start, s.end) for s in calls]
    return sum(busy) / len(busy) * 1e-6
