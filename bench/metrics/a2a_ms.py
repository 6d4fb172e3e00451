"""Device time of all-to-all ops per GET call (mean over chips): the
transport's dispatch and combine.  Nothing to read on one chip, where the
program has no exchange."""


def reduce(traced):
    calls = traced.calls("bench.get")
    if not calls or not traced.a2a:
        return None
    t = [traced.a2a_in(s.start, s.end) for s in calls]
    if not any(t):
        return None
    return sum(t) / len(t) * 1e-6
