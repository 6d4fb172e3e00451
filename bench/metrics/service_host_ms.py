"""Host time per service call: each ``bench.get``/``bench.set`` span less
the device-busy time inside it (mean over chips), averaged over the
window's calls.  It holds host dispatch, key checks and host syncs such
as ``set_many``'s read of ``status``."""


def reduce(traced):
    calls = traced.calls("bench.get") + traced.calls("bench.set")
    if not calls or not traced.modules:
        return None
    host = [(s.end - s.start) - traced.busy_in(s.start, s.end)
            for s in calls]
    return sum(host) / len(host) * 1e-6
