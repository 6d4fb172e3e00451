"""Share of a GET call's live slots that its busiest owner got: the
largest owner's ``routed`` over the call's ``routed`` summed over owners
(their ``kv.counters`` spans), mean over the window's GET calls that
routed a request.  1 / owners is an even split: 25% on four chips.
None where the counters lack ``routed``."""
from bench.metrics import _program


def reduce(traced):
    program = _program.of(traced)
    counters = program.counters("get")
    shares = []
    for call in traced.calls("bench.get"):
        c = counters.get(program.call_seq(call, "kv.get_many"))
        if c and c.get("routed", 0) > 0:
            shares.append(c["routed_max"] / c["routed"])
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
