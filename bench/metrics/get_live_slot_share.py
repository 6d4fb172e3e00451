"""Share of the GET receive-window slots that held a request: the live
slots every owner's window got (``routed``) over the slots of every
owner's window (``slots`` x ``owners``, each window S x capacity), pooled
over the window's GET calls (their ``kv.counters`` spans).  Every slot
runs a chain-VM context, so the rest is padding the VM loop still runs.
None where the counters lack ``routed``."""
from bench.metrics import _program


def reduce(traced):
    program = _program.of(traced)
    counters = program.counters("get")
    live = slots = 0
    for call in traced.calls("bench.get"):
        c = counters.get(program.call_seq(call, "kv.get_many"))
        if c and "routed" in c:
            live += c["routed"]
            slots += c["slots"] * c["owners"]
    if slots <= 0:
        return None
    return 100.0 * live / slots
