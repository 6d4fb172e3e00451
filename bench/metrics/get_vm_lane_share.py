"""Share of the vmapped lane-steps of the GET body's chain-VM loop that
executed a WR: the ``vm_steps`` of every context over contexts x trips of
its owner, pooled over the window's GET calls (their ``kv.counters``
spans).  A context that quiesced early idles in its lane until the
owner's longest context finishes."""
from bench.metrics import _program


def reduce(traced):
    program = _program.of(traced)
    counters = program.counters("get")
    steps = lanes = 0
    for call in traced.calls("bench.get"):
        c = counters.get(program.call_seq(call, "kv.get_many"))
        if c:
            steps += c["steps"]
            lanes += c["lanes"]
    if lanes <= 0:
        return None
    return 100.0 * steps / lanes
