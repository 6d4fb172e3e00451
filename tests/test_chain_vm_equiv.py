"""Property test: the Pallas chain_vm kernel and the core multi-WQ
machine agree on random single-WQ straight-line programs — the kernel
really is a NIC PU running the same ISA."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import assembler, machine
from repro.core.engine import ChainEngine


@settings(max_examples=20, deadline=None)
@given(st.data())
def test_chain_vm_matches_core_machine_on_random_programs(data):
    n_data = 8
    n_wrs = data.draw(st.integers(1, 8))
    p = assembler.Program(512)
    cells = [p.word(data.draw(st.integers(-50, 50))) for _ in range(n_data)]
    wq = p.add_wq(n_wrs + 1)

    for _ in range(n_wrs):
        op = data.draw(st.sampled_from(
            ["write", "write_imm", "read", "cas", "add", "max", "min"]))
        a = data.draw(st.sampled_from(cells))
        b = data.draw(st.sampled_from(cells))
        v = data.draw(st.integers(-50, 50))
        if op == "write":
            wq.write(src=a, dst=b, ln=1)
        elif op == "write_imm":
            wq.write_imm(dst=b, value=v)
        elif op == "read":
            wq.read(src=a, dst=b, ln=1)
        elif op == "cas":
            wq.cas(dst=b, old=v, new=data.draw(st.integers(-50, 50)))
        elif op == "add":
            wq.add(dst=b, addend=v)
        elif op == "max":
            wq.max_(dst=b, operand=v)
        else:
            wq.min_(dst=b, operand=v)
    wq.halt()

    spec, st0 = p.finalize()
    out_core = machine.run(spec, st0, max_steps=n_wrs + 2)
    # the image keeps its MAX_COPY guard words: copy verbs near the end of
    # memory clamp differently without them
    batch = jax.tree_util.tree_map(lambda a: jnp.stack([a]), st0)
    out_kern = ChainEngine.for_spec(spec, "pallas-interpret").run_batch(
        batch, n_wrs + 2)
    np.testing.assert_array_equal(np.asarray(out_core.mem),
                                  np.asarray(out_kern.mem[0]))
    assert int(out_kern.steps[0]) == int(out_core.steps)
