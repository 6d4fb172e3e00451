"""Chain-VM wrapper: batch of client chains, implementation-selected."""
from __future__ import annotations

import functools

import jax

from .kernel import run_managed_pallas
from .ref import managed_chain_loop

IMPLS = ("pallas", "interpret", "ref")


@functools.partial(jax.jit, static_argnames=("wq_base", "n_wrs", "managed",
                                             "max_steps", "impl"))
def run_managed(mems, msgs, inits, *, wq_base: int, n_wrs: int,
                managed: bool = True, max_steps: int = 64, impl: str):
    """Managed-WQ batch executor (ENABLE gate + completions + RECV).

    One client context per row; see :func:`kernel.run_managed_pallas` for
    the input layout.  ``impl``: "pallas" (compiled TPU kernel),
    "interpret" (the same kernel in pallas interpret mode), or "ref" (the
    vmapped pure-jnp oracle).
    """
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")
    if impl == "ref":
        return jax.vmap(
            lambda m, g, i: managed_chain_loop(
                m, g, i, wq_base=wq_base, n_wrs=n_wrs, managed=managed,
                max_steps=max_steps))(mems, msgs, inits)
    return run_managed_pallas(mems, msgs, inits, wq_base=wq_base,
                              n_wrs=n_wrs, managed=managed,
                              max_steps=max_steps,
                              interpret=(impl == "interpret"))
