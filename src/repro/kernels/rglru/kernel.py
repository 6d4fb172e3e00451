"""RG-LRU Pallas kernel: chunked elementwise linear recurrence.

Grid (B, T/C) with the chunk dimension sequential; the carried state lives
in VMEM scratch.  Inside a chunk the recurrence is evaluated with the
log-free two-pass form: P_t = cumprod(a) (shifted), h_t = P_t * (h_0 +
cumsum(u_t / P_t)) — two vector passes that the VPU pipelines well; chunk
length bounds 1/P's dynamic range exactly like the WKV6 kernel.  (Griffin's
own TPU kernel is likewise a VPU linear scan; this recurrence has no MXU
work by construction.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _chunk_math(a, u, h0):
    """a, u: (C, D) f32; h0: (1, D) f32 -> h (C, D), h_next (1, D)."""
    loga = jnp.log(jnp.maximum(a, 1e-12))
    P = jnp.exp(jnp.cumsum(loga, axis=0))          # (C, D) cumulative decay
    scaled = u / jnp.maximum(P, 1e-30)
    h = P * (h0 + jnp.cumsum(scaled, axis=0))
    return h, h[-1:]


def _rglru_kernel(a_ref, u_ref, h_ref, hT_ref, h_scr):
    ci = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a = a_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)
    h, h_next = _chunk_math(a, u, h_scr[...])
    h_ref[0] = h.astype(h_ref.dtype)
    h_scr[...] = h_next

    @pl.when(ci == nc - 1)
    def _fin():
        hT_ref[0] = h_scr[...][0].astype(hT_ref.dtype)


def rglru_pallas(a, u, *, chunk: int = 32, interpret: bool = False):
    b, t, d = a.shape
    c = min(chunk, t)
    assert t % c == 0
    grid = (b, t // c)
    h, hT = pl.pallas_call(
        _rglru_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, c, d), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, c, d), lambda bi, ci: (bi, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, c, d), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, d), lambda bi, ci: (bi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, d), a.dtype),
            jax.ShapeDtypeStruct((b, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(a, u)
    return h, hT
