"""Chunked WKV6 Pallas kernel (MXU-friendly matmul formulation).

Within a chunk of length C, with per-step decays w_t and cumulative
products W_t = prod_{s<=t} w_s (W_0 = 1):

    o_t    = (r_t . W_{t-1}) @ S_0
             + [ (R~ K~^T) . strict_lower ] V  + (r_t . u . k_t) v_t
    S_next = diag(W_C) S_0 + (W_C / W_t . k_t)^T V

with R~_t = r_t . W_{t-1} and K~_t = k_t / W_t — three (C,N)x(N,M)-class
matmuls per chunk instead of C rank-1 updates, so the MXU does the work
and the sequential dependency is only chunk-to-chunk (carried in VMEM
scratch).  Chunk length bounds the dynamic range of 1/W_t; C=32 with
w >= 0.5 keeps everything within f32.

Grid: (B, H, T/C) with the chunk dimension sequential.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _chunk_math(r, k, v, w, u, S0):
    """Shared chunk computation (also used by the blocked-JAX path).

    r,k,w: (C,N) f32; v: (C,M) f32; u: (N,) f32; S0: (N,M) f32.
    Returns o: (C,M) f32, S_next: (N,M) f32.
    """
    c = r.shape[0]
    logw = jnp.log(jnp.maximum(w, 1e-12))
    W = jnp.exp(jnp.cumsum(logw, axis=0))          # W_t, t = 1..C
    W_prev = jnp.concatenate([jnp.ones_like(W[:1]), W[:-1]], axis=0)
    r_t = r * W_prev                               # (C,N)
    k_t = k / jnp.maximum(W, 1e-30)                # (C,N)

    inter = jax.lax.dot(r_t, S0, preferred_element_type=jnp.float32)
    scores = jax.lax.dot_general(r_t, k_t, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (C,C)
    strict = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0) > \
        jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    intra = jax.lax.dot(jnp.where(strict, scores, 0.0), v,
                        preferred_element_type=jnp.float32)
    diag = jnp.sum(r * u[None, :] * k, axis=1, keepdims=True) * v
    o = inter + intra + diag

    WC = W[-1]                                     # (N,)
    k_scaled = k_t * WC[None, :]
    S_next = WC[:, None] * S0 + jax.lax.dot_general(
        k_scaled, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return o, S_next


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, sT_ref, s_scr,
                 *, n: int, m: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    r = r_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)

    o, s_next = _chunk_math(r, k, v, w, u, s_scr[...])
    o_ref[0, 0] = o.astype(o_ref.dtype)
    s_scr[...] = s_next

    @pl.when(ci == nc - 1)
    def _fin():
        sT_ref[0, 0] = s_scr[...]


def wkv6_pallas(r, k, v, w, u, *, chunk: int = 32, interpret: bool = False):
    b, h, t, n = r.shape
    m = v.shape[-1]
    c = min(chunk, t)
    assert t % c == 0, (t, c)
    grid = (b, h, t // c)

    kernel = functools.partial(_wkv6_kernel, n=n, m=m)
    o, sT = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, c, n), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, c, n), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, c, m), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, c, n), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, n), lambda bi, hi, ci: (hi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, c, m), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, n, m), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, m), r.dtype),
            jax.ShapeDtypeStruct((b, h, n, m), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, m), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, w, u)
    return o, sT
