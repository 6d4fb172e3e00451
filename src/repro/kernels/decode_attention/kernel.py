"""Flash-decode Pallas kernel: one query token vs. a long KV cache.

Grid (B, H, Sk/BK) with sequential KV steps; outputs the *partial*
(acc, m, l) triple so cross-shard combines stay cheap.  The q tile is a
single (1, D) row staged once; KV tiles (BK, D) stream through VMEM —
this kernel is HBM-bandwidth bound by design (roofline: bytes of cache
per step), which is exactly the decode_32k/long_500k regime.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(lengths_ref, q_ref, k_ref, v_ref,
                   acc_ref, m_ref, l_ref,
                   m_scr, l_scr, acc_scr, *, window: int, scale: float,
                   bk: int, sk: int, kpos_offset: int):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    ln = lengths_ref[0]
    first = ki * bk + kpos_offset
    visible = first < ln
    if window > 0:
        visible &= (first + bk) > (ln - window)

    @pl.when(visible)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32) * scale           # (1, D)
        k = k_ref[0, 0].astype(jnp.float32)                   # (BK, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (1,BK)
        kpos = first + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        mask = (kpos < ln) & (kpos - kpos_offset < sk)
        if window > 0:
            mask &= kpos >= ln - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, 1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _out():
        acc_ref[0, 0] = acc_scr[...]
        m_ref[0, 0] = m_scr[...]
        l_ref[0, 0] = l_scr[...]


def decode_partial_pallas(q, k, v, lengths, *, window: int = 0,
                          kpos_offset: int = 0,
                          scale: Optional[float] = None,
                          block_k: int = 512, interpret: bool = False):
    b, h, sq, d = q.shape
    assert sq == 1
    _, kh, sk, _ = k.shape
    g = h // kh
    scale = scale if scale is not None else d ** -0.5
    bk = min(block_k, sk)
    grid = (b, h, pl.cdiv(sk, bk))

    kernel = functools.partial(_decode_kernel, window=window, scale=scale,
                               bk=bk, sk=sk, kpos_offset=kpos_offset)
    acc, m, l = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda bi, hi, ki: (bi,)),
            pl.BlockSpec((1, 1, 1, d), lambda bi, hi, ki: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, hi, ki: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, hi, ki: (bi, hi // g, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, d), lambda bi, hi, ki: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1), lambda bi, hi, ki: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1), lambda bi, hi, ki: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, 1, d), jnp.float32),
            jax.ShapeDtypeStruct((b, h, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, h, 1, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, q, k, v)
    return acc, m, l
