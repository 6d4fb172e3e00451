"""Blocked FlashAttention for TPU (Pallas).

Grid: (B, H, Sq/BQ, Sk/BK); the last dimension is sequential ("arbitrary")
so the running-softmax accumulators live in VMEM scratch across KV steps.
BlockSpecs stage (BQ, D) query tiles and (BK, D) KV tiles into VMEM; the
MXU sees (BQ, D) x (D, BK) and (BQ, BK) x (BK, D) matmuls — BQ/BK default
to 128/256, multiples of the 128-lane register tiling.

GQA is handled in the index maps (kv head = h // group); causal and
sliding-window masking skip fully-masked KV blocks via ``pl.when`` (the
block still occupies a grid step, but does no MXU work or accumulator
traffic — on TPU the Mosaic pipeline overlaps the skipped steps' DMAs).

decode ('length') mode masks by a per-batch cache length carried in SMEM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(lengths_ref, q_ref, k_ref, v_ref, o_ref,
                 m_scr, l_scr, acc_scr, *, mode: str, window: int,
                 scale: float, bq: int, bk: int, sk: int, q_offset: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) \
        + q_offset
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    # block-level skip: is any (q, k) pair in this tile visible?
    if mode == "causal":
        block_visible = (ki * bk) <= (qi * bq + bq - 1 + q_offset)
        if window > 0:
            block_visible &= (ki * bk + bk - 1) > (qi * bq + q_offset
                                                   - window)
    elif mode == "length":
        block_visible = (ki * bk) < lengths_ref[0]
    else:
        block_visible = True

    @pl.when(block_visible)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (BQ, D)
        k = k_ref[0, 0].astype(jnp.float32)                  # (BK, D)
        v = v_ref[0, 0].astype(jnp.float32)                  # (BK, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)

        mask = jnp.ones((bq, bk), jnp.bool_)
        if mode == "causal":
            mask = kpos <= qpos
            if window > 0:
                mask &= kpos > qpos - window
        elif mode == "length":
            ln = lengths_ref[0]
            mask = kpos < ln
            if window > 0:
                mask &= kpos >= ln - window
        mask &= kpos < sk                                    # tail padding
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                                  # (BQ, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                               # (BQ, BK)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc = acc_scr[...] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new
        acc_scr[...] = acc

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[...]
        o_ref[0, 0] = (acc_scr[...]
                       / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, mode: str = "causal", window: int = 0,
                           lengths: Optional[jnp.ndarray] = None,
                           q_offset: int = 0, scale: Optional[float] = None,
                           block_q: int = 128, block_k: int = 256,
                           interpret: bool = False):
    b, h, sq, d = q.shape
    _, kh, sk, _ = k.shape
    assert h % kh == 0, (h, kh)
    g = h // kh
    scale = scale if scale is not None else d ** -0.5
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    grid = (b, h, pl.cdiv(sq, bq), pl.cdiv(sk, bk))

    if lengths is None:
        lengths = jnp.full((b,), sk, jnp.int32)

    kernel = functools.partial(
        _attn_kernel, mode=mode, window=window, scale=scale, bq=bq, bk=bk,
        sk=sk, q_offset=q_offset)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda bi, hi, qi, ki: (bi,)),   # lengths
            pl.BlockSpec((1, 1, bq, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, hi, qi, ki: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, hi, qi, ki: (bi, hi // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),     # running max
            pltpu.VMEM((bq, 1), jnp.float32),     # running sum
            pltpu.VMEM((bq, d), jnp.float32),     # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(lengths, q, k, v)
