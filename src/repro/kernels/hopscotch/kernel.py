"""Batched hopscotch-probe Pallas kernel — the TPU re-hosting of the
paper's Fig. 9 hash *get* offload.

The RNIC probes one bucket per chain; the TPU-native shape of the same
work is a *vectorized* probe: a block of queries is staged into VMEM, the
H-bucket neighborhood window of the (VMEM-resident) key table is compared
against all queries at once, and the matching value rows are gathered.

Instead of a data-dependent gather (poor fit for the VPU), the probe is a
**one-hot matmul**: hits (BQ, N) = OR over the H diagonals of the match
matrix, then values are pulled with hits @ values — MXU work, fully dense,
no divergence (misses contribute zero rows, which is exactly the paper's
"default value 0" miss semantics).  Grid tiles the table dimension N so
each (BQ, BN) tile's one-hot slab fits VMEM; the query-block accumulators
carry across table tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_MULT = 2654435761


def _probe_kernel(q_ref, keys_ref, vals_ref, found_ref, out_ref,
                  acc_scr, hit_scr, *, neighborhood: int, n_buckets: int,
                  bn: int, bq: int):
    ti = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(ti == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        hit_scr[...] = jnp.zeros_like(hit_scr)

    q = q_ref[...]                                     # (BQ,) int32
    home = ((q.astype(jnp.uint32) * jnp.uint32(_MULT))
            % jnp.uint32(n_buckets)).astype(jnp.int32)
    keys = keys_ref[...]                               # (BN,) this table tile
    rows = ti * bn + jax.lax.broadcasted_iota(jnp.int32, (bq, bn), 1)

    # neighborhood membership: (row - home) mod N in [0, H)
    dist = (rows - home[:, None]) % n_buckets
    in_nbhd = dist < neighborhood
    match = (keys[None, :] == q[:, None]) & in_nbhd & (q[:, None] != 0)

    onehot = match.astype(jnp.float32)                 # (BQ, BN)
    acc_scr[...] += jax.lax.dot(onehot, vals_ref[...].astype(jnp.float32),
                                preferred_element_type=jnp.float32)
    hit_scr[...] += jnp.sum(onehot, axis=1, keepdims=True)

    @pl.when(ti == nt - 1)
    def _fin():
        found_ref[...] = (hit_scr[...][:, 0] > 0)
        out_ref[...] = acc_scr[...].astype(out_ref.dtype)


def hopscotch_lookup_pallas(keys, values, queries, neighborhood: int, *,
                            block_q: int = 128, block_n: int = 1024,
                            interpret: bool = False):
    n = keys.shape[0]
    v = values.shape[-1]
    b = queries.shape[0]
    bq = min(block_q, b)
    bn = min(block_n, n)
    assert b % bq == 0 and n % bn == 0
    grid = (b // bq, n // bn)

    kernel = functools.partial(_probe_kernel, neighborhood=neighborhood,
                               n_buckets=n, bn=bn, bq=bq)
    found, out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq,), lambda qi, ti: (qi,)),
            pl.BlockSpec((bn,), lambda qi, ti: (ti,)),
            pl.BlockSpec((bn, v), lambda qi, ti: (ti, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bq,), lambda qi, ti: (qi,)),
            pl.BlockSpec((bq, v), lambda qi, ti: (qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b,), jnp.bool_),
            jax.ShapeDtypeStruct((b, v), values.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, v), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(queries, keys, values)
    return found, out
