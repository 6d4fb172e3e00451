"""The NIC-PU-per-client chain executor as a Pallas TPU kernel.

Each grid cell is one client QP context.  Its memory image (code region =
the WR chain, data region, response region), its staged inbound messages
and its init vector are copied HBM -> SMEM, a while loop fetches and
executes WRs in order, and the mutated image and the queue counters are
copied back.  This is the closest TPU analogue of a ConnectX PU walking a
managed WQ: fetch-at-execute within the image makes self-modifying chains
coherent by construction (the paper needs WAIT/ENABLE to get the same
guarantee past the RNIC's WQE prefetch).

The chain VM is scalar work: every WR field is a scalar load at an address
the chain itself computed, so the image lives in SMEM and every read and
write goes through the ref, one word at a time.  A value-level
``dynamic_slice`` has no TPU lowering, and a VMEM image would need
lane-dynamic loads.  Out-of-range addresses follow the oracle
(:func:`repro.kernels.chain_vm.ref.managed_chain_loop`) exactly: reads
clamp to the last word, writes past the image are dropped, and a copy's
source and destination windows clamp to ``[0, M - MAX_COPY]``.

HBM rows are passed as ``(n_clients, 1, width)`` with ``width`` padded to
a multiple of 128: the TPU tiles the last two dims of an HBM array, and a
per-client copy must take whole tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core import isa
from .ref import (INIT_COMPLETIONS, INIT_ENABLE, INIT_FUEL, INIT_HALTED,
                  INIT_HEAD, INIT_MSG_HEAD, INIT_MSG_TAIL, INIT_TAIL)

_LANES = 128


def _managed_vm_kernel(mem_hbm, msg_hbm, init_hbm, mem_out, stat_out,
                       img, msgs, init, stats, *, m: int, cap: int,
                       wq_base: int, n_wrs: int, managed: bool,
                       max_steps: int):
    ci = pl.program_id(0)
    pltpu.sync_copy(mem_hbm.at[ci], img)
    pltpu.sync_copy(msg_hbm.at[ci], msgs)
    pltpu.sync_copy(init_hbm.at[ci], init)

    def load(a):
        return img[0, jnp.minimum(a, m - 1)]

    def store(a, v):
        @pl.when(a < m)
        def _():
            img[0, a] = v

    def copy(src, dst, ln):
        ln = jnp.clip(ln, 0, isa.MAX_COPY)
        s0 = jnp.clip(src, 0, m - isa.MAX_COPY)
        d0 = jnp.clip(dst, 0, m - isa.MAX_COPY)
        blk = [img[0, s0 + j] for j in range(isa.MAX_COPY)]   # snapshot
        for j in range(isa.MAX_COPY):
            @pl.when(j < ln)
            def _():
                img[0, d0 + j] = blk[j]

    def store_old(addr, old):
        # CAS/ADD return-old: written only for a non-negative address
        @pl.when((addr >= 0) & (addr < m))
        def _():
            img[0, addr] = old

    def execute(opcode, src, d, ln, opa, opb, aux, pbase):
        @pl.when((opcode == isa.WRITE) | (opcode == isa.READ)
                 | ((opcode == isa.SEND) & (opb < 0)))
        def _():
            copy(src, d, ln)

        @pl.when(opcode == isa.WRITE_IMM)
        def _():
            store(d, opa)

        @pl.when(opcode == isa.RECV)
        def _():
            a = jnp.maximum(aux, 0)
            n = jnp.clip(load(a), 0, isa.MAX_SCATTER)
            for j in range(isa.MAX_SCATTER):
                dd = jnp.maximum(load(a + 1 + j), 0)

                @pl.when(j < n)
                def _():
                    store(dd, msgs[0, pbase + j])

        @pl.when((opcode == isa.CAS) | (opcode == isa.ADD))
        def _():
            old = load(d)
            new = jnp.where(opcode == isa.ADD, old + opa,
                            jnp.where(old == opa, opb, old))
            store(d, new)
            store_old(src, old)

        @pl.when(opcode == isa.MAX)
        def _():
            store(d, jnp.maximum(load(d), opa))

        @pl.when(opcode == isa.MIN)
        def _():
            store(d, jnp.minimum(load(d), opa))

    head0 = init[0, INIT_HEAD]
    tail = init[0, INIT_TAIL]
    msg_tail = init[0, INIT_MSG_TAIL]
    fuel = init[0, INIT_FUEL]
    one, zero = jnp.int32(1), jnp.int32(0)

    def cond(c):
        i, stopped = c[0], c[-1]
        return (i < max_steps) & (stopped == 0)

    def body(c):
        i, head, enable, comps, mhead, resps, halted, _ = c
        addr = wq_base + (head % n_wrs) * isa.WR_WORDS
        opcode = jnp.clip((img[0, addr + isa.F_CTRL] >> isa.ID_BITS) & 0x7F,
                          0, isa.NUM_OPCODES - 1)
        flags = img[0, addr + isa.F_FLAGS]
        src = img[0, addr + isa.F_SRC]
        d = jnp.maximum(img[0, addr + isa.F_DST], 0)
        ln = img[0, addr + isa.F_LEN]
        opa = img[0, addr + isa.F_OPA]
        opb = img[0, addr + isa.F_OPB]
        aux = img[0, addr + isa.F_AUX]
        limit = jnp.minimum(tail, enable) if managed else tail
        runnable = ((head < limit)
                    & ((opcode != isa.WAIT) | (comps >= opa))
                    & ((opcode != isa.RECV) | (mhead < msg_tail))
                    & (head - head0 < fuel))

        @pl.when(runnable)
        def _():
            execute(opcode, src, d, ln, opa, opb, aux,
                    (mhead % cap) * isa.MSG_WORDS)

        ran = jnp.where(runnable, one, zero)
        signaled = (flags & isa.FLAG_SUPPRESS_COMPLETION) == 0
        enable = jnp.where(runnable & (opcode == isa.ENABLE),
                           jnp.maximum(enable, opa), enable)
        comps = comps + jnp.where(runnable & signaled, one, zero)
        mhead = mhead + jnp.where(opcode == isa.RECV, ran, zero)
        resps = resps + jnp.where((opcode == isa.SEND) & (opb < 0), ran,
                                  zero)
        halted = halted | jnp.where(opcode == isa.HALT, ran, zero)
        stopped = (one - ran) | halted
        return (i + 1, head + ran, enable, comps, mhead, resps, halted,
                stopped)

    halted0 = jnp.where(init[0, INIT_HALTED] > 0, one, zero)
    _, head, enable, comps, mhead, resps, halted, stopped = lax.while_loop(
        cond, body,
        (zero, head0, init[0, INIT_ENABLE], init[0, INIT_COMPLETIONS],
         init[0, INIT_MSG_HEAD], zero, halted0, halted0))
    for k, v in enumerate((head, enable, comps, mhead, halted, stopped,
                           resps, zero)):
        stats[0, k] = v
    pltpu.sync_copy(img, mem_out.at[ci])
    pltpu.sync_copy(stats, stat_out.at[ci])


def _rows(x):
    """(n, w) -> (n, 1, w128): per-client rows as whole HBM tiles."""
    n, w = x.shape
    pad = -w % _LANES
    return jnp.pad(x, ((0, 0), (0, pad)))[:, None, :]


def run_managed_pallas(mems, msgs, inits, *, wq_base: int, n_wrs: int,
                       managed: bool, max_steps: int,
                       interpret: bool = False):
    """Managed-WQ chain executor: one grid cell per client context.

    The widened semantics (ENABLE-gated head limit, completion counters,
    RECV from a staged per-context message region) let a WQ-recycled get
    server's lap loop run as a grid of independent client contexts —
    the batched-offload fast path.

    ``mems``: (n_clients, M) int32 images; ``msgs``: (n_clients,
    CAP*MSG_WORDS) staged inbound messages; ``inits``: (n_clients, 8)
    int32 per :data:`repro.kernels.chain_vm.ref.INIT_HEAD` layout.
    Returns ``(mems, stats)`` with ``stats``: (n_clients, 8) per the
    STAT_* layout.
    """
    n_clients, m = mems.shape
    mw = msgs.shape[1]
    mem_rows, msg_rows, init_rows = _rows(mems), _rows(msgs), _rows(inits)
    kernel = functools.partial(
        _managed_vm_kernel, m=m, cap=mw // isa.MSG_WORDS, wq_base=wq_base,
        n_wrs=n_wrs, managed=managed, max_steps=max_steps)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out_mem, out_stats = pl.pallas_call(
        kernel,
        grid=(n_clients,),
        in_specs=[hbm, hbm, hbm],
        out_specs=[hbm, hbm],
        out_shape=[jax.ShapeDtypeStruct(mem_rows.shape, jnp.int32),
                   jax.ShapeDtypeStruct(init_rows.shape, jnp.int32)],
        scratch_shapes=[pltpu.SMEM(mem_rows.shape[1:], jnp.int32),
                        pltpu.SMEM(msg_rows.shape[1:], jnp.int32),
                        pltpu.SMEM(init_rows.shape[1:], jnp.int32),
                        pltpu.SMEM(init_rows.shape[1:], jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="chain_vm_managed",
    )(mem_rows, msg_rows, init_rows)
    return out_mem[:, 0, :m], out_stats[:, 0, :inits.shape[1]]
