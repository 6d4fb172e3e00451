"""Oracle for the single-WQ chain executor: a pure-jnp in-order interpreter
over the same 8-word WR ISA as repro.core.

:func:`step_wr_managed` / :func:`managed_chain_loop` give the managed-WQ
semantics the recycled get server needs: an ENABLE-gated head limit,
completion counters (WAIT-on-self), RECV consuming messages from a staged
per-context message region, client-response SEND, and CAS/ADD return-old.
A blocked head WR (unsatisfied WAIT, empty message queue, head at the
enable limit) quiesces the context — on a single queue nothing else can
unblock it.  A plain (unmanaged) WQ is the same loop without the ENABLE
gate.  The Pallas kernel (``kernel.run_managed_pallas``) implements the
same semantics over an SMEM image, and this interpreter is its bit-exact
oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ...core import isa

# per-context init-vector layout (int32[8]) shared with the Pallas kernel:
INIT_HEAD, INIT_TAIL, INIT_ENABLE, INIT_COMPLETIONS = 0, 1, 2, 3
INIT_MSG_HEAD, INIT_MSG_TAIL, INIT_FUEL, INIT_HALTED = 4, 5, 6, 7
STAT_HEAD, STAT_ENABLE, STAT_COMPLETIONS = 0, 1, 2
STAT_MSG_HEAD, STAT_HALTED, STAT_STOPPED, STAT_RESPONSES = 3, 4, 5, 6


def _copy(mem, src, dst, ln):
    ln = jnp.clip(ln, 0, isa.MAX_COPY)
    blk = lax.dynamic_slice(mem, (src,), (isa.MAX_COPY,))
    cur = lax.dynamic_slice(mem, (dst,), (isa.MAX_COPY,))
    out = jnp.where(jnp.arange(isa.MAX_COPY) < ln, blk, cur)
    return lax.dynamic_update_slice(mem, out, (dst,))


# the atomic return-old store is shared with the core machine so the
# "interpreter is the bit-exact oracle" contract can't drift
from ...core.machine import _maybe_store  # noqa: E402


def step_wr_managed(mem, wr_addr, payload, enable_limit):
    """Execute the WR at wr_addr with managed-WQ semantics.

    ``payload`` is the head message (MSG_WORDS,) for RECV.  Returns
    ``(mem, enable_limit, halted)``.  Mirrors repro.core.machine's verb
    semantics for a single WQ (ENABLE/WAIT targets clip to self).
    """
    ctrl = mem[wr_addr + isa.F_CTRL]
    opcode = jnp.clip((ctrl >> isa.ID_BITS) & 0x7F, 0, isa.NUM_OPCODES - 1)
    src = mem[wr_addr + isa.F_SRC]
    dst = mem[wr_addr + isa.F_DST]
    ln = mem[wr_addr + isa.F_LEN]
    opa = mem[wr_addr + isa.F_OPA]
    opb = mem[wr_addr + isa.F_OPB]
    aux = mem[wr_addr + isa.F_AUX]
    d = jnp.maximum(dst, 0)

    def noop(m):
        return m

    def write(m):
        return _copy(m, src, d, ln)

    def write_imm(m):
        return m.at[d].set(opa)

    def send(m):
        # single-WQ subset: only the client-response form (opb < 0);
        # an inter-QP SEND has no peer on a single queue.
        return jnp.where(opb < 0, _copy(m, src, d, ln), m)

    def recv(m):
        a = jnp.maximum(aux, 0)
        n = jnp.clip(m[a], 0, isa.MAX_SCATTER)

        def scatter(i, m_):
            dd = jnp.maximum(m_[a + 1 + i], 0)
            return m_.at[dd].set(jnp.where(i < n, payload[i], m_[dd]))

        return lax.fori_loop(0, isa.MAX_SCATTER, scatter, m)

    def cas(m):
        old = m[d]
        m2 = m.at[d].set(jnp.where(old == opa, opb, old))
        return _maybe_store(m2, src, old)

    def add(m):
        old = m[d]
        m2 = m.at[d].set(old + opa)
        return _maybe_store(m2, src, old)

    def max_(m):
        return m.at[d].max(opa)

    def min_(m):
        return m.at[d].min(opa)

    branches = [noop, write, write_imm, write, send, recv, cas, add,
                max_, min_, noop, noop, noop]
    mem = lax.switch(opcode, branches, mem)
    enable_limit = jnp.where(opcode == isa.ENABLE,
                             jnp.maximum(enable_limit, opa), enable_limit)
    return mem, enable_limit, opcode == isa.HALT


def managed_chain_loop(mem, msgs, init, *, wq_base: int, n_wrs: int,
                       managed: bool, max_steps: int):
    """Run one managed single-WQ context until stall/HALT/fuel exhaustion.

    ``mem``: (M,) int32 image; ``msgs``: (CAP*MSG_WORDS,) staged inbound
    messages; ``init``: int32[8] per the INIT_* layout — ``INIT_FUEL`` is
    the maximum number of *executed* WRs (mirroring ``machine.run``'s
    ``steps < max_steps`` cond), while ``max_steps`` bounds loop
    iterations.  Returns ``(mem, stats)`` with ``stats`` int32[8] per the
    STAT_* layout.
    """
    cap = msgs.shape[0] // isa.MSG_WORDS
    head0 = init[INIT_HEAD]
    tail = init[INIT_TAIL]
    msg_tail = init[INIT_MSG_TAIL]
    fuel = init[INIT_FUEL]           # max *executed* WRs, like run()'s
                                     # steps < max_steps cond

    def body(i, carry):
        mem, head, enable, comps, mhead, resps, halted, stopped = carry
        addr = wq_base + (head % n_wrs) * isa.WR_WORDS
        ctrl = mem[addr]
        opcode = jnp.clip((ctrl >> isa.ID_BITS) & 0x7F, 0,
                          isa.NUM_OPCODES - 1)
        flags = mem[addr + isa.F_FLAGS]
        opa = mem[addr + isa.F_OPA]
        opb = mem[addr + isa.F_OPB]
        limit = jnp.minimum(tail, enable) if managed else tail
        has_work = head < limit
        wait_ok = jnp.where(opcode == isa.WAIT, comps >= opa, True)
        recv_ok = jnp.where(opcode == isa.RECV, mhead < msg_tail, True)
        runnable = (has_work & wait_ok & recv_ok & ~stopped
                    & (head - head0 < fuel))

        payload = lax.dynamic_slice(
            msgs, ((mhead % cap) * isa.MSG_WORDS,), (isa.MSG_WORDS,))
        mem2, enable2, halt2 = step_wr_managed(mem, addr, payload, enable)

        signaled = (flags & isa.FLAG_SUPPRESS_COMPLETION) == 0
        is_resp = (opcode == isa.SEND) & (opb < 0)
        mem = jnp.where(runnable, mem2, mem)
        enable = jnp.where(runnable, enable2, enable)
        comps = comps + jnp.where(runnable & signaled, 1, 0)
        mhead = mhead + jnp.where(runnable & (opcode == isa.RECV), 1, 0)
        resps = resps + jnp.where(runnable & is_resp, 1, 0)
        head = head + jnp.where(runnable, 1, 0)
        halted = halted | (runnable & halt2)
        stopped = stopped | ~runnable | halted
        return (mem, head, enable, comps, mhead, resps, halted, stopped)

    halted0 = init[INIT_HALTED] > 0      # a HALTed machine stays stopped
    carry = (mem, init[INIT_HEAD], init[INIT_ENABLE],
             init[INIT_COMPLETIONS], init[INIT_MSG_HEAD],
             jnp.zeros((), jnp.int32), halted0, halted0)
    mem, head, enable, comps, mhead, resps, halted, stopped = lax.fori_loop(
        0, max_steps, body, carry)
    stats = jnp.stack([
        head, enable, comps, mhead, halted.astype(jnp.int32),
        stopped.astype(jnp.int32), resps, jnp.zeros((), jnp.int32)])
    return mem, stats
