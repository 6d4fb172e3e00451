"""The RedN chain VM — a jittable discrete-event interpreter for RDMA
work-request chains (RedN §3).

This is the functional model of "what the RNIC's processing units do":

* one PU per work queue (paper §3.5 "each WQ is allocated a single RNIC PU");
* WQs are circular buffers of 8-word WRs living *inside* the flat memory
  image, so chains can modify their own code (self-modifying WRs, §3.2);
* ``WAIT`` blocks a WQ until another WQ's completion counter reaches a
  threshold (completion ordering, Fig. 2a);
* managed WQs execute only up to a monotonic ``enable_limit`` raised by
  ``ENABLE`` (doorbell ordering, Fig. 2b) — the instruction barrier that
  makes self-modification coherent, and the wrap-around mechanism behind WQ
  recycling (§3.4): ENABLE/WAIT counts are *monotonic*, which is exactly why
  recycled loops must ADD to their own wqe_count fields each lap;
* scheduling is min-clock-first over eligible WQs, so the per-WQ latency
  clocks (priced by ``cost.py``) interleave like concurrent PUs;
* the machine stops on quiescence (no WQ eligible) or fuel exhaustion —
  nontermination (Turing requirement T3) is expressed by recycled WQs that
  never quiesce.

Everything is `lax`-traceable: `run()` is a `lax.while_loop` and the whole
machine can be `jax.jit`-ed and `jax.vmap`-ed (batched clients — the
benchmark harness runs thousands of independent QP contexts this way).

Execution is *fused*: per-WR eligibility is computed once per iteration and
threaded through the while-loop carry (the quiescence test reuses the same
result instead of recomputing it in `cond`), the spec/cost lookup tables are
closure constants of a per-spec specialized step (see :func:`_fused_step`),
and the no-op guard selects only the state fields a step can touch.  The
batched entry points (`run_batch`, `deliver_many`) are what
:class:`repro.core.engine.ChainEngine` builds its `get_many` fast path on.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import cost, isa


class MachineSpec(NamedTuple):
    """Static machine geometry (specializes the jitted step)."""
    mem_words: int
    wq_bases: tuple            # word address of WR slot 0, per WQ
    wq_sizes: tuple            # WR slots per WQ (circular)
    orderings: tuple           # isa.ORD_* per WQ (cost model)
    managed: tuple             # bool per WQ (ENABLE-gated)
    msg_capacity: int = 8      # inbound message slots per WQ

    @property
    def num_wqs(self) -> int:
        return len(self.wq_bases)


class VMState(NamedTuple):
    """Dynamic machine state — a pytree of arrays (vmap-able)."""
    mem: jnp.ndarray            # i32[mem_words + MAX_COPY guard]
    head: jnp.ndarray           # i32[N] monotonic executed count
    tail: jnp.ndarray           # i32[N] monotonic posted count (doorbell)
    enable_limit: jnp.ndarray   # i32[N] monotonic ENABLE watermark
    completions: jnp.ndarray    # i32[N] signaled-completion count
    last_comp_time: jnp.ndarray  # f32[N] clock of latest completion
    msg_buf: jnp.ndarray        # i32[N, CAP, MSG_WORDS]
    msg_head: jnp.ndarray       # i32[N]
    msg_tail: jnp.ndarray       # i32[N]
    clock: jnp.ndarray          # f32[N] per-PU latency clock (us)
    steps: jnp.ndarray          # i32[] WRs executed
    halted: jnp.ndarray         # bool[]
    verb_counts: jnp.ndarray    # i32[NUM_OPCODES] executed-verb histogram
    responses: jnp.ndarray      # i32[] count of SEND-to-client responses


# Guard pad past the addressable image: lets every copy verb *and* the
# SEND payload gather use a plain dynamic_slice with no per-step
# concatenate/bounds logic (reads past mem_words land in zeros).
GUARD_WORDS = max(isa.MAX_COPY, isa.MSG_WORDS)


def init_state(spec: MachineSpec, mem_image: np.ndarray,
               tails: Sequence[int], enable_limits: Sequence[int]) -> VMState:
    mem = np.zeros(spec.mem_words + GUARD_WORDS, dtype=np.int32)
    mem[: len(mem_image)] = mem_image
    n = spec.num_wqs
    host = VMState(
        mem=mem,
        head=np.zeros(n, np.int32),
        tail=np.asarray(tails, np.int32),
        enable_limit=np.asarray(enable_limits, np.int32),
        completions=np.zeros(n, np.int32),
        last_comp_time=np.zeros(n, np.float32),
        msg_buf=np.zeros((n, spec.msg_capacity, isa.MSG_WORDS), np.int32),
        msg_head=np.zeros(n, np.int32),
        msg_tail=np.zeros(n, np.int32),
        clock=np.zeros(n, np.float32),
        steps=np.zeros((), np.int32),
        halted=np.zeros((), np.bool_),
        verb_counts=np.zeros(isa.NUM_OPCODES, np.int32),
        responses=np.zeros((), np.int32),
    )
    # the image is pure host data: every leaf is a concrete array made
    # from numpy, even when a (cached) program builder is first reached
    # inside a trace.  A creation op such as ``jnp.zeros`` there would be
    # stamped with the trace's mesh, and the cached state would then be
    # refused inside a shard_map over a mesh of another size.
    with jax.ensure_compile_time_eval():
        return jax.tree_util.tree_map(jnp.asarray, host)


# ---------------------------------------------------------------------------
# host-side doorbells (the client/driver API)
# ---------------------------------------------------------------------------

def ring(state: VMState, wq: int, count: int = 1) -> VMState:
    """Ring the doorbell: post `count` already-written WRs on `wq`."""
    return state._replace(tail=state.tail.at[wq].add(count))


def deliver(state: VMState, wq: int, payload) -> VMState:
    """Client SEND arriving at `wq`'s QP: lands in the message queue and is
    consumed by a pre-posted RECV (Fig. 3's trigger)."""
    payload = jnp.asarray(payload, jnp.int32)
    pay = jnp.zeros(isa.MSG_WORDS, jnp.int32)
    pay = pay.at[: payload.shape[0]].set(payload)
    slot = state.msg_tail[wq] % state.msg_buf.shape[1]
    return state._replace(
        msg_buf=state.msg_buf.at[wq, slot].set(pay),
        msg_tail=state.msg_tail.at[wq].add(1),
    )


def deliver_many(state: VMState, wq: int, payloads) -> VMState:
    """Batched deliver: stack N client SENDs into a vmapped ``VMState``.

    ``payloads`` is ``(N, k)`` (k <= MSG_WORDS).  Every leaf of ``state`` is
    broadcast to a leading batch dim of N and row ``i`` receives
    ``payloads[i]`` on ``wq`` — one allocation, no per-request host loop.
    The result feeds :func:`run_batch` (or ``ChainEngine.run_many``).
    """
    payloads = jnp.asarray(payloads, jnp.int32)
    if payloads.ndim != 2:
        raise ValueError(
            f"payloads must be a (N, k) batch, got shape {payloads.shape}; "
            "use deliver() for a single request")
    n, k = payloads.shape
    if k > isa.MSG_WORDS:
        raise ValueError(f"payload of {k} words exceeds MSG_WORDS")
    if k == isa.MSG_WORDS:
        pays = payloads                  # already padded (the engine path)
    else:
        pays = jnp.zeros((n, isa.MSG_WORDS),
                         jnp.int32).at[:, :k].set(payloads)
    batch = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (n,) + a.shape), state)
    slot = state.msg_tail[wq] % state.msg_buf.shape[1]
    return batch._replace(
        msg_buf=batch.msg_buf.at[:, wq, slot].set(pays),
        msg_tail=batch.msg_tail.at[:, wq].add(1),
    )


def enable(state: VMState, wq: int, absolute_count: int) -> VMState:
    """Host-side ENABLE (used when the trigger comes from the driver)."""
    new = jnp.maximum(state.enable_limit[wq], absolute_count)
    return state._replace(enable_limit=state.enable_limit.at[wq].set(new))


# ---------------------------------------------------------------------------
# the step function
# ---------------------------------------------------------------------------

def _masked_copy(mem, src, dst, ln):
    """mem[dst:dst+ln] = mem[src:src+ln] for ln <= MAX_COPY (guarded)."""
    ln = jnp.clip(ln, 0, isa.MAX_COPY)
    blk = lax.dynamic_slice(mem, (src,), (isa.MAX_COPY,))
    cur = lax.dynamic_slice(mem, (dst,), (isa.MAX_COPY,))
    out = jnp.where(jnp.arange(isa.MAX_COPY) < ln, blk, cur)
    return lax.dynamic_update_slice(mem, out, (dst,))


def _maybe_store(mem, addr, value):
    """mem[addr] = value if addr >= 0 (atomic return-old path)."""
    safe = jnp.maximum(addr, 0)
    cur = mem[safe]
    return mem.at[safe].set(jnp.where(addr >= 0, value, cur))


@functools.lru_cache(maxsize=None)
def _fused_step(spec: MachineSpec):
    """Spec-specialized (eligibility, execute) pair.

    All static lookup tables — WQ geometry, ordering modes, and the cost
    model's fetch/exec tables — are closure constants built once per spec,
    not rebuilt inside the hot loop.  ``execute`` consumes an eligibility
    already computed for exactly the state it steps, so the fused ``run``
    evaluates eligibility once per iteration (the old cond/body split
    evaluated it twice).
    """
    # numpy (not jnp) constants: they embed as trace-local constants in any
    # jit/vmap context without leaking tracers across the lru_cache.
    bases = np.asarray(spec.wq_bases, np.int32)
    sizes = np.asarray(spec.wq_sizes, np.int32)
    managed = np.asarray(spec.managed, bool)
    orderings = np.asarray(spec.orderings, np.int32)
    fetch_tab = np.asarray(cost.FETCH_BY_ORDERING, np.float32)
    exec_tab = np.asarray(cost.EXEC_COST, np.float32)
    nwq_minus1 = spec.num_wqs - 1

    def eligibility(s: VMState):
        """Per-WQ: (eligible, ctrl-word addr of the head WR, head opcode)."""
        idx = s.head % sizes
        addr = bases + idx * isa.WR_WORDS
        limit = jnp.where(managed, jnp.minimum(s.tail, s.enable_limit),
                          s.tail)
        has_work = s.head < limit

        ctrl = s.mem[addr]
        opcode = (ctrl >> isa.ID_BITS) & 0x7F
        opa = s.mem[addr + isa.F_OPA]
        opb = s.mem[addr + isa.F_OPB]

        tgt = jnp.clip(opb, 0, nwq_minus1)
        wait_ok = jnp.where(opcode == isa.WAIT,
                            s.completions[tgt] >= opa, True)
        recv_ok = jnp.where(opcode == isa.RECV,
                            s.msg_tail > s.msg_head, True)
        eligible = has_work & wait_ok & recv_ok & ~s.halted
        return eligible, addr, opcode

    def execute(s: VMState, eligible, addrs, guard: bool = True,
                faults=None, fault_counts=None):
        """One scheduling step.  With ``faults`` (a scalar-leaf
        ``repro.core.faults.FaultPlan``) the step also applies the armed
        fault semantics — WR suppression at a step index, spurious CAS
        failure, nulled ENABLE — threaded as *traced* values so fault
        parameters never specialize the (lru-cached) step.
        ``fault_counts = (cas_seen, enable_seen)`` are the executed-verb
        ordinals the CAS/ENABLE faults index; the faulted form returns
        ``(new_state, new_counts)`` instead of just the state.
        (``kill_step`` is a loop-condition fault — see :func:`run` — not
        a per-step one.)"""
        w = jnp.argmin(jnp.where(eligible, s.clock, jnp.inf)).astype(
            jnp.int32)

        addr = addrs[w]
        ctrl = s.mem[addr + isa.F_CTRL]
        opcode = jnp.clip((ctrl >> isa.ID_BITS) & 0x7F, 0,
                          isa.NUM_OPCODES - 1)
        if faults is not None:
            cas_seen, enable_seen = fault_counts
            # WQE drop: the scheduled WR executes as nothing — head
            # still advances (the NIC skipped the entry), no effects,
            # and *no completion*, so dependent WAITs starve exactly
            # like a real lost WQE.
            suppress = ((faults.suppress_step >= 0)
                        & (s.steps == faults.suppress_step))
            opcode = jnp.where(suppress, jnp.int32(isa.NOOP), opcode)
            spur_cas = ((faults.fail_cas >= 0) & (opcode == isa.CAS)
                        & (cas_seen == faults.fail_cas))
            zero_enable = ((faults.zero_enable >= 0)
                           & (opcode == isa.ENABLE)
                           & (enable_seen == faults.zero_enable))
        flags = s.mem[addr + isa.F_FLAGS]
        src = s.mem[addr + isa.F_SRC]
        dst = s.mem[addr + isa.F_DST]
        ln = s.mem[addr + isa.F_LEN]
        opa = s.mem[addr + isa.F_OPA]
        opb = s.mem[addr + isa.F_OPB]
        aux = s.mem[addr + isa.F_AUX]
        tgt = jnp.clip(opb, 0, nwq_minus1)

        # --- verb semantics: branch-free effect pipeline -------------------
        # lax.switch under vmap evaluates *every* branch and selects — 13
        # full-state materializations per step.  Instead each verb is
        # decomposed into masked micro-effects applied exactly once:
        #   1. a block copy of <= MAX_COPY words    (WRITE/READ/SEND-resp)
        #   2. a scalar read-modify-write store     (WRITE_IMM/CAS/ADD/...)
        #   3. a return-old store                   (CAS/ADD with src >= 0)
        #   4. a <= MAX_SCATTER payload scatter     (RECV)
        #   5. msg/enable/halt side-channel updates (SEND/ENABLE/HALT)
        # Inert verbs degenerate to identity writes, so semantics are
        # bit-identical to the branch dispatch.
        is_copy = ((opcode == isa.WRITE) | (opcode == isa.READ)
                   | ((opcode == isa.SEND) & (opb < 0)))
        mem = _masked_copy(s.mem, src, dst, jnp.where(is_copy, ln, 0))

        # scalar RMW store (identity `old` write when the verb has none)
        d = jnp.maximum(dst, 0)
        old = mem[d]
        sval = old
        sval = jnp.where(opcode == isa.WRITE_IMM, opa, sval)
        cas_hit = old == opa
        if faults is not None:
            # spurious atomic failure: compare forced to mismatch; the
            # return-old path below still reports the true old value
            cas_hit = cas_hit & ~spur_cas
        sval = jnp.where(opcode == isa.CAS,
                         jnp.where(cas_hit, opb, old), sval)
        sval = jnp.where(opcode == isa.ADD, old + opa, sval)
        sval = jnp.where(opcode == isa.MAX, jnp.maximum(old, opa), sval)
        sval = jnp.where(opcode == isa.MIN, jnp.minimum(old, opa), sval)
        mem = mem.at[d].set(sval)

        # atomics' return-old path
        ret_addr = jnp.where(
            (opcode == isa.CAS) | (opcode == isa.ADD), src, -1)
        mem = _maybe_store(mem, ret_addr, old)

        # RECV: scatter the head message through the table at `aux`
        is_recv = opcode == isa.RECV
        rslot = s.msg_head[w] % s.msg_buf.shape[1]
        rpayload = s.msg_buf[w, rslot]
        a = jnp.maximum(aux, 0)
        n_scatter = jnp.where(
            is_recv, jnp.clip(mem[a], 0, isa.MAX_SCATTER), 0)

        def scatter(i, m):
            sd = jnp.maximum(m[a + 1 + i], 0)
            return m.at[sd].set(
                jnp.where(i < n_scatter, rpayload[i], m[sd]))

        mem = lax.fori_loop(0, isa.MAX_SCATTER, scatter, mem)

        # SEND to a peer QP (opb >= 0): enqueue payload on its msg queue.
        # The GUARD_WORDS pad makes this gather a plain dynamic_slice.
        send_msg = (opcode == isa.SEND) & (opb >= 0)
        payload = lax.dynamic_slice(
            s.mem, (jnp.maximum(src, 0),), (isa.MSG_WORDS,))
        mslot = s.msg_tail[tgt] % s.msg_buf.shape[1]
        msg_buf = s.msg_buf.at[tgt, mslot].set(
            jnp.where(send_msg, payload, s.msg_buf[tgt, mslot]))
        msg_tail = s.msg_tail.at[tgt].add(jnp.where(send_msg, 1, 0))
        msg_head = s.msg_head.at[w].add(jnp.where(is_recv, 1, 0))
        responses = s.responses + jnp.where(
            (opcode == isa.SEND) & (opb < 0), 1, 0)

        # ENABLE raises the target's monotonic watermark; HALT stops us
        en_raises = opcode == isa.ENABLE
        if faults is not None:
            # lost doorbell: the ENABLE executes (head, clock, ordinal
            # all advance) but the watermark write never lands
            en_raises = en_raises & ~zero_enable
        enable_limit = s.enable_limit.at[tgt].set(jnp.where(
            en_raises,
            jnp.maximum(s.enable_limit[tgt], opa), s.enable_limit[tgt]))
        halted = s.halted | (opcode == isa.HALT)

        new = s._replace(mem=mem, msg_buf=msg_buf, msg_tail=msg_tail,
                         msg_head=msg_head, responses=responses,
                         enable_limit=enable_limit, halted=halted)

        # --- bookkeeping: head, completions, clock, stats ------------------
        # Pre-posted chains parked on a WAIT/RECV (the paper's "pre-post
        # chains, client triggers" pattern) don't pay the doorbell+fetch at
        # trigger time — the WQE was fetched when the chain was posted.
        parked = (opcode == isa.WAIT) | (opcode == isa.RECV)
        first = s.head[w] == 0
        fetch = jnp.where(
            first & parked, 0.0,
            jnp.where(first, cost.DOORBELL_BASE,
                      jnp.asarray(fetch_tab)[jnp.asarray(orderings)[w]]))
        exec_cost = jnp.asarray(exec_tab)[opcode]
        t = s.clock[w] + fetch + exec_cost
        # WAIT synchronizes with the producer's completion time (Fig 2a)
        t = jnp.where(opcode == isa.WAIT,
                      jnp.maximum(t, new.last_comp_time[tgt]), t)

        signaled = (flags & isa.FLAG_SUPPRESS_COMPLETION) == 0
        if faults is not None:
            signaled = signaled & ~suppress
        completions = new.completions.at[w].add(jnp.where(signaled, 1, 0))
        last_ct = new.last_comp_time.at[w].set(
            jnp.where(signaled, t, new.last_comp_time[w]))

        new = new._replace(
            head=new.head.at[w].add(1),
            completions=completions,
            last_comp_time=last_ct,
            clock=new.clock.at[w].set(t),
            steps=new.steps + 1,
            verb_counts=new.verb_counts.at[opcode].add(1),
        )
        # if nothing was eligible, this step is a no-op; only the fields a
        # step can touch are selected — `tail` is host-owned and never
        # written.  The fused `run` skips the guard entirely: its cond
        # guarantees eligibility, and under vmap the while_loop batching
        # rule masks finished machines itself.
        if faults is not None:
            # ordinal counters index *executed* verbs (a suppressed CAS
            # never reached an execution unit, so it consumes no slot)
            counts_out = (
                cas_seen + (opcode == isa.CAS).astype(jnp.int32),
                enable_seen + (opcode == isa.ENABLE).astype(jnp.int32))
            if not guard:
                return new, counts_out
            return _select_touched(jnp.any(eligible), new, s), counts_out
        if not guard:
            return new
        return _select_touched(jnp.any(eligible), new, s)

    return eligibility, execute


def _select_touched(pred, new: VMState, old: VMState) -> VMState:
    sel = lambda a, b: jnp.where(pred, a, b)   # noqa: E731
    return old._replace(
        mem=sel(new.mem, old.mem),
        head=sel(new.head, old.head),
        enable_limit=sel(new.enable_limit, old.enable_limit),
        completions=sel(new.completions, old.completions),
        last_comp_time=sel(new.last_comp_time, old.last_comp_time),
        msg_buf=sel(new.msg_buf, old.msg_buf),
        msg_head=sel(new.msg_head, old.msg_head),
        msg_tail=sel(new.msg_tail, old.msg_tail),
        clock=sel(new.clock, old.clock),
        steps=sel(new.steps, old.steps),
        halted=sel(new.halted, old.halted),
        verb_counts=sel(new.verb_counts, old.verb_counts),
        responses=sel(new.responses, old.responses))


def _eligibility(spec: MachineSpec, s: VMState):
    """Per-WQ: (eligible, ctrl-word addr of the head WR, head opcode)."""
    eligibility, _ = _fused_step(spec)
    return eligibility(s)


def step(spec: MachineSpec, s: VMState) -> VMState:
    """One scheduling step (standalone form; `run` uses the fused loop)."""
    eligibility, execute = _fused_step(spec)
    eligible, addrs, _ = eligibility(s)
    return execute(s, eligible, addrs)


def quiescent(spec: MachineSpec, s: VMState) -> jnp.ndarray:
    eligible, _, _ = _eligibility(spec, s)
    return ~jnp.any(eligible)


@functools.partial(jax.jit, static_argnums=(0, 2))
def run(spec: MachineSpec, state: VMState, max_steps: int = 4096,
        faults=None) -> VMState:
    """Run until quiescence / HALT / fuel exhaustion.

    Fused loop: the eligibility of the *current* state rides in the carry,
    so quiescence is read off the carry instead of re-deriving it in
    ``cond`` — one eligibility evaluation per executed WR.

    ``faults`` (a scalar-leaf :class:`repro.core.faults.FaultPlan`)
    injects the plan's armed faults into this run: ``kill_step`` stops
    the loop before executing step ``k`` (exactly ``k`` WRs run — the
    shard/process died mid-chain), the per-step faults apply inside
    :func:`_fused_step`'s ``execute``.  Fault parameters are *traced*,
    so every cut-point of a sweep shares one compilation.  A fully
    disarmed plan is bit-identical to the plain run (tested).
    """
    eligibility, execute = _fused_step(spec)

    if faults is None:
        def cond(carry):
            s, eligible, _ = carry
            return jnp.any(eligible) & (~s.halted) & (s.steps < max_steps)

        def body(carry):
            s, eligible, addrs = carry
            new = execute(s, eligible, addrs, guard=False)
            e2, a2, _ = eligibility(new)
            return new, e2, a2

        elig0, addrs0, _ = eligibility(state)
        out, _, _ = lax.while_loop(cond, body, (state, elig0, addrs0))
        return out

    def cond(carry):
        s, eligible, _, _ = carry
        killed = (faults.kill_step >= 0) & (s.steps >= faults.kill_step)
        return (jnp.any(eligible) & (~s.halted) & (s.steps < max_steps)
                & ~killed)

    def body(carry):
        s, eligible, addrs, counts = carry
        new, counts = execute(s, eligible, addrs, guard=False,
                              faults=faults, fault_counts=counts)
        e2, a2, _ = eligibility(new)
        return new, e2, a2, counts

    elig0, addrs0, _ = eligibility(state)
    zero = jnp.zeros((), jnp.int32)
    out, _, _, _ = lax.while_loop(
        cond, body, (state, elig0, addrs0, (zero, zero)))
    return out


def run_batch(spec: MachineSpec, states: VMState,
              max_steps: int = 4096, faults=None) -> VMState:
    """vmapped run — a fleet of independent QP contexts (batched clients).

    ``faults`` leaves, when given, carry a leading batch dim matching the
    states — one independent plan per context."""
    if faults is None:
        return jax.vmap(lambda s: run(spec, s, max_steps))(states)
    return jax.vmap(lambda s, f: run(spec, s, max_steps, f))(states, faults)


def total_time_us(state: VMState) -> jnp.ndarray:
    """End-to-end chain latency: the latest PU clock."""
    return jnp.max(state.clock)


# -- multi-writer scheduling --------------------------------------------------
#
# Many independent chains share ONE memory image; a Schedule decides, round
# by round, how many VM steps each writer's WQ group may take.  This extends
# the FaultPlan data-threading idiom (``repro.core.faults``): a Schedule is a
# NamedTuple of int32 leaves, rounds are rows, and the sentinel ``-1`` means
# "unlimited" the same way FaultPlan's ``NONE = -1`` means "disarmed".
# Schedules are *traced* pytree inputs, so every cut-point of an interleaving
# sweep shares a single compilation of :func:`run_scheduled`.

SCHED_DRAIN = -1  # quota sentinel: run this writer to quiescence this round


class Schedule(NamedTuple):
    """Deterministic multi-writer interleaving plan.

    ``quota`` is int32 of shape ``(n_rounds, n_writers)``.  Round ``r``
    advances writers in index order ``0..n-1``; writer ``w`` executes at most
    ``quota[r, w]`` VM steps (``SCHED_DRAIN`` = -1: run to quiescence, 0:
    skip).  A step is one executed WR picked min-clock-first among the
    writer's *own* eligible WQs — the same scheduler as :func:`run`, masked
    to the writer's WQ slice.
    """
    quota: jnp.ndarray

    # -- constructors (mirror FaultPlan's classmethod style) -----------------
    @classmethod
    def serialized(cls, n_writers: int,
                   order: Sequence[int] | None = None) -> "Schedule":
        """One writer per round, each run to quiescence — the serialized
        oracle order (default 0..n-1)."""
        order = tuple(range(n_writers)) if order is None else tuple(order)
        q = np.zeros((len(order), n_writers), np.int32)
        for r, w in enumerate(order):
            q[r, w] = SCHED_DRAIN
        return cls(jnp.asarray(q))

    @classmethod
    def round_robin(cls, n_writers: int, quantum: int,
                    n_rounds: int) -> "Schedule":
        """``n_rounds`` rounds of ``quantum`` steps each, then a drain round
        so outstanding work always completes."""
        q = np.full((n_rounds, n_writers), int(quantum), np.int32)
        drain = np.full((1, n_writers), SCHED_DRAIN, np.int32)
        return cls(jnp.asarray(np.concatenate([q, drain])))

    @classmethod
    def cut(cls, c, n_writers: int = 2) -> "Schedule":
        """Cut-point schedule (the interleaving analogue of
        ``FaultPlan.kill_at``): writer 0 runs exactly ``c`` steps, writer 1
        drains against the half-done state, then everyone drains.  ``c`` may
        be a traced scalar — all cut-points share one compilation."""
        c = jnp.asarray(c, jnp.int32)
        zero = jnp.zeros((), jnp.int32)
        drain = jnp.full((), SCHED_DRAIN, jnp.int32)
        pad = [zero] * (n_writers - 2)
        rows = [
            jnp.stack([c, zero] + pad),
            jnp.stack([zero, drain] + pad),
            jnp.stack([drain] * n_writers),
            jnp.stack([drain] * n_writers),
        ]
        return cls(jnp.stack(rows))

    # -- row plumbing (FaultPlan.as_rows/from_row idiom) ---------------------
    def as_rows(self) -> jnp.ndarray:
        return jnp.asarray(self.quota, jnp.int32)

    @classmethod
    def from_rows(cls, rows) -> "Schedule":
        return cls(jnp.asarray(rows, jnp.int32))

    @property
    def n_rounds(self) -> int:
        return self.quota.shape[0]

    @property
    def n_writers(self) -> int:
        return self.quota.shape[1]


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def run_scheduled(spec: MachineSpec, state: VMState, schedule: Schedule,
                  writer_slices: tuple, max_steps: int = 4096) -> VMState:
    """Run many writers' chains over ONE shared memory image under a
    deterministic :class:`Schedule`.

    ``writer_slices`` is a static tuple of ``(lo, hi)`` WQ index ranges, one
    per writer; writer ``w`` owns WQs ``lo..hi-1``.  Slices must be disjoint
    (shared *memory* is the point; shared *WQs* are not).  Any WQ outside
    every slice (e.g. the null guard WQ) never advances.

    The per-writer step is the same fused execute as :func:`run` with
    eligibility masked to the writer's slice, so a round's steps are
    min-clock-first *within* that writer.  ``max_steps`` bounds the global
    step count across all rounds; fault injection is not supported here
    (interleaving sweeps and fault sweeps compose at the harness level, not
    in one run).
    """
    eligibility, execute = _fused_step(spec)
    masks = []
    for lo, hi in writer_slices:
        m = np.zeros(spec.num_wqs, bool)
        m[lo:hi] = True
        masks.append(m)

    def writer_round(s: VMState, quota, mask):
        # quota counts *this round's* steps, so the counter is local —
        # VMState.steps is the global (max_steps) odometer.
        def cond(carry):
            s, eligible, _, k = carry
            under = jnp.where(quota < 0, True, k < quota)
            return (jnp.any(eligible) & (~s.halted)
                    & (s.steps < max_steps) & under)

        def body(carry):
            s, eligible, addrs, k = carry
            new = execute(s, eligible, addrs, guard=False)
            e2, a2, _ = eligibility(new)
            return new, e2 & mask, a2, k + 1

        elig0, addrs0, _ = eligibility(s)
        out, _, _, _ = lax.while_loop(
            cond, body, (s, elig0 & mask, addrs0, jnp.zeros((), jnp.int32)))
        return out

    def round_step(s, quota_row):
        for w, mask in enumerate(masks):
            s = writer_round(s, quota_row[w], mask)
        return s, None

    out, _ = lax.scan(round_step, state, schedule.as_rows())
    return out
