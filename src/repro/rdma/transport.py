"""RC transport over the ICI mesh.

RDMA semantics mapped to jax collectives (DESIGN.md §2): a *get* request
travels to the shard that owns the key (``all_to_all`` dispatch), the owner
executes the offload chain against local HBM, and the response travels back
(``all_to_all`` combine).  One collective phase pair == one network RTT in
the paper's latency structure.

The dispatch is fixed-capacity (like MoE routing): each source shard can
send up to ``capacity`` requests to each destination per step; overflow
requests are dropped and reported (back-pressure is the serving engine's
job, mirroring how an RNIC's WQ depth bounds outstanding verbs).  Every
entry point threads a per-request ``ok`` mask so a dropped (or
isolation-deferred) request is *distinguishable* from a served request
whose answer happens to be zero — drops must never read as misses.

The owner-side work comes in two flavors:

* :func:`triggered_chain` — a Python callable stands in for the offload
  (the two-sided/RPC baseline: the *host* does the lookup);
* :func:`triggered_chain_engine` — the RedN path proper: the arriving
  requests are delivered to a pre-posted **chain VM program** and executed
  by :class:`repro.core.engine.ChainEngine` where the data lives, one
  vmapped run per serving step;
* :func:`triggered_chain_stateful` — the read-*write* variant (the SET
  offload): the receive window streams through the chain sequentially and
  the owner's authoritative state is threaded as a scan carry.
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .. import obs


def rank_within_dest(dest: jnp.ndarray,
                     live: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """pos[i] = #{j < i : dest[j] == dest[i] and live[j]} (slot in the group).

    Sort/segment-cumsum formulation: O(B log B) and O(B) memory, vs the
    B x B boolean mask of the quadratic version (16M entries at batch
    4096).  ``live=None`` means all requests count.  Non-live requests get
    the rank they *would* have had, but consume no slot for anyone else.
    """
    b = dest.shape[0]
    order = jnp.argsort(dest, stable=True)        # stable: keeps batch order
    sd = dest[order]
    lv = (jnp.ones((b,), jnp.int32) if live is None
          else live[order].astype(jnp.int32))
    csum = jnp.cumsum(lv) - lv                    # exclusive live count
    is_start = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), sd[1:] != sd[:-1]])
    # live count at each group's first row, carried across the group
    base = lax.cummax(jnp.where(is_start, csum, 0))
    rank_sorted = (csum - base).astype(jnp.int32)
    return jnp.zeros((b,), jnp.int32).at[order].set(rank_sorted)


def dispatch(payload: jnp.ndarray, dest: jnp.ndarray, n_shards: int,
             capacity: int, axis_name: str,
             live: Optional[jnp.ndarray] = None):
    """Route local requests to their destination shards.

    payload: (B, W) int32; dest: (B,) int32 in [0, n_shards); live: (B,)
    bool — requests an admission stage deferred (not dispatched, no slot
    consumed).
    Returns (recv, pos, ok):
      recv : (n_shards, capacity, W) — slot [s, c] = c-th live request from
             source shard s (zero-padded);
      pos  : (B,) my requests' slots (for collecting responses);
      ok   : (B,) bool — True iff the request was actually dispatched
             (live and within capacity); a False row's response is not
             authoritative and must not be read as a miss.
    """
    b, w = payload.shape
    with obs.scope("kv.route"):
        pos = rank_within_dest(dest, live)
        ok = pos < capacity
        if live is not None:
            ok = ok & live
        send = jnp.zeros((n_shards, capacity, w), payload.dtype)
        # not-ok rows get an out-of-range slot and are dropped by scatter
        slot = jnp.where(ok, pos, capacity)
        send = send.at[dest, slot].set(payload, mode="drop")
        recv = lax.all_to_all(send, axis_name, split_axis=0, concat_axis=0,
                              tiled=False)
    return recv, pos, ok


def combine(responses: jnp.ndarray, dest: jnp.ndarray, pos: jnp.ndarray,
            ok: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Return responses to their source shards and gather per-request.

    responses: (n_shards, capacity, V) — slot [s, c] answers source s's
    c-th request; ``ok`` is the dispatch mask.  Returns (B, V) aligned with
    the original local requests; rows with ``ok == False`` are zeroed
    (their content is meaningless — the caller must consult ``ok``, which
    is what keeps drops from aliasing with misses).
    """
    with obs.scope("kv.route"):
        back = lax.all_to_all(responses, axis_name, split_axis=0,
                              concat_axis=0, tiled=False)
        # back[s, c] = response from shard s for my c-th request to it
        capacity = back.shape[1]
        safe = jnp.minimum(pos, capacity - 1)
        out = back[dest, safe]
        return out * ok[:, None].astype(out.dtype)


def one_sided_read(remote: jnp.ndarray, shard: jnp.ndarray,
                   rows: jnp.ndarray, axis_name: str,
                   n_shards: int, capacity: int,
                   live: Optional[jnp.ndarray] = None
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """RDMA READ: fetch ``remote[rows]`` from the shard owning them.

    remote: (local_rows, W) this shard's slice of a dim-0-sharded array.
    shard/rows: (B,) target shard and *local* row on that shard.
    Pure data movement — the remote side executes no logic (the defining
    property of a one-sided verb).  Returns (data, ok).
    """
    req = jnp.stack([rows, jnp.ones_like(rows)], axis=1)     # row, live
    recv, pos, ok = dispatch(req, shard, n_shards, capacity, axis_name,
                             live)
    rrows = recv[..., 0].reshape(-1)
    filled = recv[..., 1].reshape(-1)
    data = remote[jnp.clip(rrows, 0, remote.shape[0] - 1)]
    data = data * filled[:, None].astype(data.dtype)
    data = data.reshape(n_shards, capacity, -1)
    return combine(data, shard, pos, ok, axis_name), ok


def triggered_chain(remote_fn: Callable, payload: jnp.ndarray,
                    dest: jnp.ndarray, n_shards: int, capacity: int,
                    axis_name: str, resp_words: int,
                    live: Optional[jnp.ndarray] = None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """SEND triggers a *function* stand-in at the owner (the RPC baseline).

    ``remote_fn(requests) -> responses`` runs where the data lives but is
    executed by the host CPU — this is the two-sided comparison path; the
    RedN path proper is :func:`triggered_chain_engine`.  Returns
    (responses (B, resp_words), ok (B,)).
    """
    recv, pos, ok = dispatch(payload, dest, n_shards, capacity, axis_name,
                             live)
    flat = recv.reshape(-1, recv.shape[-1])
    resp = remote_fn(flat).reshape(n_shards, capacity, resp_words)
    return combine(resp, dest, pos, ok, axis_name), ok


def triggered_chain_stateful(step_fn: Callable, carry, payload: jnp.ndarray,
                             dest: jnp.ndarray, n_shards: int, capacity: int,
                             axis_name: str, resp_words: int,
                             live: Optional[jnp.ndarray] = None,
                             faults: Optional[jnp.ndarray] = None):
    """SEND-triggered chains that *mutate* owner state (the §3.5 read-write
    offload — the SET path's wire pattern).

    Same 1-RTT dispatch/combine structure as
    :func:`triggered_chain_engine`, but the owner's receive window is
    streamed through ``step_fn(carry, request_row) -> (carry, resp_row)``
    **sequentially** (one ``lax.scan``), so every chain run observes every
    earlier request's writes — the NIC serializes atomics against local
    memory, and a batch therefore behaves exactly like the requests
    applied one at a time.  ``carry`` is the owner's authoritative state
    (e.g. the shard's hopscotch arrays); zero-padded window slots reach
    ``step_fn`` too and must be self-guarding (the chain programs' null
    guard WQ / key-0 commit mask).  Returns
    ``(responses (B, resp_words), ok (B,), final_carry)``.

    Stages compose: a caller may re-dispatch a *subset* of one stage's
    admitted rows through a second stateful stage, threading the carry
    through both (the SET path's displacement escalation does exactly
    this).  Because :func:`rank_within_dest` ranks only live rows, every
    row of a ``live2 <= ok1`` subset gets a rank <= its stage-1 rank, so
    at equal capacity the escalation stage can never introduce new drops
    — the invariant ``test_escalation_subset_never_drops`` pins down.

    ``faults`` (optional): (B, ``faults_mod.FIELDS``) int32 packed
    :class:`repro.core.faults.FaultPlan` rows, one per request.  A
    request's fault *rides its payload through dispatch* — the columns
    are concatenated onto the payload, routed in the same collective,
    and split back off at the receive window — so the fault lands on
    whatever shard (and window slot) the request lands on, exactly like
    a real WQE corruption travels with the WQE.  When present,
    ``step_fn`` receives ``(payload_row, fault_row)`` tuples.
    """
    if faults is not None:
        wire = jnp.concatenate(
            [payload, faults.astype(payload.dtype)], axis=1)
        recv, pos, ok = dispatch(wire, dest, n_shards, capacity,
                                 axis_name, live)
        flat = recv.reshape(-1, recv.shape[-1])
        w = payload.shape[1]
        carry, resp = lax.scan(step_fn, carry,
                               (flat[:, :w], flat[:, w:]))
    else:
        recv, pos, ok = dispatch(payload, dest, n_shards, capacity,
                                 axis_name, live)
        flat = recv.reshape(-1, recv.shape[-1])
        carry, resp = lax.scan(step_fn, carry, flat)
    resp = resp.reshape(n_shards, capacity, resp_words)
    return combine(resp, dest, pos, ok, axis_name), ok, carry


def triggered_chain_group(group_fn: Callable, carry, payload: jnp.ndarray,
                          dest: jnp.ndarray, n_shards: int, capacity: int,
                          axis_name: str, resp_words: int, n_writers: int,
                          live: Optional[jnp.ndarray] = None):
    """:func:`triggered_chain_stateful` with the receive window partitioned
    into **racing writer QPs** (the §3.5 multi-writer wire pattern).

    The owner's window rows are grouped into *laps* of ``n_writers``
    consecutive slots; each lap's rows are delivered to ``n_writers``
    independent pre-posted writer lanes that execute **concurrently**
    against the shard's shared state (one
    :meth:`repro.core.programs.MultiWriterGroup.run_group` call), while
    laps themselves serialize through the scan carry.  So within a lap
    the chains genuinely race their claim CASes; across laps request
    ``i`` observes lap ``< i``'s committed writes, preserving the
    serialized-oracle equivalence lap by lap (CAS linearizability).

    ``group_fn(carry, lap_rows (n_writers, W)) -> (carry, resp
    (n_writers, resp_words))``.  The window is zero-padded up to a
    multiple of ``n_writers``; padded rows reach the lanes and must be
    self-guarding exactly like the stateful path's padded slots.
    Returns ``(responses (B, resp_words), ok (B,), final_carry)``.
    """
    recv, pos, ok = dispatch(payload, dest, n_shards, capacity, axis_name,
                             live)
    flat = recv.reshape(-1, recv.shape[-1])
    rows = flat.shape[0]
    pad = (-rows) % n_writers
    if pad:
        flat = jnp.concatenate(
            [flat, jnp.zeros((pad, flat.shape[1]), flat.dtype)])
    laps = flat.reshape(-1, n_writers, flat.shape[1])
    carry, resp = lax.scan(group_fn, carry, laps)
    resp = resp.reshape(-1, resp_words)[:rows]
    resp = resp.reshape(n_shards, capacity, resp_words)
    return combine(resp, dest, pos, ok, axis_name), ok, carry


def local_chain_stateful(step_fn: Callable, carry, payload: jnp.ndarray,
                         faults: Optional[jnp.ndarray] = None):
    """Loopback chains: the owner triggers its *own* pre-posted chain.

    Maintenance offloads — table growth, compaction — originate at the
    shard that owns the data, so there is no dispatch/combine pair at
    all: the NIC is both requester and responder (a loopback QP), and
    the request stream is simply scanned through the chain with the
    owner's authoritative state as the carry, exactly like the receive
    window of :func:`triggered_chain_stateful` but with zero network
    RTTs.  This is what lets ``store.sharded_resize`` keep migrating
    with the host driver dead: every lap is a chain execution against
    device state, never a host computation.

    ``step_fn(carry, request_row) -> (carry, resp_row)``; zero-padded
    rows must be self-guarding (the chain programs' null guard WQ).
    Returns ``(responses (B, resp_words), final_carry)``.

    ``faults`` (optional): (B, FIELDS) packed
    :class:`repro.core.faults.FaultPlan` rows — no dispatch here, so
    they are simply scanned alongside the payload; ``step_fn`` then
    receives ``(payload_row, fault_row)`` tuples.  Modeling note: a
    loopback lap's fault is the *shard itself* dying mid-lap, which is
    why the migration cut-point sweep drives this path.
    """
    if faults is not None:
        carry, resp = lax.scan(step_fn, carry,
                               (payload, faults.astype(payload.dtype)))
    else:
        carry, resp = lax.scan(step_fn, carry, payload)
    return resp, carry


def local_chain_group(group_fn: Callable, carry, payload: jnp.ndarray,
                      n_lanes: int):
    """Loopback analogue of :func:`triggered_chain_group`.

    Maintenance lanes that originate at the owning shard (the CLOCK
    sweeper's laps, a local compaction pass) race against foreground
    writer lanes over the same shared state, but need no dispatch/
    combine pair: the request stream is partitioned into laps of
    ``n_lanes`` consecutive rows and each lap is delivered to the
    group's pre-posted lanes in one
    :meth:`repro.core.programs.MultiWriterGroup.run_group` call, laps
    serializing through the scan carry exactly like
    :func:`local_chain_stateful`.  Zero-padded rows reach the lanes and
    must be self-guarding.

    ``group_fn(carry, lap_rows (n_lanes, W)) -> (carry, resp
    (n_lanes, resp_words))``.  Returns ``(responses (B, resp_words),
    final_carry)`` with responses aligned to the input rows.
    """
    rows = payload.shape[0]
    pad = (-rows) % n_lanes
    flat = payload
    if pad:
        flat = jnp.concatenate(
            [flat, jnp.zeros((pad, flat.shape[1]), flat.dtype)])
    laps = flat.reshape(-1, n_lanes, flat.shape[1])
    carry, resp = lax.scan(group_fn, carry, laps)
    return resp.reshape(-1, resp.shape[-1])[:rows], carry


def triggered_chain_engine(engine, state, segment, words, recv_wq: int,
                           resp_region: int, resp_words: int,
                           payload: jnp.ndarray, dest: jnp.ndarray,
                           n_shards: int, capacity: int, axis_name: str,
                           live: Optional[jnp.ndarray] = None,
                           max_steps: int = 256):
    """The RedN pattern: SEND triggers a pre-posted chain VM program.

    Every arriving request (one slot of the owner's (n_shards, capacity)
    receive window) is delivered as a client SEND to ``recv_wq`` of an
    independent chain-VM context, and all contexts execute in one vmapped
    ``ChainEngine.run_many_segmented`` call — the chain, not the host,
    computes the answer.  The owner's image is split at the program's
    read-only ``segment``: each context carries its own copy of the
    private image ``state`` (``resp_region`` is the response's address
    there), and all of them read the segment's ``words`` from one array.
    The caller pays exactly one dispatch/combine pair (1 RTT) regardless
    of the chain's complexity — the paper's core performance claim.

    Returns (responses (B, resp_words), ok (B,), steps (n_shards *
    capacity,), breached (B,), routed ()): each response is the context's
    ``resp_region`` snapshot after its chain quiesced, ``steps`` the WRs
    each context of the owner's receive window executed, ``breached``
    marks a request whose context halted on a store into the segment —
    not an answer, so ``ok`` is False there — and ``routed`` counts the
    owner's live window slots: those whose first payload word, the key
    in the chain servers' request layout, is nonzero (key 0 is padding).
    """
    recv, pos, ok = dispatch(payload, dest, n_shards, capacity, axis_name,
                             live)
    flat = recv.reshape(-1, recv.shape[-1])
    routed = jnp.sum(flat[:, 0] != 0, dtype=jnp.int32)
    with obs.scope("kv.get.vm"):
        out, breach = engine.run_many_segmented(state, segment, words,
                                                recv_wq, flat, max_steps)
    resp = out.mem[:, resp_region:resp_region + resp_words]
    # the breach flag travels back with the response it spoils
    resp = jnp.concatenate([resp, breach[:, None].astype(resp.dtype)], 1)
    back = combine(resp.reshape(n_shards, capacity, resp_words + 1), dest,
                   pos, ok, axis_name)
    breached = back[:, -1] > 0
    return back[:, :-1], ok & ~breached, out.steps, breached, routed
