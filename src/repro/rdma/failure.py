"""Failure resiliency (paper §5.6).

The paper's trick: RDMA resources live in an "empty hull" parent process,
so the NIC keeps executing pre-posted recycled chains when the Memcached
child (or the whole OS) dies.  The TPU analogue: the serving state — the
recycled chain VM state, the hash table, the response regions — lives in
*device buffers* owned by :class:`DeviceResidentService`; the *host driver*
(config, logging) is a disposable Python object.  Crashing and restarting
the driver touches no device state, so gets — and, on the sharded store,
*every* chain-offloaded set, hopscotch displacement included — keep being
served with zero recovery time; a cold restart must rebuild the table and
re-post chains (the multi-second gap Fig. 16 shows).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional, Sequence, Tuple

import numpy as np

from .. import obs
from ..core import faults as faults_mod
from ..core import programs
# module alias, not from-import of names: kvstore.store itself imports
# repro.rdma (transport/isolation), so its class definitions may not have
# executed yet when this module loads — attributes are resolved at call time
from ..kvstore import store as kv_store


class ChainInterrupted(RuntimeError):
    """A chain-offloaded request could not be completed within the
    recovery retry budget: every attempt either faulted or came back
    with a non-terminal status, and fsck + repair + re-issue did not
    converge.  Carries what the operator needs: the key, the attempt
    count, and the last status observed.  Distinct from
    :class:`repro.kvstore.store.ResizeStuck` (a capacity dead end, not
    an interrupted chain)."""

    def __init__(self, key: int, attempts: int, last_status: int,
                 fsck_clean: bool):
        self.key = int(key)
        self.attempts = int(attempts)
        self.last_status = int(last_status)
        self.fsck_clean = bool(fsck_clean)
        super().__init__(
            f"set of key {self.key:#x} interrupted and unrecovered after "
            f"{self.attempts} attempts (last status {self.last_status}, "
            f"fsck {'clean' if fsck_clean else 'NOT clean'})")


class HostDriver:
    """Host-side, crash-prone state (the 'Memcached process')."""

    def __init__(self):
        self.config = {"name": "memcached-redn", "pid": id(self)}
        self.log: list = []
        self.alive = True

    def crash(self):
        self.alive = False
        self.config = None
        self.log = None


class _HostDriverLifecycle:
    """Shared §5.6 crash/restart semantics.  Mixed into services whose
    dataclasses declare ``driver``/``bootstrap_s``/``rebuild_s`` fields:
    killing the driver never touches device state, so serving continues;
    a restart is instant; the cold numbers are what vanilla would pay."""

    def crash_host(self):
        """Kill the host process. Device chains keep running (§5.6)."""
        if self.driver is not None:
            self.driver.crash()
        self.driver = None

    def restart_host(self):
        """Restart the driver: instant, because device state is intact."""
        self.driver = HostDriver()

    def host_alive(self) -> bool:
        return self.driver is not None and self.driver.alive

    def cold_restart_downtime_s(self) -> float:
        """What a vanilla (non-offloaded) server would pay after a crash."""
        return self.bootstrap_s + self.rebuild_s


@dataclasses.dataclass
class DeviceResidentService(_HostDriverLifecycle):
    """Device-resident serving state: survives host driver crashes."""
    server: programs.RecycledGetServer
    driver: Optional[HostDriver]
    bootstrap_s: float = 1.0       # vanilla restart cost (Fig. 16: ~1s boot)
    rebuild_s: float = 1.25        # + metadata/hashtable rebuild (~1.25s)

    @classmethod
    def start(cls, items, n_buckets: int = 64, val_len: int = 2):
        srv = programs.build_recycled_get_server(n_buckets, val_len)
        for k, v in items:
            srv.insert(k, v)
        srv.load()
        return cls(server=srv, driver=HostDriver())

    # -- the serving path (pure device state) --------------------------------
    def get(self, key: int) -> np.ndarray:
        return self.server.serve(key)

    def get_many(self, keys) -> np.ndarray:
        """Batched serving path: the whole key stream flows through the
        recycled chain in one device call (ChainEngine.serve_stream) —
        equivalent to N get() calls, laps and all, but with no host
        round-trip between requests.  Works with the driver dead, same as
        :meth:`get`."""
        return self.server.serve_many(keys)


@dataclasses.dataclass
class ShardedKVService(_HostDriverLifecycle):
    """The §5.6 story at production scale: the *sharded* store's serving
    state — device arrays plus the pre-posted per-shard chain programs —
    is device-resident; the host driver (config, logging) is a disposable
    Python object.  Kill the driver and sharded gets *and every* SET path
    — update, in-neighborhood insert, *and* hopscotch displacement (the
    bounded bubble runs as the displacer chain at the owner shard) — keep
    executing their chain VM programs with zero recovery time.  The host
    holds no serving role at all anymore; only a ``SET_NEEDS_RESIZE``
    answer (table genuinely full) requires operator intervention, and
    that is a capacity event, not a failure-recovery one.
    """
    kv: "kv_store.ShardedKV"       # host handle (bootstrap/geometry only)
    mesh: object                   # jax Mesh over the serving axis
    axis: str
    keys: object                   # (S, B) device array
    vals: object                   # (S, B, V) device array
    driver: Optional[HostDriver]
    bootstrap_s: float = 1.0
    rebuild_s: float = 1.25
    # -- online growth (§5.6 extension: resize *while* serving) --------------
    resize: Optional["kv_store.ResizeState"] = None
    auto_resize: bool = True       # SET_NEEDS_RESIZE escalates to growth
    resize_quantum: int = 16       # buckets migrated per serving call
    resizes_completed: int = 0
    # -- crash-consistent retry (interrupted chains, not dead drivers) -------
    retry_budget: int = 4          # re-issues before ChainInterrupted
    backoff_base_s: float = 1e-4   # first retry delay (doubles per attempt)
    backoff_cap_s: float = 0.05    # exponential backoff ceiling
    repairs_applied: int = 0       # fsck repairs across the service lifetime
    # -- concurrent serving (racing writer QPs over shared shard state) ------
    n_writers: int = 1             # writer lanes per shard on the SET path
    # -- full lifecycle (DELETE + TTL eviction; Memcached parity) ------------
    exp: object = None             # (S, B) int32 deadlines, or None (no TTL)
    sweep_hand: object = None      # (S,) int32 CLOCK hand per shard
    deletes_applied: int = 0       # buckets vacated by the deleter chain
    sweeps_reclaimed: int = 0      # buckets reclaimed by the sweeper chain
    chained_growths: int = 0       # 2n frames that dead-ended into a 4n one
    # resize-window TTL bookkeeping (commit-layer modeling, host-held):
    # the frame snapshot the exp column is aligned to, and deadlines
    # stamped while the frames were doubled — folded back at cutover.
    _exp_keys: object = None
    _pending_deadlines: dict = dataclasses.field(default_factory=dict)
    # -- tracing (repro.obs): serving calls made, and the device counters
    # of the last call made while tracing, read when the next call begins
    _seq: int = dataclasses.field(default=0, init=False)
    _counters: Optional[tuple] = dataclasses.field(default=None, init=False)

    def __post_init__(self):
        obs.install_gc_spans()

    @classmethod
    def start(cls, items: Sequence[Tuple[int, Sequence[int]]],
              n_shards: int = 1, buckets_per_shard: int = 128,
              val_words: int = 2, axis: str = "kv",
              ttl: bool = False) -> "ShardedKVService":
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        mesh = kv_store.serving_mesh(n_shards, axis)
        sharding = NamedSharding(mesh, PartitionSpec(axis))
        kv = kv_store.ShardedKV.build(n_shards, buckets_per_shard, val_words)
        for k, v in items:
            if not kv.set(int(k), list(v)):
                # the bounded host insert mirrors the chain's search/move
                # budget — a failure here would silently drop the item
                # and surface later as an inexplicable miss
                raise ValueError(
                    f"bootstrap insert of key {int(k)} needs a resize "
                    f"(buckets_per_shard={buckets_per_shard} too tight "
                    "for this item set)")
        keys, vals = kv.device_arrays(sharding)
        svc = cls(kv=kv, mesh=mesh, axis=axis, keys=keys, vals=vals,
                  driver=HostDriver())
        if ttl:
            # bootstrap items carry no TTL; deadlines arrive via
            # set_many(..., deadlines=...) and are served/evicted by the
            # TTL get server and the CLOCK sweeper chains
            svc.exp = jax.device_put(
                np.full(keys.shape, programs.NO_TTL, np.int32), sharding)
            svc.sweep_hand = jax.device_put(
                np.zeros((n_shards,), np.int32), sharding)
        return svc

    # -- tracing: spans and counters of the serving calls ---------------------
    def _begin_call(self) -> int:
        """Emit the previous traced call's counters as its ``kv.counters``
        span, then number this call.  The read comes before this call's
        own span opens, and in a closed loop the previous call's answers
        are on the host already, so it waits on no running program."""
        pending, self._counters = self._counters, None
        if pending is not None and obs.enabled():
            seq, kind, arrays = pending
            counts = [np.asarray(a) for a in arrays]
            if kind == "get":
                steps, words, routed = counts  # (S, S * capacity), (S,), (S,)
                trips = steps.max(axis=1)
                obs.mark("kv.counters", seq=seq, kind=kind,
                         trips=int(trips.max()), steps=int(steps.sum()),
                         lanes=int((trips * steps.shape[1]).sum()),
                         image_words=int(words.max()),
                         routed=int(routed.sum()),
                         routed_max=int(routed.max()),
                         slots=steps.shape[1], owners=steps.shape[0])
            else:
                scanned, escalated = counts      # (S,) each
                obs.mark("kv.counters", seq=seq, kind=kind,
                         scanned=int(scanned.sum()),
                         scanned_max=int(scanned.max()),
                         escalated=int(escalated.sum()))
        self._seq += 1
        return self._seq

    def _hold_counters(self, seq: int, kind: str, *arrays):
        if obs.enabled() and arrays[0] is not None:
            self._counters = (seq, kind, arrays)

    def _sync(self, what: str, x) -> np.ndarray:
        """A host read of device state on the served path, as a
        ``kv.sync`` span."""
        with obs.span("kv.sync", what=what):
            return np.asarray(x)

    # -- the serving path (pure device state) --------------------------------
    def get_many(self, queries, now=None, **kwargs) -> "kv_store.GetResult":
        """Sharded redn gets: see :meth:`_get_many`.  While a profiler
        trace is active the call is a ``kv.get_many`` span, and its VM
        steps are emitted when the next call begins (:mod:`repro.obs`)."""
        seq = self._begin_call()
        with obs.span("kv.get_many", seq=seq, width=int(np.size(queries))):
            res = self._get_many(queries, now, **kwargs)
        self._hold_counters(seq, "get", res.vm_steps, res.image_words,
                            res.routed)
        return res

    def _get_many(self, queries, now=None, **kwargs) -> "kv_store.GetResult":
        """Sharded redn gets: chain programs execute at the owner shards.
        Works with the driver dead — no host state is touched.  While a
        resize is in flight the store serves from the double frame
        (new-then-old probes, watermark-gated) and each call also
        advances the migration by one quantum — "resize *while*
        serving", with the serving traffic itself driving the growth.

        ``now`` (TTL services only): the clock.  Steady state, the GET
        server chain evaluates the expiry compare *in verbs* — an
        expired resident answers as a miss without any host compare, so
        lazy expiry keeps working with the driver dead.  During a resize
        window the double-frame server has no deadline column; expired
        hits are filtered host-side from the parked deadline snapshot (a
        documented commit-layer stopgap — the resize window is bounded,
        steady state is the headline path)."""
        import jax.numpy as jnp

        q = jnp.asarray(queries, jnp.int32)
        if q.ndim == 1:
            q = q[None, :]
        if self.resize is not None:
            res = kv_store.sharded_get(
                self.mesh, self.axis, self.resize, q, **kwargs)
            self._advance_resize()
            if self.exp is not None and now is not None:
                res = self._filter_expired(res, q, now)
            return res
        if self.exp is not None and now is not None:
            kwargs = dict(kwargs, exp=self.exp, now=now)
        return kv_store.sharded_get(self.mesh, self.axis, self.keys,
                                    self.vals, q, method="redn", **kwargs)

    def _filter_expired(self, res, q, now):
        """Resize-window TTL stopgap: mask expired hits host-side."""
        import jax.numpy as jnp

        deadlines = self._deadline_map()
        if not deadlines:
            return res
        qn = np.asarray(q)
        expired = np.zeros(qn.shape, bool)
        for k, d in deadlines.items():
            if d != programs.NO_TTL and d - int(now) <= 0:
                expired |= qn == k
        if not expired.any():
            return res
        keep = jnp.asarray(~expired)
        return res._replace(found=res.found & keep,
                            values=jnp.where(keep[..., None], res.values, 0))

    def _deadline_map(self) -> dict:
        """key -> deadline as of the resize window (snapshot + stamps)."""
        out = {}
        if self._exp_keys is not None:
            kn = np.asarray(self._exp_keys)
            en = np.asarray(self.exp)
            mask = kn != 0
            out.update(zip(kn[mask].tolist(), en[mask].tolist()))
        out.update(self._pending_deadlines)
        return out

    def set_many(self, set_keys, set_vals, deadlines=None,
                 **kwargs) -> "kv_store.SetResult":
        """Batched chain-offloaded sets: see :meth:`_set_many`.  While a
        profiler trace is active the call is a ``kv.set_many`` span, and
        its scan counts are emitted when the next call begins."""
        seq = self._begin_call()
        with obs.span("kv.set_many", seq=seq, width=int(np.size(set_keys))):
            res = self._set_many(set_keys, set_vals, deadlines, **kwargs)
        self._hold_counters(seq, "set", res.scanned, res.escalated)
        return res

    def _set_many(self, set_keys, set_vals, deadlines=None,
                  **kwargs) -> "kv_store.SetResult":
        """Batched chain-offloaded sets: the writer chain programs execute
        at the owner shards against the authoritative device arrays, and
        neighborhood-full rows escalate to the displacer chain in the
        same call.  Works with the driver dead.

        A ``SET_NEEDS_RESIZE`` answer (bounded search/bubble exhausted)
        no longer just reports: with ``auto_resize`` the service opens
        the doubled frame (:func:`repro.kvstore.store.begin_resize`),
        re-issues exactly the unplaced rows through the double-frame
        path — where the old frame's neighborhood-full insert escalates
        into the half-empty new frame — and continues the migration
        incrementally on every subsequent serving call.  All of it is
        chain execution against device state, so the escalation path
        works with the driver dead too.

        With ``n_writers`` > 1 the steady-state path serves each shard's
        window through that many *racing* writer lanes
        (:func:`repro.kvstore.store.sharded_set` ``n_writers=``); the
        resize path stays serialized, and combining the writer race with
        ``faults=`` raises :class:`repro.kvstore.store.
        WriterFaultConflict` — the old behavior silently dropped the
        writer group and ran a different experiment than asked for.

        ``deadlines`` (TTL services only): (S, B) int32 absolute expiry
        deadlines aligned with ``set_keys``.  ``None`` stamps NO_TTL —
        a set without a TTL *clears* any previous one, Memcached's
        replace-the-TTL semantics.
        """
        import jax.numpy as jnp

        qk = jnp.asarray(set_keys, jnp.int32)
        qv = jnp.asarray(set_vals, jnp.int32)
        if qk.ndim == 1:
            qk, qv = qk[None, :], qv[None, :, :]
        if self.resize is not None:
            res, self.resize = kv_store.sharded_set(
                self.mesh, self.axis, self.resize, qk, qv, **kwargs)
            self._advance_resize()
            self._stamp_pending(res.applied, qk, deadlines)
            return res
        if self.n_writers > 1:
            if kwargs.get("faults") is not None:
                raise kv_store.WriterFaultConflict(self.n_writers)
            kwargs = dict(kwargs, n_writers=self.n_writers)
        if self.exp is not None:
            res, self.keys, self.vals, self.exp = kv_store.sharded_set(
                self.mesh, self.axis, self.keys, self.vals, qk, qv,
                exp=self.exp, deadlines=deadlines, **kwargs)
        else:
            res, self.keys, self.vals = kv_store.sharded_set(
                self.mesh, self.axis, self.keys, self.vals, qk, qv,
                **kwargs)
        if not self.auto_resize:
            return res
        # (materializing status here is a host sync — only pay it when
        # the answer can actually change the control flow)
        needs = self._sync("status", res.status) == programs.SET_NEEDS_RESIZE
        if not needs.any():
            return res
        # --- auto-escalation: grow, then land the unplaced rows ----------
        self._park_exp()
        self.resize = kv_store.begin_resize(self.keys, self.vals)
        retry = jnp.asarray(needs)
        # needs-resize rows were necessarily live/admitted, so the retry
        # mask subsumes any caller admission mask
        rekw = {k: v for k, v in kwargs.items()
                if k not in ("live", "n_writers")}
        res2, self.resize = kv_store.sharded_set(
            self.mesh, self.axis, self.resize, qk, qv, live=retry,
            **rekw)
        self._stamp_pending(res2.applied, qk, deadlines)
        self._advance_resize()
        return res._replace(status=jnp.where(retry, res2.status, res.status),
                            applied=res.applied | res2.applied,
                            ok=jnp.where(retry, res2.ok, res.ok),
                            dropped=res.dropped + res2.dropped)

    # -- resize-window TTL bookkeeping (commit-layer, host-held) -------------
    def _park_exp(self):
        """Snapshot the frame the exp column is aligned to.  Keys keep
        their identity across migration/displacement, so the deadlines
        are re-derived by key match at cutover
        (:func:`repro.kvstore.store.relocate_exp`)."""
        if self.exp is not None and self._exp_keys is None:
            self._exp_keys = self.keys

    def _stamp_pending(self, applied, qk, deadlines):
        """Record deadlines stamped while the frames were doubled; the
        cutover folds them over the relocated column (last write wins,
        None clears — Memcached's replace-the-TTL semantics)."""
        if self.exp is None:
            return
        app = np.asarray(applied)
        kn = np.asarray(qk)
        dn = None if deadlines is None else np.asarray(deadlines)
        for s, b in np.argwhere(app):
            self._pending_deadlines[int(kn[s, b])] = (
                programs.NO_TTL if dn is None else int(dn[s, b]))

    # -- the delete path: deleter chain at the owner shards ------------------
    def delete_many(self, del_keys, **kwargs) -> "kv_store.DeleteResult":
        """Batched chain-offloaded DELETEs: the deleter chain matches the
        key across its neighborhood and retires the bucket with the
        re-read-comparand vacate CAS.  Works with the driver dead.

        While a resize is in flight the delete runs against **both**
        frames: vacating only the live copy would leave a stale old-frame
        resident for the migrator to faithfully re-home — resurrecting
        the deleted key at cutover.  Deleting from both frames leaves the
        migrator nothing to copy, so a DELETE observed during growth
        stays deleted after it (the no-resurrection property the
        lifecycle tests pin)."""
        import jax.numpy as jnp

        qk = jnp.asarray(del_keys, jnp.int32)
        if qk.ndim == 1:
            qk = qk[None, :]
        if self.resize is not None:
            rs = self.resize
            res_new, nk_new, nv_new = kv_store.sharded_delete(
                self.mesh, self.axis, rs.new_keys, rs.new_vals, qk,
                **kwargs)
            res_old, nk_old, nv_old = kv_store.sharded_delete(
                self.mesh, self.axis, rs.keys, rs.vals, qk, **kwargs)
            self.resize = rs._replace(keys=nk_old, vals=nv_old,
                                      new_keys=nk_new, new_vals=nv_new)
            self._advance_resize()
            hit_new = res_new.status == programs.DEL_DELETED
            res = kv_store.DeleteResult(
                jnp.where(hit_new, res_new.status, res_old.status),
                res_new.applied | res_old.applied,
                res_new.ok & res_old.ok,
                jnp.maximum(res_new.dropped, res_old.dropped),
                res_new.deferred)
            if self.exp is not None:
                kn = np.asarray(qk)
                for s, b in np.argwhere(np.asarray(res.applied)):
                    self._pending_deadlines.pop(int(kn[s, b]), None)
        elif self.exp is not None:
            res, self.keys, self.vals, self.exp = kv_store.sharded_delete(
                self.mesh, self.axis, self.keys, self.vals, qk,
                exp=self.exp, **kwargs)
        else:
            res, self.keys, self.vals = kv_store.sharded_delete(
                self.mesh, self.axis, self.keys, self.vals, qk, **kwargs)
        self.deletes_applied += int(np.asarray(res.applied).sum())
        return res

    def delete(self, key: int) -> bool:
        """One DELETE through the deleter chain; True iff a bucket was
        vacated (``DEL_MISS`` — deleting an absent key — returns False
        but is not an error, as in Memcached)."""
        kv_store.ShardedKV.check_key(key)
        qk = np.zeros((self.kv.n_shards, 1), np.int32)
        qk[0, 0] = key
        res = self.delete_many(qk)
        return bool(np.asarray(res.applied)[0, 0])

    # -- the eviction path: CLOCK sweeper chain laps -------------------------
    def sweep(self, now, count: int = 16) -> "kv_store.SweepReport":
        """Advance the background CLOCK sweeper by ``count`` buckets per
        shard: the sweeper chain reads each visited bucket's deadline,
        evaluates the expiry predicate in Calc verbs, and vacates
        expired buckets (deadline reset to NO_TTL).  Pure chain/device
        work, driver-dead safe — eviction is a background writer lane,
        exactly like the resize migrator."""
        if self.exp is None:
            raise ValueError(
                "sweep() needs a TTL-enabled service "
                "(ShardedKVService.start(..., ttl=True))")
        if self.resize is not None:
            raise ValueError(
                "sweep() cannot run against the doubled frame — drive "
                "the resize to completion first (drive_resize())")
        report, self.keys, self.vals, self.exp = kv_store.sharded_sweep(
            self.mesh, self.axis, self.keys, self.vals, self.exp,
            self.sweep_hand, now, count=count)
        self.sweep_hand = report.hand
        self.sweeps_reclaimed += int(np.asarray(report.reclaimed).sum())
        return report

    # -- incremental growth driver (device chains only; driver-dead safe) ----
    def _advance_resize(self, step: Optional[int] = None):
        if self.resize is None:
            return
        before = int(self._sync("resize", self.resize.watermark).min())
        self.resize, report = kv_store.sharded_resize(
            self.mesh, self.axis, self.resize,
            step=step or self.resize_quantum)
        after = int(self._sync("resize", self.resize.watermark).min())
        if after == before and int(self._sync("resize", report.stuck).sum()):
            # the watermark parks exactly on the bucket the quantum
            # could not place.  PR 5 raised ResizeStuck here — a capacity
            # dead end the operator had to resolve.  Now the dead end
            # *chains*: the doubled frame itself grows (2n -> 4n) and the
            # parked residents land there; only a stuck *inner* growth
            # still raises.
            self._chain_growth()
            return
        if kv_store.resize_done(self.resize):
            self._cutover(*kv_store.finish_resize(self.resize))

    def _chain_growth(self):
        """Second chained growth: the 2n frame dead-ended (a resident is
        unplaceable even displaced), so grow *it* — the migrator chains
        drain 2n into a fresh 4n frame, then the still-parked old-frame
        residents land in 4n through the writer chain.  Every step is
        chain execution against device state; :class:`repro.kvstore.
        store.ResizeStuck` survives only for a stuck inner growth."""
        import jax.numpy as jnp

        rs = self.resize
        ok_np = np.asarray(rs.keys)
        ov_np = np.asarray(rs.vals)
        inner = kv_store.begin_resize(rs.new_keys, rs.new_vals)
        while not kv_store.resize_done(inner):
            before = int(np.asarray(inner.watermark).min())
            inner, report = kv_store.sharded_resize(
                self.mesh, self.axis, inner, step=self.resize_quantum)
            after = int(np.asarray(inner.watermark).min())
            if after == before and int(np.asarray(report.stuck).sum()):
                stuck = np.asarray(report.stuck)
                wm = np.asarray(inner.watermark)
                shards = [s for s in range(len(stuck)) if stuck[s] > 0]
                raise kv_store.ResizeStuck(
                    shards, [int(wm[s]) for s in shards],
                    "chained growth stuck: resident unplaceable even in "
                    "the quadrupled frame (shards "
                    f"{[int(s) for s in shards]})")
        keys4, vals4 = kv_store.finish_resize(inner)
        self.resizes_completed += 1          # the inner 2n -> 4n growth
        # re-issue the parked old-frame residents through the writer
        # chain against the quadrupled frame (zero-key slots are dead)
        n_shards = ok_np.shape[0]
        rows = [np.flatnonzero(ok_np[s] != 0) for s in range(n_shards)]
        width = max([len(r) for r in rows] + [1])
        qk = np.zeros((n_shards, width), np.int32)
        qv = np.zeros((n_shards, width, ov_np.shape[-1]), np.int32)
        for s, idx in enumerate(rows):
            qk[s, :len(idx)] = ok_np[s, idx]
            qv[s, :len(idx)] = ov_np[s, idx]
        qkj = jnp.asarray(qk)
        res, keys4, vals4 = kv_store.sharded_set(
            self.mesh, self.axis, keys4, vals4, qkj, jnp.asarray(qv),
            live=qkj != 0)
        status = np.asarray(res.status)
        landed = np.isin(status, (programs.SET_UPDATED,
                                  programs.SET_INSERTED,
                                  programs.SET_DISPLACED))
        if ((qk != 0) & ~landed).any():
            bad = np.argwhere((qk != 0) & ~landed)
            raise kv_store.ResizeStuck(
                [int(s) for s, _ in bad], [0 for _ in bad],
                "chained growth stuck: parked resident did not land in "
                "the quadrupled frame (statuses "
                f"{status[(qk != 0) & ~landed].tolist()})")
        self.chained_growths += 1
        self._cutover(keys4, vals4)

    def _cutover(self, keys, vals):
        """Adopt a finished frame; on TTL services, re-derive the
        deadline column (key match against the parked snapshot, then
        the resize-window stamps, last write wins)."""
        if self.exp is not None:
            import jax.numpy as jnp

            snap = self._exp_keys if self._exp_keys is not None \
                else self.keys
            exp = kv_store.relocate_exp(snap, self.exp, keys)
            if self._pending_deadlines:
                kn = np.asarray(keys)
                en = np.array(exp)
                for k, d in self._pending_deadlines.items():
                    en[kn == k] = d
                exp = jnp.asarray(en)
            self.exp = exp
            self._exp_keys = None
            self._pending_deadlines = {}
        self.keys, self.vals = keys, vals
        self.resize = None
        self.resizes_completed += 1

    def drive_resize(self):
        """Run the in-flight migration to completion (cutover included).
        Pure chain/device work — callable, and tested, with the host
        driver dead."""
        while self.resize is not None:
            self._advance_resize()

    def resizing(self) -> bool:
        return self.resize is not None

    # -- the set path: fully chain-served, displacement included -------------
    def set(self, key: int, value: Sequence[int]) -> bool:
        """One SET through the full chain pipeline — update,
        in-neighborhood insert, or displacement, all device state only,
        all serving with the driver dead.  A ``SET_NEEDS_RESIZE``
        answer auto-escalates into online growth (the doubled frame
        opens and the key lands through the double-frame path), so with
        ``auto_resize`` on, False only means the escalation itself was
        dropped/stuck; with it off, False is the classic bounded
        needs-resize report — intact store, growth required."""
        kv_store.ShardedKV.check_key(key)
        n_shards = self.kv.n_shards
        # one real request from shard 0; other source shards contribute a
        # zero-padded slot that the chains' null guards ignore
        qk = np.zeros((n_shards, 1), np.int32)
        qk[0, 0] = key
        qv = np.zeros((n_shards, 1, self.kv.val_words), np.int32)
        qv[0, 0, :len(value)] = value
        res = self.set_many(qk, qv)
        status = int(np.asarray(res.status)[0, 0])
        return status in (programs.SET_UPDATED, programs.SET_INSERTED,
                          programs.SET_DISPLACED)

    # -- crash-consistent recovery (§ robustness: interrupted chains) --------
    def fsck_and_repair(self):
        """Audit the store's frames for torn state and mend what the
        policy knows how to mend (:mod:`repro.kvstore.fsck`).  Host-side
        and quiesced by construction — recovery runs *between* serving
        calls.  Returns the pre-repair :class:`~repro.kvstore.fsck.
        FsckReport`; the applied-repair count accumulates on
        ``repairs_applied``."""
        from ..kvstore import fsck

        h = self.kv.neighborhood
        if self.resize is not None:
            report = fsck.check_invariants(resize=self.resize,
                                           neighborhood=h)
            if not report.clean:
                self.resize, actions = fsck.repair_resize(
                    self.resize, report, neighborhood=h)
                self.repairs_applied += len(actions)
        else:
            report = fsck.check_invariants(self.keys, self.vals,
                                           neighborhood=h)
            if not report.clean:
                self.keys, self.vals, actions = fsck.repair(
                    self.keys, self.vals, report, neighborhood=h)
                self.repairs_applied += len(actions)
        return report

    def set_reliable(self, key: int, value: Sequence[int],
                     faults: Optional["faults_mod.FaultPlan"] = None
                     ) -> Tuple[int, int]:
        """One SET that *survives interrupted chains*: issue, and on any
        non-terminal outcome run fsck + repair and re-issue with bounded
        exponential backoff (``backoff_base_s`` doubling up to
        ``backoff_cap_s``, at most ``retry_budget`` re-issues).

        ``faults`` (a scalar :class:`repro.core.faults.FaultPlan`) arms
        the *first* attempt's writer chain — the recovery drill: the
        fault fires once (a chain is not re-killed by the same crash),
        every retry runs clean against whatever torn state the first
        attempt left.  Injection needs the steady-state path; if a
        resize is in flight the plan is not armed (lap faults go through
        ``sharded_resize(faults=...)`` instead).

        Returns ``(status, attempts)`` on success; raises
        :class:`ChainInterrupted` when the budget is exhausted — with
        the store *fsck-clean* (the failed retries never leave torn
        state behind; that is the half of the §5.6 claim a dead driver
        cannot test)."""
        import jax.numpy as jnp

        kv_store.ShardedKV.check_key(key)
        n_shards = self.kv.n_shards
        qk = np.zeros((n_shards, 1), np.int32)
        qk[0, 0] = key
        qv = np.zeros((n_shards, 1, self.kv.val_words), np.int32)
        qv[0, 0, :len(value)] = value

        plan = None
        if faults is not None and self.resize is None:
            rows = np.full((n_shards, 1, faults_mod.FIELDS), faults_mod.NONE,
                           np.int32)
            rows[0, 0] = np.asarray(faults.as_rows(), np.int32)
            plan = faults_mod.FaultPlan.from_row(jnp.asarray(rows))

        last_status = 0
        attempts = 0
        for attempt in range(self.retry_budget + 1):
            if attempt:
                time.sleep(min(self.backoff_base_s * (2 ** (attempt - 1)),
                               self.backoff_cap_s))
            kwargs = {} if plan is None else {"faults": plan}
            plan = None          # the injected fault fires exactly once
            res = self.set_many(qk, qv, **kwargs)
            attempts = attempt + 1
            last_status = int(np.asarray(res.status)[0, 0])
            if last_status in (programs.SET_UPDATED, programs.SET_INSERTED,
                               programs.SET_DISPLACED):
                return last_status, attempts
            # non-terminal (or needs-resize with auto_resize off): the
            # chain was interrupted — audit, mend, re-issue
            self.fsck_and_repair()
        report = self.fsck_and_repair()
        raise ChainInterrupted(key, attempts, last_status, report.clean)
