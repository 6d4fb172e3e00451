"""Sharded KV store over the device mesh with the paper's three get paths.

* ``redn``      — §5.2: the request is routed to the owner shard, the
                  *offload chain* — an actual chain VM program
                  (:class:`repro.core.programs.HopscotchShardServer`,
                  executed by ``ChainEngine.run_many``) — runs there, the
                  value comes back: **1 RTT**, no host involvement.
* ``one_sided`` — FaRM/Pilaf style: RDMA READ of the H-bucket neighborhood
                  metadata, client-side match, RDMA READ of the value:
                  **2 RTTs**, no host involvement, 6x metadata overhead
                  (neighborhood reads) exactly as §5.2.2 describes.
* ``two_sided`` — RPC: request routed to the owner, the *host* performs the
                  lookup (the plain ``hopscotch.lookup`` function — which
                  doubles as the bit-exact oracle for the chain program),
                  response routed back: 1 RTT + host service time (the
                  contended resource in §5.5).

All three return identical values on served requests (tested); they differ
in collective phases and in which resource does the work — which is what
the fidelity benchmarks price.

Writes are chain-offloaded too — *all* of them: :func:`sharded_set`
routes SET batches to the owner shards, where the pre-posted *writer*
chain (:func:`repro.core.programs.build_hopscotch_writer`) match-updates
or CAS-claims buckets against the **authoritative device arrays**, and
any ``SET_NEEDS_DISPLACEMENT`` rows escalate to the *displacer* chain
(:func:`repro.core.programs.build_hopscotch_displacer`), which runs the
bounded hopscotch bubble on-device.  The host tables are pure oracles;
no SET path touches them.

Every path returns a :class:`GetResult` (sets: :class:`SetResult`,
deletes: :class:`DeleteResult`) whose per-request ``ok`` mask says
whether the response is authoritative: a request dropped at the
transport's capacity limit, or deferred by the per-client admission
stage (``sharded_get(..., isolation=Admission(...))``), has ``ok=False``
and must never be read as a key miss (or a failed set).

:func:`sharded_get` and :func:`sharded_set` are the *only* entry
points: admission control rides the ``isolation=`` keyword, and passing
a :class:`ResizeState` instead of device arrays selects the double-frame
mid-migration arm.  The old per-mode names
(``sharded_get_isolated`` / ``sharded_get_migrating`` /
``sharded_set_migrating``) survive as thin :class:`DeprecationWarning`
shims.

The full Memcached lifecycle is device-authoritative too:
:func:`sharded_delete` runs the *deleter* chain
(:func:`repro.core.programs.build_hopscotch_deleter`) — re-read-comparand
CAS vacates the key word, then zeroes the stale row — and
:func:`sharded_set` with ``exp=``/``deadlines=`` stamps per-bucket TTL
deadline words that the TTL-aware GET server compares on-device
(expired hit ⇒ miss, no host help).  :func:`sharded_sweep` drives the
CLOCK-style *sweeper* chain (:func:`repro.core.programs.
build_clock_sweeper`) over a window of buckets, reclaiming expired
entries as a background writer lane.

The store also *grows* online (§5.6 "resize while serving"):
:func:`begin_resize` opens a doubled frame, :func:`sharded_resize`
drives the migrator chain (:func:`repro.core.programs.
build_hopscotch_migrator`) in quanta, and the resize arms of
:func:`sharded_get` / :func:`sharded_set` keep every get and set
authoritative mid-growth until :func:`finish_resize` cuts over — no
request is dropped or misrouted by the migration, and none of it
involves the host.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import warnings
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..core import faults as faults_mod
from ..core import machine
from ..core import programs
from ..rdma import isolation, transport
from . import hopscotch

# the unified entry points take an `isolation=` keyword, which shadows
# the module inside their bodies — this alias keeps it reachable there
isolation_mod = isolation

_SHARD_MULT = 0x9E3779B1


def shard_of(key, n_shards: int):
    """Owner shard of a key — identical for python ints and jnp arrays.

    Both paths normalize to uint32 before the xor/shift/multiply: a python
    int is masked to its 32-bit pattern first (negative or >= 2**32 keys
    previously diverged from the device path, routing the same key to two
    different shards depending on which side hashed it).
    """
    if isinstance(key, (int, np.integer)):
        k = int(key) & 0xFFFFFFFF
        k ^= k >> 13
        return (k * _SHARD_MULT & 0xFFFFFFFF) % n_shards
    k = key.astype(jnp.uint32)
    return (((k ^ (k >> 13)) * jnp.uint32(_SHARD_MULT))
            % jnp.uint32(n_shards)).astype(jnp.int32)


def keys_homed_at(bucket: int, count: int, n_buckets: int, start: int = 1,
                  n_shards: Optional[int] = None, shard: int = 0):
    """Brute-force enumerate 24-bit keys whose home bucket is ``bucket``
    (optionally also pinned to one owner shard).

    The engineered-collision helper the displacement tests and
    benchmarks share: hopscotch displacement only triggers when a whole
    neighborhood fills, so scenarios are built from keys with chosen
    homes.  Centralized here (the one module that sees both the bucket
    hash and the shard hash) so a hashing change cannot silently strand
    the scenarios on wrong buckets.
    """
    out, k = [], start
    while len(out) < count:
        if k > 0xFFFFFF:
            # never hand out keys past the id space: the chain truncates
            # to 24 bits while the host oracle would hash the full int —
            # exactly the parity split this helper exists to prevent
            raise ValueError(
                f"ran out of 24-bit keys homed at bucket {bucket} "
                f"(found {len(out)}/{count} from start={start})")
        if (int(hopscotch.bucket_of(k, n_buckets)) == bucket
                and (n_shards is None
                     or int(shard_of(k, n_shards)) == shard)):
            out.append(k)
        k += 1
    return out


def _check_key_batch(arr, *, what: str, allow_zero: bool, live=None):
    """Host-side 24-bit key validation for the batched paths.

    Keys live in the chain ISA's id space (``opcode:8 | id:24`` — see
    :meth:`ShardedKV.check_key`): a wider key's top byte would decode as
    an opcode once a probe READ lands it on a WR's control word, and a
    negative key aliases some other key's bit pattern.  The batched
    entry points are eager (they jit internally), so concrete inputs are
    validated here; traced inputs (callers who wrapped the store in
    their own jit) skip the check — garbage-in keys then surface as
    ordinary misses/claims of their masked alias, never as decoded
    opcodes, because the scatter path truncates to the id field anyway.
    Rows masked dead by an admission stage (``live=False``) are never
    dispatched, so a sentinel there is legal and skipped.
    """
    if isinstance(arr, jax.core.Tracer) or isinstance(live, jax.core.Tracer):
        return
    a = np.asarray(arr)
    lo = 0 if allow_zero else 1
    bad = (a < lo) | (a > 0xFFFFFF)
    if live is not None:
        bad &= np.asarray(live).astype(bool)
    if bad.any():
        offender = a[bad].ravel()[0]
        raise ValueError(
            f"{what} keys are 24-bit chain ids"
            f"{' (0 = unused slot)' if allow_zero else ''}; "
            f"got {int(offender):#x}")


class GetResult(NamedTuple):
    """Distributed get outcome. ``found``/``values`` are authoritative only
    where ``ok`` is True — a False row was dropped (capacity) or deferred
    (admission), *not* a miss."""
    found: jnp.ndarray      # (S, B) bool
    values: jnp.ndarray     # (S, B, V) int32
    ok: jnp.ndarray         # (S, B) bool — response authoritative
    dropped: jnp.ndarray    # (S,) int32 — capacity drops at the source
    deferred: jnp.ndarray   # (S,) int32 — admission-deferred at the source

    def __repr__(self):
        # summarized, not the raw-array tuple dump — results show up in
        # assertion diffs and logs where "37/64 found" is the question.
        # Traced instances (inside a caller's jit) can't be summarized.
        if isinstance(self.found, jax.core.Tracer):
            return (f"GetResult(traced: found={self.found}, "
                    f"ok={self.ok})")
        found, ok = np.asarray(self.found), np.asarray(self.ok)
        return (f"GetResult(found {int(found.sum())}/{found.size}, "
                f"ok {int(ok.sum())}/{ok.size}, "
                f"dropped={int(np.asarray(self.dropped).sum())}, "
                f"deferred={int(np.asarray(self.deferred).sum())})")


def serving_mesh(n_shards: int, axis: str = "kv") -> Mesh:
    """A 1-D mesh with one device per shard.  Raises when JAX sees fewer
    devices than shards: a smaller mesh would silently serve every shard
    from fewer chips than the deployment asked for."""
    devices = jax.devices()
    if len(devices) < n_shards:
        raise ValueError(
            f"n_shards={n_shards} needs one device per shard, but JAX sees "
            f"{len(devices)} {devices[0].platform} device(s)")
    return Mesh(np.array(devices[:n_shards]), (axis,))


@dataclasses.dataclass
class ShardedKV:
    """Host handle: per-shard hopscotch tables + device arrays."""
    tables: list                       # [HopscotchTable] * n_shards
    n_shards: int
    val_words: int
    neighborhood: int

    @classmethod
    def build(cls, n_shards: int, buckets_per_shard: int, val_words: int,
              neighborhood: int = 8) -> "ShardedKV":
        tables = [hopscotch.make_table(buckets_per_shard, val_words,
                                       neighborhood)
                  for _ in range(n_shards)]
        return cls(tables, n_shards, val_words, neighborhood)

    @staticmethod
    def check_key(key: int):
        """Keys live in the chain ISA's 24-bit id space (the CAS-convertible
        control word packs ``opcode:8 | id:24``) — a wider key's top byte
        would decode as an opcode once a probe READ lands it on a WR's ctrl
        word, and key 0 is the EMPTY bucket marker."""
        if not 0 < key <= 0xFFFFFF:
            raise ValueError(f"keys are 24-bit chain ids, got {key:#x}")

    def set(self, key: int, value: Sequence[int]) -> bool:
        """Host-side set (bootstrap/tests only; serving goes through the
        chain-offloaded :func:`sharded_set`, displacement included)."""
        self.check_key(key)
        return self.tables[int(shard_of(key, self.n_shards))].insert(
            key, value)

    def device_arrays(self, sharding=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """The tables as ``(S, B)`` keys and ``(S, B, V)`` values, placed
        with ``sharding`` (one shard per device of a serving mesh) or on
        the default device."""
        keys = np.stack([t.keys for t in self.tables])
        vals = np.stack([t.values for t in self.tables])
        return jax.device_put(keys, sharding), jax.device_put(vals, sharding)

    def sync_from_device(self, keys, vals):
        """Refresh the host tables *from* the authoritative device arrays
        (chain-offloaded sets mutate only the device state; the host copy
        is a debugging/verification mirror)."""
        kk, vv = np.asarray(keys), np.asarray(vals)
        for s, t in enumerate(self.tables):
            t.keys = kk[s].copy()
            t.values = vv[s].copy()


# ---------------------------------------------------------------------------
# the three get paths (shard_map bodies; local table slice has leading dim 1)
# ---------------------------------------------------------------------------

def _redn_get_local(keys, vals, queries, live, *, n_shards, capacity, axis,
                    neighborhood, val_words):
    """RedN path: the pre-posted chain VM program executes at the owner —
    1 RTT, the hash probing done by verbs, not the host."""
    q = queries.reshape(-1)
    dest = shard_of(q, n_shards)
    n_buckets = keys.shape[1]
    srv = programs.build_hopscotch_server(n_buckets, val_words, neighborhood)
    state = srv.device_state(keys[0], vals[0])
    payload = srv.device_payloads(q, hopscotch.bucket_of(q, n_buckets))
    resp, ok = transport.triggered_chain_engine(
        srv.engine, state, srv.recv_wq, srv.resp_region, srv.resp_words,
        payload, dest, n_shards, capacity, axis, live.reshape(-1))
    return (resp[:, 0] > 0)[None], resp[None, :, 1:], ok[None]


def _redn_get_ttl_local(keys, vals, exp, now, queries, live, *, n_shards,
                        capacity, axis, neighborhood, val_words):
    """TTL-aware redn path: the server chain built with ``ttl=True``
    ADDs the client's negated clock onto each probed deadline and gates
    the response write on the Calc-verb compare — an expired hit
    quiesces exactly like a miss, with the deadline compared on device
    (bit-exact with :func:`repro.kvstore.hopscotch.lookup_ttl`)."""
    q = queries.reshape(-1)
    dest = shard_of(q, n_shards)
    n_buckets = keys.shape[1]
    srv = programs.build_hopscotch_server(n_buckets, val_words,
                                          neighborhood, ttl=True)
    state = srv.device_state(keys[0], vals[0], exp[0])
    payload = srv.device_payloads(q, hopscotch.bucket_of(q, n_buckets),
                                  now[0])
    resp, ok = transport.triggered_chain_engine(
        srv.engine, state, srv.recv_wq, srv.resp_region, srv.resp_words,
        payload, dest, n_shards, capacity, axis, live.reshape(-1))
    return (resp[:, 0] > 0)[None], resp[None, :, 1:], ok[None]


def _one_sided_get_local(keys, vals, queries, live, *, n_shards, capacity,
                         axis, neighborhood, val_words):
    """FaRM-style: READ the neighborhood metadata, match locally, READ the
    value — 2 RTTs, and H-fold metadata amplification."""
    q = queries.reshape(-1)
    n_buckets = keys.shape[1]
    dest = shard_of(q, n_shards)
    home = hopscotch.bucket_of(q, n_buckets)
    lv = live.reshape(-1)

    # RTT 1: one READ of the H-bucket neighborhood (metadata; this is the
    # 6x-amplified read FaRM pays — H contiguous buckets per request)
    remote_window = jnp.stack(
        [jnp.roll(keys[0], -d) for d in range(neighborhood)], axis=1)
    window, ok = transport.one_sided_read(remote_window, dest, home, axis,
                                          n_shards, capacity, lv)  # (B, H)
    hit = window == q[:, None].astype(window.dtype)
    # a query of EMPTY (0) compares equal to every empty bucket in the
    # window — mask it or it ghost-hits with garbage-zero values
    found = jnp.any(hit, axis=1) & (q != hopscotch.EMPTY)
    slot = jnp.argmax(hit, axis=1).astype(jnp.int32)
    row = (home + slot) % n_buckets

    # RTT 2: fetch the value row (same dest/live -> same ok mask)
    v, _ = transport.one_sided_read(vals[0], dest, row, axis, n_shards,
                                    capacity, lv)
    v = v * found[:, None].astype(v.dtype)
    return found[None], v[None], ok[None]


def _two_sided_get_local(keys, vals, queries, live, *, n_shards, capacity,
                         axis, neighborhood, val_words):
    """RPC: identical wire pattern to redn, but the lookup runs as a plain
    host function (the benchmarks price the host service + contention).
    ``hopscotch.lookup`` here is the same function the tests use as the
    chain program's bit-exact oracle."""
    q = queries.reshape(-1)
    dest = shard_of(q, n_shards)
    payload = q[:, None]

    def host_lookup(reqs):
        found, v = hopscotch.lookup(keys[0], vals[0], reqs[:, 0],
                                    neighborhood)
        return jnp.concatenate([found[:, None].astype(jnp.int32), v], axis=1)

    resp, ok = transport.triggered_chain(
        host_lookup, payload, dest, n_shards, capacity, axis, val_words + 1,
        live.reshape(-1))
    return (resp[:, 0] > 0)[None], resp[None, :, 1:], ok[None]


_PATHS = dict(redn=_redn_get_local, one_sided=_one_sided_get_local,
              two_sided=_two_sided_get_local)

# collective phases per path (the fidelity latency model reads these):
#   redn: dispatch+combine (1 RTT); one_sided: 2x(dispatch+combine);
#   two_sided: 1 RTT + host service
RTTS = dict(redn=1, one_sided=2, two_sided=1)
HOST_SERVICE = dict(redn=False, one_sided=False, two_sided=True)


class Admission(NamedTuple):
    """Per-client token-bucket admission parameters for the unified
    :func:`sharded_get` (the §5.5 isolation stage, previously the
    separate ``sharded_get_isolated`` entry point).

    ``clients``: (S, B) int32 global client/QP ids aligned with the
    queries; ``bucket``: the :class:`repro.rdma.isolation.BucketState`
    carried across calls.  Passing ``isolation=Admission(...)`` admits
    each request against its client's bucket first — deferred rows are
    never dispatched, surface ``ok=False``, and are counted per shard —
    and makes the call return ``(GetResult, new BucketState)``.
    """
    clients: jnp.ndarray
    bucket: isolation.BucketState
    now_us: float
    rate_per_us: float
    burst: float


def _bind_args(fname: str, names: Tuple[str, ...], args, kwargs) -> dict:
    """Map a dispatcher's ``*args`` onto the selected implementation's
    parameter names (the unified entry points accept both spellings'
    positional orders, chosen by the state argument's type)."""
    if len(args) > len(names):
        raise TypeError(
            f"{fname}: too many positional arguments "
            f"({len(args)} given, at most {len(names)}: {names})")
    bound = dict(kwargs)
    for name, val in zip(names, args):
        if name in bound:
            raise TypeError(
                f"{fname}: got multiple values for argument '{name}'")
        bound[name] = val
    return bound


def sharded_get(mesh: Mesh, axis: str, table_or_resize_state, *args,
                isolation: Optional[Admission] = None, **kwargs):
    """Batched distributed get — the one serving entry point.

    The third argument selects the store's mode:

    * device ``keys`` array (steady state) — followed by ``(vals,
      queries, method="redn", neighborhood=8, capacity=None,
      live=None, exp=None, now=None)``; passing a per-bucket deadline
      column ``exp`` (S, n) plus the clock ``now`` serves TTL-aware
      gets (chain path only): an expired hit answers as a miss.
    * a :class:`ResizeState` (mid-growth) — followed by ``(queries,
      neighborhood=8, capacity=None, live=None)``; served from the
      double frame with the watermark-gated second probe.

    ``live`` (optional, (S, B) bool) is an admission mask — False
    requests are never dispatched and come back with ``ok=False`` and a
    ``deferred`` count.  ``isolation=Admission(...)`` runs the §5.5
    per-client token-bucket stage to *produce* that mask (composed with
    any explicit ``live``) and returns ``(GetResult, new BucketState)``
    instead of a bare :class:`GetResult`.
    """
    if isinstance(table_or_resize_state, ResizeState):
        bound = _bind_args(
            "sharded_get", ("queries", "neighborhood", "capacity", "live"),
            args, kwargs)
        run = functools.partial(_get_resize, mesh, axis,
                                table_or_resize_state)
    else:
        bound = _bind_args(
            "sharded_get", ("vals", "queries", "method", "neighborhood",
                            "capacity", "live", "exp", "now"),
            args, kwargs)
        run = functools.partial(_get_table, mesh, axis,
                                table_or_resize_state)
    if isolation is None:
        return run(**bound)
    adm = isolation
    bucket, admitted = isolation_mod.admit(
        adm.bucket, adm.clients.reshape(-1), adm.now_us, adm.rate_per_us,
        adm.burst)
    live = admitted.reshape(bound["queries"].shape)
    if bound.get("live") is not None:
        live = live & bound["live"]
    bound["live"] = live
    return run(**bound), bucket


def _get_table(mesh: Mesh, axis: str, keys: jnp.ndarray, vals: jnp.ndarray,
               queries: jnp.ndarray, method: str = "redn",
               neighborhood: int = 8, capacity: Optional[int] = None,
               live: Optional[jnp.ndarray] = None,
               exp: Optional[jnp.ndarray] = None, now=None) -> GetResult:
    """Steady-state get (see :func:`sharded_get`).
    queries: (S, B_local) int32 (dim 0 sharded)."""
    if (exp is None) != (now is None):
        raise ValueError("TTL gets need both exp and now (or neither): "
                         f"exp given={exp is not None}, "
                         f"now given={now is not None}")
    if exp is not None and method != "redn":
        raise ValueError("TTL-aware serving is chain-only: the deadline "
                         "compare is a Calc verb in the server chain "
                         f"(method='redn'), got method={method!r}")
    _check_key_batch(queries, what="query", allow_zero=True, live=live)
    n_shards = mesh.shape[axis]
    b_local = queries.shape[1]
    # `capacity or b_local` would silently turn an explicit capacity=0
    # into the default; 0 is a legal (drop-everything) limit
    capacity = b_local if capacity is None else capacity
    if live is None:
        live = jnp.ones(queries.shape, jnp.bool_)
    if capacity == 0:
        # nothing can be dispatched: every live request is a capacity drop
        return GetResult(
            found=jnp.zeros(queries.shape, jnp.bool_),
            values=jnp.zeros(queries.shape + (vals.shape[-1],), vals.dtype),
            ok=jnp.zeros(queries.shape, jnp.bool_),
            dropped=jnp.sum(live, axis=1, dtype=jnp.int32),
            deferred=jnp.sum(~live, axis=1, dtype=jnp.int32))

    if exp is not None:
        mapped = _mapped_get_ttl(mesh, axis, n_shards, capacity,
                                 neighborhood, vals.shape[-1])
        nows = jnp.full((keys.shape[0],), now, jnp.int32)
        return GetResult(*mapped(keys, vals, exp, nows, queries, live))
    mapped = _mapped_get(mesh, axis, method, n_shards, capacity,
                         neighborhood, vals.shape[-1])
    return GetResult(*mapped(keys, vals, queries, live))


# Compile caches for the shard_map serving bodies, keyed on *mesh
# geometry* (axis names, shape, device ids) rather than the Mesh object:
# an lru_cache keyed on the Mesh itself retained every test's mesh — and
# through it the devices' buffers — for the process lifetime, and two
# equal-geometry meshes each paid a full re-trace.  One entry per
# distinct geometry (the first mesh of a geometry is captured by the
# compiled closure; later equal meshes share it) — LRU-bounded, because
# a long-lived service cycling through capacities / writer counts /
# geometries would otherwise pin every compiled executable it ever
# built (regression-tested in tests/test_multiwriter.py).  Evicted
# entries only cost a re-trace on the next same-key call.
_MAPPED_CACHE: "collections.OrderedDict" = collections.OrderedDict()
_MAPPED_CACHE_LIMIT = 64
_MAPPED_CACHE_STATS = {"hits": 0, "misses": 0, "evictions": 0}


def _mapped_cache_get(key):
    fn = _MAPPED_CACHE.get(key)
    if fn is not None:
        _MAPPED_CACHE.move_to_end(key)
        _MAPPED_CACHE_STATS["hits"] += 1
    return fn


def _mapped_cache_put(key, fn):
    _MAPPED_CACHE_STATS["misses"] += 1
    _MAPPED_CACHE[key] = fn
    while len(_MAPPED_CACHE) > _MAPPED_CACHE_LIMIT:
        _MAPPED_CACHE.popitem(last=False)
        _MAPPED_CACHE_STATS["evictions"] += 1
    return fn


def mapped_cache_stats() -> dict:
    """Snapshot of the serving-body compile cache: size/limit plus
    cumulative hit/miss/eviction counters."""
    return {"size": len(_MAPPED_CACHE), "limit": _MAPPED_CACHE_LIMIT,
            **_MAPPED_CACHE_STATS}


def _mesh_fingerprint(mesh: Mesh):
    # device ids repeat across platforms (a described TPU topology and the
    # host CPU both have a device 0), so the platform is part of the key
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape),
            tuple((d.platform, d.id) for d in mesh.devices.flat))


def _mapped_get(mesh: Mesh, axis: str, method: str, n_shards: int,
                capacity: int, neighborhood: int, val_words: int):
    """Compile-cache the sharded get per (mesh geometry, path geometry):
    the shard_map body is built once and jitted, so repeated serving
    calls reuse the compiled step instead of re-tracing the chain VM
    loop per call (and eager/jit callers cannot disagree about trace
    context)."""
    key = ("get", _mesh_fingerprint(mesh), axis, method, n_shards,
           capacity, neighborhood, val_words)
    cached = _mapped_cache_get(key)
    if cached is not None:
        return cached
    path = functools.partial(
        _PATHS[method], n_shards=n_shards, capacity=capacity, axis=axis,
        neighborhood=neighborhood, val_words=val_words)

    def body(keys, vals, queries, live):
        found, v, ok = path(keys, vals, queries, live)
        deferred = jnp.sum(~live, dtype=jnp.int32).reshape(1)
        dropped = (jnp.sum(live, dtype=jnp.int32)
                   - jnp.sum(ok, dtype=jnp.int32)).reshape(1)
        return found, v, ok, dropped, deferred

    spec = P(axis)
    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec, spec),
        out_specs=(spec, spec, spec, spec, spec), check_vma=False))
    return _mapped_cache_put(key, fn)


def _mapped_get_ttl(mesh: Mesh, axis: str, n_shards: int, capacity: int,
                    neighborhood: int, val_words: int):
    """Compile-cache for the TTL-aware redn get (its body takes the
    deadline column and the replicated clock as two more sharded
    inputs; see :func:`_mapped_get`)."""
    key = ("get-ttl", _mesh_fingerprint(mesh), axis, n_shards, capacity,
           neighborhood, val_words)
    cached = _mapped_cache_get(key)
    if cached is not None:
        return cached
    path = functools.partial(
        _redn_get_ttl_local, n_shards=n_shards, capacity=capacity,
        axis=axis, neighborhood=neighborhood, val_words=val_words)

    def body(keys, vals, exp, nows, queries, live):
        found, v, ok = path(keys, vals, exp, nows, queries, live)
        deferred = jnp.sum(~live, dtype=jnp.int32).reshape(1)
        dropped = (jnp.sum(live, dtype=jnp.int32)
                   - jnp.sum(ok, dtype=jnp.int32)).reshape(1)
        return found, v, ok, dropped, deferred

    spec = P(axis)
    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(spec,) * 6, out_specs=(spec,) * 5,
        check_vma=False))
    return _mapped_cache_put(key, fn)


def sharded_get_isolated(mesh: Mesh, axis: str, keys: jnp.ndarray,
                         vals: jnp.ndarray, queries: jnp.ndarray,
                         clients: jnp.ndarray, bucket: isolation.BucketState,
                         now_us: float, rate_per_us: float, burst: float,
                         **kwargs) -> Tuple[GetResult, isolation.BucketState]:
    """Deprecated spelling of the §5.5 isolated get — now
    ``sharded_get(..., isolation=Admission(...))``.  Thin shim, bit-exact
    with the unified path (tested)."""
    warnings.warn(
        "sharded_get_isolated is deprecated: call sharded_get(mesh, axis, "
        "keys, vals, queries, isolation=Admission(clients, bucket, now_us, "
        "rate_per_us, burst)) instead",
        DeprecationWarning, stacklevel=2)
    return sharded_get(
        mesh, axis, keys, vals, queries,
        isolation=Admission(clients, bucket, now_us, rate_per_us, burst),
        **kwargs)


# ---------------------------------------------------------------------------
# the chain-offloaded SET path (§3.5: the device structure is the source
# of truth; update, insert, and displacement all execute on-chain)
# ---------------------------------------------------------------------------

def _guarded_step(run_one, budget, run_one_faulted=None):
    """Scan step that skips the chain VM entirely for the window's
    zero-padded slots (key 0: capacity padding and non-dispatched
    rows).  Per-slot lax.cond is safe here — the scan body contains
    no collectives, unlike the dispatch/combine around it, so shards
    may branch independently; batching the whole escalation stage
    behind a global `any(live)` would put collectives under a cond.
    A padded slot's run is a proven no-op (status 0, carry
    unchanged), so skipping it is bit-identical and keeps
    steady-state serving from paying a quiesce-run per dead slot.

    Generic over the carry arity: ``run_one(*carry, payload, budget)
    -> (status, *carry)`` — the writer/displacer thread ``(keys,
    vals)``, the resize migrator threads both frames.

    With ``run_one_faulted`` the returned step consumes ``(payload,
    fault_row)`` tuples (the transport's ``faults=`` wire format) and
    arms each live slot's chain with its unpacked
    :class:`repro.core.faults.FaultPlan`.  Dead (key-0) slots skip the
    chain — and therefore the fault — entirely: a zero-padded window
    slot's fault columns are zeroed by the dispatch scatter, and a
    fault with nothing to execute against is a non-event, exactly like
    a WQE corruption on a QP nobody posted to.
    """
    def live_slot(op):
        return run_one(*op[:-1], op[-1], budget)

    def dead_slot(op):
        return (jnp.zeros((), jnp.int32),) + tuple(op[:-1])

    if run_one_faulted is None:
        def step(carry, pay):
            out = jax.lax.cond(pay[0] != hopscotch.EMPTY, live_slot,
                               dead_slot, tuple(carry) + (pay,))
            return tuple(out[1:]), out[0][None]
        return step

    def live_slot_f(op):
        plan = faults_mod.FaultPlan.from_row(op[-1])
        return run_one_faulted(*op[:-2], op[-2], budget, plan)

    def dead_slot_f(op):
        return (jnp.zeros((), jnp.int32),) + tuple(op[:-2])

    def step_f(carry, xs):
        pay, frow = xs
        out = jax.lax.cond(pay[0] != hopscotch.EMPTY, live_slot_f,
                           dead_slot_f, tuple(carry) + (pay, frow))
        return tuple(out[1:]), out[0][None]
    return step_f


class WriterFaultConflict(ValueError):
    """``sharded_set(..., n_writers=N, faults=...)`` — the two arguments
    are mutually exclusive, and silently dropping either would run a
    different experiment than the caller asked for.  FaultPlan rows
    address a single chain's WQ layout, which the racing writer group
    does not share; run the fault sweep single-writer, or the race
    un-faulted (composing them is the ROADMAP's open item)."""

    def __init__(self, n_writers: int):
        self.n_writers = int(n_writers)
        super().__init__(
            f"n_writers={n_writers} and faults=... are mutually "
            f"exclusive: FaultPlan rows address one chain's WQ layout, "
            f"which the racing writer group does not share")


def _mutation_repr(name: str, result) -> str:
    """Shared summary ``__repr__`` for the mutation results (SetResult /
    DeleteResult): a status histogram by *name* (hopscotch.STATUS_NAMES),
    not a raw int32 array — "SET_INSERTED=30, SET_NEEDS_RESIZE=2" is
    what a failing test or a log line actually needs to say.  Traced
    instances (inside a caller's jit) can't be summarized."""
    if isinstance(result.status, jax.core.Tracer):
        return (f"{name}(traced: status={result.status}, "
                f"ok={result.ok})")
    st, ok = np.asarray(result.status), np.asarray(result.ok)
    codes, counts = np.unique(st[ok.astype(bool)], return_counts=True)
    hist = ", ".join(f"{hopscotch.status_name(c)}={n}"
                     for c, n in zip(codes.tolist(), counts.tolist()))
    return (f"{name}({hist or 'no served rows'}, "
            f"ok {int(ok.sum())}/{ok.size}, "
            f"applied={int(np.asarray(result.applied).sum())}, "
            f"dropped={int(np.asarray(result.dropped).sum())}, "
            f"deferred={int(np.asarray(result.deferred).sum())})")


class SetResult(NamedTuple):
    """Distributed set outcome.  ``status`` is authoritative only where
    ``ok`` is True (a False row was dropped/deferred, status 0); values:
    ``SET_UPDATED`` (1), ``SET_INSERTED`` (2), ``SET_DISPLACED`` (4 —
    the displacer bubbled a slot into the neighborhood and claimed it),
    or ``SET_NEEDS_RESIZE`` (5 — the bounded search/bubble failed;
    nothing committed, the table needs to grow).
    ``SET_NEEDS_DISPLACEMENT`` (3) is internal-only — the fast writer's
    cue to the displacer stage; every such row resolves to 1/2/4/5
    within the same call (the escalation re-dispatch provably cannot
    drop), so callers never observe it.  ``applied`` acks the rows the
    device arrays absorbed."""
    status: jnp.ndarray     # (S, B) int32 — the path taken per request
    applied: jnp.ndarray    # (S, B) bool — committed to the device arrays
    ok: jnp.ndarray         # (S, B) bool — response authoritative
    dropped: jnp.ndarray    # (S,) int32
    deferred: jnp.ndarray   # (S,) int32

    def __repr__(self):
        return _mutation_repr("SetResult", self)


class DeleteResult(NamedTuple):
    """Distributed delete outcome.  ``status`` is ``DEL_DELETED`` (9 —
    the deleter chain's vacate CAS retired the bucket) or ``DEL_MISS``
    (10 — no resident with that key; deleting an absent key is a
    success of a different color, as in Memcached), authoritative only
    where ``ok`` is True.  ``applied`` acks the rows that actually
    vacated a bucket."""
    status: jnp.ndarray     # (S, B) int32
    applied: jnp.ndarray    # (S, B) bool — a bucket was vacated
    ok: jnp.ndarray         # (S, B) bool — response authoritative
    dropped: jnp.ndarray    # (S,) int32
    deferred: jnp.ndarray   # (S,) int32

    def __repr__(self):
        return _mutation_repr("DeleteResult", self)


def _writer_set_local(keys, vals, qk, qv, live, *, n_shards, capacity, axis,
                      neighborhood, val_words, max_steps, max_search,
                      max_moves):
    """Owner-side SET serving: the pre-posted writer chain CAS-claims /
    updates buckets; requests against one shard are serialized so each
    chain observes its predecessors' writes (no host lookup anywhere).

    Rows the fast writer answers ``SET_NEEDS_DISPLACEMENT`` re-run
    through the *displacer* chain as a second stateful stage (same
    dispatch/scan/combine pattern, one more RTT for just those rows):
    the bounded hopscotch bubble executes on-device, so a
    neighborhood-full insert needs no host either.  The escalation
    re-dispatch can never drop: stage-2 live rows are a subset of
    stage-1's admitted rows, and ``rank_within_dest`` ranks only live
    rows, so every stage-2 rank is <= its stage-1 rank < capacity.
    """
    q = qk.reshape(-1)
    dest = shard_of(q, n_shards)
    n_buckets = keys.shape[1]
    lv = live.reshape(-1)
    writer = programs.build_hopscotch_writer(n_buckets, val_words,
                                             neighborhood)
    payload = writer.device_payloads(q, hopscotch.bucket_of(q, n_buckets),
                                     qv.reshape(-1, val_words))

    resp, ok, (nk, nv) = transport.triggered_chain_stateful(
        _guarded_step(writer.run_one, max_steps), (keys[0], vals[0]),
        payload, dest, n_shards, capacity, axis, 1, lv)
    status = resp[:, 0]
    live2 = ok & (status == programs.SET_NEEDS_DISPLACEMENT)

    if neighborhood < 2 or max_search < neighborhood:
        # degenerate geometries the displacer cannot be built for — an
        # H=1 bubble's window [free-H+1, free) is empty, and a search
        # window smaller than the neighborhood (tiny shard, or a
        # caller-chosen bound) probes only already-known-full buckets.
        # Either way an escalated row is unplaceable, which is exactly
        # the bounded oracle's SET_NEEDS_RESIZE answer — resolve it
        # without building a displacer.
        status = jnp.where(live2, jnp.int32(programs.SET_NEEDS_RESIZE),
                           status)
        return status[None], ok[None], nk[None], nv[None]

    # --- escalation: the displacement bubble, still on-chain --------------
    disp = programs.build_hopscotch_displacer(
        n_buckets, val_words, neighborhood, max_search, max_moves)
    payload2 = disp.device_payloads(q, hopscotch.bucket_of(q, n_buckets),
                                    qv.reshape(-1, val_words))
    # the displacer's step budget must cover its full unroll (which
    # grows with max_search/max_moves) — `fuel` is the exact bound, so
    # no tunable geometry can exhaust fuel mid-bubble and misreport a
    # placeable key as needs-resize
    disp_steps = max(max_steps, disp.fuel)
    step2 = _guarded_step(disp.run_one, disp_steps)

    resp2, ok2, (nk, nv) = transport.triggered_chain_stateful(
        step2, (nk, nv), payload2, dest, n_shards, capacity, axis, 1,
        live2)
    status = jnp.where(live2 & ok2, resp2[:, 0], status)
    return status[None], ok[None], nk[None], nv[None]


def _writer_set_local_faulted(keys, vals, qk, qv, live, frows, *, n_shards,
                              capacity, axis, neighborhood, val_words,
                              max_steps, max_search, max_moves):
    """Owner-side SET serving under injected faults — the recovery
    drill's first act.  Same wire pattern as :func:`_writer_set_local`,
    with two deliberate differences:

    * each request's packed fault row rides its payload through
      dispatch (``transport.triggered_chain_stateful(faults=...)``) and
      arms the writer chain for exactly that request
      (``run_one_faulted`` — torn commit), so the fault lands wherever
      the request lands, like a WQE corruption traveling with the WQE;
    * an *armed* row never escalates to the displacer: a killed
      writer's response region still holds the pre-set
      ``SET_NEEDS_DISPLACEMENT`` default, and escalating on it would
      run a clean displacement that silently papers over the fault.
      Armed rows return their (possibly non-terminal) status as-is —
      turning that into fsck + repair + re-issue is the service's job
      (:meth:`repro.rdma.failure.ShardedKVService.set_reliable`).
    """
    q = qk.reshape(-1)
    dest = shard_of(q, n_shards)
    n_buckets = keys.shape[1]
    lv = live.reshape(-1)
    fr = frows.reshape(-1, faults_mod.FIELDS)
    writer = programs.build_hopscotch_writer(n_buckets, val_words,
                                             neighborhood)
    payload = writer.device_payloads(q, hopscotch.bucket_of(q, n_buckets),
                                     qv.reshape(-1, val_words))

    resp, ok, (nk, nv) = transport.triggered_chain_stateful(
        _guarded_step(writer.run_one, max_steps, writer.run_one_faulted),
        (keys[0], vals[0]), payload, dest, n_shards, capacity, axis, 1,
        lv, faults=fr)
    status = resp[:, 0]
    armed = faults_mod.FaultPlan.from_row(fr).active()
    live2 = ok & (status == programs.SET_NEEDS_DISPLACEMENT) & ~armed

    if neighborhood < 2 or max_search < neighborhood:
        status = jnp.where(live2, jnp.int32(programs.SET_NEEDS_RESIZE),
                           status)
        return status[None], ok[None], nk[None], nv[None]

    disp = programs.build_hopscotch_displacer(
        n_buckets, val_words, neighborhood, max_search, max_moves)
    payload2 = disp.device_payloads(q, hopscotch.bucket_of(q, n_buckets),
                                    qv.reshape(-1, val_words))
    disp_steps = max(max_steps, disp.fuel)
    resp2, ok2, (nk, nv) = transport.triggered_chain_stateful(
        _guarded_step(disp.run_one, disp_steps), (nk, nv), payload2,
        dest, n_shards, capacity, axis, 1, live2)
    status = jnp.where(live2 & ok2, resp2[:, 0], status)
    return status[None], ok[None], nk[None], nv[None]


def _mw_set_local(keys, vals, qk, qv, live, *, n_shards, capacity, axis,
                  neighborhood, val_words, max_steps, max_search,
                  max_moves, n_writers):
    """Owner-side SET serving with **racing writer QPs**: each shard's
    receive window is partitioned into laps of ``n_writers`` slots, and a
    lap's requests execute *concurrently* — ``n_writers`` independent
    pre-posted writer lanes over ONE shared table image
    (:func:`repro.core.programs.build_multi_writer_group`), their claim
    CASes genuinely racing under a fair round-robin
    :class:`repro.core.machine.Schedule`.  Laps serialize through the
    scan carry, so the batch is lap-serialized / intra-lap concurrent —
    and by CAS linearizability each lap's outcome equals *some*
    serialized order of its rows, keeping the whole batch equivalent to
    a serialized run (the single-writer path remains the oracle; see the
    2-writer sweep).

    Escalation is unchanged: ``SET_NEEDS_DISPLACEMENT`` rows re-dispatch
    through the single-writer displacer stage (displacement bubbles
    mutate many buckets and stay serialized, like the NIC serializes
    bounded atomics)."""
    q = qk.reshape(-1)
    dest = shard_of(q, n_shards)
    n_buckets = keys.shape[1]
    lv = live.reshape(-1)
    group = programs.build_multi_writer_group(n_buckets, val_words,
                                              neighborhood, n_writers)
    payload = group.device_payloads(q, hopscotch.bucket_of(q, n_buckets),
                                    qv.reshape(-1, val_words))
    # fair interleave: quantum-16 rounds while lanes are busy, then the
    # drain round completes stragglers; fuel bounds any schedule's run
    sched = machine.Schedule.round_robin(n_writers, quantum=16, n_rounds=8)
    gsteps = max(max_steps, group.fuel)

    def group_fn(carry, lap):
        status, nk, nv = group.run_group(*carry, lap, sched, gsteps)
        return (nk, nv), status[:, None]

    resp, ok, (nk, nv) = transport.triggered_chain_group(
        group_fn, (keys[0], vals[0]), payload, dest, n_shards, capacity,
        axis, 1, n_writers, lv)
    status = resp[:, 0]
    live2 = ok & (status == programs.SET_NEEDS_DISPLACEMENT)

    if neighborhood < 2 or max_search < neighborhood:
        status = jnp.where(live2, jnp.int32(programs.SET_NEEDS_RESIZE),
                           status)
        return status[None], ok[None], nk[None], nv[None]

    disp = programs.build_hopscotch_displacer(
        n_buckets, val_words, neighborhood, max_search, max_moves)
    payload2 = disp.device_payloads(q, hopscotch.bucket_of(q, n_buckets),
                                    qv.reshape(-1, val_words))
    disp_steps = max(max_steps, disp.fuel)
    resp2, ok2, (nk, nv) = transport.triggered_chain_stateful(
        _guarded_step(disp.run_one, disp_steps), (nk, nv), payload2,
        dest, n_shards, capacity, axis, 1, live2)
    status = jnp.where(live2 & ok2, resp2[:, 0], status)
    return status[None], ok[None], nk[None], nv[None]


def relocate_exp(old_keys: jnp.ndarray, old_exp: jnp.ndarray,
                 new_keys: jnp.ndarray,
                 req_keys: Optional[jnp.ndarray] = None,
                 req_deadlines: Optional[jnp.ndarray] = None,
                 applied: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Re-derive a per-bucket deadline column after keys moved.

    For every bucket of ``new_keys`` (S, m): carry the deadline its key
    had in ``(old_keys (S, n), old_exp)`` — displacement and migration
    move keys between buckets but never change their expiry — else
    :data:`repro.kvstore.hopscotch.NO_TTL`.  Rows of ``req_keys``
    (S, B) with ``applied`` True then override their key's deadline with
    ``req_deadlines`` (``None`` = NO_TTL — a set without a TTL clears
    any previous one, Memcached's replace-the-TTL semantics); when a
    batch sets the same key twice the *later* request wins, matching the
    owner windows' serialization order (source-major row order).

    The deadline column is commit-layer state: the chains compare and
    reset deadlines in-place for steady-state GET/sweep/delete, and this
    helper re-homes the column when a writer/displacer/migrator chain
    relocated the keys themselves.
    """
    empty = new_keys != hopscotch.EMPTY
    m_old = (new_keys[:, :, None] == old_keys[:, None, :]) & empty[:, :, None]
    has_old = jnp.any(m_old, axis=-1)
    j = jnp.argmax(m_old, axis=-1)
    carried = jnp.take_along_axis(old_exp, j, axis=-1)
    out = jnp.where(has_old, carried, jnp.int32(hopscotch.NO_TTL))
    if req_keys is None:
        return out
    rk = req_keys.reshape(-1)
    ap = (jnp.ones_like(rk, jnp.bool_) if applied is None
          else applied.reshape(-1)) & (rk != hopscotch.EMPTY)
    rd = (jnp.full_like(rk, hopscotch.NO_TTL) if req_deadlines is None
          else req_deadlines.reshape(-1).astype(jnp.int32))
    m_req = ((new_keys[:, :, None] == rk[None, None, :])
             & ap[None, None, :] & empty[:, :, None])
    any_req = jnp.any(m_req, axis=-1)
    idx = jnp.arange(rk.shape[0], dtype=jnp.int32)
    last = jnp.max(jnp.where(m_req, idx[None, None, :], -1), axis=-1)
    return jnp.where(any_req, rd[jnp.clip(last, 0, None)], out)


def sharded_set(mesh: Mesh, axis: str, table_or_resize_state, *args,
                **kwargs):
    """Batched chain-offloaded distributed SET — the one entry point.

    The third argument selects the store's mode:

    * device ``keys`` array (steady state) — followed by ``(vals,
      set_keys, set_vals, neighborhood=8, capacity=None, live=None,
      max_steps=512, max_search=..., max_moves=..., faults=None,
      n_writers=1, exp=None, deadlines=None)``; returns ``(SetResult,
      new_keys, new_vals)``, plus the updated deadline column when
      ``exp`` is given (TTL mode — ``deadlines`` (S, B) stamps each
      applied request's expiry; omitted means no-expiry).
    * a :class:`ResizeState` (mid-growth) — followed by ``(set_keys,
      set_vals, neighborhood=8, capacity=None, live=None,
      max_steps=512, max_search=..., max_moves=...)``;
      watermark-routed over the double frame, returns ``(SetResult,
      new ResizeState)``.
    """
    if isinstance(table_or_resize_state, ResizeState):
        bound = _bind_args(
            "sharded_set", ("set_keys", "set_vals", "neighborhood",
                            "capacity", "live", "max_steps", "max_search",
                            "max_moves"),
            args, kwargs)
        return _set_resize(mesh, axis, table_or_resize_state, **bound)
    bound = _bind_args(
        "sharded_set", ("vals", "set_keys", "set_vals", "neighborhood",
                        "capacity", "live", "max_steps", "max_search",
                        "max_moves", "faults", "n_writers", "exp",
                        "deadlines"),
        args, kwargs)
    return _set_table(mesh, axis, table_or_resize_state, **bound)


def _set_table(mesh: Mesh, axis: str, keys: jnp.ndarray, vals: jnp.ndarray,
               set_keys: jnp.ndarray, set_vals: jnp.ndarray,
               neighborhood: int = 8, capacity: Optional[int] = None,
               live: Optional[jnp.ndarray] = None,
               max_steps: int = 512,
               max_search: int = hopscotch.DEFAULT_MAX_SEARCH,
               max_moves: int = hopscotch.DEFAULT_MAX_MOVES,
               faults: Optional[faults_mod.FaultPlan] = None,
               n_writers: int = 1,
               exp: Optional[jnp.ndarray] = None,
               deadlines: Optional[jnp.ndarray] = None
               ) -> Tuple[SetResult, jnp.ndarray, jnp.ndarray]:
    """Steady-state SET (see :func:`sharded_set`) — displacement included.

    set_keys: (S, B_local) int32 keys in 1..2^24-1 (dim 0 sharded; 0 marks
    an unused slot — never dispatched, never committed, reported
    ``ok=False``/status 0 and excluded from the drop/defer counters;
    wider or negative keys raise); set_vals: (S, B_local, V).
    Each request is routed to its owner shard, where the pre-posted
    **writer chain program** (:func:`repro.core.programs.
    build_hopscotch_writer`) match-updates or CAS-claims a bucket — the
    same 1-RTT wire pattern as the redn get, with the *device arrays as
    the authoritative store*.  Rows the writer reports
    ``SET_NEEDS_DISPLACEMENT`` escalate to the **displacer chain**
    (:func:`repro.core.programs.build_hopscotch_displacer`, bounded by
    ``max_search``/``max_moves``) in a second stateful stage, so every
    SET outcome — update, insert, displacement — is computed by verbs
    against device state; only ``SET_NEEDS_RESIZE`` (table full) leaves
    a request uncommitted.  Returns ``(SetResult, new_keys, new_vals)``;
    the caller must adopt the returned arrays (functional update, like
    any jnp state).

    ``faults`` (optional): a :class:`repro.core.faults.FaultPlan` with
    ``(S, B_local)`` leaves — per-request fault injection into the
    writer stage (armed rows commit torn state and never escalate; see
    :func:`_writer_set_local_faulted`).  The interpreter is the
    authority on fault semantics; recovery is
    :meth:`repro.rdma.failure.ShardedKVService.set_reliable`.

    ``n_writers`` > 1 partitions each shard's receive window into laps
    of ``n_writers`` concurrently-racing writer lanes over the shared
    table (:func:`_mw_set_local`) — same results as the serialized path
    up to lap-internal serialization order (CAS linearizability), same
    ``SetResult`` contract.  Mutually exclusive with ``faults`` (the
    fault format addresses a single chain's WQs; arming one lane of a
    racing group is not yet modeled).
    """
    if n_writers < 1:
        raise ValueError(f"n_writers must be >= 1, got {n_writers}")
    if n_writers > 1 and faults is not None:
        raise WriterFaultConflict(n_writers)
    if deadlines is not None and exp is None:
        raise ValueError("deadlines= stamps per-request expiry into the "
                         "exp column — pass exp= (the store's deadline "
                         "state) alongside it")
    _check_key_batch(set_keys, what="set", allow_zero=True, live=live)
    n_shards = mesh.shape[axis]
    b_local = set_keys.shape[1]
    # the displacer's search window cannot exceed the shard's bucket count
    max_search = min(max_search, int(keys.shape[1]))
    capacity = b_local if capacity is None else capacity
    if live is None:
        live = jnp.ones(set_keys.shape, jnp.bool_)
    real = set_keys != hopscotch.EMPTY
    if capacity == 0:
        zi = jnp.zeros(set_keys.shape, jnp.int32)
        res0 = SetResult(
            status=zi, applied=zi.astype(bool), ok=zi.astype(bool),
            dropped=jnp.sum(live & real, axis=1, dtype=jnp.int32),
            deferred=jnp.sum(~live & real, axis=1, dtype=jnp.int32))
        if exp is not None:
            return res0, keys, vals, exp
        return res0, keys, vals

    mapped = _mapped_set(mesh, axis, n_shards, capacity, neighborhood,
                         vals.shape[-1], max_steps, max_search, max_moves,
                         faulted=faults is not None, n_writers=n_writers)
    if faults is not None:
        status, ok, dropped, deferred, nk, nv = mapped(
            keys, vals, set_keys, set_vals, live, faults.as_rows())
    else:
        status, ok, dropped, deferred, nk, nv = mapped(keys, vals, set_keys,
                                                       set_vals, live)
    applied = ok & ((status == programs.SET_UPDATED)
                    | (status == programs.SET_INSERTED)
                    | (status == programs.SET_DISPLACED))
    result = SetResult(status, applied, ok, dropped, deferred)
    if exp is not None:
        # deadline follow-up is commit-layer state: the writer/displacer
        # chains may have relocated keys, so re-home the column by key
        # and stamp the applied requests' own deadlines
        new_exp = relocate_exp(keys, exp, nk, set_keys, deadlines, applied)
        return result, nk, nv, new_exp
    return result, nk, nv


def _mapped_set(mesh: Mesh, axis: str, n_shards: int, capacity: int,
                neighborhood: int, val_words: int, max_steps: int,
                max_search: int, max_moves: int, faulted: bool = False,
                n_writers: int = 1):
    """Compile-cache the sharded set per (mesh geometry, path geometry),
    like :func:`_mapped_get` — one trace of the writer + displacer scan
    serves every subsequent batch of the same shape.  The faulted
    variant caches separately ("set-faulted") and takes the packed
    fault rows as one more sharded input — fault *parameters* stay
    traced, so a whole cut-point sweep reuses a single compile.  The
    multi-writer variant ("set-mw") swaps the serialized writer stage
    for the racing group (:func:`_mw_set_local`)."""
    key = ("set-faulted" if faulted else
           f"set-mw{n_writers}" if n_writers > 1 else "set",
           _mesh_fingerprint(mesh),
           axis, n_shards, capacity, neighborhood, val_words, max_steps,
           max_search, max_moves)
    cached = _mapped_cache_get(key)
    if cached is not None:
        return cached
    if n_writers > 1 and not faulted:
        path = functools.partial(
            _mw_set_local, n_shards=n_shards, capacity=capacity,
            axis=axis, neighborhood=neighborhood, val_words=val_words,
            max_steps=max_steps, max_search=max_search,
            max_moves=max_moves, n_writers=n_writers)
    else:
        path = functools.partial(
            _writer_set_local_faulted if faulted else _writer_set_local,
            n_shards=n_shards, capacity=capacity, axis=axis,
            neighborhood=neighborhood, val_words=val_words,
            max_steps=max_steps, max_search=max_search,
            max_moves=max_moves)

    if faulted:
        def body(keys, vals, qk, qv, live, frows):
            real = qk != hopscotch.EMPTY
            live = live & real
            status, ok, nk, nv = path(keys, vals, qk, qv, live, frows)
            deferred = jnp.sum(~live & real, dtype=jnp.int32).reshape(1)
            dropped = (jnp.sum(live, dtype=jnp.int32)
                       - jnp.sum(ok, dtype=jnp.int32)).reshape(1)
            return status, ok, dropped, deferred, nk, nv
        n_in = 6
    else:
        def body(keys, vals, qk, qv, live):
            # unused (key-0) slots are inert: no dispatch slot, no counter
            real = qk != hopscotch.EMPTY
            live = live & real
            status, ok, nk, nv = path(keys, vals, qk, qv, live)
            deferred = jnp.sum(~live & real, dtype=jnp.int32).reshape(1)
            dropped = (jnp.sum(live, dtype=jnp.int32)
                       - jnp.sum(ok, dtype=jnp.int32)).reshape(1)
            return status, ok, dropped, deferred, nk, nv
        n_in = 5

    spec = P(axis)
    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(spec,) * n_in, out_specs=(spec,) * 6,
        check_vma=False))
    return _mapped_cache_put(key, fn)


# ---------------------------------------------------------------------------
# the chain-offloaded DELETE path and the CLOCK sweeper (the remaining
# Memcached lifecycle verbs: forget on request, forget on expiry)
# ---------------------------------------------------------------------------

def _del_local(keys, vals, qk, live, *, n_shards, capacity, axis,
               neighborhood, val_words, max_steps):
    """Owner-side DELETE serving: the pre-posted deleter chain matches
    the key across its neighborhood and retires the bucket with the
    re-read-comparand vacate CAS — same 1-RTT wire pattern as the
    writer, no escalation stage (a delete never needs to displace)."""
    q = qk.reshape(-1)
    dest = shard_of(q, n_shards)
    n_buckets = keys.shape[1]
    lv = live.reshape(-1)
    deleter = programs.build_hopscotch_deleter(n_buckets, val_words,
                                               neighborhood)
    payload = deleter.device_payloads(q, hopscotch.bucket_of(q, n_buckets))
    resp, ok, (nk, nv) = transport.triggered_chain_stateful(
        _guarded_step(deleter.run_one, max_steps), (keys[0], vals[0]),
        payload, dest, n_shards, capacity, axis, 1, lv)
    return resp[:, 0][None], ok[None], nk[None], nv[None]


def sharded_delete(mesh: Mesh, axis: str, keys: jnp.ndarray,
                   vals: jnp.ndarray, del_keys: jnp.ndarray,
                   neighborhood: int = 8, capacity: Optional[int] = None,
                   live: Optional[jnp.ndarray] = None, max_steps: int = 512,
                   exp: Optional[jnp.ndarray] = None):
    """Batched chain-offloaded distributed DELETE.

    del_keys: (S, B_local) int32 keys (dim 0 sharded; 0 marks an unused
    slot — never dispatched, status 0).  Each request routes to its
    owner shard, where the pre-posted **deleter chain**
    (:func:`repro.core.programs.build_hopscotch_deleter`) matches the
    key across its H-bucket neighborhood and, on a hit, retires the
    bucket via ``emit_bucket_vacate`` — a re-read-comparand CAS
    ``key -> EMPTY`` plus stale-row zeroing, behind per-probe
    exclusivity.  Returns ``(DeleteResult, new_keys, new_vals)``; with
    a TTL deadline column ``exp`` (S, n), also its update (a vacated
    bucket's deadline resets to NO_TTL), as a 4th element.
    """
    _check_key_batch(del_keys, what="delete", allow_zero=True, live=live)
    n_shards = mesh.shape[axis]
    b_local = del_keys.shape[1]
    capacity = b_local if capacity is None else capacity
    if live is None:
        live = jnp.ones(del_keys.shape, jnp.bool_)
    real = del_keys != hopscotch.EMPTY
    if capacity == 0:
        zi = jnp.zeros(del_keys.shape, jnp.int32)
        res0 = DeleteResult(
            status=zi, applied=zi.astype(bool), ok=zi.astype(bool),
            dropped=jnp.sum(live & real, axis=1, dtype=jnp.int32),
            deferred=jnp.sum(~live & real, axis=1, dtype=jnp.int32))
        if exp is not None:
            return res0, keys, vals, exp
        return res0, keys, vals

    mapped = _mapped_del(mesh, axis, n_shards, capacity, neighborhood,
                         vals.shape[-1], max_steps)
    status, ok, dropped, deferred, nk, nv = mapped(keys, vals, del_keys,
                                                   live)
    applied = ok & (status == programs.DEL_DELETED)
    result = DeleteResult(status, applied, ok, dropped, deferred)
    if exp is not None:
        # a vacated bucket carries no deadline; surviving buckets keep
        # theirs (the deleter never relocates keys)
        new_exp = jnp.where(nk == hopscotch.EMPTY,
                            jnp.int32(hopscotch.NO_TTL), exp)
        return result, nk, nv, new_exp
    return result, nk, nv


def _mapped_del(mesh: Mesh, axis: str, n_shards: int, capacity: int,
                neighborhood: int, val_words: int, max_steps: int):
    key = ("del", _mesh_fingerprint(mesh), axis, n_shards, capacity,
           neighborhood, val_words, max_steps)
    cached = _mapped_cache_get(key)
    if cached is not None:
        return cached
    path = functools.partial(
        _del_local, n_shards=n_shards, capacity=capacity, axis=axis,
        neighborhood=neighborhood, val_words=val_words,
        max_steps=max_steps)

    def body(keys, vals, qk, live):
        real = qk != hopscotch.EMPTY
        live = live & real
        status, ok, nk, nv = path(keys, vals, qk, live)
        deferred = jnp.sum(~live & real, dtype=jnp.int32).reshape(1)
        dropped = (jnp.sum(live, dtype=jnp.int32)
                   - jnp.sum(ok, dtype=jnp.int32)).reshape(1)
        return status, ok, dropped, deferred, nk, nv

    spec = P(axis)
    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(spec,) * 4, out_specs=(spec,) * 6,
        check_vma=False))
    return _mapped_cache_put(key, fn)


class SweepReport(NamedTuple):
    """Outcome of one :func:`sharded_sweep` quantum: per-visited-bucket
    statuses (``SWEEP_RECLAIMED`` / ``SWEEP_LIVE``), per-shard reclaim
    counts, and the advanced CLOCK hand."""
    status: jnp.ndarray      # (S, count) int32
    reclaimed: jnp.ndarray   # (S,) int32
    hand: jnp.ndarray        # (S,) int32 — next quantum starts here

    def __repr__(self):
        if isinstance(self.status, jax.core.Tracer):
            return f"SweepReport(traced: status={self.status})"
        return (f"SweepReport(reclaimed="
                f"{int(np.asarray(self.reclaimed).sum())}"
                f"/{np.asarray(self.status).size}, "
                f"hand={np.asarray(self.hand).tolist()})")


def _sweep_local(keys, vals, exp, hand, nows, *, count, val_words):
    """One owner-shard CLOCK quantum: ``count`` laps of the sweeper
    chain from the hand (loopback QP — the requests originate at the
    shard that owns the buckets, like the resize migrator)."""
    n = keys.shape[1]
    swp = programs.build_clock_sweeper(n, val_words)
    buckets = (hand[0] + jnp.arange(count, dtype=jnp.int32)) % n
    pay = swp.device_payloads(buckets, nows[0])

    def step(carry, p):
        status, tk, tv, te = swp.run_one(*carry, p, swp.fuel)
        return (tk, tv, te), status[None]

    resp, (nk, nv, ne) = transport.local_chain_stateful(
        step, (keys[0], vals[0], exp[0]), pay)
    st = resp[:, 0]
    reclaimed = jnp.sum(st == programs.SWEEP_RECLAIMED,
                        dtype=jnp.int32).reshape(1)
    new_hand = ((hand + count) % n).astype(jnp.int32)
    return st[None], nk[None], nv[None], ne[None], new_hand, reclaimed


def sharded_sweep(mesh: Mesh, axis: str, keys: jnp.ndarray,
                  vals: jnp.ndarray, exp: jnp.ndarray, hand: jnp.ndarray,
                  now, count: int = 16):
    """Advance the CLOCK sweeper by ``count`` buckets per shard.

    Every lap is the **sweeper chain** (:func:`repro.core.programs.
    build_clock_sweeper`) executed against device state over a loopback
    QP: the chain reads the visited bucket's deadline, evaluates the
    expiry predicate in Calc verbs, and an expired bucket is vacated
    (``emit_bucket_vacate`` + deadline reset to NO_TTL) — the host
    contributes no compare, so eviction keeps running with the driver
    dead, exactly like the resize migrator.  ``hand``: (S,) int32
    per-shard CLOCK hands; ``now``: the clock (int).  Returns
    ``(SweepReport, new_keys, new_vals, new_exp)`` — adopt all three
    arrays plus ``report.hand``.
    """
    mapped = _mapped_sweep(mesh, axis, count, vals.shape[-1])
    nows = jnp.full((keys.shape[0],), now, jnp.int32)
    st, nk, nv, ne, new_hand, reclaimed = mapped(
        keys, vals, exp, hand.astype(jnp.int32), nows)
    return SweepReport(st, reclaimed, new_hand), nk, nv, ne


def _mapped_sweep(mesh: Mesh, axis: str, count: int, val_words: int):
    key = ("sweep", _mesh_fingerprint(mesh), axis, count, val_words)
    cached = _mapped_cache_get(key)
    if cached is not None:
        return cached
    body = functools.partial(_sweep_local, count=count,
                             val_words=val_words)
    spec = P(axis)
    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(spec,) * 5, out_specs=(spec,) * 6,
        check_vma=False))
    return _mapped_cache_put(key, fn)


# ---------------------------------------------------------------------------
# online resize (§5.6 extension): chain-assisted growth with double-frame
# serving and a watermark cutover — gets and sets keep serving throughout
# ---------------------------------------------------------------------------

class ResizeState(NamedTuple):
    """A store mid-growth: two frames serve at once.

    ``keys``/``vals`` are the old ``(S, n)`` frame, ``new_keys``/
    ``new_vals`` the doubled ``(S, 2n)`` frame, and ``watermark`` (S,)
    counts migrated source buckets per shard: buckets ``[0, w)`` have
    been drained into the new frame (their residents re-homed by the
    migrator chain), buckets ``[w, n)`` still serve from the old frame.
    Invariants the serving paths rely on:

    * a key is *writable* in exactly one frame — SETs route by watermark
      (:func:`sharded_set_migrating`), and the only transient double
      residency (a key re-written into the new frame while its stale
      copy awaits migration) is resolved by the migrator's match-discard
      with the *new* frame winning;
    * a key whose entire old neighborhood is behind the watermark cannot
      be in the old frame, which is what gates the second get probe;
    * old-frame claims never land behind the watermark (wrap-around
      homes route to the new frame), so the watermark never has to
      re-visit a bucket.
    """
    keys: jnp.ndarray        # (S, n)  old frame
    vals: jnp.ndarray        # (S, n, V)
    new_keys: jnp.ndarray    # (S, 2n) doubled frame
    new_vals: jnp.ndarray    # (S, 2n, V)
    watermark: jnp.ndarray   # (S,) int32 — buckets [0, w) migrated

    @property
    def n_buckets(self) -> int:
        return int(self.keys.shape[1])


class MigrateReport(NamedTuple):
    """Per-shard outcome counts of one :func:`sharded_resize` quantum."""
    moved: jnp.ndarray       # (S,) re-homed by the migrator chain
    discarded: jnp.ndarray   # (S,) stale copies dropped (new frame won)
    escalated: jnp.ndarray   # (S,) placed via the new-frame displacer
    stuck: jnp.ndarray       # (S,) unplaceable even displaced (watermark
    #                              parks on the first such bucket)


class ResizeStuck(RuntimeError):
    """A resize quantum made no progress: a shard's watermark is parked
    on a bucket whose resident cannot be placed in the doubled frame
    even by the bounded displacer (its whole new-frame neighborhood is
    full of immovable keys).

    The silent alternative — leaving the watermark parked and reporting
    nothing — deadlocks the escalation loop (each quantum re-runs the
    same stuck lap forever); the old generic ``RuntimeError`` named the
    symptom but not the bucket.  This error carries the parked
    (shard, bucket) pairs so the operator — or a double-growth
    escalation — knows exactly where the dead end is.
    """

    def __init__(self, shards, buckets, message: Optional[str] = None):
        self.shards = [int(s) for s in shards]
        self.buckets = [int(b) for b in buckets]
        if message is None:
            where = ", ".join(
                f"shard {s} bucket {b}"
                for s, b in zip(self.shards, self.buckets))
            message = (
                f"resize stuck: resident unplaceable in the doubled "
                f"frame even displaced ({where}); the table needs "
                f"another growth step or a larger displacement budget")
        super().__init__(message)

    @property
    def stuck(self):
        """``[(shard, bucket), ...]`` — every parked migration."""
        return list(zip(self.shards, self.buckets))


def begin_resize(keys: jnp.ndarray, vals: jnp.ndarray) -> ResizeState:
    """Open the doubled frame next to the live one (watermark 0).

    The bucket count must be a power of two — growth exposes exactly one
    more hash-mask bit, which is what the migrator chain's select branch
    recomputes in verbs.
    """
    n = int(keys.shape[1])
    if n < 1 or (n & (n - 1)):
        raise ValueError(
            f"resize needs a power-of-two bucket count, got {n}")
    s = keys.shape[0]
    return ResizeState(
        keys=keys, vals=vals,
        new_keys=jnp.zeros((s, 2 * n), keys.dtype),
        new_vals=jnp.zeros((s, 2 * n, vals.shape[-1]), vals.dtype),
        watermark=jnp.zeros((s,), jnp.int32))


def resize_done(rs: ResizeState) -> bool:
    """True once every shard's watermark has swept its whole old frame."""
    return bool(np.asarray(rs.watermark).min() >= rs.n_buckets)


def finish_resize(rs: ResizeState) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The cutover: adopt the doubled frame as *the* store.

    Only legal once :func:`resize_done`; the old frame must be fully
    drained (every bucket vacated by the migrator) — a resident left
    behind would silently vanish from serving, so that is checked, not
    assumed.
    """
    if not resize_done(rs):
        raise ValueError(
            f"resize incomplete: watermarks "
            f"{np.asarray(rs.watermark).tolist()} < {rs.n_buckets}")
    leftover = np.asarray(rs.keys)
    if (leftover != hopscotch.EMPTY).any():
        raise RuntimeError(
            "old frame still holds residents after a full sweep — "
            "migration lost track of a bucket")
    return rs.new_keys, rs.new_vals


def _resize_local(ok, ov, nk, nv, wm, frows=None, *, step, neighborhood,
                  val_words, max_search, max_moves):
    """One owner-shard migration quantum (no collectives: the requests
    originate at the shard that owns the buckets — a loopback QP, see
    ``transport.local_chain_stateful``).

    Scans ``step`` source buckets from the watermark through the
    migrator chain; ``MIG_NEEDS_DISPLACE`` laps escalate through the
    *new* frame's displacer chain (the same bounded bubble SET uses) and
    their source buckets are vacated on success.  The watermark advances
    past everything that resolved and parks on the first stuck bucket —
    so the serving invariant "behind the watermark means not in the old
    frame" survives even the (pathological) double-growth dead end.

    ``frows`` (optional): (step, FIELDS) packed per-lap fault rows —
    lap ``i`` of the quantum runs under its
    :class:`repro.core.faults.FaultPlan` (this is how "shard dies at
    migration lap j" is modeled: the loopback chain for that bucket is
    interrupted mid-flight).  An armed lap commits its torn image,
    never escalates, and **parks the watermark**: the quantum's
    watermark stops at the first lap whose fault actually fired, so
    the next quantum — after fsck + repair — re-drives exactly the
    interrupted bucket (an already-drained later bucket re-runs as a
    no-op lap).
    """
    n = ok.shape[1]
    mig = programs.build_hopscotch_migrator(n, val_words, neighborhood)
    w = wm[0]
    buckets = w + jnp.arange(step, dtype=jnp.int32)
    valid = buckets < n
    b_safe = jnp.clip(buckets, 0, n - 1)
    pay = mig.device_payloads(b_safe, ok[0])
    pay = pay * valid[:, None].astype(pay.dtype)

    if frows is None:
        resp, (tk, tv, gk, gv) = transport.local_chain_stateful(
            _guarded_step(mig.run_one, mig.fuel),
            (ok[0], ov[0], nk[0], nv[0]), pay)
        fired = jnp.zeros((step,), jnp.bool_)
    else:
        resp, (tk, tv, gk, gv) = transport.local_chain_stateful(
            _guarded_step(mig.run_one, mig.fuel, mig.run_one_faulted),
            (ok[0], ov[0], nk[0], nv[0]), pay, faults=frows)
        # a fault only *fires* on a lap that ran a chain: an EMPTY
        # source bucket's lap is guarded out before the fault could act
        fired = (faults_mod.FaultPlan.from_row(frows).active()
                 & (pay[:, 0] != hopscotch.EMPTY))
    st = resp[:, 0]

    # --- escalation: the bounded bubble, on the doubled frame ------------
    # an armed lap's status may be the pre-set NEEDS_DISPLACE default —
    # escalating on it would paper over the fault with a clean bubble
    esc = valid & (st == programs.MIG_NEEDS_DISPLACE) & ~fired
    ms = min(max(max_search, neighborhood), 2 * n)
    if neighborhood >= 2 and ms >= neighborhood:
        disp = programs.build_hopscotch_displacer(
            2 * n, val_words, neighborhood, ms, max_moves)
        k_esc = tk[b_safe]
        pay2 = disp.device_payloads(
            k_esc, hopscotch.bucket_of(k_esc, 2 * n), tv[b_safe])
        pay2 = pay2 * esc[:, None].astype(pay2.dtype)
        resp2, (gk, gv) = transport.local_chain_stateful(
            _guarded_step(disp.run_one, disp.fuel), (gk, gv), pay2)
        st2 = resp2[:, 0]
        placed = esc & ((st2 == programs.SET_INSERTED)
                        | (st2 == programs.SET_DISPLACED)
                        | (st2 == programs.SET_UPDATED))
    else:
        # degenerate geometry: no displacer can be built — every
        # escalation is stuck (H=1 growth still serves; it just cannot
        # bubble, same as the bounded oracle)
        placed = jnp.zeros_like(esc)

    # vacate the source buckets the displacer placed
    tk = tk.at[b_safe].set(
        jnp.where(placed, jnp.int32(hopscotch.EMPTY), tk[b_safe]))
    tv = tv.at[b_safe].set(
        jnp.where(placed[:, None], jnp.zeros_like(tv[b_safe]),
                  tv[b_safe]))

    stuck = esc & ~placed
    first_stuck = jnp.min(jnp.where(stuck, buckets, n))
    first_fault = jnp.min(jnp.where(fired & valid, buckets, n))
    new_w = jnp.minimum(jnp.minimum(w + step, n),
                        jnp.minimum(first_stuck, first_fault))

    def count(m):
        return jnp.sum(m, dtype=jnp.int32).reshape(1)

    return (tk[None], tv[None], gk[None], gv[None],
            new_w.astype(jnp.int32).reshape(1),
            count(st == programs.MIG_MOVED),
            count(st == programs.MIG_DISCARDED), count(placed),
            count(stuck))


def sharded_resize(mesh: Mesh, axis: str, rs: ResizeState, step: int = 16,
                   neighborhood: int = 8,
                   max_search: int = hopscotch.DEFAULT_MAX_SEARCH,
                   max_moves: int = hopscotch.DEFAULT_MAX_MOVES,
                   faults: Optional[faults_mod.FaultPlan] = None
                   ) -> Tuple[ResizeState, MigrateReport]:
    """Advance the migration by up to ``step`` source buckets per shard.

    Every lap is a chain execution against device state (the migrator
    program, plus the new frame's displacer for neighborhood-full
    escalations) — the host contributes no lookup, so growth keeps
    making progress with the driver dead, and gets/sets interleave
    freely between quanta via :func:`sharded_get_migrating` /
    :func:`sharded_set_migrating`.  Returns the advanced state and a
    :class:`MigrateReport`.

    ``faults`` (optional): a :class:`repro.core.faults.FaultPlan` with
    ``(S, step)`` leaves — per-lap fault injection (a shard dying at
    lap j of the quantum).  A fired lap commits torn state and parks
    the watermark on its bucket; see :func:`_resize_local`.
    """
    mapped = _mapped_resize(mesh, axis, step, neighborhood,
                            rs.vals.shape[-1], max_search, max_moves,
                            faulted=faults is not None)
    if faults is not None:
        (tk, tv, gk, gv, wm, moved, disc, escd, stuck) = mapped(
            rs.keys, rs.vals, rs.new_keys, rs.new_vals, rs.watermark,
            faults.as_rows())
    else:
        (tk, tv, gk, gv, wm, moved, disc, escd, stuck) = mapped(
            rs.keys, rs.vals, rs.new_keys, rs.new_vals, rs.watermark)
    return (ResizeState(tk, tv, gk, gv, wm),
            MigrateReport(moved, disc, escd, stuck))


def _mapped_resize(mesh: Mesh, axis: str, step: int, neighborhood: int,
                   val_words: int, max_search: int, max_moves: int,
                   faulted: bool = False):
    key = ("resize-faulted" if faulted else "resize",
           _mesh_fingerprint(mesh), axis, step, neighborhood,
           val_words, max_search, max_moves)
    cached = _mapped_cache_get(key)
    if cached is not None:
        return cached
    kw = dict(step=step, neighborhood=neighborhood, val_words=val_words,
              max_search=max_search, max_moves=max_moves)
    if faulted:
        def body(ok, ov, nk, nv, wm, frows):
            return _resize_local(ok, ov, nk, nv, wm, frows[0], **kw)
        n_in = 6
    else:
        body = functools.partial(_resize_local, **kw)
        n_in = 5
    spec = P(axis)
    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(spec,) * n_in, out_specs=(spec,) * 9,
        check_vma=False))
    return _mapped_cache_put(key, fn)


def _mig_get_local(ok, ov, nk, nv, wm, queries, live, *, n_shards,
                   capacity, axis, neighborhood, val_words):
    """Double-frame get: probe the new frame, then — only where needed —
    the old one.

    Stage 1 is the ordinary redn chain server against the doubled frame.
    Stage 2 re-dispatches the *misses* against the old frame, gated on
    the owner's migration watermark (``lax.all_gather`` of the per-shard
    watermarks — the client caches the servers' progress): a key whose
    whole old neighborhood is already behind the watermark cannot be in
    the old frame, so fully-migrated keys pay a single probe even
    mid-resize.  Stage-2 lives are a subset of stage-1 admits, so the
    second hop can never introduce drops.
    """
    q = queries.reshape(-1)
    dest = shard_of(q, n_shards)
    lv = live.reshape(-1)
    n = ok.shape[1]

    srv_new = programs.build_hopscotch_server(2 * n, val_words,
                                              neighborhood)
    st_new = srv_new.device_state(nk[0], nv[0])
    pay_new = srv_new.device_payloads(q, hopscotch.bucket_of(q, 2 * n))
    resp1, ok1 = transport.triggered_chain_engine(
        srv_new.engine, st_new, srv_new.recv_wq, srv_new.resp_region,
        srv_new.resp_words, pay_new, dest, n_shards, capacity, axis, lv)
    found1 = resp1[:, 0] > 0

    wms = jax.lax.all_gather(wm, axis).reshape(-1)      # (S,) watermarks
    h_old = hopscotch.bucket_of(q, n)
    owner_w = wms[dest]
    mig_done = ((h_old + neighborhood <= owner_w)
                & (h_old + neighborhood <= n))
    live2 = lv & ok1 & ~found1 & ~mig_done

    srv_old = programs.build_hopscotch_server(n, val_words, neighborhood)
    st_old = srv_old.device_state(ok[0], ov[0])
    pay_old = srv_old.device_payloads(q, h_old)
    resp2, _ = transport.triggered_chain_engine(
        srv_old.engine, st_old, srv_old.recv_wq, srv_old.resp_region,
        srv_old.resp_words, pay_old, dest, n_shards, capacity, axis, live2)
    found2 = resp2[:, 0] > 0

    found = found1 | found2
    vals = jnp.where(found1[:, None], resp1[:, 1:], resp2[:, 1:])
    return found[None], vals[None], ok1[None]


def sharded_get_migrating(mesh: Mesh, axis: str, rs: ResizeState,
                          queries: jnp.ndarray, neighborhood: int = 8,
                          capacity: Optional[int] = None,
                          live: Optional[jnp.ndarray] = None) -> GetResult:
    """Deprecated spelling of the mid-growth get — now ``sharded_get(
    mesh, axis, resize_state, queries, ...)`` (the unified entry point
    dispatches on the state argument's type).  Thin shim, bit-exact."""
    warnings.warn(
        "sharded_get_migrating is deprecated: pass the ResizeState as "
        "sharded_get's third argument instead",
        DeprecationWarning, stacklevel=2)
    return _get_resize(mesh, axis, rs, queries, neighborhood=neighborhood,
                       capacity=capacity, live=live)


def _get_resize(mesh: Mesh, axis: str, rs: ResizeState,
                queries: jnp.ndarray, neighborhood: int = 8,
                capacity: Optional[int] = None,
                live: Optional[jnp.ndarray] = None) -> GetResult:
    """Batched distributed get against a store mid-growth.

    Same contract as the steady-state get (redn path), but served from
    the double frame: new-then-old probes, the second gated per request
    on the owner shard's migration watermark.  Bit-exact with "lookup
    the new frame, else the old frame" on the oracle tables.
    """
    _check_key_batch(queries, what="query", allow_zero=True, live=live)
    n_shards = mesh.shape[axis]
    b_local = queries.shape[1]
    capacity = b_local if capacity is None else capacity
    if live is None:
        live = jnp.ones(queries.shape, jnp.bool_)
    if capacity == 0:
        return GetResult(
            found=jnp.zeros(queries.shape, jnp.bool_),
            values=jnp.zeros(queries.shape + (rs.vals.shape[-1],),
                             rs.vals.dtype),
            ok=jnp.zeros(queries.shape, jnp.bool_),
            dropped=jnp.sum(live, axis=1, dtype=jnp.int32),
            deferred=jnp.sum(~live, axis=1, dtype=jnp.int32))
    mapped = _mapped_mig_get(mesh, axis, n_shards, capacity, neighborhood,
                             rs.vals.shape[-1])
    return GetResult(*mapped(rs.keys, rs.vals, rs.new_keys, rs.new_vals,
                             rs.watermark, queries, live))


def _mapped_mig_get(mesh: Mesh, axis: str, n_shards: int, capacity: int,
                    neighborhood: int, val_words: int):
    key = ("mig_get", _mesh_fingerprint(mesh), axis, n_shards, capacity,
           neighborhood, val_words)
    cached = _mapped_cache_get(key)
    if cached is not None:
        return cached
    path = functools.partial(
        _mig_get_local, n_shards=n_shards, capacity=capacity, axis=axis,
        neighborhood=neighborhood, val_words=val_words)

    def body(ok, ov, nk, nv, wm, queries, live):
        found, v, okk = path(ok, ov, nk, nv, wm, queries, live)
        deferred = jnp.sum(~live, dtype=jnp.int32).reshape(1)
        dropped = (jnp.sum(live, dtype=jnp.int32)
                   - jnp.sum(okk, dtype=jnp.int32)).reshape(1)
        return found, v, okk, dropped, deferred

    spec = P(axis)
    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(spec,) * 7, out_specs=(spec,) * 5,
        check_vma=False))
    return _mapped_cache_put(key, fn)


def _mig_set_local(ok_, ov, nk, nv, wm, qk, qv, live, *, n_shards,
                   capacity, axis, neighborhood, val_words, max_steps,
                   max_search, max_moves):
    """Watermark-routed double-frame SET (up to three chain stages).

    Routing: a key whose old home bucket is behind the owner's watermark
    — or whose old neighborhood would wrap past the frame end — writes
    the **new** frame; everything else writes the **old** frame, where
    claims provably land at or ahead of the watermark (no wrap, home >=
    w), so a bucket is writable in exactly one frame at any instant.
    Old-frame rows the writer answers ``SET_NEEDS_DISPLACEMENT``
    escalate to the new-frame writer (the old frame never bubbles during
    growth — the free space is all in the doubled frame), and new-frame
    neighborhood-full rows escalate to the new frame's displacer,
    exactly like the steady-state path.
    """
    q = qk.reshape(-1)
    dest = shard_of(q, n_shards)
    lv = live.reshape(-1)
    n = ok_.shape[1]
    h = neighborhood

    wms = jax.lax.all_gather(wm, axis).reshape(-1)
    owner_w = wms[dest]
    h_old = hopscotch.bucket_of(q, n)
    route_new = (h_old < owner_w) | (h_old + h > n)

    # --- stage 1: old-frame writer (match/update or claim >= watermark) --
    writer_old = programs.build_hopscotch_writer(n, val_words, h)
    pay1 = writer_old.device_payloads(q, h_old,
                                      qv.reshape(-1, val_words))
    live1 = lv & ~route_new
    resp1, ok1, (tk, tv) = transport.triggered_chain_stateful(
        _guarded_step(writer_old.run_one, max_steps), (ok_[0], ov[0]),
        pay1, dest, n_shards, capacity, axis, 1, live1)
    st1 = resp1[:, 0]
    esc1 = ok1 & (st1 == programs.SET_NEEDS_DISPLACEMENT)

    # --- stage 2: new-frame writer (routed + escalated rows) -------------
    writer_new = programs.build_hopscotch_writer(2 * n, val_words, h)
    pay2 = writer_new.device_payloads(q, hopscotch.bucket_of(q, 2 * n),
                                      qv.reshape(-1, val_words))
    live2 = lv & (route_new | esc1)
    resp2, ok2, (gk, gv) = transport.triggered_chain_stateful(
        _guarded_step(writer_new.run_one, max_steps), (nk[0], nv[0]),
        pay2, dest, n_shards, capacity, axis, 1, live2)
    st2 = resp2[:, 0]
    status = jnp.where(live2 & ok2, st2, st1)
    live3 = live2 & ok2 & (st2 == programs.SET_NEEDS_DISPLACEMENT)

    ms = min(max(max_search, h), 2 * n)
    if h < 2 or ms < h:
        status = jnp.where(live3, jnp.int32(programs.SET_NEEDS_RESIZE),
                           status)
    else:
        # --- stage 3: the displacement bubble, on the doubled frame ------
        disp = programs.build_hopscotch_displacer(2 * n, val_words, h,
                                                  ms, max_moves)
        pay3 = disp.device_payloads(q, hopscotch.bucket_of(q, 2 * n),
                                    qv.reshape(-1, val_words))
        disp_steps = max(max_steps, disp.fuel)
        resp3, ok3, (gk, gv) = transport.triggered_chain_stateful(
            _guarded_step(disp.run_one, disp_steps), (gk, gv), pay3,
            dest, n_shards, capacity, axis, 1, live3)
        status = jnp.where(live3 & ok3, resp3[:, 0], status)

    # a row is authoritative when every stage it needed admitted it
    okf = jnp.where(route_new, ok2, jnp.where(esc1, ok1 & ok2, ok1))
    okf = okf & lv
    status = status * okf.astype(status.dtype)
    return (status[None], okf[None], tk[None], tv[None], gk[None],
            gv[None])


def sharded_set_migrating(mesh: Mesh, axis: str, rs: ResizeState,
                          set_keys: jnp.ndarray, set_vals: jnp.ndarray,
                          **kwargs) -> Tuple[SetResult, ResizeState]:
    """Deprecated spelling of the mid-growth set — now ``sharded_set(
    mesh, axis, resize_state, set_keys, set_vals, ...)``.  Thin shim,
    bit-exact."""
    warnings.warn(
        "sharded_set_migrating is deprecated: pass the ResizeState as "
        "sharded_set's third argument instead",
        DeprecationWarning, stacklevel=2)
    return _set_resize(mesh, axis, rs, set_keys, set_vals, **kwargs)


def _set_resize(mesh: Mesh, axis: str, rs: ResizeState,
                set_keys: jnp.ndarray, set_vals: jnp.ndarray,
                neighborhood: int = 8,
                capacity: Optional[int] = None,
                live: Optional[jnp.ndarray] = None,
                max_steps: int = 512,
                max_search: int = hopscotch.DEFAULT_MAX_SEARCH,
                max_moves: int = hopscotch.DEFAULT_MAX_MOVES
                ) -> Tuple[SetResult, ResizeState]:
    """Batched chain-offloaded SET against a store mid-growth.

    Same contract as the steady-state set, but routed by the migration
    watermark over the double frame (see :func:`_mig_set_local`).  A
    key re-written into the new frame while its stale copy awaits
    migration is the *intended* transient: gets probe new-first, and the
    migrator discards the stale copy when its bucket's turn comes.
    Returns ``(SetResult, new ResizeState)`` — the watermark is
    untouched (only :func:`sharded_resize` advances it).
    """
    _check_key_batch(set_keys, what="set", allow_zero=True, live=live)
    n_shards = mesh.shape[axis]
    b_local = set_keys.shape[1]
    capacity = b_local if capacity is None else capacity
    if live is None:
        live = jnp.ones(set_keys.shape, jnp.bool_)
    real = set_keys != hopscotch.EMPTY
    if capacity == 0:
        zi = jnp.zeros(set_keys.shape, jnp.int32)
        return (SetResult(
            status=zi, applied=zi.astype(bool), ok=zi.astype(bool),
            dropped=jnp.sum(live & real, axis=1, dtype=jnp.int32),
            deferred=jnp.sum(~live & real, axis=1, dtype=jnp.int32)),
            rs)
    mapped = _mapped_mig_set(mesh, axis, n_shards, capacity, neighborhood,
                             rs.vals.shape[-1], max_steps, max_search,
                             max_moves)
    status, okf, dropped, deferred, tk, tv, gk, gv = mapped(
        rs.keys, rs.vals, rs.new_keys, rs.new_vals, rs.watermark,
        set_keys, set_vals, live)
    applied = okf & ((status == programs.SET_UPDATED)
                     | (status == programs.SET_INSERTED)
                     | (status == programs.SET_DISPLACED))
    return (SetResult(status, applied, okf, dropped, deferred),
            ResizeState(tk, tv, gk, gv, rs.watermark))


def _mapped_mig_set(mesh: Mesh, axis: str, n_shards: int, capacity: int,
                    neighborhood: int, val_words: int, max_steps: int,
                    max_search: int, max_moves: int):
    key = ("mig_set", _mesh_fingerprint(mesh), axis, n_shards, capacity,
           neighborhood, val_words, max_steps, max_search, max_moves)
    cached = _mapped_cache_get(key)
    if cached is not None:
        return cached
    path = functools.partial(
        _mig_set_local, n_shards=n_shards, capacity=capacity, axis=axis,
        neighborhood=neighborhood, val_words=val_words,
        max_steps=max_steps, max_search=max_search, max_moves=max_moves)

    def body(ok_, ov, nk, nv, wm, qk, qv, live):
        real = qk != hopscotch.EMPTY
        live = live & real
        status, okf, tk, tv, gk, gv = path(ok_, ov, nk, nv, wm, qk, qv,
                                           live)
        deferred = jnp.sum(~live & real, dtype=jnp.int32).reshape(1)
        dropped = (jnp.sum(live, dtype=jnp.int32)
                   - jnp.sum(okf, dtype=jnp.int32)).reshape(1)
        return status, okf, dropped, deferred, tk, tv, gk, gv

    spec = P(axis)
    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(spec,) * 8, out_specs=(spec,) * 8,
        check_vma=False))
    return _mapped_cache_put(key, fn)


# ---------------------------------------------------------------------------
# crash recovery primitive (fsck's repair driver applies its policy
# through this — see repro.kvstore.fsck)
# ---------------------------------------------------------------------------

def repair_bucket(keys: jnp.ndarray, vals: jnp.ndarray, shard: int,
                  bucket: int, key: int = hopscotch.EMPTY,
                  val=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Rewrite one bucket (key word + value row) of one shard's frame.

    The host-side equivalent of an ``emit_bucket_vacate`` chain aimed at
    a known-torn bucket: recovery runs *between* serving quanta with the
    frame quiesced, so a plain functional update is faithful — there is
    no concurrent chain whose CAS could interleave.  Defaults vacate the
    bucket (key EMPTY, zero row), matching the invariant ``fsck``
    enforces: an EMPTY bucket's value row is all-zero.  Returns the
    updated ``(keys, vals)`` — works on either frame of a
    :class:`ResizeState` (pass ``rs.new_keys``/``rs.new_vals`` for the
    doubled frame).
    """
    row = (jnp.zeros((vals.shape[-1],), vals.dtype) if val is None
           else jnp.asarray(val, vals.dtype))
    keys = keys.at[shard, bucket].set(jnp.asarray(key, keys.dtype))
    vals = vals.at[shard, bucket].set(row)
    return keys, vals


# ---------------------------------------------------------------------------
# host-reference oracle
# ---------------------------------------------------------------------------

def reference_get(kv: ShardedKV, queries: np.ndarray):
    """Oracle gets against the host tables: each query is looked up in
    its owner shard's :class:`repro.kvstore.hopscotch.HopscotchTable` by
    :func:`repro.kvstore.hopscotch.lookup`, one batched lookup per owner.
    Returns ``(found (B,), values (B, V))``."""
    q = np.asarray(queries, np.int32).reshape(-1)
    out = np.zeros((len(q), kv.val_words), np.int32)
    found = np.zeros(len(q), bool)
    owner = np.asarray(shard_of(jnp.asarray(q), kv.n_shards))
    for s in np.unique(owner).tolist():
        idx = np.flatnonzero(owner == s)
        f, v = hopscotch.lookup(*kv.tables[s].as_device(),
                                jnp.asarray(q[idx]), kv.neighborhood)
        found[idx] = np.asarray(f)
        out[idx] = np.asarray(v)
    return found, out
