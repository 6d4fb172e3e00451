"""RedN offload programs: the paper's use-cases as verb chains.

* :func:`build_rpc_echo` — Fig. 3's offloaded RPC handler: a client SEND
  triggers a pre-posted RECV whose scatter list injects the argument into
  the posted chain (self-modifying, data-dependent execution).
* :class:`HashLookupOffload` — Fig. 9's hash-table *get*: RECV scatters the
  key into the CAS comparand and the bucket address into the READ; the READ
  pulls ``[key, pad, val_ptr]`` straight onto the response WR's
  ``[ctrl, flags, src]`` fields (our bucket layout mirrors the WR field
  layout so one READ performs both of Fig. 9's patches); the CAS converts
  the response NOOP into the value-returning WRITE only on a key match.
  Sequential (RedN-Seq) and parallel (RedN-Parallel) probe variants.
* :class:`HopscotchShardServer` / :class:`HopscotchShardWriter` /
  :class:`HopscotchShardDisplacer` — §5.2's sharded-store *get*, §3.5's
  CAS-claiming *set*, and the bounded hopscotch displacement bubble as
  per-shard chain programs over the same hopscotch layout (the device
  arrays are the store's source of truth; no SET path touches the host).
* :class:`HopscotchShardMigrator` — online table growth (§5.6 "resize
  while serving"): one source bucket per lap re-homed into a doubled
  frame — Calc-verb select on the new mask bit, match-discard for
  double-residency transients, CAS-claim + cross-frame value copy, and
  a vacate of the source bucket; maintenance is an offload too.
* :class:`ListTraversalOffload` — Fig. 12's linked-list walk, unrolled, with
  the optional Fig. 6-style break.
* :func:`build_recycled_get_server` — a §3.4 WQ-recycled *get* server: the
  chain loops forever (RECV-triggered laps, self-re-arming), which is what
  survives host process/OS crashes in §5.6.

All offloads execute through :class:`repro.core.engine.ChainEngine`
(compile-cached per spec).  The single-request ``get()``/``serve()`` entry
points remain for latency-style use; throughput callers should use the
batched ``get_many()``/``serve_many()`` — one ``materialize()`` and one
vmapped (or scanned, for the persistent recycled server) device call for
the whole key batch instead of N numpy round-trips.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import constructs, isa, machine
from .assembler import Program, WRRef
from .engine import ChainEngine

EMPTY_KEY = 0          # bucket key 0 == empty; live keys are 1..2^24-1
MISS_SENTINEL = 0      # response region default (paper: "default value 0")

# SET outcome codes reported by the hopscotch writer/displacer chains'
# response words (mirrored in repro.kvstore.hopscotch, which core must not
# import — kept numerically identical, cross-checked in tests)
SET_UPDATED = 1              # key matched in neighborhood, value rewritten
SET_INSERTED = 2             # EMPTY bucket CAS-claimed, key + value written
SET_NEEDS_DISPLACEMENT = 3   # neighborhood full: displacer chain required
SET_DISPLACED = 4            # displacer bubbled a slot home and claimed it
SET_NEEDS_RESIZE = 5         # bounded search/bubble failed: resize required

# migration outcome codes reported by the table-growth migrator chain
# (also mirrored in repro.kvstore.hopscotch; disjoint from the SET codes
# so a mixed trace can never alias a migration with a write)
MIG_MOVED = 6                # source bucket re-homed into the new frame
MIG_DISCARDED = 7            # key already in the new frame: stale copy dropped
MIG_NEEDS_DISPLACE = 8       # new-frame neighborhood full: displacer needed

# DELETE / sweep outcome codes (the full Memcached lifecycle; mirrored in
# repro.kvstore.hopscotch like the SET/MIG codes, disjoint from both)
DEL_DELETED = 9              # bucket matched and vacated (key -> EMPTY)
DEL_MISS = 10                # no probe matched; the pre-set default response
SWEEP_RECLAIMED = 11         # expired bucket vacated by the CLOCK sweeper
SWEEP_LIVE = 12              # deadline still ahead; bucket left untouched

# TTL sentinel: a bucket with no deadline carries INT32_MAX in its expiry
# word, so the chains' one signed compare — expired <=> deadline - now <= 0
# — needs no "has a TTL" special case (NO_TTL - now stays positive for any
# plausible now)
NO_TTL = 0x7FFFFFFF

# the hopscotch home-bucket hash, array form — numerically identical to
# repro.kvstore.hopscotch.bucket_of (core must not import kvstore; the
# displacer's device_state derives per-bucket home distances with it)
_HASH_MULT = 2654435761


def bucket_home(keys: jnp.ndarray, n_buckets: int) -> jnp.ndarray:
    k = keys.astype(jnp.uint32) * jnp.uint32(_HASH_MULT)
    return (k % jnp.uint32(n_buckets)).astype(jnp.int32)


def _batched_get(off, keys: Sequence[int], max_steps: int):
    """Shared get_many body: one materialize(), one vmapped engine run,
    one response-region gather for the whole key batch."""
    st = off.materialize()
    payloads = np.asarray([off._payload(int(k)) for k in keys], np.int32)
    out = off.engine.run_many(st, off.recv_wq, payloads, max_steps)
    vals = np.asarray(out.mem[:, off.resp_region:
                              off.resp_region + off.val_len])
    return vals, out


# ---------------------------------------------------------------------------
# Fig. 3 — RPC offload
# ---------------------------------------------------------------------------

def build_rpc_echo(mem_words: int = 1024, bias: int = 1000):
    """RPC handler computing ``f(arg) = arg + bias`` entirely on the chain.

    The client's SEND carries ``arg``; the RECV scatter injects it into an
    ADD's immediate field (self-modifying) and the chain responds with the
    sum — the minimal data-dependent offload of Fig. 3.
    """
    p = Program(mem_words)
    acc = p.word(bias, "acc")
    resp = p.word(0, "resp")

    rq = p.add_wq(4)
    wq = p.add_wq(8, ordering=isa.ORD_DOORBELL)
    wq.wait(rq, 1, tag="rpc.trigger")                    # pre-posted chain
    add = wq.add(dst=acc, addend=0, tag="rpc.add")       # addend patched
    wq.send(src=acc, ln=1, dst_region=resp, target_qp=-1, tag="rpc.resp")
    tbl = p.scatter_table([add.addr("opa")])
    rq.recv(scatter_table=tbl, tag="rpc.recv")

    spec, state = p.finalize()
    return spec, state, dict(resp=resp, acc=acc, bias=bias, recv_wq=rq.index,
                             chain_wq=wq.index, prog=p)


# ---------------------------------------------------------------------------
# Fig. 9 — hash-table get
# ---------------------------------------------------------------------------

BUCKET_WORDS = 3       # [key, pad(=flags default 0), val_ptr]


@dataclasses.dataclass
class HashLookupOffload:
    prog: Program
    spec: machine.MachineSpec
    state0: machine.VMState
    n_buckets: int
    val_len: int
    table_base: int
    values_base: int
    resp_region: int
    recv_wq: int
    parallel: bool
    kv: Dict[int, Tuple[int, List[int]]]

    # -- hashes (client-side, like the paper: the client computes bucket
    #    addresses and sends them with the key) ------------------------------
    def h1(self, key: int) -> int:
        return key % self.n_buckets

    def h2(self, key: int) -> int:
        return (key * 2654435761 >> 8) % self.n_buckets

    def bucket_addr(self, b: int) -> int:
        return self.table_base + b * BUCKET_WORDS

    # -- host-side set path (the server CPU populates; gets are offloaded) --
    def insert(self, key: int, value: Sequence[int]) -> bool:
        assert 0 < key <= isa.ID_MASK and len(value) <= self.val_len
        for b in (self.h1(key), self.h2(key)):
            cur = self.kv.get(b)
            if cur is None or cur[0] == key:
                self.kv[b] = (key, list(value))
                return True
        return False   # displacement is the kvstore layer's job

    def materialize(self) -> machine.VMState:
        """Fresh machine state with the current table contents."""
        mem = np.asarray(self.state0.mem).copy()
        for b, (key, value) in self.kv.items():
            vslot = self.values_base + b * self.val_len
            a = self.bucket_addr(b)
            mem[a], mem[a + 1], mem[a + 2] = key, 0, vslot
            mem[vslot: vslot + len(value)] = value
        return self.state0._replace(mem=jnp.asarray(mem))

    @property
    def engine(self) -> ChainEngine:
        return ChainEngine.for_spec(self.spec)

    def _payload(self, key: int) -> List[int]:
        return [key, key, self.bucket_addr(self.h1(key)),
                self.bucket_addr(self.h2(key))]

    # -- the offloaded get ---------------------------------------------------
    def get(self, key: int, state: Optional[machine.VMState] = None,
            max_steps: int = 256):
        st = self.materialize() if state is None else state
        st = machine.deliver(st, self.recv_wq, self._payload(key))
        out = self.engine.run(st, max_steps)
        val = np.asarray(out.mem[self.resp_region:
                                 self.resp_region + self.val_len])
        return val, out

    def get_many(self, keys: Sequence[int], max_steps: int = 256):
        """Batched get: one materialize(), one vmapped run for all keys.

        Returns ``(vals (N, val_len) np.ndarray, batched VMState)`` —
        row i identical to ``get(keys[i])`` against the same table.
        """
        return _batched_get(self, keys, max_steps)


def build_hash_lookup(n_buckets: int = 64, val_len: int = 4,
                      parallel: bool = True,
                      mem_words: int = 4096) -> HashLookupOffload:
    p = Program(mem_words)
    resp = p.alloc(val_len, [MISS_SENTINEL] * val_len, "resp")
    values = p.alloc(n_buckets * val_len, name="values")
    table = p.alloc(n_buckets * BUCKET_WORDS,
                    [0] * (n_buckets * BUCKET_WORDS), "table")

    rq = p.add_wq(4)
    probes = []
    for pi in range(2):
        # WQ1: probe READ (RECV-patched -> doorbell-ordered)
        wq1 = p.add_wq(4, ordering=isa.ORD_DOORBELL, managed=True)
        # WQ2: CAS + response (READ- and CAS-patched)
        wq2 = p.add_wq(6, ordering=isa.ORD_DOORBELL, managed=True,
                       initial_enable=3)
        if pi == 1 and not parallel:
            # RedN-Seq: second bucket probed only after the first completes
            wq1.wait(probes[0]["wq2"], 4, tag="hash.seq")
        wq1.wait(rq, 1, tag=f"hash.trig{pi}")
        wq1.initial_enable = wq1.n_posted + 1
        rd = wq1.read(src=0, dst=0, ln=BUCKET_WORDS, tag=f"hash.read{pi}")

        wq2.wait(wq1, rd.completion_count, tag=f"hash.sync{pi}")
        cas = wq2.cas(dst=0, old=isa.pack_ctrl(isa.NOOP, 0),
                      new=isa.pack_ctrl(isa.WRITE, 0), tag=f"hash.cas{pi}")
        wq2.enable(wq2, upto=4, tag=f"hash.en{pi}")
        # R4: the response — NOOP unless the CAS converts it
        # (bucket [key, pad, val_ptr] lands on its [ctrl, flags, src])
        r4 = wq2.post(isa.NOOP, src=0, dst=resp, ln=val_len,
                      tag=f"hash.resp{pi}")
        wq1.wrs[rd.slot]["dst"] = r4.ctrl_addr      # READ patches R4
        wq2.wrs[cas.slot]["dst"] = r4.ctrl_addr     # CAS tests/converts R4
        probes.append(dict(wq1=wq1, wq2=wq2, rd=rd, cas=cas, r4=r4))

    # RECV scatter: key -> both CAS comparands; bucket addrs -> the READs
    tbl = p.scatter_table([
        probes[0]["cas"].addr("opa"), probes[1]["cas"].addr("opa"),
        probes[0]["rd"].addr("src"), probes[1]["rd"].addr("src")])
    rq.recv(scatter_table=tbl, tag="hash.recv")

    spec, st0 = p.finalize()
    return HashLookupOffload(
        prog=p, spec=spec, state0=st0, n_buckets=n_buckets, val_len=val_len,
        table_base=table, values_base=values, resp_region=resp,
        recv_wq=rq.index, parallel=parallel, kv={})


# ---------------------------------------------------------------------------
# §5.2 — the sharded-store get server: hopscotch probes as a chain program
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class HopscotchShardServer:
    """Fig. 9's get offload generalized to the hopscotch neighborhood.

    One pre-posted chain per owner shard: the client SEND carries the key
    plus the H probe-bucket addresses (the client computes hashes, like the
    paper); H RedN-Parallel probe pairs each READ a bucket onto their
    response WR's ``[ctrl, flags, src]`` and CAS-convert it into the
    value-returning WRITE on a key match.  Value rows are
    ``[found, v0..v{V-1}]`` — the leading found-flag word rides the same
    WRITE, so the response region reads ``[found, value...]`` and a served
    miss is ``[0, 0...]``, bit-exact with
    :func:`repro.kvstore.hopscotch.lookup`.  The flag word is *dynamic*:
    ``device_state`` sets it to ``keys != EMPTY``, so a query of key 0 —
    which CAS-matches every empty bucket exactly like the jnp probe does —
    lands flag 0 and reads back as the miss it is (the empty-key ghost-hit
    fix; a static flag 1 here used to report ``found=True`` with
    garbage-zero values).

    WQ0 is a never-posted all-zero guard: a zero-padded request slot
    (capacity padding in the transport's receive window) probes address 0,
    reads the all-zero null bucket, and resolves to a harmless zero write.

    The table contents are *dynamic*: :meth:`device_state` scatters a
    shard's ``(keys, vals)`` arrays — traced or concrete — into the image,
    so the same compiled program serves every shard of a
    ``shard_map``-partitioned store.  Instances are frozen and cached per
    geometry (:func:`build_hopscotch_server`); all mutable state lives in
    the ``VMState`` values they produce.

    **TTL variant** (``ttl=True``): each bucket's otherwise-unused pad
    word carries an expiry deadline (:data:`NO_TTL` = never), the client
    additionally sends ``-now``, and each probe's conversion WQ grows a
    Calc-verb expiry check — ``e = min(max(deadline - now, 0), 1)`` over
    the deadline the probe READ landed on the response WR's flags field —
    whose result conditionally converts a *tester* CAS that un-converts a
    matched response WRITE back into a NOOP.  An expired hit therefore
    quiesces exactly like a miss (no response write), bit-exact with
    :func:`repro.kvstore.hopscotch.lookup_ttl`; the deadline is compared
    on device, not by the host.

    **Shared segment.**  The chain never writes the table or the value
    rows: every write lands in code, the response region, the scatter
    table or the TTL ``e`` cells, at addresses fixed when the program is
    built.  The program declares the table plus the value rows, ``[table_base,
    resp_region)``, one read-only :class:`machine.Segment`, and
    :meth:`device_segment` builds the split form of :meth:`device_state`:
    one copy of those rows that every context of a batch reads, and a
    private image of about a thousand words per context
    (:meth:`ChainEngine.run_many_segmented`).  ``device_state``,
    ``state0`` and :meth:`get_many` stay the whole-image oracle.
    """
    prog: Program
    spec: machine.MachineSpec
    state0: machine.VMState
    n_buckets: int
    val_len: int
    neighborhood: int
    table_base: int
    values_base: int
    resp_region: int
    recv_wq: int
    ttl: bool = False
    segment: Optional[machine.Segment] = None
    private0: Optional[machine.VMState] = None   # state0 outside the segment

    @property
    def resp_words(self) -> int:
        return self.val_len + 1            # [found, value...]

    @property
    def engine(self) -> ChainEngine:
        return ChainEngine.for_spec(self.spec)

    def device_state(self, keys: jnp.ndarray, vals: jnp.ndarray,
                     exp: Optional[jnp.ndarray] = None) -> machine.VMState:
        """Image with this shard's hopscotch slice scattered in.

        keys: (n_buckets,) int32 (0 = empty); vals: (n_buckets, val_len).
        Pure jnp — works on traced arrays inside ``shard_map``.  The
        val_ptr columns are static (baked at build time); keys, values,
        and the per-row found flag (``keys != EMPTY`` — empty rows must
        answer a ghost-matching query 0 with found=0) are written here.
        A TTL build additionally scatters the per-bucket deadline column
        ``exp`` into the bucket pad words.
        """
        if self.ttl != (exp is not None):
            raise ValueError(
                "exp column required iff the server was built with "
                f"ttl=True (ttl={self.ttl}, exp given={exp is not None})")
        row_stride = self.val_len + 1
        rows = jnp.arange(self.n_buckets, dtype=jnp.int32)
        mem = self.state0.mem
        mem = mem.at[self.table_base + rows * BUCKET_WORDS].set(
            keys.astype(jnp.int32))
        if exp is not None:
            mem = mem.at[self.table_base + rows * BUCKET_WORDS + 1].set(
                exp.astype(jnp.int32))
        mem = mem.at[self.values_base + rows * row_stride].set(
            (keys != EMPTY_KEY).astype(jnp.int32))
        vidx = (self.values_base + rows[:, None] * row_stride + 1
                + jnp.arange(self.val_len, dtype=jnp.int32)[None, :])
        mem = mem.at[vidx.reshape(-1)].set(
            vals.astype(jnp.int32).reshape(-1))
        return self.state0._replace(mem=mem)

    @property
    def private_resp_region(self) -> int:
        """The response region's address in the private image."""
        return self.resp_region - self.segment.width

    def device_segment(self, keys: jnp.ndarray, vals: jnp.ndarray,
                       exp: Optional[jnp.ndarray] = None):
        """:meth:`device_state` split at :attr:`segment`: ``(private state,
        segment words)``, built without a scatter.  The segment is the
        table rows ``[key, pad | deadline, val_ptr]`` followed by the
        value rows ``[keys != EMPTY, v...]``; the private state is the
        same for every shard."""
        if self.ttl != (exp is not None):
            raise ValueError(
                "exp column required iff the server was built with "
                f"ttl=True (ttl={self.ttl}, exp given={exp is not None})")
        keys = keys.astype(jnp.int32)
        rows = jnp.arange(self.n_buckets, dtype=jnp.int32)
        pad = jnp.zeros_like(keys) if exp is None else exp.astype(jnp.int32)
        val_ptr = self.values_base + rows * (self.val_len + 1)
        table = jnp.stack([keys, pad, val_ptr], axis=1).reshape(-1)
        values = jnp.concatenate(
            [(keys != EMPTY_KEY).astype(jnp.int32)[:, None],
             vals.astype(jnp.int32)], axis=1).reshape(-1)
        return self.private0, jnp.concatenate([table, values])

    def device_payloads(self, queries: jnp.ndarray, home: jnp.ndarray,
                        now=None) -> jnp.ndarray:
        """Client-side request assembly: ``[key x H, probe addrs x H]``
        (default build) or ``[key, -now, probe addrs x H]`` (TTL build —
        the chain ADDs the negated clock onto each probed deadline, so
        the client sends it pre-negated; a padded row keeps ``-now`` 0).

        queries: (B,) int32; home: (B,) int32 home buckets (the client
        computes the hash, exactly as the paper's client computes bucket
        addresses).  Probes cover the wrapping neighborhood
        ``[home, home + H)``.
        """
        if self.ttl != (now is not None):
            raise ValueError(
                "now required iff the server was built with ttl=True "
                f"(ttl={self.ttl}, now given={now is not None})")
        h = self.neighborhood
        offs = jnp.arange(h, dtype=jnp.int32)
        rows = (home[:, None] + offs[None, :]) % self.n_buckets
        addrs = (self.table_base + rows * BUCKET_WORDS).astype(jnp.int32)
        if now is not None:
            live = (queries != EMPTY_KEY)
            negnow = jnp.broadcast_to(
                -jnp.asarray(now, jnp.int32), queries.shape
            ) * live.astype(jnp.int32)
            return jnp.concatenate(
                [queries[:, None].astype(jnp.int32), negnow[:, None],
                 addrs], axis=1)
        keys_rep = jnp.broadcast_to(queries[:, None].astype(jnp.int32),
                                    rows.shape)
        return jnp.concatenate([keys_rep, addrs], axis=1)

    def get_many(self, keys: jnp.ndarray, vals: jnp.ndarray,
                 queries: jnp.ndarray, home: jnp.ndarray,
                 max_steps: int = 96, exp=None, now=None):
        """Single-machine batched get (tests / benchmarks; the sharded
        path goes through ``transport.triggered_chain_engine``).
        Returns (found bool (B,), values (B, val_len))."""
        st = self.device_state(keys, vals, exp)
        out = self.engine.run_many(
            st, self.recv_wq, self.device_payloads(queries, home, now),
            max_steps)
        resp = out.mem[:, self.resp_region:self.resp_region + self.resp_words]
        return resp[:, 0] > 0, resp[:, 1:]


@functools.lru_cache(maxsize=None)
def build_hopscotch_server(n_buckets: int, val_len: int,
                           neighborhood: int = 8,
                           ttl: bool = False) -> HopscotchShardServer:
    """Build (and cache per geometry) the per-shard hopscotch get chain.

    ``2 * neighborhood`` payload words / scatter entries must fit the
    RECV scatter limit (§5.3: 16 scatters), so ``neighborhood <= 8``.

    With ``ttl=True`` each probe additionally evaluates the expiry
    predicate on device (see :class:`HopscotchShardServer`): the probe
    READ already lands the bucket's pad word — now the deadline — on the
    response WR's flags field; a Calc chain (ADD the scattered ``-now``,
    MAX 0, MIN 1) collapses it to ``e in {0, 1}`` and an ``e == 0`` CAS
    arms a *tester* that un-converts the matched response WRITE, so an
    expired hit answers as a miss without any host compare.  The request
    sends ``[key, -now]`` once (plus the probe addrs), so the scatter
    budget is ``2 + H <= 16`` instead of the default build's ``2H``.
    """
    if not 1 <= neighborhood <= isa.MAX_SCATTER // 2:
        raise ValueError(
            f"neighborhood must be in [1, {isa.MAX_SCATTER // 2}] "
            f"(2 payload words per probe, {isa.MAX_SCATTER}-scatter RECV)")
    if val_len + 1 > isa.MAX_COPY:
        raise ValueError(f"val_len {val_len} exceeds one-WRITE response")
    row_stride = val_len + 1
    h = neighborhood

    # size the image exactly: code (1 guard + recv + 6 [ttl: 17] slots per
    # probe) grows up, data grows down
    code_words = (1 + 2 + (4 + 13 if ttl else 6) * h) * isa.WR_WORDS
    data_words = (row_stride                      # response region
                  + n_buckets * row_stride        # value rows [flag, v...]
                  + n_buckets * BUCKET_WORDS      # table
                  + (2 + h if ttl else 0)         # key/-now words, e cells
                  + 1 + (2 + h if ttl else 2 * h))  # scatter table
    mem_words = -(-(code_words + data_words + 32) // 128) * 128

    p = Program(mem_words)
    p.add_wq(1)                                   # WQ0: all-zero null bucket
    resp = p.alloc(row_stride, [MISS_SENTINEL] * row_stride, "resp")
    # value rows [found, v...]: the found flag is per-row dynamic state
    # (device_state writes keys != EMPTY), so the static image is zeros —
    # a query-0 CAS ghost-match on an empty row must land found=0
    values = p.alloc(n_buckets * row_stride,
                     [0] * (n_buckets * row_stride), "values")
    # table rows [key=0, pad, val_ptr]: val_ptr column baked statically
    # (the pad column holds the deadline in a TTL build; device_state
    # scatters it, NO_TTL statically so an unscattered row never expires)
    tbl_init = [NO_TTL if ttl else 0] * (n_buckets * BUCKET_WORDS)
    for b in range(n_buckets):
        tbl_init[b * BUCKET_WORDS] = 0
        tbl_init[b * BUCKET_WORDS + 2] = values + b * row_stride
    table = p.alloc(n_buckets * BUCKET_WORDS, tbl_init, "table")
    key_w = p.word(0, "key") if ttl else None
    negnow_w = p.word(0, "negnow") if ttl else None

    rq = p.add_wq(2)
    cas_opa_addrs, read_src_addrs = [], []
    for pi in range(h):
        if not ttl:
            wq1 = p.add_wq(2, ordering=isa.ORD_DOORBELL, managed=True)
            wq2 = p.add_wq(4, ordering=isa.ORD_DOORBELL, managed=True,
                           initial_enable=3)
            wq1.wait(rq, 1, tag=f"hs.trig{pi}")
            wq1.initial_enable = wq1.n_posted + 1
            rd = wq1.read(src=0, dst=0, ln=BUCKET_WORDS, tag=f"hs.read{pi}")

            wq2.wait(wq1, rd.completion_count, tag=f"hs.sync{pi}")
            cas = wq2.cas(dst=0, old=isa.pack_ctrl(isa.NOOP, 0),
                          new=isa.pack_ctrl(isa.WRITE, 0),
                          tag=f"hs.cas{pi}")
            wq2.enable(wq2, upto=4, tag=f"hs.en{pi}")
            # the response: NOOP unless the CAS converts it; the bucket row
            # [key, pad, val_ptr] lands on its [ctrl, flags, src]
            r4 = wq2.post(isa.NOOP, src=0, dst=resp, ln=row_stride,
                          tag=f"hs.resp{pi}")
            wq1.wrs[rd.slot]["dst"] = r4.ctrl_addr
            wq2.wrs[cas.slot]["dst"] = r4.ctrl_addr
            cas_opa_addrs.append(cas.addr("opa"))
            read_src_addrs.append(rd.addr("src"))
            continue

        # TTL probe: wq1 patches key/-now into wq2's compare verbs, then
        # the usual 3-word probe READ; wq2 computes e = clamp(deadline -
        # now) between the match CAS and the response slot and arms the
        # tester iff expired.  Chained self-enables fence the tester (10)
        # and the response (12) behind the arithmetic.
        e_cell = p.word(0, f"e{pi}")
        wq1 = p.add_wq(4, ordering=isa.ORD_DOORBELL, managed=True)
        wq2 = p.add_wq(13, ordering=isa.ORD_DOORBELL, managed=True,
                       initial_enable=10)
        wq1.wait(rq, 1, tag=f"hs.trig{pi}")
        wq1.write(src=key_w, dst=wq2.future_wr_addr(1, "opa"),
                  tag=f"hs.key{pi}")              # match comparand <- key
        wq1.write(src=negnow_w, dst=wq2.future_wr_addr(4, "opa"),
                  tag=f"hs.now{pi}")              # ADD operand <- -now
        rd = wq1.read(src=0, dst=0, ln=BUCKET_WORDS, tag=f"hs.read{pi}")
        wq1.initial_enable = wq1.n_posted + 1

        wq2.wait(wq1, rd.completion_count, tag=f"hs.sync{pi}")      # [0]
        cas = wq2.cas(dst=0, old=isa.pack_ctrl(isa.NOOP, 0),
                      new=isa.pack_ctrl(isa.WRITE, 0),
                      tag=f"hs.cas{pi}")                            # [1]
        wq2.write(src=wq2.future_wr_addr(10, "flags"), dst=e_cell,
                  tag=f"hs.exp{pi}")              # [2] deadline -> e
        wq2.write_imm(dst=wq2.future_wr_addr(9, "flags"), value=0,
                      tag=f"hs.fl0{pi}")          # [3] flags hygiene
        wq2.add(dst=e_cell, addend=0, tag=f"hs.sub{pi}")            # [4]
        wq2.max_(dst=e_cell, operand=0, tag=f"hs.clm{pi}")          # [5]
        wq2.min_(dst=e_cell, operand=1, tag=f"hs.cl1{pi}")          # [6]
        wq2.write(src=e_cell, dst=wq2.future_wr_addr(3, "ctrl"),
                  tag=f"hs.et{pi}")               # [7] e -> tester ctrl
        wq2.cas(dst=wq2.future_wr_addr(2, "ctrl"),
                old=isa.pack_ctrl(isa.NOOP, 0),
                new=isa.pack_ctrl(isa.CAS, 0),
                tag=f"hs.arm{pi}")                # [8] arm tester iff e=0
        wq2.enable(wq2, upto=12, tag=f"hs.en{pi}")                  # [9]
        # the tester: NOOP unless armed; armed, it CASes the response WR
        # back WRITE -> NOOP (an expired match answers as a miss)
        wq2.post(isa.NOOP, src=-1, dst=wq2.future_wr_addr(2, "ctrl"),
                 opa=isa.pack_ctrl(isa.WRITE, 0),
                 opb=isa.pack_ctrl(isa.NOOP, 0),
                 tag=f"hs.tst{pi}")               # [10]
        wq2.enable(wq2, upto=13, tag=f"hs.en2{pi}")                 # [11]
        r4 = wq2.post(isa.NOOP, src=0, dst=resp, ln=row_stride,
                      tag=f"hs.resp{pi}")         # [12]
        wq1.wrs[rd.slot]["dst"] = r4.ctrl_addr
        wq2.wrs[cas.slot]["dst"] = r4.ctrl_addr
        read_src_addrs.append(rd.addr("src"))

    tbl = p.scatter_table(
        ([key_w, negnow_w] if ttl else cas_opa_addrs) + read_src_addrs)
    rq.recv(scatter_table=tbl, tag="hs.recv")
    # the table and the value rows, contiguous below the response region
    segment = p.read_only(table, values + n_buckets * row_stride)

    spec, st0 = p.finalize()
    private0, _ = machine.split_image(st0, segment)
    return HopscotchShardServer(
        prog=p, spec=spec, state0=st0, n_buckets=n_buckets, val_len=val_len,
        neighborhood=neighborhood, table_base=table, values_base=values,
        resp_region=resp, recv_wq=rq.index, ttl=ttl, segment=segment,
        private0=private0)


# ---------------------------------------------------------------------------
# §3.5 — the sharded-store SET writer: CAS-claimed hopscotch writes
# ---------------------------------------------------------------------------

def _set_templates(p: Program, val_stage: int, val_len: int, resp: int,
                   stage_default: int):
    """16-word Fig.-6 template (over two event WRs): a suppressed value
    WRITE (dst patched with the bucket's val_ptr at run time) and a
    suppressed ``[status, bucket_addr]`` response WRITE.  Shared by the
    writer's match/claim phases and the displacer's match/claim phases."""
    stage = p.alloc(2, [stage_default, 0])
    tmpl = p.alloc(2 * isa.WR_WORDS, [
        isa.pack_ctrl(isa.WRITE, 0), isa.FLAG_SUPPRESS_COMPLETION,
        val_stage, 0, val_len, 0, 0, -1,
        isa.pack_ctrl(isa.WRITE, 0), isa.FLAG_SUPPRESS_COMPLETION,
        stage, resp, 2, 0, 0, -1])
    return tmpl, stage


def _emit_set_match_phase(p: Program, rq, h: int, key_w: int, val_stage: int,
                          val_len: int, resp: int,
                          home_w: Optional[int] = None):
    """The SET programs' shared match phase: H parallel probe pairs.

    Each probe READs its bucket's key onto a conditional WR's control
    word and CAS-tests it against the query key; a hit converts the
    conditional into a Fig.-6 template WRITE whose two suppressed event
    WRITEs rewrite the bucket's value row and land ``[SET_UPDATED,
    bucket_addr]`` in the response region — and the missing event
    completions starve everything gated on ``wait(m_mod, 3)`` (the
    writer's claim phase, the displacer's search phase).

    Probe addresses: with ``home_w=None`` each probe READ's src is left
    for the RECV scatter (the writer's client sends all H addresses);
    with ``home_w`` set they are derived in-chain as ``home + d *
    BUCKET_WORDS`` from the single scattered home address (the
    displacer's unwrapped frame).  Returns ``(rd1s, m_tmpls, m_mods)``.
    """
    rd1s, m_tmpls, m_mods = [], [], []
    for pi in range(h):
        tmpl, stage = _set_templates(p, val_stage, val_len, resp,
                                     SET_UPDATED)
        mmod = p.add_wq(3, ordering=isa.ORD_DOORBELL, managed=True,
                        initial_enable=0)
        mdrv = p.add_wq(9 if home_w is not None else 7,
                        ordering=isa.ORD_DOORBELL, managed=True)
        mexe = p.add_wq(3, ordering=isa.ORD_DOORBELL, managed=True,
                        initial_enable=3)

        c_i = mmod.post(isa.NOOP, src=tmpl,
                        dst=mmod.future_wr_addr(1, "ctrl"),
                        ln=2 * isa.WR_WORDS, tag=f"wr.mc{pi}")
        mmod.post(isa.NOOP, tag=f"wr.me{pi}")     # event: value WRITE slot
        mmod.post(isa.NOOP, tag=f"wr.mf{pi}")     # event: response slot

        mdrv.wait(rq, 1, tag=f"wr.trig{pi}")
        if home_w is not None:
            mdrv.write(src=home_w, dst=mdrv.future_wr_addr(3, "src"),
                       tag=f"wr.home{pi}")        # probe addr <- home + d*BW
            mdrv.add(dst=mdrv.future_wr_addr(2, "src"),
                     addend=pi * BUCKET_WORDS, tag=f"wr.hoff{pi}")
        mdrv.write(src=key_w, dst=mexe.future_wr_addr(1, "opa"),
                   tag=f"wr.key{pi}")             # CAS comparand <- key
        rd1 = mdrv.read(src=0, dst=c_i.ctrl_addr, ln=1,
                        tag=f"wr.read{pi}")       # src scatter/self-patched
        mdrv.write(src=rd1.addr("src"), dst=mdrv.future_wr_addr(2, "src"),
                   tag=f"wr.vp_patch{pi}")
        mdrv.add(dst=mdrv.future_wr_addr(1, "src"), addend=2,
                 tag=f"wr.vp_off{pi}")
        mdrv.read(src=0, dst=tmpl + isa.F_DST, ln=1,
                  tag=f"wr.vp{pi}")               # val_ptr -> template dst
        last = mdrv.write(src=rd1.addr("src"), dst=stage + 1,
                          tag=f"wr.addr{pi}")     # bucket addr -> response
        mdrv.initial_enable = mdrv.n_posted + 1

        mexe.wait(mdrv, last.completion_count, tag=f"wr.sync{pi}")
        mexe.cas(dst=c_i.ctrl_addr, old=isa.pack_ctrl(isa.NOOP, 0),
                 new=isa.pack_ctrl(isa.WRITE, 0), tag=f"wr.cas{pi}")
        mexe.enable(mmod, upto=3, tag=f"wr.en{pi}")
        rd1s.append(rd1)
        m_tmpls.append(tmpl)
        m_mods.append(mmod)
    return rd1s, m_tmpls, m_mods


def _emit_set_claim_phase(p: Program, rd1s, m_tmpls, m_mods, h: int,
                          key_w: int, val_stage: int, val_len: int,
                          resp: int):
    """The SET programs' claim phase: sequential CAS-claims over the H
    probed buckets, gated on an all-miss match phase.  Shared by the
    single-writer hopscotch SET and the multi-writer group program (one
    claim lane per writer, all aimed at the same shared table)."""
    cdrv = p.add_wq(5 * h, ordering=isa.ORD_DOORBELL, managed=True)
    cexe = p.add_wq(4 * h, ordering=isa.ORD_DOORBELL, managed=True)
    cmod = p.add_wq(3 * h, ordering=isa.ORD_DOORBELL, managed=True,
                    initial_enable=0)

    claims = []
    for pi in range(h):
        tmpl, stage = _set_templates(p, val_stage, val_len, resp,
                                     SET_INSERTED)
        if pi == 0:
            # every cdrv patch below completed (and, transitively, every
            # match probe finished without a hit)
            cexe.wait(cdrv, 5 * h, tag="wr.cgate")
        else:
            # previous claim resolved un-claimed (its events completed)
            cexe.wait(cmod, 3 * pi, tag=f"wr.cseq{pi}")
        refs = constructs.emit_cas_claim(
            cexe, cmod, cell=0, expect=EMPTY_KEY, new=0, then_src=tmpl,
            then_dst=cmod.future_wr_addr(1, "ctrl"),
            then_len=2 * isa.WR_WORDS)
        cmod.post(isa.NOOP, tag=f"wr.ce{pi}")     # event: value WRITE slot
        cmod.post(isa.NOOP, tag=f"wr.cf{pi}")     # event: response slot
        cexe.enable(cmod, upto=3 * (pi + 1), tag=f"wr.cen{pi}")
        claims.append((refs, tmpl, stage))
    cexe.initial_enable = cexe.n_posted + 1

    for pi in range(h):
        cdrv.wait(m_mods[pi], 3, tag=f"wr.nomatch{pi}")
    for pi, (refs, tmpl, stage) in enumerate(claims):
        cdrv.write(src=rd1s[pi].addr("src"), dst=refs.cell_dst_addr,
                   tag=f"wr.cdst{pi}")            # claim the probed bucket
        cdrv.write(src=key_w, dst=refs.new_opb_addr,
                   tag=f"wr.cnew{pi}")            # CAS new <- key
        cdrv.write(src=m_tmpls[pi] + isa.F_DST, dst=tmpl + isa.F_DST,
                   tag=f"wr.cvp{pi}")             # reuse probed val_ptr
        cdrv.write(src=rd1s[pi].addr("src"), dst=stage + 1,
                   tag=f"wr.caddr{pi}")           # bucket addr -> response
    cdrv.initial_enable = cdrv.n_posted + 1
    return cdrv, cexe, cmod

@dataclasses.dataclass(frozen=True, eq=False)
class HopscotchShardWriter:
    """The write-side companion of :class:`HopscotchShardServer`.

    One pre-posted chain per owner shard makes SET a first-class offload
    (§3.5: chained CAS builds atomics wider than one verb; the device
    structure stays the source of truth).  The client SEND carries
    ``[key, value x V, probe-bucket addrs x H]`` (the client computes the
    hashes, like the paper); the chain then runs two phases:

    * **match** — H RedN-Parallel probe pairs READ each bucket key onto a
      conditional WR's control word and CAS-test it against the query key.
      A hit converts the conditional into a Fig.-6-style template WRITE
      that rewrites the two event WRs behind it into completion-suppressed
      WRITEs: one copies the staged value over the bucket's value row
      (through the val_ptr the probe READ forwarded into the template),
      one lands ``[SET_UPDATED, bucket_addr]`` in the response region —
      and the missing completions starve the claim phase entirely.
    * **claim** — gated on *every* match probe completing un-hit, the
      probes run again **sequentially**, each a
      :func:`repro.core.constructs.emit_cas_claim`: CAS the bucket's key
      word ``EMPTY -> key`` (the real atomic claim, against the table
      itself), convert on success into the same suppressed
      value-WRITE + ``[SET_INSERTED, bucket_addr]`` response pair, whose
      missing completions break out of the remaining probes — first EMPTY
      bucket wins, exactly like the host oracle's scan.

    Neither phase firing leaves the pre-set default response
    ``[SET_NEEDS_DISPLACEMENT, 0]`` — the cue for the displacer-chain
    escalation stage (:class:`HopscotchShardDisplacer`).

    Requests against one shard are serialized
    (``transport.triggered_chain_stateful`` / :meth:`set_many` scan), as
    the NIC serializes atomics against local memory — so a batch behaves
    exactly like the host oracle applied in order.  Two steps serve a
    request:

    * the **carried image** (:meth:`run_carried`, the steady-state sharded
      path): the scan carries the writer's whole image, built once per
      call by :meth:`device_image` without a scatter.  Table and value
      rows are one contiguous range (:attr:`rows`); each request resets
      only the words outside it, so the rows a chain wrote stay in the
      image for the next request, and :meth:`image_arrays` reads the
      shard arrays back after the scan.  The chain stores into the rows
      only on a path that answers ``SET_UPDATED``/``SET_INSERTED``, so
      this equals the oracle below bit for bit.
    * the **whole-image oracle** (:meth:`run_one`, :meth:`set_many`, the
      faulted, racing-writer and migrating paths): the authoritative
      arrays live outside the image, :meth:`device_state` scatters them
      in per request, and :meth:`commit` folds a finished context's
      effects (status word, bucket address, and the value row *the chain
      wrote*) back into the arrays.
    """
    prog: Program
    spec: machine.MachineSpec
    state0: machine.VMState
    n_buckets: int
    val_len: int
    neighborhood: int
    table_base: int
    values_base: int
    resp_region: int
    recv_wq: int

    resp_words = 2                     # [status, bucket addr]

    @property
    def engine(self) -> ChainEngine:
        return ChainEngine.for_spec(self.spec)

    @property
    def fuel(self) -> int:
        """An exact safe step budget for one request: no WQ in the SET
        programs is recycled, so every posted WR executes at most once
        and the total posted count bounds any run — callers that expose
        tunable unroll bounds (the displacer's ``max_search``/
        ``max_moves``) must use this rather than a fixed guess, or a
        larger unroll silently exhausts fuel mid-bubble and misreports
        a placeable key as ``SET_NEEDS_RESIZE``."""
        return int(np.asarray(self.state0.tail).sum()) + 1

    def device_state(self, keys: jnp.ndarray,
                     vals: jnp.ndarray) -> machine.VMState:
        """Image with this shard's authoritative slice scattered in.

        keys: (n_buckets,) int32 (0 = empty); vals: (n_buckets, val_len).
        Pure jnp — works on traced arrays inside ``shard_map``/``scan``;
        the val_ptr columns are static (baked at build time).
        """
        rows = jnp.arange(self.n_buckets, dtype=jnp.int32)
        mem = self.state0.mem
        mem = mem.at[self.table_base + rows * BUCKET_WORDS].set(
            keys.astype(jnp.int32))
        vidx = (self.values_base + rows[:, None] * self.val_len
                + jnp.arange(self.val_len, dtype=jnp.int32)[None, :])
        mem = mem.at[vidx.reshape(-1)].set(
            vals.astype(jnp.int32).reshape(-1))
        return self.state0._replace(mem=mem)

    def device_payloads(self, queries: jnp.ndarray, home: jnp.ndarray,
                        values: jnp.ndarray) -> jnp.ndarray:
        """Client-side request assembly: ``[key, value x V, addrs x H]``.

        queries: (B,) int32 keys (1..2^24-1); home: (B,) int32 home
        buckets; values: (B, val_len) int32.
        """
        h = self.neighborhood
        offs = jnp.arange(h, dtype=jnp.int32)
        rows = (home[:, None] + offs[None, :]) % self.n_buckets
        addrs = (self.table_base + rows * BUCKET_WORDS).astype(jnp.int32)
        return jnp.concatenate(
            [queries[:, None].astype(jnp.int32),
             values.astype(jnp.int32).reshape(-1, self.val_len), addrs],
            axis=1)

    def commit(self, out_mem: jnp.ndarray, payload: jnp.ndarray,
               keys: jnp.ndarray, vals: jnp.ndarray):
        """Fold one quiesced context's effects into the shard arrays.

        Returns ``(status, keys, vals)``.  Only UPDATED/INSERTED commit;
        the committed value row is read back from where the chain wrote
        it, not from the request.  A zero-padded request slot (key 0 — the
        transport's capacity padding probes the null guard WQ) is never
        committed and reports status 0.
        """
        status = out_mem[self.resp_region]
        addr = out_mem[self.resp_region + 1]
        applied = ((payload[0] != EMPTY_KEY)
                   & ((status == SET_UPDATED) | (status == SET_INSERTED)))
        row = jnp.where(applied,
                        (addr - self.table_base) // BUCKET_WORDS, 0)
        value = jax.lax.dynamic_slice(
            out_mem, (self.values_base + row * self.val_len,),
            (self.val_len,))
        new_key = jnp.where(status == SET_INSERTED,
                            payload[0].astype(keys.dtype), keys[row])
        keys = keys.at[row].set(jnp.where(applied, new_key, keys[row]))
        vals = vals.at[row].set(jnp.where(applied, value, vals[row]))
        return jnp.where(payload[0] == EMPTY_KEY, 0, status), keys, vals

    def commit_torn(self, out_mem: jnp.ndarray, payload: jnp.ndarray,
                    keys: jnp.ndarray, vals: jnp.ndarray):
        """Fault-mode commit: fold back *whatever the chain wrote*,
        terminal status or not.

        The normal :meth:`commit` gates on a terminal status — the
        modeling convenience that keeps a dead-ended run bit-identical
        to the plan-first oracle.  Physically, though, every WR that
        executed already landed its write in device memory before the
        fault hit; a faulted run's truth is the torn image itself.  This
        commit reads the table and value regions straight back (any
        untouched word equals the input arrays by construction), so
        ``fsck`` and the recovery re-issue observe exactly the state a
        real interrupted chain leaves behind — key claimed but value row
        not crossed, a half-done bubble move, a response written but
        never completed.  Returns ``(status, keys, vals)`` where
        ``status`` may be the pre-set non-terminal default (a completion
        is not an applied state — and vice versa)."""
        rows = jnp.arange(self.n_buckets, dtype=jnp.int32)
        keys_out = out_mem[self.table_base + rows * BUCKET_WORDS]
        cols = jnp.arange(self.val_len, dtype=jnp.int32)[None, :]
        vals_out = out_mem[self.values_base
                           + rows[:, None] * self.val_len + cols]
        status = out_mem[self.resp_region]
        return (jnp.where(payload[0] == EMPTY_KEY, 0, status),
                keys_out.astype(keys.dtype), vals_out.astype(vals.dtype))

    def run_one(self, keys: jnp.ndarray, vals: jnp.ndarray,
                payload: jnp.ndarray, max_steps: int = 512):
        """Serve one assembled request against the shard arrays: build the
        image, deliver the SEND, run the chain to quiescence, commit.
        The step :meth:`set_many` and the whole-image sharded paths
        (faulted, racing writers, migrating) are built from.
        Returns ``(status, new_keys, new_vals)``.
        """
        st = machine.deliver(self.device_state(keys, vals), self.recv_wq,
                             payload)
        out = self.engine.run(st, max_steps)
        return self.commit(out.mem, payload, keys, vals)

    @property
    def rows(self) -> machine.Segment:
        """The table rows ``[key, pad, val_ptr]`` and the value rows: one
        contiguous range, since the program allocates downward."""
        return machine.Segment(self.table_base,
                               self.values_base + self.n_buckets * self.val_len)

    def device_image(self, keys: jnp.ndarray,
                     vals: jnp.ndarray) -> jnp.ndarray:
        """``device_state(keys, vals).mem`` built without a scatter: the
        table rows by ``stack``, the value rows by ``reshape``, both
        written into the static image with one ``dynamic_update_slice``."""
        keys = keys.astype(jnp.int32)
        val_ptr = self.values_base + self.val_len * jnp.arange(
            self.n_buckets, dtype=jnp.int32)
        table = jnp.stack([keys, jnp.zeros_like(keys), val_ptr], axis=1)
        words = jnp.concatenate([table.reshape(-1),
                                 vals.astype(jnp.int32).reshape(-1)])
        return jax.lax.dynamic_update_slice(self.state0.mem, words,
                                            (self.table_base,))

    def image_arrays(self, mem: jnp.ndarray):
        """The shard arrays ``(keys, vals)`` of a carried image, by a
        strided slice and a reshape."""
        lo, hi = self.rows
        # lax.slice: a strided jnp index would lower to a gather
        keys = jax.lax.slice(mem, (lo,), (self.values_base,), (BUCKET_WORDS,))
        vals = mem[self.values_base:hi].reshape(self.n_buckets, self.val_len)
        return keys, vals

    def run_carried(self, mem: jnp.ndarray, payload: jnp.ndarray,
                    max_steps: int = 512):
        """:meth:`run_one` against a carried image: reset every word
        outside :attr:`rows` and every other field of the state to
        ``state0``, deliver the SEND, run the chain to quiescence.  The
        rows the chain wrote stay in the returned image; nothing is
        committed.  Returns ``(status, mem)``, status 0 for key 0."""
        lo, hi = self.rows
        mem = mem.at[:lo].set(self.state0.mem[:lo])
        mem = mem.at[hi:].set(self.state0.mem[hi:])
        st = machine.deliver(self.state0._replace(mem=mem), self.recv_wq,
                             payload)
        out = self.engine.run(st, max_steps)
        status = out.mem[self.resp_region]
        return jnp.where(payload[0] == EMPTY_KEY, 0, status), out.mem

    def run_one_faulted(self, keys: jnp.ndarray, vals: jnp.ndarray,
                        payload: jnp.ndarray, max_steps: int,
                        faults):
        """:meth:`run_one` under a :class:`repro.core.faults.FaultPlan`
        (scalar leaves): the chain runs with the plan's faults armed and
        an **armed** row commits the torn image (:meth:`commit_torn`) —
        the device state a real interrupted chain leaves behind, for
        fsck/recovery to repair and re-issue against.  A *disarmed* row
        commits through the ordinary status-gated fold, so a
        ``FaultPlan.none()`` row is bit-exact with :meth:`run_one`
        (the storm benchmark's un-hit requests must not drift)."""
        st = machine.deliver(self.device_state(keys, vals), self.recv_wq,
                             payload)
        out = self.engine.run(st, max_steps, faults)
        torn = self.commit_torn(out.mem, payload, keys, vals)
        clean = self.commit(out.mem, payload, keys, vals)
        act = faults.active()
        return tuple(jnp.where(act, t, c) for t, c in zip(torn, clean))

    def set_many(self, keys: jnp.ndarray, vals: jnp.ndarray,
                 queries: jnp.ndarray, home: jnp.ndarray,
                 values: jnp.ndarray, max_steps: int = 512):
        """Single-machine batched SET (tests / benchmarks; the sharded
        path goes through ``transport.triggered_chain_stateful``).

        One ``lax.scan`` over the request batch: each chain runs against
        the arrays as left by its predecessors and its effects are
        committed before the next — request i observes writes 0..i-1,
        bit-exact with :func:`repro.kvstore.hopscotch.insert_many`.
        Returns ``(status (B,), new_keys, new_vals)``.
        """
        payloads = self.device_payloads(queries, home, values)

        def step(carry, pay):
            status, tk, tv = self.run_one(*carry, pay, max_steps)
            return (tk, tv), status

        (nk, nv), statuses = jax.lax.scan(step, (keys, vals), payloads)
        return statuses, nk, nv


@functools.lru_cache(maxsize=None)
def build_hopscotch_writer(n_buckets: int, val_len: int,
                           neighborhood: int = 8) -> HopscotchShardWriter:
    """Build (and cache per geometry) the per-shard hopscotch SET chain.

    The request is one SEND: ``1 + val_len + neighborhood`` payload words
    must fit the RECV scatter/message limits (§5.3: 16 scatters), so
    ``val_len <= 15 - neighborhood``.
    """
    if not 1 <= neighborhood:
        raise ValueError("neighborhood must be >= 1")
    if 1 + val_len + neighborhood > min(isa.MAX_SCATTER, isa.MSG_WORDS):
        raise ValueError(
            f"val_len {val_len} + neighborhood {neighborhood} exceeds the "
            f"one-SEND request budget ({isa.MAX_SCATTER}-scatter RECV)")
    h = neighborhood

    # size the image exactly: 1 guard WR + 2 recv slots + per probe
    # (7 match-driver + 3 match-exec + 3 match-cond) + claim
    # (5 driver-patch + 4 exec + 3 cond per probe); data grows down
    code_words = (1 + 2 + h * (7 + 3 + 3) + 5 * h + 4 * h + 3 * h) \
        * isa.WR_WORDS
    data_words = (2 + 1 + val_len              # resp, key_w, val_stage
                  + n_buckets * val_len        # value rows
                  + n_buckets * BUCKET_WORDS   # table
                  + h * 2 * (2 * isa.WR_WORDS + 2)   # templates + stages
                  + 2 + val_len + h)           # scatter table
    mem_words = -(-(code_words + data_words + 32) // 128) * 128

    p = Program(mem_words)
    p.add_wq(1)                 # WQ0: all-zero null bucket (padding guard)

    # data: response defaults to the needs-displacement report
    resp = p.alloc(2, [SET_NEEDS_DISPLACEMENT, 0], "resp")
    key_w = p.word(0, "key")
    val_stage = p.alloc(val_len, [0] * val_len, "val_stage")
    values = p.alloc(n_buckets * val_len, name="values")
    # table rows [key=0, pad, val_ptr]: val_ptr column baked statically
    tbl_init = [0] * (n_buckets * BUCKET_WORDS)
    for b in range(n_buckets):
        tbl_init[b * BUCKET_WORDS + 2] = values + b * val_len
    table = p.alloc(n_buckets * BUCKET_WORDS, tbl_init, "table")

    rq = p.add_wq(2)

    # --- match phase: H parallel probe pairs (shared with the displacer) --
    rd1s, m_tmpls, m_mods = _emit_set_match_phase(
        p, rq, h, key_w, val_stage, val_len, resp)

    # --- claim phase: sequential CAS-claims, gated on an all-miss match ---
    _emit_set_claim_phase(p, rd1s, m_tmpls, m_mods, h, key_w, val_stage,
                          val_len, resp)

    # RECV scatter: key, staged value words, one probe addr per READ
    tbl = p.scatter_table(
        [key_w] + [val_stage + j for j in range(val_len)]
        + [rd.addr("src") for rd in rd1s])
    rq.recv(scatter_table=tbl, tag="wr.recv")

    spec, st0 = p.finalize()
    return HopscotchShardWriter(
        prog=p, spec=spec, state0=st0, n_buckets=n_buckets,
        val_len=val_len, neighborhood=neighborhood, table_base=table,
        values_base=values, resp_region=resp, recv_wq=rq.index)


# ---------------------------------------------------------------------------
# §3.5 multi-writer: N independent SET lanes racing over ONE shared table
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class MultiWriterGroup:
    """N independent hopscotch SET writers sharing ONE memory image.

    Each *lane* is a full :class:`HopscotchShardWriter` pipeline — private
    recv WQ, match phase, claim phase, response/staging regions — but the
    table and value rows are allocated once and shared, so the lanes'
    pre-posted :func:`repro.core.constructs.emit_cas_claim`\\ s genuinely
    race: the claim CAS ``EMPTY -> key`` against the shared bucket word is
    the arbitration point, exactly the paper's §3.5 concurrent-writer
    story.  Interleaving is controlled by a :class:`machine.Schedule` over
    ``writer_slices`` (each lane's contiguous WQ index range).

    **Linearizability.** A claim CAS is one atomic VM step, so each bucket
    cell is won by exactly one lane at one step; a loser observes ``old !=
    expect``, leaves the cell and its conditional untouched, and re-probes
    the next bucket — the same path it would take running strictly after
    the winner.  Lanes share *nothing else* (disjoint WQs, completions,
    staging, responses), so for distinct keys the committed state under
    ANY schedule equals the serialized order in which the contended claims
    won — proven exhaustively by the 2-writer cut-point sweep in
    ``tests/test_faults.py``.  (Two lanes inserting the *same* key can
    both claim distinct EMPTY buckets — a duplicate no serial order
    produces; the store's sharded path never issues that, and fsck flags
    ``dup-key`` if a client does.)
    """
    prog: Program
    spec: machine.MachineSpec
    state0: machine.VMState
    n_buckets: int
    val_len: int
    neighborhood: int
    n_writers: int
    table_base: int
    values_base: int
    lanes: tuple               # per writer: (recv_wq, resp_region)
    writer_slices: tuple       # per writer: (lo, hi) WQ index range
    lane_kinds: tuple          # per writer: "set" | "delete"

    resp_words = 2             # [status, bucket addr] per lane

    @property
    def engine(self) -> ChainEngine:
        return ChainEngine.for_spec(self.spec)

    @property
    def fuel(self) -> int:
        """Safe global step budget: nothing is recycled, so the total
        posted count bounds any schedule's run."""
        return int(np.asarray(self.state0.tail).sum()) + 1

    @property
    def writer_fuel(self) -> int:
        """Steps after which any single lane has certainly quiesced — the
        cut-point sweep's upper bound (per-lane posted count max)."""
        tails = np.asarray(self.state0.tail)
        return int(max(tails[lo:hi].sum()
                       for lo, hi in self.writer_slices)) + 1

    def device_state(self, keys: jnp.ndarray, vals: jnp.ndarray,
                     exp: Optional[jnp.ndarray] = None) -> machine.VMState:
        """Image with the shared shard slice scattered in (see
        :meth:`HopscotchShardWriter.device_state`).  ``exp`` (only with a
        ``"sweep"`` lane): per-bucket TTL deadlines into the pad words."""
        rows = jnp.arange(self.n_buckets, dtype=jnp.int32)
        mem = self.state0.mem
        mem = mem.at[self.table_base + rows * BUCKET_WORDS].set(
            keys.astype(jnp.int32))
        if exp is not None:
            mem = mem.at[self.table_base + rows * BUCKET_WORDS + 1].set(
                exp.astype(jnp.int32))
        vidx = (self.values_base + rows[:, None] * self.val_len
                + jnp.arange(self.val_len, dtype=jnp.int32)[None, :])
        mem = mem.at[vidx.reshape(-1)].set(
            vals.astype(jnp.int32).reshape(-1))
        return self.state0._replace(mem=mem)

    def device_payloads(self, queries: jnp.ndarray, home: jnp.ndarray,
                        values: jnp.ndarray) -> jnp.ndarray:
        """``[key, value x V, probe addrs x H]`` — one row per request;
        row ``w`` of a ``(n_writers, ...)`` batch feeds lane ``w``."""
        h = self.neighborhood
        offs = jnp.arange(h, dtype=jnp.int32)
        rows = (home[:, None] + offs[None, :]) % self.n_buckets
        addrs = (self.table_base + rows * BUCKET_WORDS).astype(jnp.int32)
        return jnp.concatenate(
            [queries[:, None].astype(jnp.int32),
             values.astype(jnp.int32).reshape(-1, self.val_len), addrs],
            axis=1)

    def device_delete_payloads(self, queries: jnp.ndarray,
                               home: jnp.ndarray) -> jnp.ndarray:
        """``[key, probe addrs x H]`` for a DELETE lane — narrower than a
        SET row; the caller zero-pads rows to a common width (a lane's
        RECV scatters exactly its own scatter-table length, so trailing
        pad words are never read)."""
        h = self.neighborhood
        offs = jnp.arange(h, dtype=jnp.int32)
        rows = (home[:, None] + offs[None, :]) % self.n_buckets
        addrs = (self.table_base + rows * BUCKET_WORDS).astype(jnp.int32)
        return jnp.concatenate(
            [queries[:, None].astype(jnp.int32), addrs], axis=1)

    def device_sweep_payloads(self, buckets: jnp.ndarray,
                              now) -> jnp.ndarray:
        """``[bucket_addr, deadline_addr, -now]`` for a SWEEP lane (same
        wire row as :meth:`ClockSweeper.device_payloads`); caller
        zero-pads rows to the group's common width."""
        b = buckets.astype(jnp.int32)
        addr = self.table_base + b * BUCKET_WORDS
        negnow = jnp.broadcast_to(-jnp.asarray(now, jnp.int32), b.shape)
        return jnp.stack([addr, addr + 1, negnow], axis=1)

    def run_group(self, keys: jnp.ndarray, vals: jnp.ndarray,
                  payloads: jnp.ndarray, schedule: machine.Schedule,
                  max_steps: int = 4096,
                  exp: Optional[jnp.ndarray] = None):
        """One concurrent group round: deliver payload row ``w`` to lane
        ``w``, run all lanes over the shared image under ``schedule``,
        read the table/value regions straight back (torn-image commit —
        every executed WR's write is already in device memory; see
        :meth:`HopscotchShardWriter.commit_torn`).

        Returns ``(status (n_writers,), new_keys, new_vals)``.  A
        zero-padded lane (key 0) probes the null guard region and reports
        status 0; its claim phase starves on the ghost match, so it never
        touches the table.

        With ``exp`` (a group that has a ``"sweep"`` lane) the deadline
        column rides the image too and the return gains a fourth element
        ``new_exp``.  Buckets that came back EMPTY are normalized to
        :data:`NO_TTL` — the delete lane's deadline reset is modeled at
        the commit layer, same as the sharded store's
        ``sharded_delete``.
        """
        st = self.device_state(keys, vals, exp)
        for w, (recv_wq, _) in enumerate(self.lanes):
            st = machine.deliver(st, recv_wq, payloads[w])
        out = machine.run_scheduled(self.spec, st, schedule,
                                    self.writer_slices, max_steps)
        rows = jnp.arange(self.n_buckets, dtype=jnp.int32)
        keys_out = out.mem[self.table_base + rows * BUCKET_WORDS]
        cols = jnp.arange(self.val_len, dtype=jnp.int32)[None, :]
        vals_out = out.mem[self.values_base
                           + rows[:, None] * self.val_len + cols]
        status = jnp.stack(
            [jnp.where(payloads[w][0] == EMPTY_KEY, 0, out.mem[resp])
             for w, (_, resp) in enumerate(self.lanes)])
        if exp is None:
            return (status, keys_out.astype(keys.dtype),
                    vals_out.astype(vals.dtype))
        exp_out = out.mem[self.table_base + rows * BUCKET_WORDS + 1]
        exp_out = jnp.where(keys_out == EMPTY_KEY, jnp.int32(NO_TTL),
                            exp_out)
        return (status, keys_out.astype(keys.dtype),
                vals_out.astype(vals.dtype), exp_out.astype(exp.dtype))


@functools.lru_cache(maxsize=None)
def build_multi_writer_group(n_buckets: int, val_len: int,
                             neighborhood: int = 8, n_writers: int = 2,
                             lane_kinds: Optional[tuple] = None,
                             ) -> MultiWriterGroup:
    """Build (and cache per geometry) the N-writer shared-table group.

    Structurally ``n_writers`` copies of :func:`build_hopscotch_writer`'s
    lane emitted into one :class:`Program` against one table/values
    allocation; each lane's WQs form a contiguous index slice for
    :func:`machine.run_scheduled` masking.

    ``lane_kinds`` (default: all ``"set"``) assigns each lane a verb —
    ``"set"``, ``"delete"``, or ``"sweep"`` — so the full Memcached write
    mix races under one schedule; a delete lane is
    :func:`_emit_delete_probes` against the shared table (payload rows:
    :meth:`MultiWriterGroup.device_delete_payloads`), a sweep lane is the
    CLOCK eviction body (:func:`_emit_sweep_lane`; payload rows:
    :meth:`MultiWriterGroup.device_sweep_payloads`, table pad words carry
    the deadlines — pass ``exp`` to ``device_state``/``run_group``).
    """
    if n_writers < 1:
        raise ValueError("n_writers must be >= 1")
    if lane_kinds is None:
        lane_kinds = ("set",) * n_writers
    lane_kinds = tuple(lane_kinds)
    if len(lane_kinds) != n_writers:
        raise ValueError(
            f"lane_kinds has {len(lane_kinds)} entries for "
            f"{n_writers} writers")
    bad = sorted(set(lane_kinds) - {"set", "delete", "sweep"})
    if bad:
        raise ValueError(f"unknown lane kinds {bad!r} "
                         "(expected 'set', 'delete', or 'sweep')")
    if not 1 <= neighborhood:
        raise ValueError("neighborhood must be >= 1")
    if 1 + val_len + neighborhood > min(isa.MAX_SCATTER, isa.MSG_WORDS):
        raise ValueError(
            f"val_len {val_len} + neighborhood {neighborhood} exceeds the "
            f"one-SEND request budget ({isa.MAX_SCATTER}-scatter RECV)")
    h = neighborhood
    n_del = lane_kinds.count("delete")
    n_swp = lane_kinds.count("sweep")
    n_set = n_writers - n_del - n_swp

    # exact image sizing: guard + per-lane code; shared table/values + per-
    # lane data (mirrors build_hopscotch_writer's / the deleter's / the
    # sweeper's accounting).  A delete or sweep lane's ghost lap covers
    # words [0..2] and a val_len zero-write, so the guard widens when one
    # is present.
    lane_code_set = (2 + h * (7 + 3 + 3) + 5 * h + 4 * h + 3 * h)
    lane_code_del = 2 + h * (8 + 3 + 4 + 3)
    lane_code_swp = 2 + sum(_SWEEP_WQS)
    guard_slots = (1 if not (n_del or n_swp)
                   else max(1, -(-val_len // isa.WR_WORDS)))
    code_words = (guard_slots + n_set * lane_code_set
                  + n_del * lane_code_del
                  + n_swp * lane_code_swp) * isa.WR_WORDS
    lane_data_set = (2 + 1 + val_len                 # resp, key_w, val_stage
                     + h * 2 * (2 * isa.WR_WORDS + 2)  # templates + stages
                     + 2 + val_len + h)              # scatter table
    lane_data_del = (2 + 1                           # resp, key_w
                     + h * (2 * isa.WR_WORDS + 2)    # templates + stages
                     + 2 + h)                        # scatter table
    lane_data_swp = 2 + 2 + 1 + 3                    # resp, cells, scatter
    data_words = (n_buckets * val_len + n_buckets * BUCKET_WORDS
                  + (val_len if (n_del or n_swp) else 0)  # shared zero row
                  + (1 if n_swp else 0)              # shared NO_TTL word
                  + n_set * lane_data_set
                  + n_del * lane_data_del
                  + n_swp * lane_data_swp)
    mem_words = -(-(code_words + data_words + 32) // 128) * 128

    p = Program(mem_words)
    p.add_wq(guard_slots)       # WQ0: all-zero null bucket (padding guard)

    # shared state: ONE value region, ONE table (pad words carry the TTL
    # deadlines when a sweep lane is present — NO_TTL until scattered)
    values = p.alloc(n_buckets * val_len, name="values")
    tbl_init = [0] * (n_buckets * BUCKET_WORDS)
    for b in range(n_buckets):
        if n_swp:
            tbl_init[b * BUCKET_WORDS + 1] = NO_TTL
        tbl_init[b * BUCKET_WORDS + 2] = values + b * val_len
    table = p.alloc(n_buckets * BUCKET_WORDS, tbl_init, "table")
    zeros_v = (p.alloc(val_len, [0] * val_len, "zeros")
               if (n_del or n_swp) else None)
    no_ttl_w = p.word(NO_TTL, "no_ttl") if n_swp else None

    lanes, slices = [], []
    for w, kind in enumerate(lane_kinds):
        if kind == "set":
            resp = p.alloc(2, [SET_NEEDS_DISPLACEMENT, 0], f"resp{w}")
            key_w = p.word(0, f"key{w}")
            val_stage = p.alloc(val_len, [0] * val_len, f"val_stage{w}")

            lo = len(p.wqs)
            rq = p.add_wq(2)
            rd1s, m_tmpls, m_mods = _emit_set_match_phase(
                p, rq, h, key_w, val_stage, val_len, resp)
            _emit_set_claim_phase(p, rd1s, m_tmpls, m_mods, h, key_w,
                                  val_stage, val_len, resp)
            tbl = p.scatter_table(
                [key_w] + [val_stage + j for j in range(val_len)]
                + [rd.addr("src") for rd in rd1s])
            rq.recv(scatter_table=tbl, tag="wr.recv")
        elif kind == "delete":
            resp = p.alloc(2, [DEL_MISS, 0], f"resp{w}")
            key_w = p.word(0, f"key{w}")

            lo = len(p.wqs)
            rq = p.add_wq(2)
            rd1s = _emit_delete_probes(p, rq, h, val_len, key_w, resp,
                                       zeros_v)
            tbl = p.scatter_table(
                [key_w] + [rd.addr("src") for rd in rd1s])
            rq.recv(scatter_table=tbl, tag="dl.recv")
        else:
            resp = p.alloc(2, [SWEEP_LIVE, 0], f"resp{w}")
            bucket_w = p.word(0, f"bucket{w}")
            e_cell = p.word(0, f"e{w}")

            lo = len(p.wqs)
            rq = p.add_wq(2)
            scatter = _emit_sweep_lane(p, rq, val_len, resp, bucket_w,
                                       e_cell, no_ttl_w, zeros_v)
            tbl = p.scatter_table(scatter)
            rq.recv(scatter_table=tbl, tag="sw.recv")
        lanes.append((rq.index, resp))
        slices.append((lo, len(p.wqs)))

    spec, st0 = p.finalize()
    return MultiWriterGroup(
        prog=p, spec=spec, state0=st0, n_buckets=n_buckets,
        val_len=val_len, neighborhood=neighborhood, n_writers=n_writers,
        table_base=table, values_base=values, lanes=tuple(lanes),
        writer_slices=tuple(slices), lane_kinds=lane_kinds)


# ---------------------------------------------------------------------------
# bounded CAS-retry demo: two writers racing retry loops on one static cell
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class CasRetryPair:
    """Two chains running :func:`repro.core.constructs.emit_cas_retry_loop`
    against ONE statically named cell — the minimal genuinely-racing
    program (the verifier's race pass *must* flag it; the retry-loop
    proof admits it).  The winner's stamped template writes ``w + 1`` to
    its mark word; a loser retries with exponential NOOP backoff until
    its attempts exhaust, leaving its mark 0."""
    prog: Program
    spec: machine.MachineSpec
    state0: machine.VMState
    cell: int
    marks: tuple               # per writer: mark word address
    writer_slices: tuple       # per writer: (lo, hi) WQ index range
    attempts: int

    @property
    def fuel(self) -> int:
        return int(np.asarray(self.state0.tail).sum()) + 1


def build_cas_retry_pair(attempts: int = 2,
                         backoff_base: int = 1) -> CasRetryPair:
    """Build the two-writer CAS-retry race (not memoized: tests mutate
    the posted image to engineer structurally-broken variants)."""
    p = Program(1024)
    cell = p.word(0, "cell")
    marks, slices = [], []
    n_ctl = sum(3 + ((1 + (backoff_base << (a - 1))) if a else 0)
                for a in range(attempts))
    for w in range(2):
        mark = p.word(0, f"mark{w}")
        # 2-WR suppressed result template: WRITE_IMM mark <- w+1, NOOP pad
        tmpl = p.alloc(2 * isa.WR_WORDS, [
            isa.pack_ctrl(isa.WRITE_IMM, 0), isa.FLAG_SUPPRESS_COMPLETION,
            -1, mark, 1, w + 1, 0, -1,
            isa.pack_ctrl(isa.NOOP, 0), isa.FLAG_SUPPRESS_COMPLETION,
            0, 0, 1, 0, 0, -1], f"tmpl{w}")
        lo = len(p.wqs)
        ctl = p.add_wq(n_ctl, ordering=isa.ORD_DOORBELL)
        mod = p.add_wq(3 * attempts, ordering=isa.ORD_DOORBELL,
                       managed=True, initial_enable=0)
        constructs.emit_cas_retry_loop(
            ctl, mod, cell=cell, expect=0, new=w + 1, template=tmpl,
            attempts=attempts, backoff_base=backoff_base, tag=f"w{w}")
        marks.append(mark)
        slices.append((lo, len(p.wqs)))
    spec, st0 = p.finalize()
    return CasRetryPair(prog=p, spec=spec, state0=st0, cell=cell,
                        marks=tuple(marks), writer_slices=tuple(slices),
                        attempts=attempts)


# ---------------------------------------------------------------------------
# §3.5 + Fig. 5/6 — the hopscotch DISPLACER: the bubble loop as a chain
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class HopscotchShardDisplacer(HopscotchShardWriter):
    """The displacement escalation of :class:`HopscotchShardWriter` — the
    last piece of SET the host used to own, as one pre-posted chain.

    A neighborhood-full insert needs the hopscotch *bubble*: find the
    first EMPTY bucket past the neighborhood, repeatedly move a bucket
    from the window ``[free-H+1, free)`` into it (any resident whose own
    home is within H of the free slot may move), and stop once the free
    slot lands inside the requester's neighborhood — a loop with three
    data-dependent exits.  This program is that loop, bounded and
    unrolled (Fig. 5), with Calc-verb branch constructs
    (:func:`repro.core.constructs.emit_enable_branch`) as the exits:

    * **match** — the shared H-probe phase; a hit updates in place
      (``SET_UPDATED``) and starves everything below.
    * **search** — up to ``max_search`` sequential probes from the home
      bucket; the first key-is-EMPTY branch latches the free slot's
      address and home-distance into the ``free``/``dist`` carry words.
    * **bubble** — up to ``max_moves`` laps.  Each lap opens with a
      break-check (``dist <= H-1`` releases the claim phase — the loop's
      early exit) and then scans the window ``back = H-1 .. 1``: a probe
      READs the candidate's *home-distance word* (the ``pad`` field the
      writer never used — :meth:`device_state` precomputes it per bucket)
      and branches on ``pad + back <= H-1``; the first movable candidate
      releases an :func:`~repro.core.constructs.emit_displace_move` (value
      row out, key READ across, CAS ``key -> EMPTY``, stale row zeroed,
      carries advanced) and the next lap's break-check.
    * **claim** — :func:`~repro.core.constructs.emit_cas_claim` on the
      final free slot (``EMPTY -> key``), committing the value row and a
      ``[SET_INSERTED | SET_DISPLACED, bucket_addr]`` response (the
      status word is flipped to ``SET_DISPLACED`` by the first move).

    Any dead end — no EMPTY within ``max_search``, a window with nothing
    movable, ``max_moves`` exhausted — simply quiesces, leaving the
    pre-set default response ``[SET_NEEDS_RESIZE, 0]``; :meth:`commit`
    then discards the image's partial moves, so a failed SET leaves the
    store bit-identical (exactly like the bounded host oracle
    ``hopscotch.HopscotchTable.set_full``).

    **The unwrapped frame.** Verbs add constants; they do not reduce
    modulo the table.  So the image carries ``n_buckets + max_search``
    bucket/value rows where row ``r`` mirrors bucket ``r % n_buckets``,
    and every address this request touches is the *unwrapped* position
    ``home + d`` (``d < max_search``) — within one request each bucket
    appears at exactly one unwrapped position, so the two copies can
    never diverge mid-run.  :meth:`commit` folds the image back by
    per-word diff against the pre-state (at most one copy of any word
    changed), which also makes the multi-row effects of a bubble —
    unknowable from the response alone — commit exactly.
    """
    max_search: int = 0
    max_moves: int = 0

    def run_carried(self, mem, payload, max_steps: int = 512):
        """Refused: a failed bubble leaves moved rows in the image and the
        frame is unwrapped, so each displacer request runs against an
        image rebuilt from the arrays (:meth:`run_one`)."""
        raise NotImplementedError(
            "the displacer runs only against a rebuilt image (run_one)")

    def device_state(self, keys: jnp.ndarray,
                     vals: jnp.ndarray) -> machine.VMState:
        """Image with the shard slice scattered into the unwrapped frame.

        Each of the ``n + max_search`` rows gets ``[key, pad, val_ptr]``
        where ``pad`` is the resident key's home distance ``(row -
        home(key)) % n`` — the word the movability branch reads.  EMPTY
        rows get ``pad = H`` so no window offset can make them "movable"
        (they are never candidates in a valid table; the marker keeps
        arbitrary images safe too).
        """
        n, ext = self.n_buckets, self.n_buckets + self.max_search
        v = self.val_len
        rows = jnp.arange(ext, dtype=jnp.int32)
        src = rows % n
        k = keys.astype(jnp.int32)[src]
        pad = jnp.where(k != EMPTY_KEY,
                        (src - bucket_home(k, n)) % n,
                        self.neighborhood).astype(jnp.int32)
        mem = self.state0.mem
        mem = mem.at[self.table_base + rows * BUCKET_WORDS].set(k)
        mem = mem.at[self.table_base + rows * BUCKET_WORDS + 1].set(pad)
        vidx = (self.values_base + rows[:, None] * v
                + jnp.arange(v, dtype=jnp.int32)[None, :])
        mem = mem.at[vidx.reshape(-1)].set(
            vals.astype(jnp.int32)[src].reshape(-1))
        return self.state0._replace(mem=mem)

    def device_payloads(self, queries: jnp.ndarray, home: jnp.ndarray,
                        values: jnp.ndarray) -> jnp.ndarray:
        """``[key, value x V, home_addr]`` — one scattered home address;
        the chain derives every probe address from it (the unwrapped
        frame makes them plain ``home + d * BUCKET_WORDS`` sums)."""
        addrs = (self.table_base
                 + home.astype(jnp.int32) * BUCKET_WORDS)
        return jnp.concatenate(
            [queries[:, None].astype(jnp.int32),
             values.astype(jnp.int32).reshape(-1, self.val_len),
             addrs[:, None]], axis=1)

    def commit(self, out_mem: jnp.ndarray, payload: jnp.ndarray,
               keys: jnp.ndarray, vals: jnp.ndarray):
        """Fold a quiesced context back into the shard arrays by diff.

        A bubble touches up to ``2 * max_moves + 1`` bucket rows at
        positions the response does not enumerate; but any touched word
        lives in exactly one copy (primary row ``b`` or mirror ``n + b``,
        ``b < max_search``), so ``where(img != pre, img, mirror-merged)``
        reconstructs the post-state exactly.  Nothing commits unless the
        status is UPDATED/INSERTED/DISPLACED — a NEEDS_RESIZE run (or a
        zero-padded request, which quiesces in the match phase against
        the null guard) leaves the arrays bit-identical.
        """
        n, s, v = self.n_buckets, self.max_search, self.val_len
        status = out_mem[self.resp_region]
        applied = ((payload[0] != EMPTY_KEY)
                   & ((status == SET_UPDATED) | (status == SET_INSERTED)
                      | (status == SET_DISPLACED)))
        rows = jnp.arange(n, dtype=jnp.int32)
        mir = jnp.arange(s, dtype=jnp.int32)

        base_k = keys.astype(jnp.int32)
        img_k = out_mem[self.table_base + rows * BUCKET_WORDS]
        mir_k = out_mem[self.table_base + (n + mir) * BUCKET_WORDS]
        merged_k = base_k.at[:s].set(
            jnp.where(mir_k != base_k[:s], mir_k, base_k[:s]))
        new_k = jnp.where(img_k != base_k, img_k, merged_k)

        base_v = vals.astype(jnp.int32)
        cols = jnp.arange(v, dtype=jnp.int32)[None, :]
        img_v = out_mem[self.values_base + rows[:, None] * v + cols]
        mir_v = out_mem[self.values_base + (n + mir)[:, None] * v + cols]
        merged_v = base_v.at[:s].set(
            jnp.where(mir_v != base_v[:s], mir_v, base_v[:s]))
        new_v = jnp.where(img_v != base_v, img_v, merged_v)

        keys_out = jnp.where(applied, new_k, base_k).astype(keys.dtype)
        vals_out = jnp.where(applied, new_v, base_v).astype(vals.dtype)
        return (jnp.where(payload[0] == EMPTY_KEY, 0, status),
                keys_out, vals_out)

    def commit_torn(self, out_mem: jnp.ndarray, payload: jnp.ndarray,
                    keys: jnp.ndarray, vals: jnp.ndarray):
        """Fault-mode commit: the diff + mirror-merge fold of
        :meth:`commit` with the status gate removed.  An interrupted
        bubble's executed moves have physically landed (a half-done move
        leaves a duplicate key across two buckets); folding them back
        ungated is what lets ``fsck`` see — and recovery repair — the
        torn displacement."""
        n, s, v = self.n_buckets, self.max_search, self.val_len
        status = out_mem[self.resp_region]
        dead = payload[0] == EMPTY_KEY
        rows = jnp.arange(n, dtype=jnp.int32)
        mir = jnp.arange(s, dtype=jnp.int32)

        base_k = keys.astype(jnp.int32)
        img_k = out_mem[self.table_base + rows * BUCKET_WORDS]
        mir_k = out_mem[self.table_base + (n + mir) * BUCKET_WORDS]
        merged_k = base_k.at[:s].set(
            jnp.where(mir_k != base_k[:s], mir_k, base_k[:s]))
        new_k = jnp.where(img_k != base_k, img_k, merged_k)

        base_v = vals.astype(jnp.int32)
        cols = jnp.arange(v, dtype=jnp.int32)[None, :]
        img_v = out_mem[self.values_base + rows[:, None] * v + cols]
        mir_v = out_mem[self.values_base + (n + mir)[:, None] * v + cols]
        merged_v = base_v.at[:s].set(
            jnp.where(mir_v != base_v[:s], mir_v, base_v[:s]))
        new_v = jnp.where(img_v != base_v, img_v, merged_v)

        keys_out = jnp.where(dead, base_k, new_k).astype(keys.dtype)
        vals_out = jnp.where(dead, base_v, new_v).astype(vals.dtype)
        return jnp.where(dead, 0, status), keys_out, vals_out


@functools.lru_cache(maxsize=None)
def build_hopscotch_displacer(n_buckets: int, val_len: int,
                              neighborhood: int = 8, max_search: int = 16,
                              max_moves: int = 8) -> HopscotchShardDisplacer:
    """Build (and cache per geometry) the per-shard displacement chain.

    ``max_search`` bounds the free-slot probe from the home bucket (and
    sizes the unwrapped mirror rows); ``max_moves`` bounds the bubble.
    Both bounds are mirrored by the host oracle
    ``hopscotch.HopscotchTable.set_full``.
    """
    h, s, m = neighborhood, max_search, max_moves
    if h < 2:
        raise ValueError("displacement needs a neighborhood >= 2 "
                         "(the bubble window [free-H+1, free) is empty)")
    if not h <= s <= n_buckets:
        raise ValueError(
            f"max_search must be in [neighborhood, n_buckets], got {s}")
    if m < 1:
        raise ValueError("max_moves must be >= 1")
    if 1 + val_len + 1 > min(isa.MAX_SCATTER, isa.MSG_WORDS):
        raise ValueError(
            f"val_len {val_len} exceeds the one-SEND request budget")
    ext = n_buckets + s

    # exact image sizing: WQ slots (code) + data
    SCTL, SMOD, SFND = 9, 2, 4            # per search probe
    BCTL, BMOD = 7, 2                     # per break-check
    PCTL, PMOD, PMOVE = 13, 2, 20         # per window probe
    CLDRV, CLMOD = 9, 3
    # null-guard sizing: a zero-padded request derives its H probe
    # addresses from home_w = 0, so the guard's zero words must cover
    # every derived read — probe pi reads [pi*BW] and [pi*BW + 2] — and
    # the ghost update's value write of val_len words at val_ptr 0
    guard_slots = max(2, -(-((h - 1) * BUCKET_WORDS + 3) // isa.WR_WORDS),
                      -(-val_len // isa.WR_WORDS))
    wq_slots = (guard_slots + 2 + h * (3 + 9 + 3) + (h + 1)
                + s * (SCTL + SMOD + SFND) + (m + 1) * (BCTL + BMOD)
                + m * (h - 1) * (PCTL + PMOD + PMOVE) + CLDRV + CLMOD)
    data_words = (2 + 5 + 2 * val_len            # resp, carries, stages
                  + ext * val_len                # value rows (mirrored)
                  + ext * BUCKET_WORDS           # table (mirrored)
                  + (h + 1) * 18                 # match + claim templates
                  + 2 + val_len + 1)             # scatter table
    mem_words = -(-(wq_slots * isa.WR_WORDS + data_words + 32) // 128) * 128

    p = Program(mem_words)
    # WQ0: the null region a zero-padded request's match probes hit —
    # sized so every derived probe address (h-1)*BW + 2 and the ghost
    # update's val_len zero-write at val_ptr 0 land on guard zeros, never
    # on a live WR (the RECV's fields sit right behind it)
    guard = p.add_wq(guard_slots)

    resp = p.alloc(2, [SET_NEEDS_RESIZE, 0], "resp")
    key_w = p.word(0, "key")
    home_w = p.word(0, "home")
    free_w = p.word(0, "free")     # carry: free slot's (unwrapped) address
    dist_w = p.word(0, "dist")     # carry: its bucket distance from home
    cand_w = p.word(0, "cand")     # scratch: current window candidate
    val_stage = p.alloc(val_len, [0] * val_len, "val_stage")
    zeros_v = p.alloc(val_len, [0] * val_len, "zeros")
    values = p.alloc(ext * val_len, name="values")
    tbl_init = [0] * (ext * BUCKET_WORDS)
    for b in range(ext):
        tbl_init[b * BUCKET_WORDS + 2] = values + b * val_len
    table = p.alloc(ext * BUCKET_WORDS, tbl_init, "table")

    rq = p.add_wq(2)

    # --- match phase (shared emission; probe addrs derived from home) -----
    _, _, m_mods = _emit_set_match_phase(
        p, rq, h, key_w, val_stage, val_len, resp, home_w=home_w)

    # --- create the control-flow WQs up front (branches name successors) --
    sgate = p.add_wq(h + 1, ordering=isa.ORD_DOORBELL, managed=True)
    sctl = [p.add_wq(SCTL, ordering=isa.ORD_DOORBELL, managed=True,
                     initial_enable=0) for _ in range(s)]
    smod = [p.add_wq(SMOD, ordering=isa.ORD_DOORBELL, managed=True,
                     initial_enable=0) for _ in range(s)]
    sfnd = [p.add_wq(SFND, ordering=isa.ORD_DOORBELL, managed=True,
                     initial_enable=0) for _ in range(s)]
    bctl = [p.add_wq(BCTL, ordering=isa.ORD_DOORBELL, managed=True,
                     initial_enable=0) for _ in range(m + 1)]
    bmod = [p.add_wq(BMOD, ordering=isa.ORD_DOORBELL, managed=True,
                     initial_enable=0) for _ in range(m + 1)]
    pctl = [[p.add_wq(PCTL, ordering=isa.ORD_DOORBELL, managed=True,
                      initial_enable=0) for _ in range(h - 1)]
            for _ in range(m)]
    pmod = [[p.add_wq(PMOD, ordering=isa.ORD_DOORBELL, managed=True,
                      initial_enable=0) for _ in range(h - 1)]
            for _ in range(m)]
    pmove = [[p.add_wq(PMOVE, ordering=isa.ORD_DOORBELL, managed=True,
                       initial_enable=0) for _ in range(h - 1)]
             for _ in range(m)]
    cldrv = p.add_wq(CLDRV, ordering=isa.ORD_DOORBELL, managed=True,
                     initial_enable=0)
    clmod = p.add_wq(CLMOD, ordering=isa.ORD_DOORBELL, managed=True,
                     initial_enable=0)

    # --- search phase: gated on every match probe resolving un-hit --------
    for pi in range(h):
        sgate.wait(m_mods[pi], 3, tag=f"dp.nomatch{pi}")
    sgate.enable(sctl[0], upto=SCTL, tag="dp.search")
    sgate.initial_enable = sgate.n_posted + 1

    for si in range(s):
        ctl = sctl[si]

        def load_key(a_addr, b_addr, ctl=ctl, si=si):
            ctl.write(src=home_w, dst=ctl.future_wr_addr(2, "src"),
                      tag=f"dp.sp{si}")
            ctl.add(dst=ctl.future_wr_addr(1, "src"),
                    addend=si * BUCKET_WORDS, tag=f"dp.so{si}")
            ctl.read(src=0, dst=a_addr, ln=1, tag=f"dp.skey{si}")
            ctl.write(src=a_addr, dst=b_addr, tag=f"dp.scp{si}")

        nxt = (sctl[si + 1].index, SCTL) if si + 1 < s else (guard.index, 0)
        constructs.emit_enable_branch(
            ctl, smod[si], threshold=EMPTY_KEY,
            then_wq=sfnd[si].index, then_upto=SFND,
            else_wq=nxt[0], else_upto=nxt[1], load=load_key,
            tag=f"dp.sbr{si}")

        # found: latch the free slot's unwrapped address + home distance
        sfnd[si].write(src=home_w, dst=free_w, tag=f"dp.free{si}")
        sfnd[si].add(dst=free_w, addend=si * BUCKET_WORDS,
                     tag=f"dp.foff{si}")
        sfnd[si].write_imm(dst=dist_w, value=si, tag=f"dp.dist{si}")
        sfnd[si].enable(bctl[0], upto=BCTL, tag=f"dp.go{si}")

    # --- bubble laps: break-check + window scan + one move ----------------
    for li in range(m + 1):
        def load_dist(a_addr, b_addr, ctl=bctl[li], li=li):
            ctl.write(src=dist_w, dst=a_addr, tag=f"dp.bd{li}")
            ctl.write(src=dist_w, dst=b_addr, tag=f"dp.bd2{li}")

        cont = ((pctl[li][0].index, PCTL) if li < m else (guard.index, 0))
        constructs.emit_enable_branch(
            bctl[li], bmod[li], threshold=h - 1,
            then_wq=cldrv.index, then_upto=CLDRV,
            else_wq=cont[0], else_upto=cont[1], load=load_dist,
            tag=f"dp.brk{li}")

    cl_tmpl, cl_stage = _set_templates(p, val_stage, val_len, resp,
                                       SET_INSERTED)

    for li in range(m):
        for j in range(h - 1):
            back = h - 1 - j            # scan order: farthest-back first
            ctl = pctl[li][j]
            ctl.write(src=free_w, dst=cand_w, tag=f"dp.c{li}.{j}")
            ctl.add(dst=cand_w, addend=-back * BUCKET_WORDS,
                    tag=f"dp.cb{li}.{j}")

            def load_pad(a_addr, b_addr, ctl=ctl, back=back):
                ctl.write(src=cand_w, dst=ctl.future_wr_addr(2, "src"),
                          tag="dp.pp")
                ctl.add(dst=ctl.future_wr_addr(1, "src"), addend=1,
                        tag="dp.po")
                ctl.read(src=0, dst=a_addr, ln=1, tag="dp.pad")
                ctl.write(src=a_addr, dst=b_addr, tag="dp.pcp")
                ctl.add(dst=a_addr, addend=back, tag="dp.pb1")
                ctl.add(dst=b_addr, addend=back, tag="dp.pb2")

            nxt = ((pctl[li][j + 1].index, PCTL) if j + 1 < h - 1
                   else (guard.index, 0))
            constructs.emit_enable_branch(
                ctl, pmod[li][j], threshold=h - 1,
                then_wq=pmove[li][j].index, then_upto=PMOVE,
                else_wq=nxt[0], else_upto=nxt[1], load=load_pad,
                tag=f"dp.mv{li}.{j}")

            constructs.emit_displace_move(
                pmove[li][j], cand_w=cand_w, free_w=free_w, dist_w=dist_w,
                back=back, val_len=val_len, zeros=zeros_v,
                status_addr=cl_stage, status_val=SET_DISPLACED,
                next_wq=bctl[li + 1].index, next_upto=BCTL,
                empty_key=EMPTY_KEY, tag=f"dp.mv{li}.{j}")

    # --- claim phase: CAS-claim the final free slot -----------------------
    cldrv.write(src=free_w, dst=cldrv.future_wr_addr(2, "src"),
                tag="dp.clvp")
    cldrv.add(dst=cldrv.future_wr_addr(1, "src"), addend=2, tag="dp.clvo")
    cldrv.read(src=0, dst=cl_tmpl + isa.F_DST, ln=1, tag="dp.clv")
    cldrv.write(src=free_w, dst=cl_stage + 1, tag="dp.claddr")
    cldrv.write(src=free_w, dst=cldrv.future_wr_addr(2, "dst"),
                tag="dp.clcell")
    cldrv.write(src=key_w, dst=cldrv.future_wr_addr(1, "opb"),
                tag="dp.clnew")
    constructs.emit_cas_claim(
        cldrv, clmod, cell=0, expect=EMPTY_KEY, new=0, then_src=cl_tmpl,
        then_dst=clmod.future_wr_addr(1, "ctrl"), then_len=2 * isa.WR_WORDS)
    clmod.post(isa.NOOP, tag="dp.cle")        # event: value WRITE slot
    clmod.post(isa.NOOP, tag="dp.clf")        # event: response slot
    cldrv.enable(clmod, upto=3, tag="dp.clen")

    # RECV scatter: key, staged value words, the single home address
    tbl = p.scatter_table(
        [key_w] + [val_stage + j for j in range(val_len)] + [home_w])
    rq.recv(scatter_table=tbl, tag="dp.recv")

    spec, st0 = p.finalize()
    return HopscotchShardDisplacer(
        prog=p, spec=spec, state0=st0, n_buckets=n_buckets,
        val_len=val_len, neighborhood=neighborhood, table_base=table,
        values_base=values, resp_region=resp, recv_wq=rq.index,
        max_search=max_search, max_moves=max_moves)


# ---------------------------------------------------------------------------
# §5.6 extension — the table-growth MIGRATOR: online resize as a chain
# ---------------------------------------------------------------------------

def _mig_templates(p: Program, resp: int, status_default: int,
                   enable_wq: int, enable_upto: int):
    """16-word migrator template (two event WRs): a suppressed
    ``[status, bucket_addr]`` response WRITE and a suppressed **ENABLE**
    releasing the vacate path.  The ENABLE-as-event is what lets one
    Fig.-6 conversion both answer and hand control to the retirement WQ
    without a third event slot (a 3-WR template would exceed the one-WRITE
    ``MAX_COPY`` budget)."""
    stage = p.alloc(2, [status_default, 0])
    tmpl = p.alloc(2 * isa.WR_WORDS, [
        isa.pack_ctrl(isa.WRITE, 0), isa.FLAG_SUPPRESS_COMPLETION,
        stage, resp, 2, 0, 0, -1,
        isa.pack_ctrl(isa.ENABLE, 0), isa.FLAG_SUPPRESS_COMPLETION,
        -1, -1, 1, enable_upto, enable_wq, -1])
    return tmpl, stage


@dataclasses.dataclass(frozen=True, eq=False)
class HopscotchShardMigrator:
    """One lap of online table growth (§5.6 "resize *while* serving").

    The store grows by migrating one **source bucket** per request from
    the old ``n``-bucket frame into a doubled ``2n``-bucket frame that
    serves concurrently (the double-frame mode in ``kvstore.store``).
    The chain per lap:

    * **select** — the new home under the doubled geometry is
      ``h_old + sel * n`` where ``sel`` is the next hash bit the wider
      mask exposes (``n`` must be a power of two).  The client scatters
      ``sel`` and the *lower-half* probe base; a Calc-verb branch
      (:func:`repro.core.constructs.emit_enable_branch` on ``sel``)
      either releases the probes directly or first ADDs ``n`` buckets to
      the base — the mask recompute, in verbs.
    * **match** — H parallel probe pairs test the new-frame neighborhood
      for the key.  A hit means the key was re-written into the new
      frame while this stale copy still sat in the old frame (the
      double-frame SET routes writes by watermark): the conversion lands
      ``[MIG_DISCARDED, addr]`` and releases the **vacate** WQ directly —
      the old copy is dropped, the newer value wins.  Missing event
      completions starve the claim phase.
    * **claim** — gated on an all-miss match, sequential
      :func:`~repro.core.constructs.emit_cas_claim` probes CAS the first
      EMPTY new-frame bucket ``EMPTY -> key``; the winning conversion
      lands ``[MIG_MOVED, addr]`` and releases the per-probe **copy** WQ,
      whose WRITE moves the old value row across frames (src/dst both
      patched from the frames' val_ptrs) before releasing the vacate.
    * **vacate** — :func:`~repro.core.constructs.emit_bucket_vacate` on
      the source bucket: CAS ``key -> EMPTY`` (comparand re-read), stale
      value row zeroed.  Runs only after the key is safe in the new
      frame, so a concurrent double-frame get always finds the key in at
      least one frame.

    A full new-frame neighborhood quiesces with the pre-set default
    ``[MIG_NEEDS_DISPLACE, 0]`` and the source bucket untouched — the
    caller escalates through the new frame's displacer chain.  The new
    frame is mirrored unwrapped (``2n + H - 1`` rows) exactly like the
    displacer's frame, and :meth:`commit` folds it back by per-word diff.
    """
    prog: Program
    spec: machine.MachineSpec
    state0: machine.VMState
    n_buckets: int             # OLD frame size n; the new frame holds 2n
    val_len: int
    neighborhood: int
    old_table_base: int
    old_values_base: int
    new_table_base: int
    new_values_base: int
    resp_region: int
    recv_wq: int

    resp_words = 2             # [status, bucket addr]

    @property
    def engine(self) -> ChainEngine:
        return ChainEngine.for_spec(self.spec)

    @property
    def fuel(self) -> int:
        """Exact step budget (no WQ recycles; see
        :attr:`HopscotchShardWriter.fuel`)."""
        return int(np.asarray(self.state0.tail).sum()) + 1

    def device_state(self, old_keys: jnp.ndarray, old_vals: jnp.ndarray,
                     new_keys: jnp.ndarray,
                     new_vals: jnp.ndarray) -> machine.VMState:
        """Image with both frames scattered in (new frame unwrapped:
        rows ``r >= 2n`` mirror ``r - 2n``).  Pure jnp — works on traced
        arrays inside ``shard_map``/``scan``."""
        n, h, v = self.n_buckets, self.neighborhood, self.val_len
        ext = 2 * n + h - 1
        mem = self.state0.mem

        rows_o = jnp.arange(n, dtype=jnp.int32)
        mem = mem.at[self.old_table_base + rows_o * BUCKET_WORDS].set(
            old_keys.astype(jnp.int32))
        oidx = (self.old_values_base + rows_o[:, None] * v
                + jnp.arange(v, dtype=jnp.int32)[None, :])
        mem = mem.at[oidx.reshape(-1)].set(
            old_vals.astype(jnp.int32).reshape(-1))

        rows_n = jnp.arange(ext, dtype=jnp.int32)
        src = rows_n % (2 * n)
        mem = mem.at[self.new_table_base + rows_n * BUCKET_WORDS].set(
            new_keys.astype(jnp.int32)[src])
        nidx = (self.new_values_base + rows_n[:, None] * v
                + jnp.arange(v, dtype=jnp.int32)[None, :])
        mem = mem.at[nidx.reshape(-1)].set(
            new_vals.astype(jnp.int32)[src].reshape(-1))
        return self.state0._replace(mem=mem)

    def device_payloads(self, buckets: jnp.ndarray,
                        old_keys: jnp.ndarray) -> jnp.ndarray:
        """Request assembly: ``[key, sel, old_addr, lo_base]`` per source
        bucket.  ``buckets``: (B,) int32 source-bucket indices;
        ``old_keys``: the shard's (n,) old-frame key column.  The client
        computes the hash (as everywhere) and sends the *select bit* the
        doubled mask exposes plus the lower-half probe base; the chain
        recomputes the actual home by branching on ``sel``.  Rows whose
        source bucket is EMPTY are zeroed — inert padding."""
        n = self.n_buckets
        shift = n.bit_length() - 1
        k = old_keys.astype(jnp.int32)[buckets]
        live = k != EMPTY_KEY
        h_old = bucket_home(k, n)
        ku = k.astype(jnp.uint32) * jnp.uint32(_HASH_MULT)
        sel = ((ku >> shift) & jnp.uint32(1)).astype(jnp.int32)
        old_addr = (self.old_table_base
                    + buckets.astype(jnp.int32) * BUCKET_WORDS)
        lo = self.new_table_base + h_old * BUCKET_WORDS
        pay = jnp.stack([k, sel, old_addr, lo], axis=1)
        return pay * live[:, None].astype(pay.dtype)

    def commit(self, out_mem: jnp.ndarray, payload: jnp.ndarray,
               old_keys: jnp.ndarray, old_vals: jnp.ndarray,
               new_keys: jnp.ndarray, new_vals: jnp.ndarray):
        """Fold one quiesced lap back into both frames.

        Old frame rows are read straight off the image (the lap touches
        only the source bucket); the new frame folds by per-word diff
        with the mirror merge (a claim may land on an unwrapped row).
        Nothing commits unless the status is MOVED/DISCARDED — a
        NEEDS_DISPLACE lap (or a zero-padded slot) leaves both frames
        bit-identical.  Returns ``(status, old_keys, old_vals, new_keys,
        new_vals)``."""
        n, h, v = self.n_buckets, self.neighborhood, self.val_len
        status = out_mem[self.resp_region]
        applied = ((payload[0] != EMPTY_KEY)
                   & ((status == MIG_MOVED) | (status == MIG_DISCARDED)))

        rows_o = jnp.arange(n, dtype=jnp.int32)
        img_ko = out_mem[self.old_table_base + rows_o * BUCKET_WORDS]
        cols = jnp.arange(v, dtype=jnp.int32)[None, :]
        img_vo = out_mem[self.old_values_base + rows_o[:, None] * v + cols]

        rows_n = jnp.arange(2 * n, dtype=jnp.int32)
        mir = jnp.arange(h - 1, dtype=jnp.int32)
        base_kn = new_keys.astype(jnp.int32)
        img_kn = out_mem[self.new_table_base + rows_n * BUCKET_WORDS]
        mir_kn = out_mem[self.new_table_base + (2 * n + mir) * BUCKET_WORDS]
        merged_kn = base_kn.at[:h - 1].set(
            jnp.where(mir_kn != base_kn[:h - 1], mir_kn, base_kn[:h - 1]))
        new_kn = jnp.where(img_kn != base_kn, img_kn, merged_kn)

        base_vn = new_vals.astype(jnp.int32)
        img_vn = out_mem[self.new_values_base + rows_n[:, None] * v + cols]
        mir_vn = out_mem[self.new_values_base + (2 * n + mir)[:, None] * v
                         + cols]
        merged_vn = base_vn.at[:h - 1].set(
            jnp.where(mir_vn != base_vn[:h - 1], mir_vn,
                      base_vn[:h - 1]))
        new_vn = jnp.where(img_vn != base_vn, img_vn, merged_vn)

        old_keys_out = jnp.where(applied, img_ko,
                                 old_keys.astype(jnp.int32))
        old_vals_out = jnp.where(applied, img_vo,
                                 old_vals.astype(jnp.int32))
        new_keys_out = jnp.where(applied, new_kn, base_kn)
        new_vals_out = jnp.where(applied, new_vn, base_vn)
        return (jnp.where(payload[0] == EMPTY_KEY, 0, status),
                old_keys_out.astype(old_keys.dtype),
                old_vals_out.astype(old_vals.dtype),
                new_keys_out.astype(new_keys.dtype),
                new_vals_out.astype(new_vals.dtype))

    def commit_torn(self, out_mem: jnp.ndarray, payload: jnp.ndarray,
                    old_keys: jnp.ndarray, old_vals: jnp.ndarray,
                    new_keys: jnp.ndarray, new_vals: jnp.ndarray):
        """Fault-mode commit: :meth:`commit`'s fold with the status gate
        removed.  A lap interrupted between the new-frame claim and the
        old-frame vacate has physically written both/either — folding
        the torn image back ungated exposes the cross-frame duplicate
        (or the claimed-but-uncopied row) to ``fsck``."""
        n, h, v = self.n_buckets, self.neighborhood, self.val_len
        status = out_mem[self.resp_region]
        dead = payload[0] == EMPTY_KEY

        rows_o = jnp.arange(n, dtype=jnp.int32)
        img_ko = out_mem[self.old_table_base + rows_o * BUCKET_WORDS]
        cols = jnp.arange(v, dtype=jnp.int32)[None, :]
        img_vo = out_mem[self.old_values_base + rows_o[:, None] * v + cols]

        rows_n = jnp.arange(2 * n, dtype=jnp.int32)
        mir = jnp.arange(h - 1, dtype=jnp.int32)
        base_kn = new_keys.astype(jnp.int32)
        img_kn = out_mem[self.new_table_base + rows_n * BUCKET_WORDS]
        mir_kn = out_mem[self.new_table_base + (2 * n + mir) * BUCKET_WORDS]
        merged_kn = base_kn.at[:h - 1].set(
            jnp.where(mir_kn != base_kn[:h - 1], mir_kn, base_kn[:h - 1]))
        new_kn = jnp.where(img_kn != base_kn, img_kn, merged_kn)

        base_vn = new_vals.astype(jnp.int32)
        img_vn = out_mem[self.new_values_base + rows_n[:, None] * v + cols]
        mir_vn = out_mem[self.new_values_base + (2 * n + mir)[:, None] * v
                         + cols]
        merged_vn = base_vn.at[:h - 1].set(
            jnp.where(mir_vn != base_vn[:h - 1], mir_vn,
                      base_vn[:h - 1]))
        new_vn = jnp.where(img_vn != base_vn, img_vn, merged_vn)

        old_keys_out = jnp.where(dead, old_keys.astype(jnp.int32), img_ko)
        old_vals_out = jnp.where(dead, old_vals.astype(jnp.int32), img_vo)
        new_keys_out = jnp.where(dead, base_kn, new_kn)
        new_vals_out = jnp.where(dead, base_vn, new_vn)
        return (jnp.where(dead, 0, status),
                old_keys_out.astype(old_keys.dtype),
                old_vals_out.astype(old_vals.dtype),
                new_keys_out.astype(new_keys.dtype),
                new_vals_out.astype(new_vals.dtype))

    def run_one(self, old_keys: jnp.ndarray, old_vals: jnp.ndarray,
                new_keys: jnp.ndarray, new_vals: jnp.ndarray,
                payload: jnp.ndarray, max_steps: int = 2048):
        """One migration lap: build the double-frame image, deliver the
        trigger, run to quiescence, commit.  Returns ``(status,
        old_keys, old_vals, new_keys, new_vals)``."""
        st = machine.deliver(
            self.device_state(old_keys, old_vals, new_keys, new_vals),
            self.recv_wq, payload)
        out = self.engine.run(st, max_steps)
        return self.commit(out.mem, payload, old_keys, old_vals,
                           new_keys, new_vals)

    def run_one_faulted(self, old_keys: jnp.ndarray, old_vals: jnp.ndarray,
                        new_keys: jnp.ndarray, new_vals: jnp.ndarray,
                        payload: jnp.ndarray, max_steps: int, faults):
        """:meth:`run_one` under a scalar
        :class:`repro.core.faults.FaultPlan`: an armed row commits the
        torn image (:meth:`commit_torn`); a disarmed row commits through
        the status-gated fold, bit-exact with :meth:`run_one`."""
        st = machine.deliver(
            self.device_state(old_keys, old_vals, new_keys, new_vals),
            self.recv_wq, payload)
        out = self.engine.run(st, max_steps, faults)
        torn = self.commit_torn(out.mem, payload, old_keys, old_vals,
                                new_keys, new_vals)
        clean = self.commit(out.mem, payload, old_keys, old_vals,
                            new_keys, new_vals)
        act = faults.active()
        return tuple(jnp.where(act, t, c) for t, c in zip(torn, clean))


@functools.lru_cache(maxsize=None)
def build_hopscotch_migrator(n_buckets: int, val_len: int,
                             neighborhood: int = 8
                             ) -> HopscotchShardMigrator:
    """Build (and cache per geometry) the per-shard table-growth chain.

    ``n_buckets`` is the OLD frame size and must be a power of two — the
    doubled geometry's home recompute is "one more mask bit", which is
    what the in-chain select branch implements.
    """
    h = neighborhood
    if h < 1:
        raise ValueError("neighborhood must be >= 1")
    if n_buckets < 1 or (n_buckets & (n_buckets - 1)):
        raise ValueError(
            f"resize needs a power-of-two bucket count (the doubled "
            f"mask exposes exactly one more hash bit), got {n_buckets}")
    if val_len > isa.MAX_COPY:
        raise ValueError(
            f"val_len {val_len} exceeds the one-WRITE row copy budget")
    n = n_buckets
    ext = 2 * n + h - 1

    # exact image sizing (code slots + data words)
    SELDRV, SELMOD = 11 + h, 2
    GOLO, GOHI = h, h + 1
    MDRV, MEXE, MMOD = 5, 3, 3
    CDRV, CEXE, CMOD = 7 * h, 4 * h, 3 * h
    VCLAIM, VMATCH = 2, 8
    # null-guard: a zero-padded slot probes [0, (h-1)*BW + key] and its
    # ghost vacate reads [0..2] and zero-writes val_len words at ptr 0
    guard_slots = max(2, -(-((h - 1) * BUCKET_WORDS + 3) // isa.WR_WORDS),
                      -(-val_len // isa.WR_WORDS))
    wq_slots = (guard_slots + 2 + SELDRV + SELMOD + GOLO + GOHI
                + h * (MDRV + MEXE + MMOD) + CDRV + CEXE + CMOD
                + h * VCLAIM + VMATCH)
    data_words = (2 + 5 + val_len                    # resp, words, zeros
                  + n * (val_len + BUCKET_WORDS)     # old frame
                  + ext * (val_len + BUCKET_WORDS)   # new frame (mirrored)
                  + 2 * h * 18                       # match+claim templates
                  + 1 + 4)                           # scatter table
    mem_words = -(-(wq_slots * isa.WR_WORDS + data_words + 32) // 128) * 128

    p = Program(mem_words)
    guard = p.add_wq(guard_slots)          # WQ0: the padding null region

    resp = p.alloc(2, [MIG_NEEDS_DISPLACE, 0], "resp")
    key_w = p.word(0, "key")
    sel_w = p.word(0, "sel")               # the doubled mask's new bit
    old_addr_w = p.word(0, "old_addr")     # source bucket (old frame)
    base_w = p.word(0, "base")             # probe base (new frame, lo half)
    vptr_w = p.word(0, "vptr")             # source bucket's value row
    zeros_v = p.alloc(val_len, [0] * val_len, "zeros")

    values_old = p.alloc(n * val_len, name="values_old")
    tbl_o = [0] * (n * BUCKET_WORDS)
    for b in range(n):
        tbl_o[b * BUCKET_WORDS + 2] = values_old + b * val_len
    table_old = p.alloc(n * BUCKET_WORDS, tbl_o, "table_old")
    values_new = p.alloc(ext * val_len, name="values_new")
    tbl_n = [0] * (ext * BUCKET_WORDS)
    for b in range(ext):
        tbl_n[b * BUCKET_WORDS + 2] = values_new + b * val_len
    table_new = p.alloc(ext * BUCKET_WORDS, tbl_n, "table_new")

    rq = p.add_wq(2)

    # --- control-flow WQs up front (templates/branches name successors) ---
    seldrv = p.add_wq(SELDRV, ordering=isa.ORD_DOORBELL, managed=True)
    selmod = p.add_wq(SELMOD, ordering=isa.ORD_DOORBELL, managed=True,
                      initial_enable=0)
    golo = p.add_wq(GOLO, ordering=isa.ORD_DOORBELL, managed=True,
                    initial_enable=0)
    gohi = p.add_wq(GOHI, ordering=isa.ORD_DOORBELL, managed=True,
                    initial_enable=0)
    vmatch = p.add_wq(VMATCH, ordering=isa.ORD_DOORBELL, managed=True,
                      initial_enable=0)
    vclaim = [p.add_wq(VCLAIM, ordering=isa.ORD_DOORBELL, managed=True,
                       initial_enable=0) for _ in range(h)]

    # --- vacate: retire the source bucket once the key is safe -----------
    constructs.emit_bucket_vacate(vmatch, bucket_w=old_addr_w,
                                  val_len=val_len, zeros=zeros_v,
                                  empty_key=EMPTY_KEY, tag="mg.vac")

    # --- per-probe cross-frame value copy (claim path only) --------------
    vclaim_wrs = []
    for pi in range(h):
        vw = vclaim[pi].write(src=0, dst=0, ln=val_len, tag=f"mg.vcp{pi}")
        vclaim[pi].enable(vmatch, upto=vmatch.n_posted, tag=f"mg.vgo{pi}")
        vclaim_wrs.append(vw)

    # --- match phase: H parallel probe pairs against the new frame -------
    rd1s, m_mods, m_drvs = [], [], []
    for pi in range(h):
        m_tmpl, m_stage = _mig_templates(p, resp, MIG_DISCARDED,
                                         vmatch.index, vmatch.n_posted)
        mmod = p.add_wq(MMOD, ordering=isa.ORD_DOORBELL, managed=True,
                        initial_enable=0)
        mdrv = p.add_wq(MDRV, ordering=isa.ORD_DOORBELL, managed=True,
                        initial_enable=0)
        mexe = p.add_wq(MEXE, ordering=isa.ORD_DOORBELL, managed=True,
                        initial_enable=3)

        c_i = mmod.post(isa.NOOP, src=m_tmpl,
                        dst=mmod.future_wr_addr(1, "ctrl"),
                        ln=2 * isa.WR_WORDS, tag=f"mg.mc{pi}")
        mmod.post(isa.NOOP, tag=f"mg.me{pi}")     # event: response slot
        mmod.post(isa.NOOP, tag=f"mg.mf{pi}")     # event: ENABLE(vacate)

        mdrv.write(src=base_w, dst=mdrv.future_wr_addr(2, "src"),
                   tag=f"mg.mb{pi}")              # probe addr <- base + d*BW
        mdrv.add(dst=mdrv.future_wr_addr(1, "src"),
                 addend=pi * BUCKET_WORDS, tag=f"mg.mo{pi}")
        rd1 = mdrv.read(src=0, dst=c_i.ctrl_addr, ln=1, tag=f"mg.mr{pi}")
        mdrv.write(src=key_w, dst=mexe.future_wr_addr(1, "opa"),
                   tag=f"mg.mk{pi}")              # CAS comparand <- key
        last = mdrv.write(src=rd1.addr("src"), dst=m_stage + 1,
                          tag=f"mg.ma{pi}")       # match addr -> response

        mexe.wait(mdrv, last.completion_count, tag=f"mg.ms{pi}")
        mexe.cas(dst=c_i.ctrl_addr, old=isa.pack_ctrl(isa.NOOP, 0),
                 new=isa.pack_ctrl(isa.WRITE, 0), tag=f"mg.mx{pi}")
        mexe.enable(mmod, upto=3, tag=f"mg.men{pi}")
        rd1s.append(rd1)
        m_mods.append(mmod)
        m_drvs.append(mdrv)

    # --- claim phase: sequential CAS-claims, gated on an all-miss match --
    cdrv = p.add_wq(CDRV, ordering=isa.ORD_DOORBELL, managed=True)
    cexe = p.add_wq(CEXE, ordering=isa.ORD_DOORBELL, managed=True)
    cmod = p.add_wq(CMOD, ordering=isa.ORD_DOORBELL, managed=True,
                    initial_enable=0)

    claims = []
    for pi in range(h):
        cl_tmpl, cl_stage = _mig_templates(p, resp, MIG_MOVED,
                                           vclaim[pi].index, VCLAIM)
        if pi == 0:
            cexe.wait(cdrv, CDRV, tag="mg.cgate")
        else:
            cexe.wait(cmod, 3 * pi, tag=f"mg.cseq{pi}")
        refs = constructs.emit_cas_claim(
            cexe, cmod, cell=0, expect=EMPTY_KEY, new=0, then_src=cl_tmpl,
            then_dst=cmod.future_wr_addr(1, "ctrl"),
            then_len=2 * isa.WR_WORDS)
        cmod.post(isa.NOOP, tag=f"mg.ce{pi}")     # event: response slot
        cmod.post(isa.NOOP, tag=f"mg.cf{pi}")     # event: ENABLE(copy)
        cexe.enable(cmod, upto=3 * (pi + 1), tag=f"mg.cen{pi}")
        claims.append((refs, cl_stage))
    cexe.initial_enable = cexe.n_posted + 1

    for pi in range(h):
        cdrv.wait(m_mods[pi], 3, tag=f"mg.nomatch{pi}")
    for pi, (refs, cl_stage) in enumerate(claims):
        cdrv.write(src=rd1s[pi].addr("src"), dst=refs.cell_dst_addr,
                   tag=f"mg.cdst{pi}")            # claim the probed bucket
        cdrv.write(src=key_w, dst=refs.new_opb_addr,
                   tag=f"mg.cnew{pi}")            # CAS new <- key
        cdrv.write(src=rd1s[pi].addr("src"),
                   dst=cdrv.future_wr_addr(2, "src"), tag=f"mg.cvp{pi}")
        cdrv.add(dst=cdrv.future_wr_addr(1, "src"), addend=2,
                 tag=f"mg.cvo{pi}")
        cdrv.read(src=0, dst=vclaim_wrs[pi].addr("dst"), ln=1,
                  tag=f"mg.cvr{pi}")              # claimed val_ptr -> copy dst
        cdrv.write(src=rd1s[pi].addr("src"), dst=cl_stage + 1,
                   tag=f"mg.caddr{pi}")           # claimed addr -> response
    cdrv.initial_enable = cdrv.n_posted + 1

    # --- select: the doubled mask's new bit, as a Calc-verb branch -------
    seldrv.wait(rq, 1, tag="mg.trig")
    # source value row -> every copy WR's src (the old row READ)
    seldrv.write(src=old_addr_w, dst=seldrv.future_wr_addr(2, "src"),
                 tag="mg.vp_p")
    seldrv.add(dst=seldrv.future_wr_addr(1, "src"), addend=2, tag="mg.vp_o")
    seldrv.read(src=0, dst=vptr_w, ln=1, tag="mg.vp")
    for pi in range(h):
        seldrv.write(src=vptr_w, dst=vclaim_wrs[pi].addr("src"),
                     tag=f"mg.vsrc{pi}")

    def load_sel(a_addr, b_addr):
        seldrv.write(src=sel_w, dst=a_addr, tag="mg.s1")
        seldrv.write(src=sel_w, dst=b_addr, tag="mg.s2")

    constructs.emit_enable_branch(
        seldrv, selmod, threshold=0,
        then_wq=golo.index, then_upto=GOLO,
        else_wq=gohi.index, else_upto=GOHI, load=load_sel, tag="mg.sel")
    seldrv.initial_enable = seldrv.n_posted + 1

    for pi in range(h):
        golo.enable(m_drvs[pi], upto=MDRV + 1, tag=f"mg.lo{pi}")
    gohi.add(dst=base_w, addend=n * BUCKET_WORDS, tag="mg.hi")
    for pi in range(h):
        gohi.enable(m_drvs[pi], upto=MDRV + 1, tag=f"mg.hi{pi}")

    # RECV scatter: key, select bit, source bucket, lo probe base
    tbl = p.scatter_table([key_w, sel_w, old_addr_w, base_w])
    rq.recv(scatter_table=tbl, tag="mg.recv")

    spec, st0 = p.finalize()
    return HopscotchShardMigrator(
        prog=p, spec=spec, state0=st0, n_buckets=n, val_len=val_len,
        neighborhood=h, old_table_base=table_old,
        old_values_base=values_old, new_table_base=table_new,
        new_values_base=values_new, resp_region=resp, recv_wq=rq.index)


# ---------------------------------------------------------------------------
# the Memcached lifecycle verbs: DELETE and the CLOCK expiry sweeper
# ---------------------------------------------------------------------------

def _emit_delete_probes(p: Program, rq, h: int, val_len: int, key_w: int,
                        resp: int, zeros: int):
    """The DELETE programs' match-and-vacate phase: H parallel probes.

    Migrator-shaped (``_mig_templates`` conversions, ENABLE-as-event),
    but with no claim phase — a delete of an absent key does nothing, so
    an all-miss batch simply quiesces on the pre-set ``[DEL_MISS, 0]``
    default.  Each probe READs its bucket key onto a conditional WR's
    control word and CAS-tests it against the query key; a hit converts
    the conditional into a template copy whose two suppressed events land
    ``[DEL_DELETED, bucket_addr]`` in the response region and ENABLE the
    probe's private vacate WQ — :func:`repro.core.constructs.
    emit_bucket_vacate` on the matched bucket (re-read-comparand CAS
    ``key -> EMPTY``, then the stale value row zeroed).  The hopscotch
    invariant (a key occupies at most one bucket) means at most one
    probe converts per request.  Shared by
    :func:`build_hopscotch_deleter` and the delete lanes of
    :func:`build_multi_writer_group`.  Returns the probe READs (their
    ``src`` fields are the RECV scatter targets).
    """
    VAC = 8                    # emit_bucket_vacate's exact WR count
    rd1s = []
    for pi in range(h):
        vac = p.add_wq(VAC, ordering=isa.ORD_DOORBELL, managed=True,
                       initial_enable=0)
        m_tmpl, m_stage = _mig_templates(p, resp, DEL_DELETED,
                                         vac.index, VAC)
        mmod = p.add_wq(3, ordering=isa.ORD_DOORBELL, managed=True,
                        initial_enable=0)
        mdrv = p.add_wq(4, ordering=isa.ORD_DOORBELL, managed=True)
        mexe = p.add_wq(3, ordering=isa.ORD_DOORBELL, managed=True,
                        initial_enable=3)

        c_i = mmod.post(isa.NOOP, src=m_tmpl,
                        dst=mmod.future_wr_addr(1, "ctrl"),
                        ln=2 * isa.WR_WORDS, tag=f"dl.mc{pi}")
        mmod.post(isa.NOOP, tag=f"dl.me{pi}")     # event: response slot
        mmod.post(isa.NOOP, tag=f"dl.mf{pi}")     # event: ENABLE(vacate)

        mdrv.wait(rq, 1, tag=f"dl.trig{pi}")
        mdrv.write(src=key_w, dst=mexe.future_wr_addr(1, "opa"),
                   tag=f"dl.key{pi}")             # CAS comparand <- key
        rd1 = mdrv.read(src=0, dst=c_i.ctrl_addr, ln=1,
                        tag=f"dl.read{pi}")       # src RECV-scattered
        last = mdrv.write(src=rd1.addr("src"), dst=m_stage + 1,
                          tag=f"dl.addr{pi}")     # bucket addr -> response
        mdrv.initial_enable = mdrv.n_posted + 1

        mexe.wait(mdrv, last.completion_count, tag=f"dl.sync{pi}")
        mexe.cas(dst=c_i.ctrl_addr, old=isa.pack_ctrl(isa.NOOP, 0),
                 new=isa.pack_ctrl(isa.WRITE, 0), tag=f"dl.cas{pi}")
        mexe.enable(mmod, upto=3, tag=f"dl.en{pi}")

        # the vacate reads its bucket address out of the probe READ's own
        # src field — the scattered cell itself, no copy needed
        constructs.emit_bucket_vacate(vac, bucket_w=rd1.addr("src"),
                                      val_len=val_len, zeros=zeros,
                                      empty_key=EMPTY_KEY,
                                      tag=f"dl.vac{pi}")
        rd1s.append(rd1)
    return rd1s


@dataclasses.dataclass(frozen=True, eq=False)
class HopscotchShardDeleter:
    """The delete-side companion of :class:`HopscotchShardWriter` — the
    verb that makes the store a *cache* (a KV store that can never forget
    is not one).  The client SEND carries ``[key, probe-bucket addrs x
    H]``; the chain is a match phase feeding per-probe
    :func:`repro.core.constructs.emit_bucket_vacate` retirements (see
    :func:`_emit_delete_probes`), so the bucket transition ``key ->
    EMPTY`` is a re-read-comparand CAS against the table itself and the
    value row is zeroed before the response commits — exactly the
    migrator's retirement discipline, reused verbatim.

    Bit-exact with :func:`repro.kvstore.hopscotch.delete_many`
    (:meth:`HopscotchTable.delete <repro.kvstore.hopscotch.
    HopscotchTable.delete>` applied in order); commit/fault semantics
    mirror the writer's (status-gated fold vs torn-image readback).
    """
    prog: Program
    spec: machine.MachineSpec
    state0: machine.VMState
    n_buckets: int
    val_len: int
    neighborhood: int
    table_base: int
    values_base: int
    resp_region: int
    recv_wq: int

    resp_words = 2                     # [status, bucket addr]

    @property
    def engine(self) -> ChainEngine:
        return ChainEngine.for_spec(self.spec)

    @property
    def fuel(self) -> int:
        """Exact step budget (no WQ recycles; see
        :attr:`HopscotchShardWriter.fuel`)."""
        return int(np.asarray(self.state0.tail).sum()) + 1

    def device_state(self, keys: jnp.ndarray,
                     vals: jnp.ndarray) -> machine.VMState:
        """Image with this shard's authoritative slice scattered in
        (see :meth:`HopscotchShardWriter.device_state`)."""
        rows = jnp.arange(self.n_buckets, dtype=jnp.int32)
        mem = self.state0.mem
        mem = mem.at[self.table_base + rows * BUCKET_WORDS].set(
            keys.astype(jnp.int32))
        vidx = (self.values_base + rows[:, None] * self.val_len
                + jnp.arange(self.val_len, dtype=jnp.int32)[None, :])
        mem = mem.at[vidx.reshape(-1)].set(
            vals.astype(jnp.int32).reshape(-1))
        return self.state0._replace(mem=mem)

    def device_payloads(self, queries: jnp.ndarray,
                        home: jnp.ndarray) -> jnp.ndarray:
        """Client-side request assembly: ``[key, probe addrs x H]``."""
        h = self.neighborhood
        offs = jnp.arange(h, dtype=jnp.int32)
        rows = (home[:, None] + offs[None, :]) % self.n_buckets
        addrs = (self.table_base + rows * BUCKET_WORDS).astype(jnp.int32)
        return jnp.concatenate(
            [queries[:, None].astype(jnp.int32), addrs], axis=1)

    def commit(self, out_mem: jnp.ndarray, payload: jnp.ndarray,
               keys: jnp.ndarray, vals: jnp.ndarray):
        """Fold one quiesced context's effects into the shard arrays:
        a ``DEL_DELETED`` response vacates the reported bucket (key ->
        EMPTY, value row zeroed); a miss commits nothing.  Padded rows
        (key 0) report status 0."""
        status = out_mem[self.resp_region]
        addr = out_mem[self.resp_region + 1]
        applied = (payload[0] != EMPTY_KEY) & (status == DEL_DELETED)
        row = jnp.where(applied,
                        (addr - self.table_base) // BUCKET_WORDS, 0)
        keys = keys.at[row].set(
            jnp.where(applied, EMPTY_KEY, keys[row]))
        vals = vals.at[row].set(
            jnp.where(applied, jnp.zeros_like(vals[row]), vals[row]))
        return jnp.where(payload[0] == EMPTY_KEY, 0, status), keys, vals

    def commit_torn(self, out_mem: jnp.ndarray, payload: jnp.ndarray,
                    keys: jnp.ndarray, vals: jnp.ndarray):
        """Fault-mode commit: the torn image itself (see
        :meth:`HopscotchShardWriter.commit_torn`) — a vacate CAS that
        landed without its row zeroing is exactly what fsck's
        stale-row/torn-vacate classifiers exist for."""
        rows = jnp.arange(self.n_buckets, dtype=jnp.int32)
        keys_out = out_mem[self.table_base + rows * BUCKET_WORDS]
        cols = jnp.arange(self.val_len, dtype=jnp.int32)[None, :]
        vals_out = out_mem[self.values_base
                           + rows[:, None] * self.val_len + cols]
        status = out_mem[self.resp_region]
        return (jnp.where(payload[0] == EMPTY_KEY, 0, status),
                keys_out.astype(keys.dtype), vals_out.astype(vals.dtype))

    def run_one(self, keys: jnp.ndarray, vals: jnp.ndarray,
                payload: jnp.ndarray, max_steps: int = 512):
        """Serve one assembled DELETE against the shard arrays.
        Returns ``(status, new_keys, new_vals)``."""
        st = machine.deliver(self.device_state(keys, vals), self.recv_wq,
                             payload)
        out = self.engine.run(st, max_steps)
        return self.commit(out.mem, payload, keys, vals)

    def run_one_faulted(self, keys: jnp.ndarray, vals: jnp.ndarray,
                        payload: jnp.ndarray, max_steps: int, faults):
        """:meth:`run_one` under a :class:`repro.core.faults.FaultPlan`
        (see :meth:`HopscotchShardWriter.run_one_faulted`)."""
        st = machine.deliver(self.device_state(keys, vals), self.recv_wq,
                             payload)
        out = self.engine.run(st, max_steps, faults)
        torn = self.commit_torn(out.mem, payload, keys, vals)
        clean = self.commit(out.mem, payload, keys, vals)
        act = faults.active()
        return tuple(jnp.where(act, t, c) for t, c in zip(torn, clean))

    def delete_many(self, keys: jnp.ndarray, vals: jnp.ndarray,
                    queries: jnp.ndarray, home: jnp.ndarray,
                    max_steps: int = 512):
        """Single-machine batched DELETE (tests / benchmarks): one
        ``lax.scan`` over the batch, each chain committed before the
        next — bit-exact with :func:`repro.kvstore.hopscotch.
        delete_many`.  Returns ``(status (B,), new_keys, new_vals)``."""
        payloads = self.device_payloads(queries, home)

        def step(carry, pay):
            status, tk, tv = self.run_one(*carry, pay, max_steps)
            return (tk, tv), status

        (nk, nv), statuses = jax.lax.scan(step, (keys, vals), payloads)
        return statuses, nk, nv


@functools.lru_cache(maxsize=None)
def build_hopscotch_deleter(n_buckets: int, val_len: int,
                            neighborhood: int = 8) -> HopscotchShardDeleter:
    """Build (and cache per geometry) the per-shard hopscotch DELETE chain.

    ``1 + neighborhood`` payload words must fit the RECV scatter limit
    (§5.3: 16 scatters), so ``neighborhood <= 15``.
    """
    if not 1 <= neighborhood:
        raise ValueError("neighborhood must be >= 1")
    if 1 + neighborhood > min(isa.MAX_SCATTER, isa.MSG_WORDS):
        raise ValueError(
            f"neighborhood {neighborhood} exceeds the one-SEND request "
            f"budget ({isa.MAX_SCATTER}-scatter RECV)")
    if val_len > isa.MAX_COPY:
        raise ValueError(
            f"val_len {val_len} exceeds the one-WRITE row-zero budget")
    h = neighborhood

    # exact image sizing: guard + recv + per probe (8 vacate + 3 match-
    # cond + 4 match-driver + 3 match-exec); a ghost probe (padded key 0,
    # all probe addrs 0) reads bucket words [0..2] and zero-writes
    # val_len words at value-pointer 0, all inside the guard
    guard_slots = max(2, -(-val_len // isa.WR_WORDS))
    code_words = (guard_slots + 2 + h * (8 + 3 + 4 + 3)) * isa.WR_WORDS
    data_words = (2 + 1 + val_len              # resp, key_w, zeros
                  + n_buckets * val_len        # value rows
                  + n_buckets * BUCKET_WORDS   # table
                  + h * (2 * isa.WR_WORDS + 2)  # templates + stages
                  + 1 + 1 + h)                 # scatter table
    mem_words = -(-(code_words + data_words + 32) // 128) * 128

    p = Program(mem_words)
    p.add_wq(guard_slots)       # WQ0: all-zero null bucket (padding guard)

    resp = p.alloc(2, [DEL_MISS, 0], "resp")
    key_w = p.word(0, "key")
    zeros_v = p.alloc(val_len, [0] * val_len, "zeros")
    values = p.alloc(n_buckets * val_len, name="values")
    tbl_init = [0] * (n_buckets * BUCKET_WORDS)
    for b in range(n_buckets):
        tbl_init[b * BUCKET_WORDS + 2] = values + b * val_len
    table = p.alloc(n_buckets * BUCKET_WORDS, tbl_init, "table")

    rq = p.add_wq(2)
    rd1s = _emit_delete_probes(p, rq, h, val_len, key_w, resp, zeros_v)

    tbl = p.scatter_table([key_w] + [rd.addr("src") for rd in rd1s])
    rq.recv(scatter_table=tbl, tag="dl.recv")

    spec, st0 = p.finalize()
    return HopscotchShardDeleter(
        prog=p, spec=spec, state0=st0, n_buckets=n_buckets,
        val_len=val_len, neighborhood=neighborhood, table_base=table,
        values_base=values, resp_region=resp, recv_wq=rq.index)


@dataclasses.dataclass(frozen=True, eq=False)
class ClockSweeper:
    """One CLOCK-hand lap of chain-driven TTL eviction.

    Each request visits ONE bucket (the hand advances one bucket per
    request, exactly like the migrator visits one source bucket per lap):
    the chain READs the bucket's deadline word, evaluates the expiry
    predicate in Calc verbs (``e = min(max(deadline - now, 0), 1)``), and
    an :func:`repro.core.constructs.emit_enable_branch` on ``e`` either
    releases the **vacate** arm — :func:`~repro.core.constructs.
    emit_bucket_vacate` on the bucket, then the deadline reset to
    :data:`NO_TTL`, then ``SWEEP_RECLAIMED`` reported — or the **live**
    arm (``SWEEP_LIVE``, bucket untouched).  The deadline column lives in
    the bucket pad words, same as the TTL GET server's layout, so one
    ``(keys, vals, exp)`` triple describes the shard to every lifecycle
    program.

    An EMPTY bucket whose deadline was somehow left stale (a torn vacate)
    takes the vacate arm harmlessly — the CAS comparand re-reads EMPTY,
    the row is already zero, and the deadline reset self-heals exactly
    the state fsck's ``torn-vacate`` classifier flags.

    Bit-exact with :func:`repro.kvstore.hopscotch.sweep_expired`.
    """
    prog: Program
    spec: machine.MachineSpec
    state0: machine.VMState
    n_buckets: int
    val_len: int
    table_base: int
    values_base: int
    resp_region: int
    recv_wq: int

    resp_words = 2                     # [status, bucket addr]

    @property
    def engine(self) -> ChainEngine:
        return ChainEngine.for_spec(self.spec)

    @property
    def fuel(self) -> int:
        """Exact step budget (no WQ recycles; see
        :attr:`HopscotchShardWriter.fuel`)."""
        return int(np.asarray(self.state0.tail).sum()) + 1

    def device_state(self, keys: jnp.ndarray, vals: jnp.ndarray,
                     exp: jnp.ndarray) -> machine.VMState:
        """Image with the shard's ``(keys, vals, exp)`` scattered in —
        deadlines into the bucket pad words."""
        rows = jnp.arange(self.n_buckets, dtype=jnp.int32)
        mem = self.state0.mem
        mem = mem.at[self.table_base + rows * BUCKET_WORDS].set(
            keys.astype(jnp.int32))
        mem = mem.at[self.table_base + rows * BUCKET_WORDS + 1].set(
            exp.astype(jnp.int32))
        vidx = (self.values_base + rows[:, None] * self.val_len
                + jnp.arange(self.val_len, dtype=jnp.int32)[None, :])
        mem = mem.at[vidx.reshape(-1)].set(
            vals.astype(jnp.int32).reshape(-1))
        return self.state0._replace(mem=mem)

    def device_payloads(self, buckets: jnp.ndarray, now) -> jnp.ndarray:
        """Request assembly: ``[bucket_addr, deadline_addr, -now]`` per
        visited bucket (the driver computes the hand positions; the
        clock rides the payload so one compiled image serves any now)."""
        b = buckets.astype(jnp.int32)
        addr = self.table_base + b * BUCKET_WORDS
        negnow = jnp.broadcast_to(-jnp.asarray(now, jnp.int32), b.shape)
        return jnp.stack([addr, addr + 1, negnow], axis=1)

    def commit(self, out_mem: jnp.ndarray, payload: jnp.ndarray,
               keys: jnp.ndarray, vals: jnp.ndarray, exp: jnp.ndarray):
        """Fold one quiesced lap back: ``SWEEP_RECLAIMED`` vacates the
        visited bucket and resets its deadline to :data:`NO_TTL`; a live
        lap commits nothing.  Padded rows (addr 0) report status 0.
        Returns ``(status, keys, vals, exp)``."""
        status = out_mem[self.resp_region]
        applied = (payload[0] != 0) & (status == SWEEP_RECLAIMED)
        row = jnp.where(applied,
                        (payload[0] - self.table_base) // BUCKET_WORDS, 0)
        keys = keys.at[row].set(jnp.where(applied, EMPTY_KEY, keys[row]))
        vals = vals.at[row].set(
            jnp.where(applied, jnp.zeros_like(vals[row]), vals[row]))
        exp = exp.at[row].set(
            jnp.where(applied, jnp.int32(NO_TTL), exp[row]))
        return jnp.where(payload[0] == 0, 0, status), keys, vals, exp

    def commit_torn(self, out_mem: jnp.ndarray, payload: jnp.ndarray,
                    keys: jnp.ndarray, vals: jnp.ndarray,
                    exp: jnp.ndarray):
        """Fault-mode commit: straight readback of keys, values, AND the
        deadline column (see :meth:`HopscotchShardWriter.commit_torn`) —
        a cut between the vacate CAS and the deadline reset is precisely
        fsck's ``torn-vacate``."""
        rows = jnp.arange(self.n_buckets, dtype=jnp.int32)
        keys_out = out_mem[self.table_base + rows * BUCKET_WORDS]
        exp_out = out_mem[self.table_base + rows * BUCKET_WORDS + 1]
        cols = jnp.arange(self.val_len, dtype=jnp.int32)[None, :]
        vals_out = out_mem[self.values_base
                           + rows[:, None] * self.val_len + cols]
        status = out_mem[self.resp_region]
        return (jnp.where(payload[0] == 0, 0, status),
                keys_out.astype(keys.dtype), vals_out.astype(vals.dtype),
                exp_out.astype(exp.dtype))

    def run_one(self, keys: jnp.ndarray, vals: jnp.ndarray,
                exp: jnp.ndarray, payload: jnp.ndarray,
                max_steps: int = 256):
        """One sweeper lap.  Returns ``(status, keys, vals, exp)``."""
        st = machine.deliver(self.device_state(keys, vals, exp),
                             self.recv_wq, payload)
        out = self.engine.run(st, max_steps)
        return self.commit(out.mem, payload, keys, vals, exp)

    def run_one_faulted(self, keys: jnp.ndarray, vals: jnp.ndarray,
                        exp: jnp.ndarray, payload: jnp.ndarray,
                        max_steps: int, faults):
        """:meth:`run_one` under a :class:`repro.core.faults.FaultPlan`
        (see :meth:`HopscotchShardWriter.run_one_faulted`)."""
        st = machine.deliver(self.device_state(keys, vals, exp),
                             self.recv_wq, payload)
        out = self.engine.run(st, max_steps, faults)
        torn = self.commit_torn(out.mem, payload, keys, vals, exp)
        clean = self.commit(out.mem, payload, keys, vals, exp)
        act = faults.active()
        return tuple(jnp.where(act, t, c) for t, c in zip(torn, clean))

    def sweep(self, keys: jnp.ndarray, vals: jnp.ndarray,
              exp: jnp.ndarray, start: int, count: int, now,
              max_steps: int = 256):
        """``count`` CLOCK laps from the hand at ``start`` (wrapping):
        one ``lax.scan``, each lap committed before the next.  Returns
        ``(status (count,), keys, vals, exp)``."""
        buckets = (jnp.asarray(start, jnp.int32)
                   + jnp.arange(count, dtype=jnp.int32)) % self.n_buckets
        payloads = self.device_payloads(buckets, now)

        def step(carry, pay):
            status, tk, tv, te = self.run_one(*carry, pay, max_steps)
            return (tk, tv, te), status

        (nk, nv, ne), statuses = jax.lax.scan(
            step, (keys, vals, exp), payloads)
        return statuses, nk, nv, ne


#: sweeper lane WQ sizes — (ctl, mod, vacate arm, live arm); the group
#: builder's sizing and :func:`_emit_sweep_lane` must agree on these
_SWEEP_WQS = (13, 2, 11, 1)


def _emit_sweep_lane(p: Program, rq, val_len: int, resp: int,
                     bucket_w: int, e_cell: int, no_ttl_w: int,
                     zeros_v: int):
    """One CLOCK-lap chain body — shared by the standalone sweeper and a
    ``"sweep"`` lane of :func:`build_multi_writer_group`.

    Emits the control WQ (expiry predicate in Calc verbs, clamped to
    ``e in {0, 1}``), the enable-branch modifier, and the vacate / live
    arms against the caller's cells.  Returns the RECV scatter address
    list ``[bucket_w, read-src patch, ADD-operand patch]``.
    """
    CTL, MOD, VAC, LIVE = _SWEEP_WQS
    ctl = p.add_wq(CTL, ordering=isa.ORD_DOORBELL, managed=True)
    mod = p.add_wq(MOD, ordering=isa.ORD_DOORBELL, managed=True,
                   initial_enable=0)
    vac = p.add_wq(VAC, ordering=isa.ORD_DOORBELL, managed=True,
                   initial_enable=0)
    live = p.add_wq(LIVE, ordering=isa.ORD_DOORBELL, managed=True,
                    initial_enable=0)

    ctl.wait(rq, 1, tag="sw.trig")
    ctl.write(src=bucket_w, dst=resp + 1, tag="sw.addr")
    rd = ctl.read(src=0, dst=e_cell, ln=1, tag="sw.exp")  # src scattered
    ad = ctl.add(dst=e_cell, addend=0, tag="sw.sub")      # opa scattered
    ctl.max_(dst=e_cell, operand=0, tag="sw.cl0")
    ctl.min_(dst=e_cell, operand=1, tag="sw.cl1")         # e in {0, 1}

    def load_e(a_addr, b_addr):
        ctl.write(src=e_cell, dst=a_addr, tag="sw.e1")
        ctl.write(src=e_cell, dst=b_addr, tag="sw.e2")

    # e = 0 (expired) <= threshold -> vacate arm; e = 1 -> live arm
    constructs.emit_enable_branch(
        ctl, mod, threshold=0, then_wq=vac.index, then_upto=VAC,
        else_wq=live.index, else_upto=LIVE, load=load_e, tag="sw.br")
    ctl.initial_enable = ctl.n_posted + 1

    # vacate arm: retire the bucket, reset its deadline, report
    constructs.emit_bucket_vacate(vac, bucket_w=bucket_w, val_len=val_len,
                                  zeros=zeros_v, empty_key=EMPTY_KEY,
                                  tag="sw.vac")
    vac.write(src=rd.addr("src"), dst=vac.future_wr_addr(1, "dst"),
              tag="sw.rs_p")            # deadline addr <- scattered cell
    vac.write(src=no_ttl_w, dst=0, ln=1, tag="sw.rs")
    vac.write_imm(dst=resp, value=SWEEP_RECLAIMED, tag="sw.rc")

    # live arm: the bucket is untouched; the report is the (idempotent)
    # pre-set default, re-asserted so the arm completes observably
    live.write_imm(dst=resp, value=SWEEP_LIVE, tag="sw.lv")

    return [bucket_w, rd.addr("src"), ad.addr("opa")]


@functools.lru_cache(maxsize=None)
def build_clock_sweeper(n_buckets: int, val_len: int) -> ClockSweeper:
    """Build (and cache per geometry) the per-shard CLOCK sweeper chain."""
    if val_len > isa.MAX_COPY:
        raise ValueError(
            f"val_len {val_len} exceeds the one-WRITE row-zero budget")

    # exact image sizing: the ghost lap (padded addr 0) reads words
    # [0..2] and zero-writes val_len at ptr 0 — guard covers both; a
    # ghost deadline reset also lands NO_TTL on guard word 0, which is
    # never executed (WQ0 posts nothing)
    CTL, MOD, VAC, LIVE = _SWEEP_WQS
    guard_slots = max(2, -(-val_len // isa.WR_WORDS))
    code_words = (guard_slots + 2 + CTL + MOD + VAC + LIVE) * isa.WR_WORDS
    data_words = (2 + 3 + val_len              # resp, cells, zeros
                  + n_buckets * val_len        # value rows
                  + n_buckets * BUCKET_WORDS   # table (pad = deadline)
                  + 1 + 3)                     # scatter table
    mem_words = -(-(code_words + data_words + 32) // 128) * 128

    p = Program(mem_words)
    p.add_wq(guard_slots)       # WQ0: all-zero null bucket (padding guard)

    resp = p.alloc(2, [SWEEP_LIVE, 0], "resp")
    bucket_w = p.word(0, "bucket")     # scattered: visited bucket addr
    e_cell = p.word(0, "e")
    no_ttl_w = p.word(NO_TTL, "no_ttl")
    zeros_v = p.alloc(val_len, [0] * val_len, "zeros")
    values = p.alloc(n_buckets * val_len, name="values")
    tbl_init = [0] * (n_buckets * BUCKET_WORDS)
    for b in range(n_buckets):
        tbl_init[b * BUCKET_WORDS + 1] = NO_TTL
        tbl_init[b * BUCKET_WORDS + 2] = values + b * val_len
    table = p.alloc(n_buckets * BUCKET_WORDS, tbl_init, "table")

    rq = p.add_wq(2)
    scatter = _emit_sweep_lane(p, rq, val_len, resp, bucket_w, e_cell,
                               no_ttl_w, zeros_v)
    tbl = p.scatter_table(scatter)
    rq.recv(scatter_table=tbl, tag="sw.recv")

    spec, st0 = p.finalize()
    return ClockSweeper(
        prog=p, spec=spec, state0=st0, n_buckets=n_buckets,
        val_len=val_len, table_base=table, values_base=values,
        resp_region=resp, recv_wq=rq.index)


# ---------------------------------------------------------------------------
# Fig. 12 — linked-list traversal
# ---------------------------------------------------------------------------

NODE_WORDS = 4   # [key, pad, val_ptr, next]


@dataclasses.dataclass
class ListTraversalOffload:
    prog: Program
    spec: machine.MachineSpec
    state0: machine.VMState
    n_iters: int
    val_len: int
    nodes_base: int
    values_base: int
    resp_region: int
    recv_wq: int
    use_break: bool
    items: List[Tuple[int, List[int]]]

    def node_addr(self, i: int) -> int:
        return self.nodes_base + i * NODE_WORDS

    def set_list(self, items: Sequence[Tuple[int, Sequence[int]]]):
        self.items = [(k, list(v)) for k, v in items]

    def materialize(self) -> machine.VMState:
        mem = np.asarray(self.state0.mem).copy()
        for i, (key, value) in enumerate(self.items):
            a = self.node_addr(i)
            vslot = self.values_base + i * self.val_len
            nxt = self.node_addr(i + 1) if i + 1 < len(self.items) else 0
            mem[a:a + 4] = [key, 0, vslot, nxt]
            mem[vslot:vslot + len(value)] = value
        return self.state0._replace(mem=jnp.asarray(mem))

    @property
    def engine(self) -> ChainEngine:
        return ChainEngine.for_spec(self.spec)

    def _payload(self, key: int) -> List[int]:
        return [self.node_addr(0)] + [key] * self.n_iters

    def get(self, key: int, max_steps: int = 4096):
        st = self.materialize()
        st = machine.deliver(st, self.recv_wq, self._payload(key))
        out = self.engine.run(st, max_steps)
        val = np.asarray(out.mem[self.resp_region:
                                 self.resp_region + self.val_len])
        return val, out

    def get_many(self, keys: Sequence[int], max_steps: int = 4096):
        """Batched list walk: one materialize(), one vmapped run."""
        return _batched_get(self, keys, max_steps)


def build_list_traversal(n_iters: int = 8, val_len: int = 2,
                         use_break: bool = False,
                         mem_words: int = 8192) -> ListTraversalOffload:
    """Unrolled list walk (Fig. 12).

    Per iteration: ``drv`` patches and performs the node READ (filling the
    response WR's ctrl/flags/src from the node) and advances the cursor;
    ``exe`` CASes the response WR's control word against the searched key;
    ``mod`` holds the conditional response WRs.  With ``use_break`` a hit
    rewrites the *next* iteration's conditional WR into a completion-
    suppressed response WRITE, so its missing completion starves both the
    ``exe`` and ``drv`` chains — no further iterations execute (Fig. 6).
    """
    p = Program(mem_words)
    resp = p.alloc(val_len, [MISS_SENTINEL] * val_len, "resp")
    values = p.alloc(n_iters * val_len, name="values")
    nodes = p.alloc(n_iters * NODE_WORDS, [0] * (n_iters * NODE_WORDS),
                    "nodes")
    cur = p.word(0, "cur")

    rq = p.add_wq(4)
    drv = p.add_wq(10 * n_iters + 4, ordering=isa.ORD_COMPLETION)
    exe = p.add_wq(4 * n_iters + 4, ordering=isa.ORD_DOORBELL)
    mod = p.add_wq(2 * n_iters + 2, ordering=isa.ORD_DOORBELL, managed=True)

    per_iter = 2 if use_break else 1     # mod WRs per iteration
    cas_opa_addrs = []
    for i in range(n_iters):
        # --- mod: the conditional WR (and, in break mode, the adjacent
        #     event WR the next iteration gates on — Fig. 6's layout) -------
        if use_break:
            # C_i converted -> WRITE(template over E_i): E_i becomes a
            # completion-suppressed response WRITE. Response fires AND the
            # missing completion starves iteration i+1 before it can touch
            # anything.
            tmpl = p.alloc(isa.WR_WORDS, [
                isa.pack_ctrl(isa.WRITE, 0), isa.FLAG_SUPPRESS_COMPLETION,
                0, resp, val_len, 0, 0, -1])
            c_i = mod.post(isa.NOOP, src=tmpl,
                           dst=mod.future_wr_addr(1, "ctrl"), ln=8,
                           tag=f"list.c{i}")
            mod.post(isa.NOOP, tag=f"list.e{i}")      # E_i (the gate event)
        else:
            # C_i converted -> WRITE(value -> response region) directly
            c_i = mod.post(isa.NOOP, src=0, dst=resp, ln=val_len,
                           tag=f"list.c{i}")

        # --- drv: patch + node READ + cursor advance ------------------------
        if i == 0:
            drv.wait(rq, 1, tag="list.trig")
        else:
            drv.wait(mod, per_iter * i, tag=f"list.gate{i}")
        # node [key, pad(, val_ptr)] -> C_i.[ctrl, flags(, src)]; in break
        # mode C_i.src must keep pointing at the template, so the READ stops
        # after flags and the value pointer is forwarded into the template.
        drv.write(src=cur, dst=drv.future_wr_addr(1, "src"), ln=1,
                  tag=f"list.patch{i}")
        drv.read(src=0, dst=c_i.ctrl_addr, ln=(2 if use_break else 3),
                 tag=f"list.node{i}")
        if use_break:
            drv.write(src=cur, dst=drv.future_wr_addr(2, "src"), ln=1,
                      tag=f"list.patch_v{i}")
            drv.add(dst=drv.future_wr_addr(1, "src"), addend=2,
                    tag=f"list.voff{i}")
            drv.read(src=0, dst=tmpl + 2, ln=1, tag=f"list.val{i}")
        # advance: cursor <- node.next
        drv.write(src=cur, dst=drv.future_wr_addr(2, "src"), ln=1,
                  tag=f"list.patch_n{i}")
        drv.add(dst=drv.future_wr_addr(1, "src"), addend=3,
                tag=f"list.off{i}")
        rdn = drv.read(src=0, dst=cur, ln=1, tag=f"list.next{i}")

        # --- exe: the conditional (gated on the full drv iteration) ---------
        if i > 0:
            exe.wait(mod, per_iter * i, tag=f"list.syncm{i}")
        exe.wait(drv, rdn.completion_count, tag=f"list.sync{i}")
        cas = exe.cas(dst=c_i.ctrl_addr, old=isa.pack_ctrl(isa.NOOP, 0),
                      new=isa.pack_ctrl(isa.WRITE, 0), tag=f"list.cas{i}")
        exe.enable(mod, upto=per_iter * (i + 1), tag=f"list.en{i}")
        cas_opa_addrs.append(cas.addr("opa"))

    # RECV: first-node address -> cursor; x -> every CAS comparand
    tbl = p.scatter_table([cur] + cas_opa_addrs)
    rq.recv(scatter_table=tbl, tag="list.recv")

    spec, st0 = p.finalize()
    return ListTraversalOffload(
        prog=p, spec=spec, state0=st0, n_iters=n_iters, val_len=val_len,
        nodes_base=nodes, values_base=values, resp_region=resp,
        recv_wq=rq.index, use_break=use_break, items=[])


# ---------------------------------------------------------------------------
# §3.4 / §5.6 — WQ-recycled get server (survives host failures)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RecycledGetServer:
    prog: Program
    spec: machine.MachineSpec
    state: machine.VMState
    n_buckets: int
    val_len: int
    table_base: int
    values_base: int
    resp_region: int
    loop_wq: int
    lap_words: int
    laps_addr: int
    kv: Dict[int, Tuple[int, List[int]]]

    def h1(self, key: int) -> int:
        return key % self.n_buckets

    def bucket_addr(self, b: int) -> int:
        return self.table_base + b * BUCKET_WORDS

    def insert(self, key: int, value: Sequence[int]):
        self.kv[self.h1(key)] = (key, list(value))

    def load(self):
        mem = np.asarray(self.state.mem).copy()
        for b, (key, value) in self.kv.items():
            vslot = self.values_base + b * self.val_len
            a = self.bucket_addr(b)
            mem[a:a + 3] = [key, 0, vslot]
            mem[vslot:vslot + len(value)] = value
        self.state = self.state._replace(mem=jnp.asarray(mem))

    @property
    def engine(self) -> ChainEngine:
        return ChainEngine.for_spec(self.spec)

    def _payload(self, key: int) -> List[int]:
        return [key, self.bucket_addr(self.h1(key))]

    def serve(self, key: int, max_steps: int = 64):
        """One request against the *persistent* loop state — no host-side
        re-arming ever happens (that is §5.6's resiliency story)."""
        st = machine.deliver(self.state, self.loop_wq, self._payload(key))
        st = st._replace(steps=jnp.zeros((), jnp.int32))
        out = self.engine.run(st, max_steps)
        val = np.asarray(out.mem[self.resp_region:
                                 self.resp_region + self.val_len])
        self.state = out
        return val

    def serve_many(self, keys: Sequence[int],
                   max_steps: int = 64) -> np.ndarray:
        """Stream a key batch through the persistent loop in one device call.

        Equivalent to N sequential :meth:`serve` calls — same responses,
        same on-chain lap counters, state persists across the batch — but
        compiled as one ``lax.scan`` (no host round-trip between requests).
        Returns ``(N, val_len)``.
        """
        payloads = np.asarray([self._payload(int(k)) for k in keys],
                              np.int32)
        final, vals = self.engine.serve_stream(
            self.state, self.loop_wq, payloads, self.resp_region,
            self.val_len, max_steps)
        self.state = final
        return np.asarray(vals)

    def get_many(self, keys: Sequence[int], max_steps: int = 64):
        """Batched get mirroring the other offloads' ``(vals, state)``
        return shape; the state is the persistent post-batch loop state."""
        vals = self.serve_many(keys, max_steps)
        return vals, self.state


def build_recycled_get_server(n_buckets: int = 32, val_len: int = 2,
                              mem_words: int = 4096) -> RecycledGetServer:
    """Single-bucket get server on ONE recycled WQ (lap layout in code)."""
    p = Program(mem_words)
    resp = p.alloc(val_len, [MISS_SENTINEL] * val_len, "resp")
    zeros = p.alloc(val_len, [0] * val_len, "zeros")
    values = p.alloc(n_buckets * val_len, name="values")
    table = p.alloc(n_buckets * BUCKET_WORDS,
                    [0] * (n_buckets * BUCKET_WORDS), "table")
    laps = p.word(0, "laps")

    size = 12
    wq = p.add_wq(size, ordering=isa.ORD_DOORBELL, managed=True,
                  recycled=True, initial_enable=5)
    rv = wq.recv(scatter_table=0, tag="srv.recv")           # table patched in
    wq.read(src=zeros, dst=resp, ln=val_len, tag="srv.clear")
    rd = wq.read(src=0, dst=0, ln=BUCKET_WORDS, tag="srv.read")
    cas = wq.cas(dst=0, old=isa.pack_ctrl(isa.NOOP, 0),
                 new=isa.pack_ctrl(isa.WRITE, 0), tag="srv.cas")
    en = wq.enable(wq, upto=size + 5, tag="srv.enable")
    r4 = wq.post(isa.NOOP, src=0, dst=resp, ln=val_len, tag="srv.resp")
    pristine = p.alloc(isa.WR_WORDS, [
        isa.pack_ctrl(isa.NOOP, 0), 0, 0, resp, val_len, 0, 0, -1])
    wq.read(src=pristine, dst=r4.base, ln=isa.WR_WORDS, tag="srv.rearm")
    wq.add(dst=laps, addend=1, tag="srv.laps")
    wq.add(dst=en.addr("opa"), addend=size, tag="srv.bump")
    while wq.n_posted < size:
        wq.noop(signaled=False, tag="srv.pad")

    wq.wrs[rd.slot]["dst"] = r4.ctrl_addr
    wq.wrs[cas.slot]["dst"] = r4.ctrl_addr
    tbl = p.scatter_table([cas.addr("opa"), rd.addr("src")])
    wq.wrs[rv.slot]["aux"] = tbl

    spec, st0 = p.finalize()
    return RecycledGetServer(
        prog=p, spec=spec, state=st0, n_buckets=n_buckets, val_len=val_len,
        table_base=table, values_base=values, resp_region=resp,
        loop_wq=wq.index, lap_words=size, laps_addr=laps, kv={})
