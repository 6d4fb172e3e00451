"""ChainEngine — compile-cached, batched execution of RedN chains.

The paper's headline numbers come from offload chains that serve *streams*
of requests with zero host involvement.  The seed code served exactly one
request per :func:`machine.run` call and round-tripped through numpy per
key; this module is the batched front door that replaces that pattern:

* **Compile caching** — engines are memoized per ``(spec, backend)`` via
  :meth:`ChainEngine.for_spec`, and every entry point bottoms out in jitted
  functions whose only static arguments are the spec and shapes, so a
  program compiles once per (spec, batch-shape) and then serves any number
  of batches.
* **`run_many`** — one :func:`machine.deliver_many` (stack N payloads into
  a vmapped ``VMState`` batch in one shot) followed by one vmapped run:
  the engine behind ``HashLookupOffload.get_many`` /
  ``ListTraversalOffload.get_many``.
* **`serve_stream`** — a ``lax.scan`` over payloads against *persistent*
  state (the §3.4 recycled-WQ server): requests chain through the same
  machine exactly as N sequential ``serve()`` calls — same responses, same
  on-chain lap counters — but in a single device call with no host
  round-trips between requests.
* **Pallas backend** — for single-WQ programs (the recycled get server's
  lap loop, straight-line chains) ``backend="pallas"`` runs the batch on
  the TPU as a grid of client contexts through the managed-WQ kernel in
  :mod:`repro.kernels.chain_vm`, with the interpreter as oracle.

Migration (single-request → batched)::

    # before: N numpy round-trips
    vals = [off.get(k)[0] for k in keys]
    # after: one materialize, one vmapped run
    vals, out = off.get_many(keys)

"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import isa, machine

_INTERP_BACKENDS = ("interp",)
_PALLAS_BACKENDS = ("pallas", "pallas-interpret")


@functools.partial(jax.jit, static_argnums=(0, 2, 4))
def _run_many(spec, state, wq, payloads, max_steps, faults=None):
    batch = machine.deliver_many(state, wq, payloads)
    # each context gets max_steps of *fresh* fuel, like serve() does — a
    # reused persistent state must not carry its cumulative step count in
    batch = batch._replace(steps=jnp.zeros_like(batch.steps))
    return machine.run_batch(spec, batch, max_steps, faults)


@functools.partial(jax.jit, static_argnums=(0, 1, 4, 6))
def _run_many_segmented(spec, segment, state, words, wq, payloads, max_steps):
    batch = machine.deliver_many(state, wq, payloads)
    batch = batch._replace(steps=jnp.zeros_like(batch.steps))
    return jax.vmap(
        lambda s, w: machine.run_segmented(spec, segment, s, w, max_steps),
        in_axes=(0, None))(batch, words)


@functools.partial(jax.jit, static_argnums=(0, 2, 4, 5, 6))
def _serve_stream(spec, state, wq, payloads, resp, resp_len, max_steps,
                  faults=None):
    def step_fn(st, xs):
        pay, f = xs if faults is not None else (xs, None)
        st = machine.deliver(st, wq, pay)
        st = st._replace(steps=jnp.zeros((), jnp.int32))
        out = machine.run(spec, st, max_steps, f)
        val = lax.dynamic_slice(out.mem, (resp,), (resp_len,))
        return out, val

    xs = payloads if faults is None else (payloads, faults)
    return lax.scan(step_fn, state, xs)


def _pad_payloads(payloads) -> jnp.ndarray:
    if isinstance(payloads, (jax.Array, jax.core.Tracer)):
        # device / traced batch (e.g. requests arriving inside shard_map):
        # pad with jnp ops, never forcing a host round-trip
        p = payloads.astype(jnp.int32)
        if p.ndim != 2:
            raise ValueError(f"payloads must be (N, k), got shape {p.shape}")
        if p.shape[1] > isa.MSG_WORDS:
            raise ValueError(
                f"payload of {p.shape[1]} words exceeds MSG_WORDS")
        if p.shape[1] == isa.MSG_WORDS:
            return p
        return jnp.zeros((p.shape[0], isa.MSG_WORDS),
                         jnp.int32).at[:, : p.shape[1]].set(p)
    p = np.asarray(payloads, np.int32)
    if p.ndim == 1 and p.size == 0:
        p = p.reshape(0, 0)          # literal []: empty batch, no requests
    if p.ndim != 2:
        raise ValueError(f"payloads must be (N, k), got shape {p.shape}")
    if p.shape[1] > isa.MSG_WORDS:
        raise ValueError(f"payload of {p.shape[1]} words exceeds MSG_WORDS")
    out = np.zeros((p.shape[0], isa.MSG_WORDS), np.int32)
    out[:, : p.shape[1]] = p
    return jnp.asarray(out)


class ChainEngine:
    """Batched, compile-cached executor for one chain program (spec).

    Backends:

    * ``"interp"`` (default) — the multi-WQ discrete-event interpreter in
      :mod:`repro.core.machine` (full ISA, latency clocks).
    * ``"pallas"`` — the single-WQ managed-chain Pallas kernel
      (:mod:`repro.kernels.chain_vm`), compiled for the TPU; constructing
      it on any other backend raises.  Models memory, queue counters,
      steps, and client responses, but not the latency cost model: the
      ``clock``/``last_comp_time`` fields and the ``verb_counts``
      histogram are passed through unchanged.
    * ``"pallas-interpret"`` — the same kernel in pallas interpret mode
      (CPU oracle checks).
    """

    # Bounded LRU of engines keyed (spec, backend).  Evicting an engine
    # object is safe: the jitted fast paths (`_run_many`, `_serve_stream`,
    # `machine.run`) are module-level and keep their own compile caches, so
    # eviction only drops the cheap wrapper + its pallas image-check memo.
    # A long-lived service cycling through many distinct writer-count /
    # geometry specs must not grow host memory without bound (regression-
    # tested in tests/test_multiwriter.py).
    _cache: "collections.OrderedDict" = collections.OrderedDict()
    _cache_limit: int = 64
    _cache_stats: dict = {"hits": 0, "misses": 0, "evictions": 0}

    def __init__(self, spec: machine.MachineSpec, backend: str = "interp"):
        if backend not in _INTERP_BACKENDS + _PALLAS_BACKENDS:
            raise ValueError(f"unknown backend {backend!r}")
        if backend in _PALLAS_BACKENDS and spec.num_wqs != 1:
            raise ValueError(
                "pallas backend supports single-WQ programs only "
                f"(spec has {spec.num_wqs} WQs)")
        if backend == "pallas" and jax.default_backend() != "tpu":
            raise ValueError(
                "backend='pallas' compiles the chain-VM kernel for the TPU, "
                f"but JAX's default backend is {jax.default_backend()!r}; "
                "use 'pallas-interpret' to run the kernel in interpret mode")
        self.spec = spec
        self.backend = backend
        # pallas-subset validation, keyed on the code-region image: engines
        # are memoized per (spec, backend), so a boolean "checked once"
        # flag would let a *different* program image with the same spec
        # bypass the check entirely
        self._validated_wq_images: set = set()

    @classmethod
    def for_spec(cls, spec: machine.MachineSpec,
                 backend: str = "interp") -> "ChainEngine":
        key = (spec, backend)
        eng = cls._cache.get(key)
        if eng is not None:
            cls._cache.move_to_end(key)
            cls._cache_stats["hits"] += 1
            return eng
        cls._cache_stats["misses"] += 1
        eng = cls._cache[key] = cls(spec, backend)
        while len(cls._cache) > cls._cache_limit:
            cls._cache.popitem(last=False)
            cls._cache_stats["evictions"] += 1
        return eng

    @classmethod
    def cache_stats(cls) -> dict:
        """Snapshot of the engine-memo LRU: size/limit plus cumulative
        hit/miss/eviction counters (see the satellite regression test)."""
        return {"size": len(cls._cache), "limit": cls._cache_limit,
                **cls._cache_stats}

    @classmethod
    def cache_clear(cls) -> None:
        cls._cache.clear()
        cls._cache_stats.update(hits=0, misses=0, evictions=0)

    def _check_pallas_faults(self, faults):
        """The pallas kernel models exactly one fault: fuel truncation
        (``kill_step``), which it already implements as per-row fuel.
        Any other armed fault needs the interpreter's per-step hooks."""
        if faults is None:
            return
        if isinstance(faults.kill_step, jax.core.Tracer):
            raise ValueError(
                "faulted pallas runs need a concrete FaultPlan (the "
                "supported-subset check is host-side); use the interp "
                "backend for traced plans")
        if not faults.pallas_supported():
            raise ValueError(
                "pallas backend supports only kill_step (fuel "
                "truncation) faults; suppress/CAS/ENABLE faults need "
                "the interp backend")

    @staticmethod
    def _pallas_fuel(faults, max_steps: int):
        """Per-row fuel implementing ``kill_step`` bit-exactly: the
        interpreter stops before executing step k, so a killed row gets
        exactly ``k`` steps of fuel."""
        kill = jnp.asarray(faults.kill_step, jnp.int32)
        return jnp.where(kill >= 0, jnp.minimum(kill, max_steps),
                         max_steps)

    # -- single-machine paths (compile-cached via the jitted machine.run) ----
    def run(self, state: machine.VMState, max_steps: int = 4096,
            faults=None) -> machine.VMState:
        return machine.run(self.spec, state, max_steps, faults)

    def run_batch(self, states: machine.VMState, max_steps: int = 4096,
                  faults=None) -> machine.VMState:
        """Run a batched (leading-dim) ``VMState`` on the selected backend.

        ``faults`` is a :class:`repro.core.faults.FaultPlan` with one row
        per context (interpreter-authoritative; pallas supports the
        kill/fuel fault only and keeps bit-exact parity on it)."""
        if self.backend in _INTERP_BACKENDS:
            return machine.run_batch(self.spec, states, max_steps, faults)
        self._check_pallas_faults(faults)
        return self._run_batch_pallas(states, max_steps, faults)

    def run_interleaved(self, state: machine.VMState,
                        schedule: machine.Schedule,
                        writer_slices, max_steps: int = 4096
                        ) -> machine.VMState:
        """Run many writers' chains over ONE shared memory image under a
        deterministic :class:`machine.Schedule`.

        The serialized scan (``Schedule.serialized``) is the bit-exact
        oracle for the *committed* state under any schedule, for programs
        whose only cross-writer touch points are CAS claims on shared
        cells.  The argument is linearizability of the claim CAS: a CAS is
        one atomic VM step, so each contended cell is won by exactly one
        writer at one step; every loser observes ``old != expect``, takes
        its not-taken branch, and re-probes — exactly what it would have
        observed running *after* the winner in some serialized order.
        Writers' private WQs, completion counters, and staging regions are
        disjoint by construction (`writer_slices`), so the committed
        shared state (table cells + claimed value rows + per-writer
        responses) equals the serialized run whose order is the order the
        contended CASes won — proved exhaustively by the 2-writer
        cut-point sweep in ``tests/test_faults.py`` (0 diverged).

        Interpreter-only: the pallas kernel is a grid of *independent*
        single-WQ contexts and cannot share a memory image.
        """
        if self.backend not in _INTERP_BACKENDS:
            raise ValueError(
                "run_interleaved shares one memory image across writers; "
                "the pallas grid runs independent contexts — use the "
                "interp backend")
        return machine.run_scheduled(self.spec, state, schedule,
                                     tuple(writer_slices), max_steps)

    # -- batched request paths ----------------------------------------------
    def deliver_many(self, state: machine.VMState, wq: int,
                     payloads) -> machine.VMState:
        return machine.deliver_many(state, wq, _pad_payloads(payloads))

    def run_many(self, state: machine.VMState, wq: int, payloads,
                 max_steps: int = 4096, faults=None) -> machine.VMState:
        """Deliver N payloads to `wq` and run all N contexts, batched.

        Every context gets ``max_steps`` of fresh fuel (the cumulative
        ``steps`` counter of a reused persistent state is reset, exactly
        as the single-request ``serve()`` path does).  ``faults`` rows
        (leading dim N) inject per-context faults — see
        :mod:`repro.core.faults`.
        """
        pays = _pad_payloads(payloads)
        if self.backend in _INTERP_BACKENDS:
            return _run_many(self.spec, state, wq, pays, max_steps, faults)
        self._check_pallas_faults(faults)
        batch = machine.deliver_many(state, wq, pays)
        batch = batch._replace(steps=jnp.zeros_like(batch.steps))
        return self._run_batch_pallas(batch, max_steps, faults)

    def run_many_segmented(self, state: machine.VMState,
                           segment: machine.Segment, words, wq: int,
                           payloads, max_steps: int = 4096):
        """:meth:`run_many` over a split image: ``state`` holds the image
        outside ``segment`` (:func:`machine.split_image`) and ``words`` the
        segment's words, which all N contexts read from one array.
        Returns ``(batched state, breach (N,) bool)``: a context that
        stored into the segment halted there (:func:`machine.run_segmented`).
        Interpreter only."""
        if self.backend not in _INTERP_BACKENDS:
            raise ValueError(
                "a segmented run shares one read-only segment between "
                "contexts; the pallas kernel copies each context's whole "
                "image — use the interp backend")
        return _run_many_segmented(self.spec, segment, state, words, wq,
                                   _pad_payloads(payloads), max_steps)

    def serve_stream(self, state: machine.VMState, wq: int, payloads,
                     resp_region: int, resp_len: int,
                     max_steps: int = 64, faults=None):
        """Stream N requests through *persistent* state (recycled server).

        Returns ``(final_state, values)`` with ``values`` of shape
        ``(N, resp_len)`` — the response region snapshot after each
        request, exactly as N sequential ``serve()`` calls would observe
        (lap counters and all), in one compiled scan.

        Always runs on the interpreter regardless of ``backend``: the
        scan chains one persistent machine across requests, which the
        grid-of-independent-contexts pallas kernel does not model.
        ``faults`` rows (leading dim N) fault individual requests of the
        stream; a killed request's effects stay in the persistent state,
        exactly like a real recycled server interrupted mid-chain.
        """
        pays = _pad_payloads(payloads)
        return _serve_stream(self.spec, state, wq, pays, resp_region,
                             resp_len, max_steps, faults)

    # -- pallas backend -------------------------------------------------------
    def _run_batch_pallas(self, states: machine.VMState,
                          max_steps: int, faults=None) -> machine.VMState:
        from ..kernels.chain_vm import ops as chain_ops

        spec = self.spec
        n = states.mem.shape[0]
        cap = states.msg_buf.shape[2]
        msgs = states.msg_buf[:, 0].reshape(n, cap * isa.MSG_WORDS)

        # inter-QP SEND (opb >= 0) has no peer on a single queue and is
        # outside the pallas subset — reject posted ones up front rather
        # than silently no-op'ing them.  The check is keyed on the WQ
        # slice of the image (engines are memoized per (spec, backend), so
        # a one-shot flag would let a different program image with the
        # same spec bypass validation).  Eager concrete calls pay one
        # device sync per batch, but the transfer stays O(wq slice), not
        # O(batch x wq slice): the usual batch is a broadcast of one
        # image, detected with a device-side reduce, and only a
        # heterogeneous (per-row self-modified) batch pulls every row.
        # The high-throughput serving paths run under jit/shard_map and
        # skip the check entirely (tracing); a chain that self-modifies a
        # WR *into* such a SEND mid-run is likewise not detectable here.
        if not isinstance(states.mem, jax.core.Tracer):
            base, size = spec.wq_bases[0], spec.wq_sizes[0]
            stop = base + size * isa.WR_WORDS
            sl = states.mem[:, base:stop]
            if sl.shape[0] > 0 and bool(jnp.all(sl == sl[0])):
                img = np.asarray(sl[0])[None]
            else:
                img = np.asarray(sl)
            img_key = hash(img.tobytes())
            if img_key not in self._validated_wq_images:
                opcodes = ((img[:, isa.F_CTRL::isa.WR_WORDS] >> isa.ID_BITS)
                           & 0x7F)
                opbs = img[:, isa.F_OPB::isa.WR_WORDS]
                if np.any((opcodes == isa.SEND) & (opbs >= 0)):
                    raise ValueError(
                        "inter-QP SEND (opb >= 0) is outside the pallas "
                        "single-WQ subset; use the interp backend")
                self._validated_wq_images.add(img_key)

        # fuel: the interpreter's run() treats the cumulative steps
        # counter as consumed fuel (cond: steps < max_steps) — mirror it
        fuel = jnp.clip(max_steps - states.steps, 0, max_steps)
        if faults is not None:
            # kill_step as fuel: bit-exact with the interpreter's
            # killed-loop condition (exactly k WRs execute)
            fuel = jnp.minimum(fuel, self._pallas_fuel(faults, max_steps))
        inits = jnp.stack(
            [states.head[:, 0], states.tail[:, 0],
             states.enable_limit[:, 0], states.completions[:, 0],
             states.msg_head[:, 0], states.msg_tail[:, 0],
             fuel.astype(jnp.int32),
             states.halted.astype(jnp.int32)], axis=1)
        impl = "interpret" if self.backend == "pallas-interpret" else "pallas"
        mem, stats = chain_ops.run_managed(
            states.mem, msgs, inits, wq_base=spec.wq_bases[0],
            n_wrs=spec.wq_sizes[0], managed=bool(spec.managed[0]),
            max_steps=max_steps, impl=impl)
        # queue/response counters come back from the kernel; executed-WR
        # counts are the per-row head advance (one head bump per executed
        # WR, exactly like the interpreter's steps counter).  The latency
        # clocks and verb_counts histogram are interpreter-only and are
        # passed through unchanged.
        return states._replace(
            mem=mem,
            head=stats[:, 0:1],
            enable_limit=stats[:, 1:2],
            completions=stats[:, 2:3],
            msg_head=stats[:, 3:4],
            halted=stats[:, 4] > 0,
            responses=states.responses + stats[:, 6],
            steps=states.steps + (stats[:, 0] - states.head[:, 0]))
