"""Compile the serving path for a described TPU v5e, with no chip attached.

The TPU compiler refuses what interpret mode and the CPU backend accept:
block shapes off the (8, 128) tiling, value-level dynamic slices inside a
kernel, programs larger than the chip's HBM.  These tests compile the
chain-VM kernel and the sharded serving bodies for a v5e described by
``jax.experimental.topologies``, so such a refusal shows here and not on
the chip.  Nothing runs: a passing compile says nothing about results.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports every
test file.
"""
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import isa, machine, programs
from repro.kernels.chain_vm import ops as chain_ops
from repro.kvstore import hopscotch, store

ROOT = pathlib.Path(__file__).resolve().parent.parent
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def v5e():
    """A described v5e:2x2; JAX's persistent compilation cache is off
    while the module's compiles run (an entry written for a described
    chip cannot be read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    enabled = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:       # no TPU compiler installed
                pytest.skip(f"no v5e:2x2 topology can be described: {e}")
            yield topo
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            cc.reset_cache()


@pytest.fixture(scope="module")
def smoke():
    """``chip_smoke.py``'s module: its constants are the real size."""
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _shapes(mesh, *shapes):
    sh = NamedSharding(mesh, P("kv"))
    return [jax.ShapeDtypeStruct(s, d, sharding=sh) for s, d in shapes]


def _get_args(mesh, buckets, capacity, val_words):
    s = mesh.shape["kv"]
    return _shapes(mesh, ((s, buckets), jnp.int32),
                   ((s, buckets, val_words), jnp.int32),
                   ((s, capacity), jnp.int32), ((s, capacity), jnp.bool_))


def test_chain_vm_kernel_compiles_for_v5e(v5e, smoke):
    """The managed chain-VM kernel at the recycled get server's image, one
    grid cell per request of a GET batch."""
    srv = programs.build_recycled_get_server()
    spec = srv.spec
    n = smoke.GET_BATCH
    one = jax.sharding.SingleDeviceSharding(v5e.devices[0])
    args = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=one) for s in (
        (n, spec.mem_words + machine.GUARD_WORDS),
        (n, spec.msg_capacity * isa.MSG_WORDS), (n, 8))]
    compiled = chain_ops.run_managed.lower(
        *args, wq_base=spec.wq_bases[0], n_wrs=spec.wq_sizes[0],
        managed=True, max_steps=64, impl="pallas").compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("op", ["get", "set"])
def test_serving_body_compiles_for_v5e(v5e, op):
    """The shard_map serving bodies on a one-chip described mesh at 2^12
    buckets (GET capacity 64, SET capacity 16)."""
    mesh = Mesh(np.array(v5e.devices[:1]), ("kv",))
    buckets, val_words = 1 << 12, 4
    if op == "get":
        fn = store._mapped_get(mesh, "kv", "redn", 1, 64, 8, val_words)
        args = _get_args(mesh, buckets, 64, val_words)
    else:
        fn = store._mapped_set(mesh, "kv", 1, 16, 8, val_words, 512,
                               hopscotch.DEFAULT_MAX_SEARCH,
                               hopscotch.DEFAULT_MAX_MOVES)
        args = _shapes(mesh, ((1, buckets), jnp.int32),
                       ((1, buckets, val_words), jnp.int32),
                       ((1, 16), jnp.int32), ((1, 16, val_words), jnp.int32),
                       ((1, 16), jnp.bool_))
    mem = fn.lower(*args).compile().memory_analysis()
    assert mem.temp_size_in_bytes > 0


def test_get_at_smoke_size_fits_v5e_hbm(v5e, smoke):
    """The GET body at ``chip_smoke.py``'s one-chip size: every request
    context holds a whole copy of the shard image (ROADMAP S1), so this
    is the size that must still fit the chip's 16 GB."""
    mesh = Mesh(np.array(v5e.devices[:1]), ("kv",))
    fn = store._mapped_get(mesh, "kv", "redn", 1, smoke.GET_BATCH, 8,
                           smoke.VAL_WORDS)
    mem = fn.lower(*_get_args(mesh, smoke.BUCKETS, smoke.GET_BATCH,
                              smoke.VAL_WORDS)).compile().memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes)
    assert total < V5E_HBM_BYTES, total


@pytest.mark.parametrize("chips", [1, 4])
def test_get_reads_one_shared_shard_at_bench_size(v5e, chips):
    """The GET body at the benchmark's shard (2^20 buckets, GET capacity
    64, V=7), on one chip and on each of four: its contexts read the
    table and value rows from one shared read-only segment, so the body
    declares under 1 GB of temporaries (11.83 GB while every context
    carried its own copy of the shard)."""
    mesh = Mesh(np.array(v5e.devices[:chips]), ("kv",))
    fn = store._mapped_get(mesh, "kv", "redn", chips, 64, 8, 7)
    mem = fn.lower(*_get_args(mesh, 1 << 20, 64, 7)).compile(
    ).memory_analysis()
    assert 0 < mem.temp_size_in_bytes < 10**9, mem.temp_size_in_bytes
