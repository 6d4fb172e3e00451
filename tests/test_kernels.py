"""Per-kernel validation: Pallas (interpret=True on CPU) vs pure-jnp oracle,
swept over shapes and dtypes, plus hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import isa
from repro.kernels.chain_vm import ops as chain_ops
from repro.kernels.decode_attention import ops as dec_ops
from repro.kernels.decode_attention import ref as dec_ref
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.hopscotch import ops as hop_ops
from repro.kernels.rglru import ops as rg_ops
from repro.kernels.rglru import ref as rg_ref
from repro.kernels.rwkv6 import ops as wkv_ops
from repro.kernels.rwkv6 import ref as wkv_ref
from repro.kvstore import hopscotch as hs

RNG = np.random.RandomState(42)


def rand(shape, dtype, scale=1.0):
    x = RNG.randn(*shape).astype(np.float32) * scale
    return jnp.asarray(x, dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


# --- flash attention --------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [
    (1, 2, 2, 128, 64),      # B, H, KH, S, D
    (2, 4, 2, 256, 64),
    (1, 8, 1, 384, 128),     # MQA, non-pow2 seq (tail padding)
])
@pytest.mark.parametrize("mode,window", [
    ("causal", 0), ("causal", 64), ("full", 0)])
def test_flash_attention_sweep(shape, dtype, mode, window):
    b, h, kh, s, d = shape
    q, k, v = (rand((b, h, s, d), dtype), rand((b, kh, s, d), dtype),
               rand((b, kh, s, d), dtype))
    want = fa_ref.attention_reference(q, k, v, mode=mode, window=window)
    for impl in ("interpret", "blocked"):
        got = fa_ops.flash_attention(q, k, v, mode=mode, window=window,
                                     impl=impl, block_q=128, block_k=128)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=TOL[dtype], rtol=TOL[dtype], err_msg=f"{impl}")


def test_flash_attention_decode_length_mode():
    q = rand((2, 4, 1, 64), jnp.float32)
    k = rand((2, 2, 256, 64), jnp.float32)
    v = rand((2, 2, 256, 64), jnp.float32)
    lengths = jnp.asarray([100, 256], jnp.int32)
    want = fa_ref.attention_reference(q, k, v, mode="length",
                                      lengths=lengths)
    got = fa_ops.flash_attention(q, k, v, mode="length", lengths=lengths,
                                 impl="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@settings(max_examples=10, deadline=None)
@given(st.data())
def test_flash_attention_property(data):
    b = data.draw(st.integers(1, 2))
    kh = data.draw(st.sampled_from([1, 2]))
    g = data.draw(st.sampled_from([1, 2, 4]))
    s = data.draw(st.sampled_from([128, 192, 256]))
    d = data.draw(st.sampled_from([64, 128]))
    window = data.draw(st.sampled_from([0, 32, 100]))
    q = rand((b, kh * g, s, d), jnp.float32)
    k = rand((b, kh, s, d), jnp.float32)
    v = rand((b, kh, s, d), jnp.float32)
    want = fa_ref.attention_reference(q, k, v, mode="causal", window=window)
    got = fa_ops.flash_attention(q, k, v, mode="causal", window=window,
                                 impl="interpret", block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5,
                               rtol=1e-4)


# --- decode attention ---------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,kh,s,d", [(2, 4, 1, 512, 64),
                                        (1, 8, 2, 1024, 128)])
def test_decode_attention_sweep(b, h, kh, s, d, dtype):
    q = rand((b, h, 1, d), dtype)
    k = rand((b, kh, s, d), dtype)
    v = rand((b, kh, s, d), dtype)
    lengths = jnp.asarray(RNG.randint(1, s + 1, size=b), jnp.int32)
    want = dec_ref.decode_reference(q, k, v, lengths)
    got = dec_ops.decode_attention(q, k, v, lengths, impl="interpret")
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_decode_sharded_combine_matches_unsharded():
    """The distributed-KV-get identity: per-shard partials combine exactly."""
    b, h, kh, s, d = 2, 4, 2, 1024, 64
    q = rand((b, h, 1, d), jnp.float32)
    k = rand((b, kh, s, d), jnp.float32)
    v = rand((b, kh, s, d), jnp.float32)
    lengths = jnp.asarray([700, 1024], jnp.int32)
    want = dec_ref.decode_reference(q, k, v, lengths)
    for n_shards in (2, 4, 8):
        w = s // n_shards
        parts = [dec_ops.decode_partial(q, k[:, :, i * w:(i + 1) * w],
                                        v[:, :, i * w:(i + 1) * w], lengths,
                                        kpos_offset=i * w, impl="interpret")
                 for i in range(n_shards)]
        got = dec_ops.combine_partials(parts).astype(q.dtype)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, err_msg=f"S={n_shards}")


# --- rwkv6 ----------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,t,n,m", [(2, 2, 64, 32, 32),
                                       (1, 4, 128, 64, 64)])
def test_wkv6_sweep(b, h, t, n, m, dtype):
    r = rand((b, h, t, n), dtype, 0.5)
    k = rand((b, h, t, n), dtype, 0.5)
    v = rand((b, h, t, m), dtype, 0.5)
    w = jnp.asarray(RNG.uniform(0.6, 0.999, (b, h, t, n)), dtype)
    u = rand((h, n), dtype, 0.5)
    want_o, want_s = wkv_ref.wkv6_reference(r, k, v, w, u)
    for impl in ("chunked", "interpret"):
        o, s_ = wkv_ops.wkv6(r, k, v, w, u, impl=impl)
        tol = 5e-5 if dtype == jnp.float32 else 5e-2
        np.testing.assert_allclose(np.asarray(o, np.float32),
                                   np.asarray(want_o, np.float32),
                                   atol=tol, rtol=tol, err_msg=impl)
        np.testing.assert_allclose(np.asarray(s_), np.asarray(want_s),
                                   atol=tol, rtol=tol, err_msg=impl)


def test_wkv6_decode_chain_matches_parallel():
    b, h, t, n, m = 1, 2, 16, 16, 16
    r = rand((b, h, t, n), jnp.float32, 0.5)
    k = rand((b, h, t, n), jnp.float32, 0.5)
    v = rand((b, h, t, m), jnp.float32, 0.5)
    w = jnp.asarray(RNG.uniform(0.6, 0.999, (b, h, t, n)), jnp.float32)
    u = rand((h, n), jnp.float32, 0.5)
    want_o, want_s = wkv_ref.wkv6_reference(r, k, v, w, u)
    st_ = jnp.zeros((b, h, n, m))
    outs = []
    for i in range(t):
        o1, st_ = wkv_ops.wkv6_decode_step(r[:, :, i], k[:, :, i],
                                           v[:, :, i], w[:, :, i], u, st_)
        outs.append(o1)
    np.testing.assert_allclose(np.asarray(jnp.stack(outs, 2)),
                               np.asarray(want_o), atol=1e-5)
    np.testing.assert_allclose(np.asarray(st_), np.asarray(want_s),
                               atol=1e-5)


# --- rglru -----------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,t,d", [(2, 128, 64), (1, 256, 256)])
def test_rglru_sweep(b, t, d, dtype):
    a = jnp.asarray(RNG.uniform(0.4, 0.999, (b, t, d)), dtype)
    u = rand((b, t, d), dtype, 0.5)
    want_h, want_hT = rg_ref.rglru_reference(a, u)
    tol = 5e-5 if dtype == jnp.float32 else 5e-2
    for impl in ("chunked", "interpret", "assoc"):
        h, hT = rg_ops.rglru(a, u, impl=impl)
        np.testing.assert_allclose(np.asarray(h, np.float32),
                                   np.asarray(want_h, np.float32),
                                   atol=tol, rtol=tol, err_msg=impl)
        np.testing.assert_allclose(np.asarray(hT), np.asarray(want_hT),
                                   atol=tol, rtol=tol, err_msg=impl)


# --- hopscotch ---------------------------------------------------------------------

@pytest.mark.parametrize("n,b,v", [(1024, 128, 4), (2048, 256, 8)])
def test_hopscotch_kernel_sweep(n, b, v):
    t = hs.make_table(n, v, neighborhood=8)
    keys = RNG.choice(np.arange(1, 1 << 22), size=n // 3, replace=False)
    stored = {}
    for kk in keys:
        if t.insert(int(kk), [int(kk) % 251] * v):
            stored[int(kk)] = [int(kk) % 251] * v
    dk, dv = t.as_device()
    probe = np.concatenate([
        RNG.choice(keys, b - 16), RNG.randint(1 << 22, 1 << 23, 16)])
    q = jnp.asarray(probe, jnp.int32)
    want_f, want_v = hop_ops.hopscotch_lookup(dk, dv, q, 8, impl="ref")
    got_f, got_v = hop_ops.hopscotch_lookup(dk, dv, q, 8, impl="interpret",
                                            block_q=64, block_n=512)
    np.testing.assert_array_equal(np.asarray(got_f), np.asarray(want_f))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))


@settings(max_examples=10, deadline=None)
@given(nkeys=st.integers(1, 60), seed=st.integers(0, 1000))
def test_hopscotch_kernel_property(nkeys, seed):
    r = np.random.RandomState(seed)
    t = hs.make_table(256, 2, neighborhood=8)
    keys = r.choice(np.arange(1, 1 << 20), size=nkeys, replace=False)
    for kk in keys:
        t.insert(int(kk), [int(kk) % 97, int(kk) % 89])
    dk, dv = t.as_device()
    probe = np.resize(np.concatenate([keys, [1 << 21]]), 64)
    q = jnp.asarray(probe, jnp.int32)
    want = hop_ops.hopscotch_lookup(dk, dv, q, 8, impl="ref")
    got = hop_ops.hopscotch_lookup(dk, dv, q, 8, impl="interpret",
                                   block_q=64, block_n=256)
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


# --- chain_vm -------------------------------------------------------------------------

def _build_toy_chain():
    """A small self-modifying chain on one plain (unmanaged) WQ."""
    from repro.core import assembler
    p = assembler.Program(256)
    x = p.word(5)
    y = p.word(0)
    flag = p.word(0)
    wq = p.add_wq(8)
    wq.read(src=x, dst=y)                  # y = 5
    wq.add(dst=y, addend=37)               # y = 42
    # self-modification: rewrite the NOOP below into WRITE_IMM(99 -> flag)
    new_ctrl = p.word(isa.pack_ctrl(isa.WRITE_IMM, 0))
    tgt = wq.future_wr_addr(1, "ctrl")
    wq.write(src=new_ctrl, dst=tgt, ln=1)
    wq.post(isa.NOOP, dst=flag, opa=99)
    wq.cas(dst=y, old=42, new=43)
    wq.halt()
    spec, state = p.finalize()
    return np.asarray(state.mem), spec, int(state.tail[0]), dict(
        x=x, y=y, flag=flag)


def _run_plain_wq(mems, spec, tail, impl, max_steps=8):
    """Run one plain-WQ context per row of ``mems`` through the chain-VM
    batch executor (no staged messages, ``max_steps`` WRs of fuel)."""
    n = mems.shape[0]
    msgs = jnp.zeros((n, spec.msg_capacity * isa.MSG_WORDS), jnp.int32)
    inits = np.zeros((n, 8), np.int32)
    inits[:, 1] = tail                     # INIT_TAIL: posted WRs
    inits[:, 6] = max_steps                # INIT_FUEL
    mem, _ = chain_ops.run_managed(
        mems, msgs, jnp.asarray(inits), wq_base=spec.wq_bases[0],
        n_wrs=spec.wq_sizes[0], managed=False, max_steps=max_steps,
        impl=impl)
    return np.asarray(mem)


def test_chain_vm_matches_core_semantics():
    mem, spec, tail, addrs = _build_toy_chain()
    batch = jnp.asarray(np.stack([mem] * 4))
    for impl in ("ref", "interpret"):
        got = _run_plain_wq(batch, spec, tail, impl)
        assert (got[:, addrs["y"]] == 43).all(), impl
        assert (got[:, addrs["flag"]] == 99).all(), impl


def test_chain_vm_batch_independence():
    mem, spec, tail, addrs = _build_toy_chain()
    m2 = mem.copy()
    m2[addrs["x"]] = 100                    # different input for client 1
    batch = jnp.asarray(np.stack([mem, m2]))
    out = _run_plain_wq(batch, spec, tail, "interpret")
    assert out[0, addrs["y"]] == 43
    assert out[1, addrs["y"]] == 137        # 100 + 37, CAS(42) failed
