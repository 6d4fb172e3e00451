"""Training-runtime tests: optimizer, train loop (loss decreases),
checkpoint/restart bit-exactness, compression, straggler mitigation,
serving engine."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.data.pipeline import TokenPipeline, make_lm_batch
from repro.distributed import compression, fault
from repro.models import model as M
from repro.train import checkpoint as ckpt
from repro.train import loop as loop_lib
from repro.train import optimizer as opt_lib


@pytest.fixture(scope="module")
def tiny_setup():
    cfg = registry.smoke_config("smollm-135m")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    ocfg = opt_lib.AdamWConfig(lr=3e-3, warmup_steps=2, total_steps=50)
    opt_state = opt_lib.init(params)
    return cfg, params, ocfg, opt_state


def make_batches(cfg, n, b=4, s=32):
    pipe = TokenPipeline(cfg.vocab_size, s, b, seed=3)
    return [
        {k: jnp.asarray(v) for k, v in pipe.batch_at(i).items()}
        for i in range(n)]


def test_loss_decreases(tiny_setup):
    cfg, params, opt_state0 = tiny_setup[0], tiny_setup[1], None
    ocfg = opt_lib.AdamWConfig(lr=1e-2, warmup_steps=3, total_steps=200,
                               weight_decay=0.0)
    opt_state = opt_lib.init(params)
    step = jax.jit(loop_lib.make_train_step(cfg, ocfg))
    batches = make_batches(cfg, 40, b=16)
    losses = []
    for b in batches:
        params, opt_state, m = step(params, opt_state, b)
        losses.append(float(m["loss"]))
    assert min(losses[-5:]) < losses[0] - 0.3, losses


def test_grad_accumulation_matches_full_batch(tiny_setup):
    cfg, params, ocfg, _ = tiny_setup
    batch = make_batches(cfg, 1, b=4)[0]
    s1 = loop_lib.make_train_step(cfg, ocfg, microbatches=1)
    s2 = loop_lib.make_train_step(cfg, ocfg, microbatches=2)
    o1 = opt_lib.init(params)
    o2 = opt_lib.init(params)
    p1, _, m1 = jax.jit(s1)(params, o1, batch)
    p2, _, m2 = jax.jit(s2)(params, o2, batch)
    l1 = jax.tree_util.tree_leaves(p1)
    l2 = jax.tree_util.tree_leaves(p2)
    for a, b_ in zip(l1, l2):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b_, np.float32), atol=2e-5)


def test_checkpoint_restart_bit_exact(tiny_setup, tmp_path):
    """Crash at step 7, resume from step 5 checkpoint -> identical params."""
    cfg, params0, ocfg, _ = tiny_setup
    step = jax.jit(loop_lib.make_train_step(cfg, ocfg))
    pipe = TokenPipeline(cfg.vocab_size, 32, 4, seed=5)

    def batch_fn(i):
        return {k: jnp.asarray(v) for k, v in pipe.batch_at(i).items()}

    ctl = fault.TrainController(step, batch_fn, str(tmp_path / "ck"),
                                ckpt_every=5)
    # uninterrupted run to 10
    p_ref, o_ref, _ = ctl.run(params0, opt_lib.init(params0), 0, 10)

    # crashing run
    ctl2 = fault.TrainController(step, batch_fn, str(tmp_path / "ck2"),
                                 ckpt_every=5)
    with pytest.raises(RuntimeError):
        ctl2.run(params0, opt_lib.init(params0), 0, 10, crash_at=7)
    abstract_p = jax.eval_shape(lambda: params0)
    abstract_o = jax.eval_shape(lambda: opt_lib.init(params0))
    p, o, step_resumed = ctl2.resume(abstract_p, abstract_o)
    assert step_resumed == 5
    p_fin, o_fin, _ = ctl2.run(p, o, step_resumed, 10)

    for a, b in zip(jax.tree_util.tree_leaves(p_ref),
                    jax.tree_util.tree_leaves(p_fin)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_compression_error_feedback_is_contractive():
    rng = np.random.RandomState(0)
    g = {"w": jnp.asarray(rng.randn(64, 64), jnp.float32)}
    err = compression.init_error(g)
    total_true = np.zeros((64, 64), np.float32)
    total_applied = np.zeros((64, 64), np.float32)
    for i in range(20):
        gi = {"w": jnp.asarray(rng.randn(64, 64), jnp.float32)}
        total_true += np.asarray(gi["w"])
        deq, err, ratio = compression.compress_with_feedback(gi, err)
        total_applied += np.asarray(deq["w"])
    # error feedback: cumulative applied ~= cumulative true (residual bounded)
    resid = np.abs(total_applied + np.asarray(err["w"]) - total_true).max()
    assert resid < 1e-3
    assert ratio == 0.25


def test_straggler_masked_combine():
    import functools
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))

    grads = {"w": jnp.ones((1, 4), jnp.float32)}

    def body(g, alive):
        out, n_live = fault.masked_grad_combine(
            {"w": g["w"][0]}, alive[0], "data")
        return out["w"][None], n_live[None]

    f = jax.shard_map(body, mesh=mesh,
                      in_specs=(jax.sharding.PartitionSpec("data"),) * 2,
                      out_specs=(jax.sharding.PartitionSpec("data"),) * 2,
                      check_vma=False)
    out, n = f(grads, jnp.asarray([True]))
    assert float(n[0]) == 1.0
    np.testing.assert_array_equal(np.asarray(out[0]), np.ones(4))
    out, n = f(grads, jnp.asarray([False]))
    assert float(n[0]) == 0.0      # dead shard: contribution dropped


def test_remesh_plan():
    plan = fault.remesh_plan({"data": 16, "model": 16},
                             {"data": 12, "model": 16}, global_batch=240)
    assert plan["batch_ok"] and plan["new_devices"] == 192
    plan = fault.remesh_plan({"data": 16, "model": 16},
                             {"data": 12, "model": 16}, global_batch=256)
    assert not plan["batch_ok"]


def test_serve_engine_decodes_and_survives_driver_crash():
    cfg = registry.smoke_config("qwen3-1.7b")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    from repro.serve import ServeEngine
    eng = ServeEngine(cfg, params, s_max=32, n_slots=4, rate_per_us=0.5,
                      burst=2.0)
    ok = eng.admit([0, 0, 0, 1])          # client 0 over-burst -> throttled
    assert ok == [True, True, False, True]
    eng.add_request(0, 0, 5)
    eng.add_request(1, 1, 7)
    t1 = eng.step()
    eng.crash_host_driver()
    assert not eng.host_alive()
    t2 = eng.step()                        # serving continues (§5.6)
    assert t1.shape == t2.shape == (4,)
    eng.restart_host_driver()
    assert eng.host_alive()
    assert eng.stats["tokens"] >= 4
