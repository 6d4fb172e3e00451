"""Subprocess body: gpipe over a 4-stage pipeline axis == sequential apply,
and its gradients flow through the ppermute ring."""
import os

assert "--xla_force_host_platform_device_count=8" in os.environ.get(
    "XLA_FLAGS", "")

import jax                                      # noqa: E402
import jax.numpy as jnp                         # noqa: E402
import numpy as np                              # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from repro.distributed import pipeline          # noqa: E402

S = 4            # stages on the 'pod' axis
M = 6            # microbatches
B, D = 2, 16

mesh = Mesh(np.array(jax.devices()[:S]).reshape(S), ("pod",))
rng = np.random.RandomState(0)
w_all = jnp.asarray(rng.randn(S, D, D) * 0.3, jnp.float32)   # stage params
x = jnp.asarray(rng.randn(M, B, D), jnp.float32)


def stage_fn(w, h):
    return jnp.tanh(h @ w)


# sequential reference
ref = x
for s in range(S):
    ref = stage_fn(w_all[s], ref.reshape(M * B, D)).reshape(M, B, D)


def run_pipe(w_all, x):
    def body(w_stage, x_mb):
        out = pipeline.gpipe(stage_fn, w_stage[0], x_mb, axis_name="pod",
                             n_stages=S)
        # only the last stage holds real outputs; share them
        out = jax.lax.psum(out, "pod") - (S - 1) * 0.0
        return out

    f = jax.shard_map(body, mesh=mesh,
                      in_specs=(P("pod"), P()), out_specs=P(),
                      check_vma=False)
    return f(w_all, x)


got = run_pipe(w_all, x)
np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)
print("forward OK")

# gradients flow through the collective_permute ring
def _seq(w):
    h = x
    for s in range(S):
        h = stage_fn(w[s], h.reshape(M * B, D)).reshape(M, B, D)
    return h


g_pipe = jax.grad(lambda w: jnp.sum(run_pipe(w, x) ** 2))(w_all)
g_ref = jax.grad(lambda w: jnp.sum(_seq(w) ** 2))(w_all)
np.testing.assert_allclose(np.asarray(g_pipe), np.asarray(g_ref),
                           atol=1e-4, rtol=1e-4)
print("backward OK")
print("PIPELINE_OK")
