"""Pipeline parallelism tests: the microbatched fill-drain schedule must
match running the stages sequentially (oracle), forward AND backward, on
the 8-device CPU mesh (8 stages) and a 4-stage sub-mesh."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.parallel.pipeline import (pipeline_apply, stack_stage_params,
                                        unstack_local)

M, B, D = 6, 4, 16      # microbatches, per-microbatch batch, width


def _stage_fn(p, h):
    return jnp.tanh(h @ p["w"] + p["b"])


def _stages(n, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), n)
    return [{"w": 0.5 * jax.random.normal(k, (D, D)),
             "b": 0.01 * jnp.ones((D,))} for k in ks]


def _sequential(stages, x):
    h = x
    for p in stages:
        h = jax.vmap(lambda xb: _stage_fn(p, xb))(h)   # over microbatches
    return h


def _run_pipeline(stages, x, n):
    mesh = Mesh(np.array(jax.devices()[:n]), ("pipe",))
    stacked = stack_stage_params(stages)
    pspec = jax.tree_util.tree_map(lambda _: P("pipe"), stacked)

    @jax.jit
    # check off: jax 0.4-era check_rep cannot infer the scan carry's
    # replication through pipeline_apply's ppermute and rejects the grad
    # (its own error message prescribes exactly this workaround)
    @functools.partial(shard_map, mesh=mesh, in_specs=(pspec, P()),
                       out_specs=P(), check_vma=False)
    def run(stacked_local, x):
        return pipeline_apply(_stage_fn, unstack_local(stacked_local), x)

    return run, stacked


@pytest.mark.parametrize("n_stages", [4, 8])
def test_pipeline_matches_sequential(n_stages):
    stages = _stages(n_stages)
    x = jax.random.normal(jax.random.PRNGKey(1), (M, B, D))
    run, stacked = _run_pipeline(stages, x, n_stages)
    out = run(stacked, x)
    ref = _sequential(stages, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_pipeline_gradients_match_sequential():
    n = 4
    stages = _stages(n, seed=2)
    x = jax.random.normal(jax.random.PRNGKey(3), (M, B, D))
    g = jax.random.normal(jax.random.PRNGKey(4), (M, B, D))
    run, stacked = _run_pipeline(stages, x, n)

    @jax.jit
    def dist_grads(stacked, x):
        return jax.grad(lambda s: jnp.sum(run(s, x) * g))(stacked)

    @jax.jit
    def ref_grads(stages, x):
        return jax.grad(lambda s: jnp.sum(_sequential(
            [jax.tree_util.tree_map(lambda l: l[i], s) for i in range(n)],
            x) * g))(stages)

    gd = dist_grads(stacked, x)
    gr = ref_grads(stacked, x)
    for k in ("w", "b"):
        np.testing.assert_allclose(np.asarray(gd[k]), np.asarray(gr[k]),
                                   atol=2e-5, err_msg=k)


def test_single_microbatch_and_wide_shapes():
    """Edge cases: M=1 (pure fill-drain latency) and 3-D activations."""
    n = 4
    stages = _stages(n, seed=5)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, B, D))
    run, stacked = _run_pipeline(stages, x, n)
    np.testing.assert_allclose(np.asarray(run(stacked, x)),
                               np.asarray(_sequential(stages, x)),
                               atol=1e-5)
