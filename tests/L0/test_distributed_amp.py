"""Distributed amp consistency — the analog of the reference's
``tests/distributed/amp_master_params`` (2-rank O2 run; compare.py asserts
rank-consistency and master == half(model)) on the virtual 8-device mesh."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from apex_tpu import amp
from apex_tpu.optimizers import FusedAdam, FusedSGD

N_DEV = 8


@pytest.fixture
def mesh():
    return Mesh(np.array(jax.devices()[:N_DEV]), ("data",))


def _params():
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    return {"w": 0.3 * jax.random.normal(k1, (16, 8)),
            "b": jnp.zeros((8,)),
            "bn_scale": jnp.ones((8,))}


def test_amp_o2_master_model_consistency_across_devices(mesh):
    """Train amp O2 data-parallel for 3 steps with per-device batches;
    after training: (a) params are REPLICATED (identical on every device),
    (b) model params == masters cast to fp16 (keep_batchnorm leaves fp32)
    — the compare.py assertions."""
    state = amp.initialize(_params(), FusedAdam(lr=1e-2), opt_level="O2",
                           verbosity=0)
    X = jax.random.normal(jax.random.PRNGKey(1), (N_DEV * 4, 16))
    Y = jax.random.normal(jax.random.PRNGKey(2), (N_DEV * 4, 8))

    xsharding = NamedSharding(mesh, P("data"))
    X = jax.device_put(X, xsharding)
    Y = jax.device_put(Y, xsharding)

    @jax.jit
    def train_step(state, X, Y):
        def loss_fn(p):
            pred = state.cast_input(X) @ p["w"] + p["b"]
            pred = pred.astype(jnp.float32) * p["bn_scale"]
            return amp.scale_loss(jnp.mean((pred - Y) ** 2), state)

        grads = jax.grad(loss_fn)(state.model_params)
        return amp.amp_step(state, grads)

    with mesh:
        for _ in range(3):
            state = train_step(state, X, Y)

    # (a) replication: every device holds identical params
    for leaf in jax.tree_util.tree_leaves(state.master_params):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        for s in shards[1:]:
            np.testing.assert_array_equal(shards[0], s)

    # (b) model == cast(master); keep_batchnorm leaves stay fp32
    assert state.model_params["w"].dtype == jnp.float16
    assert state.model_params["bn_scale"].dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(state.model_params["w"]),
        np.asarray(state.master_params["w"].astype(jnp.float16)))
    # masters moved away from init (training actually happened)
    assert float(jnp.abs(state.master_params["w"] - _params()["w"]).max()) > 0


def test_amp_o2_shard_map_explicit_psum(mesh):
    """Same contract through the EXPLICIT collective path: per-device local
    grads + DDP allreduce inside shard_map give the same masters as the
    whole-batch single-device oracle."""
    from apex_tpu.parallel import allreduce_tree

    # SGD: the update is LINEAR in the grads, so the comparison tolerance
    # reflects gradient closeness (Adam's sign-like first step would flip
    # on fp32 reassociation noise between mean-of-means and global mean)
    params = _params()
    state = amp.initialize(params, FusedSGD(lr=0.1), opt_level="O2",
                           loss_scale=128.0, verbosity=0)
    X = jax.random.normal(jax.random.PRNGKey(3), (N_DEV, 4, 16))
    Y = jax.random.normal(jax.random.PRNGKey(4), (N_DEV, 4, 8))

    def local_loss(p, x, y, scale):
        pred = (x.astype(jnp.float16) @ p["w"] + p["b"]).astype(jnp.float32)
        pred = pred * p["bn_scale"]
        return jnp.mean((pred - y) ** 2) * scale

    from apex_tpu.utils.pallas import to_varying

    @jax.jit
    def dist_step(state, X, Y):
        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(jax.tree_util.tree_map(lambda _: P(),
                                             state.model_params),
                      P("data"), P("data")),
            out_specs=jax.tree_util.tree_map(lambda _: P(),
                                             state.model_params))
        def grads_fn(p, x, y):
            # grads wrt REPLICATED params inside shard_map come back
            # already psum-SUMMED (the vma cotangent rule) — to exercise
            # the explicit DDP allreduce, lift params to per-device
            # (varying) copies first, so grads are local like torch's
            p = jax.tree_util.tree_map(
                lambda t: to_varying(t, ("data",)), p)
            g = jax.grad(local_loss)(p, x[0], y[0], state.loss_scale)
            return allreduce_tree(g, axis_name="data")   # average=True
        grads = grads_fn(state.model_params, X, Y)
        return amp.amp_step(state, grads)

    new_state = dist_step(state, X, Y)

    # oracle: single device on the whole batch
    state2 = amp.initialize(params, FusedSGD(lr=0.1), opt_level="O2",
                            loss_scale=128.0, verbosity=0)
    g_oracle = jax.grad(local_loss)(
        state2.model_params, X.reshape(-1, 16), Y.reshape(-1, 8),
        state2.loss_scale)
    oracle = amp.amp_step(state2, g_oracle)

    for k in ("w", "b", "bn_scale"):
        np.testing.assert_allclose(
            np.asarray(new_state.master_params[k]),
            np.asarray(oracle.master_params[k]), atol=1e-4, err_msg=k)


def test_syncbn_1d_shapes(mesh):
    """BatchNorm1d analog (tests/distributed/synced_batchnorm/
    test_batchnorm1d.py): (N, C) inputs through sync_batch_norm, with the
    batch ACTUALLY sharded so the cross-device psum stats path runs."""
    from apex_tpu.parallel import sync_batch_norm

    x = jax.random.normal(jax.random.PRNGKey(5), (32, 6))

    @jax.jit
    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P("data"),), out_specs=P("data"))
    def bn(x):
        out, mean, var = sync_batch_norm(
            x, jnp.ones((6,)), jnp.zeros((6,)), jnp.zeros((6,)),
            jnp.ones((6,)), axis_name="data", training=True,
            channel_last=True)
        return out

    out = bn(x)
    # stats were GLOBAL: whole-batch normalization, not per-shard-of-4
    np.testing.assert_allclose(np.asarray(out).mean(axis=0), 0.0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out).std(axis=0), 1.0, atol=1e-2)
    ref = (x - x.mean(axis=0)) / jnp.sqrt(x.var(axis=0) + 1e-5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_allreduce_tree_handles_presummed_grads(mesh):
    """Grads wrt replicated params under vma arrive already psum-summed;
    allreduce_tree must detect this and return the AVERAGE anyway (no
    double reduction) — the mechanical guard for the cotangent-psum
    footgun."""
    from apex_tpu.parallel import allreduce_tree
    from apex_tpu.utils.pallas import to_varying

    X = jax.random.normal(jax.random.PRNGKey(7), (N_DEV, 4, 16))
    w = 0.2 * jax.random.normal(jax.random.PRNGKey(8), (16, 8))

    def loss(w, x):
        return jnp.mean((x @ w) ** 2)

    def run(lift):
        @jax.jit
        @functools.partial(shard_map, mesh=mesh,
                           in_specs=(P(), P("data")), out_specs=P())
        def f(w, x):
            if lift:
                w = to_varying(w, ("data",))
            g = jax.grad(loss)(w, x[0])
            return allreduce_tree(g, axis_name="data")
        return f(w, X)

    g_presummed = run(lift=False)    # cotangent psum already ran
    g_varying = run(lift=True)       # explicit psum path
    np.testing.assert_allclose(np.asarray(g_presummed),
                               np.asarray(g_varying), atol=1e-6)
    # oracle: global-batch mean grad
    g_oracle = jax.grad(loss)(w, X.reshape(-1, 16))
    np.testing.assert_allclose(np.asarray(g_presummed),
                               np.asarray(g_oracle), atol=1e-6)
