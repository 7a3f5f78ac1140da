"""Distributed-layer tests on the 8-device CPU mesh (conftest forces
``xla_force_host_platform_device_count=8``) — the fake-cluster capability the
reference's real-multiprocess harness lacked (SURVEY §4 takeaway).

Oracles follow the reference's pattern: SyncBN vs a single-device whole-batch
computation (``tests/distributed/synced_batchnorm/two_gpu_unit_test.py``),
DDP grad allreduce vs analytically-known sums
(``tests/distributed/DDP/ddp_race_condition_test.py:28-70``), LARC vs a
hand-written update (``tests/L0/run_amp/test_larc.py``).
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from apex_tpu import parallel
from apex_tpu.parallel import (
    DistributedDataParallel, Reducer, LARC, SyncBatchNorm,
    sync_batch_norm, create_mesh, create_grouped_mesh, use_mesh)
from apex_tpu.optimizers import FusedSGD


N_DEV = 8


@pytest.fixture(scope="module")
def mesh():
    return create_mesh({"data": N_DEV})


def test_ddp_allreduce_grads_mean(mesh):
    """Grad psum averages across the data axis (distributed.py:446-455)."""
    ddp = DistributedDataParallel(axis_name="data")
    local = jnp.arange(N_DEV, dtype=jnp.float32)  # device i holds value i

    @functools.partial(shard_map, mesh=mesh, in_specs=P("data"),
                       out_specs=P("data"))
    def reduce(x):
        grads = {"w": x}
        return ddp.allreduce_grads(grads)["w"]

    out = reduce(local)
    expected = np.full(N_DEV, np.mean(np.arange(N_DEV)), np.float32)
    np.testing.assert_allclose(np.asarray(out), expected)


def test_ddp_predivide_and_fp32_allreduce(mesh):
    """predivide_factor: divide by f pre-reduce, f/world post (:446-455);
    allreduce_always_fp32 upcasts bf16 for the reduce (:443-445)."""
    ddp = DistributedDataParallel(axis_name="data",
                                  gradient_predivide_factor=2.0,
                                  allreduce_always_fp32=True)
    local = jnp.ones((N_DEV,), jnp.bfloat16) * 3

    @functools.partial(shard_map, mesh=mesh, in_specs=P("data"),
                       out_specs=P("data"))
    def reduce(x):
        return ddp.allreduce_grads({"w": x})["w"]

    out = reduce(local)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32), 3.0)


def test_ddp_noop_outside_mesh():
    ddp = DistributedDataParallel(axis_name="data")
    g = {"w": jnp.ones((4,))}
    out = ddp.allreduce_grads(g)
    np.testing.assert_allclose(np.asarray(out["w"]), 1.0)


def test_ddp_noop_knobs_warn():
    # multi-stream options remain documented no-ops (XLA owns stream
    # scheduling) ...
    with pytest.warns(UserWarning):
        DistributedDataParallel(axis_name="data", num_allreduce_streams=2)
    # ... but message_size is LIVE again since the async-overlap work
    # (parallel.overlap bucket threshold) — it must NOT warn
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        ddp = DistributedDataParallel(axis_name="data", message_size=1)
    assert ddp.message_size == 1


def test_reducer_sum_vs_known(mesh):
    """Analytically-known reduction (ddp_race_condition_test.py pattern)."""
    red = Reducer(axis_name="data", gradient_average=False)
    local = jnp.arange(N_DEV, dtype=jnp.float32)

    @functools.partial(shard_map, mesh=mesh, in_specs=P("data"),
                       out_specs=P("data"))
    def reduce(x):
        return red.reduce(x)

    out = reduce(local)
    np.testing.assert_allclose(np.asarray(out), float(np.arange(N_DEV).sum()))


# ---------------------------------------------------------------------------
# SyncBatchNorm
# ---------------------------------------------------------------------------

def _bn_oracle(x, w, b, eps=1e-5):
    """Whole-batch NHWC batchnorm in numpy (fp64 accumulate) — the oracle of
    two_gpu_unit_test.py."""
    x64 = np.asarray(x, np.float64)
    axes = tuple(range(x64.ndim - 1))
    mean = x64.mean(axes)
    var = x64.var(axes)
    out = (x64 - mean) / np.sqrt(var + eps) * np.asarray(w) + np.asarray(b)
    return out, mean, var


def test_syncbn_matches_whole_batch_oracle(mesh):
    rng = np.random.RandomState(0)
    N, H, W, C = 16, 4, 4, 8
    x = rng.randn(N, H, W, C).astype(np.float32)
    w = rng.rand(C).astype(np.float32) + 0.5
    b = rng.randn(C).astype(np.float32)

    bn = SyncBatchNorm(C, process_group="data")
    params, state = bn.init()
    params = {"weight": jnp.asarray(w), "bias": jnp.asarray(b)}

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P("data"), P(), P(), P(), P()),
        out_specs=(P("data"), P(), P()))
    def run(xs, wt, bs, rm, rv):
        out, new_state = bn.apply({"weight": wt, "bias": bs},
                                  {"running_mean": rm, "running_var": rv}, xs)
        return out, new_state["running_mean"], new_state["running_var"]

    out, new_rm, new_rv = run(jnp.asarray(x), params["weight"], params["bias"],
                              state["running_mean"], state["running_var"])
    ref, mean, var = _bn_oracle(x, w, b)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-4)
    # running stats: momentum 0.1, unbiased var (kernel.py:55-58)
    n = N * H * W
    np.testing.assert_allclose(np.asarray(new_rm), 0.1 * mean, atol=1e-5)
    np.testing.assert_allclose(np.asarray(new_rv),
                               0.9 + 0.1 * var * n / (n - 1), atol=1e-4)


def test_syncbn_backward_matches_oracle(mesh):
    """Grad through the distributed BN == grad through single-device BN on the
    whole batch (the hand-written backward of kernel.py:97-113 comes out of
    autodiff through psum)."""
    rng = np.random.RandomState(1)
    N, C = 16, 4
    x = rng.randn(N, C).astype(np.float32)
    w = rng.rand(C).astype(np.float32) + 0.5
    b = rng.randn(C).astype(np.float32)

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(P("data"), P(), P()),
        out_specs=(P("data"), P(), P()))
    def dist_grads(xs, wt, bs):
        def f(xs, wt, bs):
            out, _, _ = sync_batch_norm(xs, wt, bs, axis_name="data")
            return jnp.sum(out ** 2)
        # with the replication check on, shard_map autodiff psums the
        # cotangents of replicated inputs itself
        return jax.grad(f, argnums=(0, 1, 2))(xs, wt, bs)

    gx, gw, gb = dist_grads(jnp.asarray(x), jnp.asarray(w), jnp.asarray(b))

    def whole(xs, wt, bs):
        out, _, _ = sync_batch_norm(xs, wt, bs, axis_name=None)
        return jnp.sum(out ** 2)

    egx, egw, egb = jax.grad(whole, argnums=(0, 1, 2))(
        jnp.asarray(x), jnp.asarray(w), jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(gx), np.asarray(egx), atol=2e-4)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(egw), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(egb), rtol=1e-4)


def test_syncbn_group_axis():
    """Group-scoped sync: stats stay inside each mesh group
    (test_groups.py analog)."""
    gmesh = create_grouped_mesh(group_size=4)
    x = np.zeros((8, 2), np.float32)
    x[4:] = 10.0  # second group of devices sees different data

    @functools.partial(shard_map, mesh=gmesh,
                       in_specs=P(("data", "group")), out_specs=P(("data", "group")))
    def run(xs):
        out, _, _ = sync_batch_norm(xs, None, None, axis_name="group")
        return out

    out = np.asarray(run(jnp.asarray(x)))
    # within each group values are identical -> normalized output is 0
    np.testing.assert_allclose(out, 0.0, atol=1e-5)


def test_syncbn_default_syncs_whole_world(mesh):
    """process_group=None (the reference default) syncs over every bound mesh
    axis — regression: the old GROUP_AXIS default crashed under a plain data
    mesh."""
    bn = SyncBatchNorm(2, affine=False, track_running_stats=False)
    x = np.zeros((8, 2), np.float32)
    x[4:] = 10.0

    @functools.partial(shard_map, mesh=mesh, in_specs=P("data"),
                       out_specs=P("data"))
    def run(xs):
        out, _ = bn.apply({}, {}, xs)
        return out

    out = np.asarray(run(jnp.asarray(x)))
    # stats are global: mean 5, so outputs are +-1 after normalize
    np.testing.assert_allclose(np.abs(out), 1.0, rtol=1e-4)


def test_syncbn_eval_without_running_stats():
    """track_running_stats=False in eval falls back to batch statistics
    (torch.nn.BatchNorm semantics) instead of crashing."""
    bn = SyncBatchNorm(2, affine=False, track_running_stats=False)
    x = jnp.asarray(np.random.RandomState(3).randn(8, 2).astype(np.float32))
    out, _ = bn.apply({}, {}, x, training=False)
    np.testing.assert_allclose(np.asarray(out).mean(0), 0.0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out).std(0), 1.0, atol=1e-2)


def test_syncbn_eval_mode_and_fused_relu():
    x = jnp.asarray(np.linspace(-2, 2, 16, dtype=np.float32).reshape(8, 2))
    rm = jnp.zeros((2,)); rv = jnp.ones((2,))
    out, _, _ = sync_batch_norm(x, None, None, rm, rv, axis_name=None,
                                training=False, fuse_relu=True)
    expected = np.maximum(np.asarray(x), 0.0)
    np.testing.assert_allclose(np.asarray(out), expected, atol=1e-5)


def test_syncbn_residual_add():
    x = jnp.ones((4, 3)); z = jnp.full((4, 3), 2.0)
    out, _, _ = sync_batch_norm(x, None, None, axis_name=None, z=z)
    np.testing.assert_allclose(np.asarray(out), 2.0, atol=1e-5)


def test_convert_syncbn_model():
    class BatchNorm:  # stand-in local BN module
        __module__ = "apex_tpu.models.layers"
        def __init__(self, n):
            self.num_features = n; self.eps = 1e-5; self.momentum = 0.1
            self.affine = True; self.track_running_stats = True

    class Block:
        __module__ = "apex_tpu.models.layers"
        def __init__(self):
            self.bn = BatchNorm(8)
            self.sub = [BatchNorm(4), "not_a_module"]

    conv = parallel.convert_syncbn_model(Block())
    assert isinstance(conv.bn, SyncBatchNorm) and conv.bn.num_features == 8
    assert isinstance(conv.sub[0], SyncBatchNorm)
    assert conv.sub[1] == "not_a_module"


# ---------------------------------------------------------------------------
# LARC
# ---------------------------------------------------------------------------

def test_larc_clip_matches_reference_math():
    """One LARC+SGD step vs hand-computed update (LARC.py:84-106)."""
    p = {"w": jnp.asarray([3.0, 4.0])}          # ||p|| = 5
    g = {"w": jnp.asarray([0.6, 0.8])}          # ||g|| = 1
    lr, tc, wd = 0.1, 0.02, 0.01
    opt = LARC(FusedSGD(lr=lr, momentum=0.0, weight_decay=wd),
               trust_coefficient=tc, clip=True)
    state = opt.init(p)
    new_p, _ = opt.step(state, g, p)

    adaptive = tc * 5.0 / (1.0 + 5.0 * wd + 1e-8)
    scale = min(adaptive / lr, 1.0)
    eff_g = (np.asarray([0.6, 0.8]) + wd * np.asarray([3.0, 4.0])) * scale
    expected = np.asarray([3.0, 4.0]) - lr * eff_g
    np.testing.assert_allclose(np.asarray(new_p["w"]), expected, rtol=1e-6)
    # inner wd restored after the step
    assert opt.optim.weight_decay == wd


def test_larc_scale_mode_zero_grad_guard():
    p = {"w": jnp.asarray([1.0, 1.0])}
    g = {"w": jnp.zeros(2)}
    opt = LARC(FusedSGD(lr=0.1, momentum=0.0), clip=False)
    state = opt.init(p)
    new_p, _ = opt.step(state, g, p)
    np.testing.assert_allclose(np.asarray(new_p["w"]), 1.0)


def test_larc_zero_grad_no_weight_decay_leak():
    """Regression: the zero-norm guard must skip the decay fold too — frozen
    params must not decay (reference guard skips the whole block)."""
    p = {"w": jnp.asarray([1.0, 1.0])}
    g = {"w": jnp.zeros(2)}
    opt = LARC(FusedSGD(lr=0.1, momentum=0.0, weight_decay=0.5))
    state = opt.init(p)
    new_p, _ = opt.step(state, g, p)
    np.testing.assert_allclose(np.asarray(new_p["w"]), 1.0)


def test_larc_schedule_lr_alignment():
    """Regression: with a callable lr, LARC clips against the lr the wrapped
    optimizer actually uses this step (count+1), so a 0-at-step-0 warmup
    schedule cannot produce inf/nan."""
    sched = lambda t: 0.1 * jnp.minimum(t / 2.0, 1.0)  # lr(0)=0, lr(1)=0.05
    p = {"w": jnp.asarray([3.0, 4.0])}
    g = {"w": jnp.asarray([0.6, 0.8])}
    opt = LARC(FusedSGD(lr=sched, momentum=0.0))
    state = opt.init(p)
    new_p, _ = opt.step(state, g, p)
    assert np.all(np.isfinite(np.asarray(new_p["w"])))
    # step used lr(1)=0.05; adaptive=0.02*5/1=0.1 => clip ratio 2 -> scale 1
    np.testing.assert_allclose(np.asarray(new_p["w"]),
                               [3.0 - 0.05 * 0.6, 4.0 - 0.05 * 0.8], rtol=1e-6)
