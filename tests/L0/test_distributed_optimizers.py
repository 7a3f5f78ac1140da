"""ZeRO sharded-optimizer tests on the 8-device CPU mesh.

Oracle pattern (SURVEY §4): the sharded collective step must match the
single-device fused optimizer run on the *averaged* gradients to tight
tolerance — the distributed machinery (psum_scatter / sharded update /
all_gather, two-level topology, bf16 gather, overflow skip) must be
numerically invisible.  The reference could only test this with real
multi-process GPUs (tests/distributed/); the virtual CPU mesh runs it in CI.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.contrib.optimizers import (DistributedFusedAdam,
                                         DistributedFusedLAMB)
from apex_tpu.optimizers import FusedAdam, FusedLAMB

SHAPES = [(33, 7), (128,), (3, 5, 11), (257,)]
ITERS = 4


def make_params(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), len(SHAPES))
    return {f"p{i}": jax.random.normal(k, s) * 0.5
            for i, (k, s) in enumerate(zip(ks, SHAPES))}


def make_local_grads(seed, n_dev):
    """Per-device grads stacked on a leading device axis; devices see
    DIFFERENT grads (realistic DP)."""
    ks = jax.random.split(jax.random.PRNGKey(seed + 100), len(SHAPES))
    return {f"p{i}": jax.random.normal(k, (n_dev,) + s)
            for i, (k, s) in enumerate(zip(ks, SHAPES))}


def mean_grads(gl):
    return jax.tree_util.tree_map(lambda g: g.mean(axis=0), gl)


def _mesh(shape, names):
    devs = np.array(jax.devices()[: int(np.prod(shape))]).reshape(shape)
    return Mesh(devs, names)


def run_sharded(opt, params, n_dev=8, iters=ITERS, mesh=None, specs=None,
                grad_scale=1.0, poison_iter=None):
    """Drive init+step inside shard_map.  Params/output replicated; grads
    arrive split over the leading device axis (local grads).

    check_vma stays at the default (True) for the xla impl — validating the
    state specs and the all_gather_invariant replication claim — but must be
    False for impl='fused': jax's pallas interpreter (the CPU test path)
    materializes the grid loop's output carry without vma typing, so ANY
    interpret-mode pallas_call under check_vma=True fails in the
    while_loop type check ("carry[i] ... varying manual axes do not
    match") regardless of how the kernel's inputs/outputs are typed.
    Compiled TPU pallas is unaffected.
    """
    mesh = mesh or _mesh((n_dev,), ("data",))
    specs = specs if specs is not None else P(*(mesh.axis_names))
    gspec = jax.tree_util.tree_map(lambda _: specs, params)
    sspec = opt.state_pspecs()
    vma_kw = {"check_vma": False} if opt.impl == "fused" else {}

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(jax.tree_util.tree_map(lambda _: P(), params),),
        out_specs=sspec)
    def init_fn(p):
        return opt.init(p)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(sspec, gspec,
                  jax.tree_util.tree_map(lambda _: P(), params)),
        out_specs=(jax.tree_util.tree_map(lambda _: P(), params), sspec),
        **vma_kw)
    def step_fn(state, grads_local, p):
        grads_local = jax.tree_util.tree_map(
            lambda g: g.reshape(g.shape[1:]) if g.shape[0] == 1 else g[0],
            grads_local)
        return opt.step(state, grads_local, p, scale=grad_scale)

    state = jax.jit(init_fn)(params)
    step = jax.jit(step_fn)
    p = params
    for i in range(iters):
        gl = make_local_grads(i, n_dev)
        if poison_iter is not None and i == poison_iter:
            gl = jax.tree_util.tree_map(lambda g: g.at[0].set(jnp.inf), gl)
        if grad_scale != 1.0:
            gl = jax.tree_util.tree_map(lambda g: g * grad_scale, gl)
        p, state = step(state, gl, p)
    return p, state


def run_single(opt, params, n_dev=8, iters=ITERS):
    """Single-device oracle on the averaged grads."""
    state = opt.init(params)
    step = jax.jit(lambda s, g, p: opt.step(s, g, p))
    p = params
    for i in range(iters):
        p, state = step(state, mean_grads(make_local_grads(i, n_dev)), p)
    return p


def assert_tree_close(a, b, atol=1e-6):
    for k in a:
        np.testing.assert_allclose(np.asarray(a[k]), np.asarray(b[k]),
                                   atol=atol, err_msg=k)


@pytest.mark.parametrize("impl", ["xla", "fused"])
@pytest.mark.parametrize("adamw,wd", [(True, 0.01), (False, 0.01)])
def test_dist_adam_matches_single_device(impl, adamw, wd):
    params = make_params()
    dopt = DistributedFusedAdam(lr=1e-2, weight_decay=wd, adam_w_mode=adamw,
                                impl=impl)
    sopt = FusedAdam(lr=1e-2, weight_decay=wd, adam_w_mode=adamw)
    p_dist, _ = run_sharded(dopt, params)
    p_single = run_single(sopt, params)
    assert_tree_close(p_dist, p_single)


@pytest.mark.parametrize("impl", ["xla", "fused"])
def test_dist_lamb_matches_single_device(impl):
    params = make_params()
    dopt = DistributedFusedLAMB(lr=1e-2, weight_decay=0.01,
                                max_grad_norm=1.0, impl=impl)
    sopt = FusedLAMB(lr=1e-2, weight_decay=0.01, max_grad_norm=1.0)
    p_dist, state = run_sharded(dopt, params)
    p_single = run_single(sopt, params)
    assert_tree_close(p_dist, p_single, atol=1e-5)
    assert float(state.gnorm) > 0


def test_dist_adam_two_level_topology():
    """2 replica groups x 4-way sharding (the dcn x ici mesh): numerics
    identical to the flat case and to the single-device oracle."""
    params = make_params()
    mesh = _mesh((2, 4), ("dcn", "ici"))
    dopt = DistributedFusedAdam(lr=1e-2, weight_decay=0.01,
                                shard_axis="ici", replica_axis="dcn")
    p_dist, _ = run_sharded(dopt, params, mesh=mesh,
                            specs=P(("dcn", "ici")))
    p_single = run_single(FusedAdam(lr=1e-2, weight_decay=0.01), params)
    assert_tree_close(p_dist, p_single)


def test_dist_adam_state_is_sharded_1_over_n():
    """The ZeRO memory claim: per-device optimizer state is 1/N of the
    flat model (the whole point of distributed_fused_adam.py)."""
    params = make_params()
    mesh = _mesh((8,), ("data",))
    dopt = DistributedFusedAdam(lr=1e-2)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(jax.tree_util.tree_map(lambda _: P(), params),),
        out_specs=dopt.state_pspecs())
    def init_fn(p):
        st = dopt.init(p)
        total = dopt._flattener(p, 8).total
        assert st.p.shape == (total // 8,)
        assert st.m.shape == (total // 8,)
        assert st.v.shape == (total // 8,)
        return st

    state = jax.jit(init_fn)(params)
    # global (stacked) view: exactly total elements per buffer across devices
    total = dopt._flattener(params, 8).total
    assert state.p.size == total


def test_dist_adam_overflow_skips_step():
    """An inf grad on ONE device must skip the step on ALL devices (state
    and params unchanged) — the select-based revert (reference
    revert_method :75-81 + strided_check_finite :535)."""
    params = make_params()
    dopt = DistributedFusedAdam(lr=1e-2)
    p1, s1 = run_sharded(dopt, params, iters=1)
    # second run: same first step, then a poisoned second step
    p2, s2 = run_sharded(dopt, params, iters=2, poison_iter=1)
    assert int(s2.count) == 1          # poisoned step did not count
    assert_tree_close(p2, p1)          # params rolled back == after step 1


def test_dist_adam_bf16_allgather():
    """bf16 param all-gather (e5m2_allgather analog) stays within bf16
    rounding of the fp32 path."""
    params = make_params()
    d32 = DistributedFusedAdam(lr=1e-2)
    d16 = DistributedFusedAdam(lr=1e-2, bf16_allgather=True)
    p32, _ = run_sharded(d32, params, iters=2)
    p16, _ = run_sharded(d16, params, iters=2)
    for k in p32:
        np.testing.assert_allclose(np.asarray(p32[k]), np.asarray(p16[k]),
                                   atol=2e-2, err_msg=k)


def test_dist_adam_scale_interop():
    """Pre-scaled grads + scale= must match the unscaled run (amp loss-
    scaling interop, reference set_global_scale)."""
    params = make_params()
    p1, _ = run_sharded(DistributedFusedAdam(lr=1e-2), params, iters=2)
    p2, _ = run_sharded(DistributedFusedAdam(lr=1e-2), params, iters=2,
                        grad_scale=64.0)
    assert_tree_close(p1, p2, atol=1e-6)


def test_dist_state_dtype_bf16_moments():
    """ZeRO with narrow (bf16) moment storage: shard dtypes honor the
    knob, master stays fp32, and the trajectory tracks the fp32-state
    sharded run to a few % (same trade as the single-device flat
    engine's state_dtype — docs/performance.md)."""
    params = make_params()
    d16 = DistributedFusedAdam(lr=1e-2, weight_decay=0.01,
                               state_dtype=jnp.bfloat16)
    d32 = DistributedFusedAdam(lr=1e-2, weight_decay=0.01)
    p16, s16 = run_sharded(d16, params)
    p32, _ = run_sharded(d32, params)
    assert s16.m.dtype == jnp.bfloat16 and s16.v.dtype == jnp.bfloat16
    assert s16.p.dtype == jnp.float32
    for k in p32:
        a, b = np.asarray(p32[k]), np.asarray(p16[k])
        rel = np.abs(a - b) / np.maximum(np.abs(a), 1e-3)
        assert np.isfinite(b).all()
        assert rel.max() < 6e-2, f"{k}: max rel drift {rel.max()}"


def test_dist_state_dtype_rejects_non_float():
    with pytest.raises(ValueError, match="float dtype"):
        DistributedFusedAdam(lr=1e-2, state_dtype=jnp.int32)
