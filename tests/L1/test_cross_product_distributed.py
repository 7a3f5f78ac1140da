"""L1 distributed cross-product — the ``tests/L1/cross_product_distributed``
analog: the SAME workload as ``test_cross_product.py`` run data-parallel
(reference: ``torch.distributed.launch --nproc_per_node=2`` over
``common/main_amp.py``; here: shard_map over the 8-device CPU mesh with the
library's DDP grad allreduce + cross-device SyncBatchNorm), cross-compared
against the single-device trajectory of the identical config.

The equivalence contract (compare.py, adapted): with the same global batch,
count-weighted SyncBN stats and mean-averaged DDP gradients, the DP run IS
the single-device run up to reduction order — curves must track within a
tight tolerance, for every opt level family.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from apex_tpu import amp
from apex_tpu.optimizers import FusedSGD
from apex_tpu.parallel import DistributedDataParallel

from .test_cross_product import (BATCH, LR, STEPS, _apply, _data,
                                 _init_params, curve)

N_DEV = 8


def _dp_apply(params, bn_state, x, compute_dtype):
    """The single-device workload with SyncBN reducing over the data axis —
    the only delta vs `_apply`."""
    return _apply(params, bn_state, x, compute_dtype, axis_name="data")


def run_config_dp(opt_level, loss_scale=None, steps=STEPS):
    """Same config as ``run_config`` but data-parallel over N_DEV shards."""
    assert BATCH % N_DEV == 0
    x, y = _data()
    params, bn_state = _init_params()
    state = amp.initialize(params, FusedSGD(lr=LR, momentum=0.9),
                           opt_level=opt_level, loss_scale=loss_scale,
                           verbosity=0)
    compute_dtype = {"O0": jnp.float32, "O1": jnp.float16,
                     "O2": jnp.float16, "O3": jnp.float16,
                     "O4": jnp.bfloat16, "O5": jnp.bfloat16}[opt_level]
    ddp = DistributedDataParallel(axis_name="data")

    mesh = Mesh(np.array(jax.devices()[:N_DEV]), ("data",))
    rep = jax.tree_util.tree_map(lambda _: P(), (state, bn_state))

    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(rep[0], rep[1], P("data"), P("data")),
        out_specs=(rep[0], rep[1], P()))
    def step(state, bn_state, xl, yl):
        def loss_fn(p):
            logits, ns = _dp_apply(p, bn_state, xl, compute_dtype)
            lp = jax.nn.log_softmax(logits)
            # local mean; DDP's average mode divides the psum by world size,
            # so the global gradient equals the full-batch mean gradient
            loss = -jnp.mean(jnp.take_along_axis(lp, yl[:, None], axis=1))
            return amp.scale_loss(loss, state), (loss, ns)

        grads, (loss, ns) = jax.grad(loss_fn, has_aux=True)(
            state.model_params)
        grads = ddp.allreduce_grads(grads)
        loss = jax.lax.pmean(loss, "data")
        return amp.amp_step(state, grads), ns, loss

    curve = []
    for _ in range(steps):
        state, bn_state, loss = step(state, bn_state, x, y)
        curve.append(float(loss))
    return curve


@pytest.mark.parametrize("opt_level,loss_scale", [
    ("O0", None), ("O1", None), ("O2", 128.0), ("O3", 128.0),
    ("O4", None), ("O5", None),
])
def test_dp_matches_single_device(opt_level, loss_scale):
    """DP curve == single-device curve for the same config (the reference's
    rank-consistency + cross-launch compare), within reduction-order slack
    scaled to the compute precision."""
    dp = np.asarray(run_config_dp(opt_level, loss_scale))
    single = np.asarray(curve(opt_level, loss_scale, None))
    assert np.all(np.isfinite(dp)), dp
    rtol = {"O0": 1e-4}.get(opt_level, 0.05)
    np.testing.assert_allclose(dp, single, rtol=rtol)


def test_dp_trains_with_dynamic_scaling():
    """Dynamic-scale DP run trains (scale state stays consistent because it
    is updated from the psum'd gradients on every shard identically)."""
    c = run_config_dp("O2", None)
    assert all(np.isfinite(c)), c
    assert c[-1] < c[0] * 0.95, c
