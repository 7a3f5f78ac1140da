"""Worker for the 2-process ZeRO e2e test: DistributedFusedLAMB
(impl='xla' — interpret-mode Pallas under a multi-process Gloo mesh is
not the target; the fused impl is covered in-process and by the dryrun)
sharded over the GLOBAL mesh spanning both processes.  Each DEVICE holds
1/4 of the optimizer state (each rank drives 2 devices, so holds 1/2);
updated params must be identical everywhere and must match the digest
printed by the peer."""
import faulthandler
import signal

faulthandler.register(signal.SIGUSR1)

from apex_tpu.utils.platform import force_cpu

force_cpu(2)

import numpy as np

from apex_tpu.parallel import initialize_distributed

initialize_distributed()

import functools                  # noqa: E402

import jax                        # noqa: E402
import jax.numpy as jnp           # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402

from jax import shard_map

from apex_tpu.contrib.optimizers import DistributedFusedLAMB  # noqa: E402

rank = jax.process_index()
assert jax.process_count() == 2
n = jax.device_count()
mesh = Mesh(np.array(jax.devices()), ("data",))

params = {"w": 0.1 * jax.random.normal(jax.random.PRNGKey(0), (32, 16)),
          "b": jnp.zeros((16,))}
opt = DistributedFusedLAMB(lr=1e-2, weight_decay=0.01, max_grad_norm=1.0,
                           impl="xla", bf16_allgather=True)
rep = jax.tree_util.tree_map(lambda _: P(), params)
sspec = opt.state_pspecs()


@jax.jit
@functools.partial(shard_map, mesh=mesh, in_specs=(rep,), out_specs=sspec)
def init_fn(p):
    return opt.init(p)


@functools.partial(jax.jit, donate_argnums=0)   # in-place state (HBM reuse
# at the jit boundary — the kernels themselves never alias, PERF_NOTES §2)
@functools.partial(shard_map, mesh=mesh, in_specs=(sspec, rep, rep),
                   out_specs=(rep, sspec))
def step_fn(state, grads, p):
    return opt.step(state, grads, p)


state = init_fn(params)
# ZeRO contract: each device owns 1/n of the flat state (the `p` master
# shard; ShardedLAMBState fields are count/p/m/v/gnorm)
shard = state.p.sharding.shard_shape(state.p.shape)
assert shard[0] * n == state.p.shape[0], (shard, state.p.shape, n)

p = params
for i in range(3):
    grads = jax.tree_util.tree_map(
        lambda x: 0.01 * (i + 1) * jnp.ones_like(x), p)
    p, state = step_fn(state, grads, p)
jax.block_until_ready(p)

w = np.asarray(jax.device_get(p["w"]), np.float32)
assert np.isfinite(w).all()
digest = float(np.abs(w).sum())
print(f"ZEROOK rank={rank} count={int(np.asarray(state.count))} "
      f"digest={digest:.6f}", flush=True)
