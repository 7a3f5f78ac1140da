"""Worker for the 2-process amp_master_params analog: O2 + DDP training
across REAL process boundaries; each rank prints digests the parent
compares (reference: tests/distributed/amp_master_params/compare.py —
rank-consistency and master == half(model))."""
import faulthandler
import signal

faulthandler.register(signal.SIGUSR1)   # kill -USR1 dumps stacks (debug)

# Pin the CPU platform BEFORE any backend can initialize: a worker must
# never take an accelerator the parent's machine may hold.  Same helper
# the test conftest uses.
from apex_tpu.utils.platform import force_cpu

force_cpu(2)

import numpy as np

from apex_tpu.parallel import initialize_distributed

initialize_distributed()

import functools                  # noqa: E402

import jax                        # noqa: E402
import jax.numpy as jnp           # noqa: E402
from jax.experimental import multihost_utils  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402

from jax import shard_map

from apex_tpu import amp          # noqa: E402
from apex_tpu.optimizers import FusedSGD  # noqa: E402
from apex_tpu.parallel import DistributedDataParallel  # noqa: E402

rank = jax.process_index()
assert jax.process_count() == 2
mesh = Mesh(np.array(jax.devices()), ("data",))
n = jax.device_count()

# identical params everywhere (same seed); per-device different data shards
params = {"w": 0.1 * jax.random.normal(jax.random.PRNGKey(0), (8, 4)),
          "b": jnp.zeros((4,))}
state = amp.initialize(params, FusedSGD(lr=0.1, momentum=0.9),
                       opt_level="O2", verbosity=0)
ddp = DistributedDataParallel(axis_name="data")

B = 4  # per-device batch
x_all = np.random.RandomState(7).randn(n * B, 8).astype(np.float32)
y_all = np.sin(x_all[:, :4]).astype(np.float32)
x = multihost_utils.host_local_array_to_global_array(
    x_all[rank * (n // 2) * B:(rank + 1) * (n // 2) * B], mesh, P("data"))
y = multihost_utils.host_local_array_to_global_array(
    y_all[rank * (n // 2) * B:(rank + 1) * (n // 2) * B], mesh, P("data"))

rep = jax.tree_util.tree_map(lambda _: P(), state)


@jax.jit
@functools.partial(shard_map, mesh=mesh, in_specs=(rep, P("data"), P("data")),
                   out_specs=(rep, P()))
def train_step(state, xl, yl):
    def loss_fn(p):
        pred = xl.astype(jnp.float16) @ p["w"] + p["b"]
        return amp.scale_loss(
            jnp.mean((pred.astype(jnp.float32) - yl) ** 2), state)

    loss, grads = jax.value_and_grad(loss_fn)(state.model_params)
    grads = ddp.allreduce_grads(grads)
    return amp.amp_step(state, grads), jax.lax.pmean(loss, "data")


for _ in range(5):
    state, loss = train_step(state, x, y)

master = np.asarray(
    multihost_utils.process_allgather(
        np.asarray(state.master_params["w"], np.float32)))
model = np.asarray(
    multihost_utils.process_allgather(
        np.asarray(state.model_params["w"], np.float16).astype(np.float32)))

# rank-consistency: every process computed identical params
assert np.array_equal(master[0], master[1]), "masters diverged across ranks"
assert np.array_equal(model[0], model[1]), "models diverged across ranks"
# O2 contract: model == half(master)
np.testing.assert_array_equal(
    model[0], master[0].astype(np.float16).astype(np.float32))
digest = float(np.abs(master[0]).sum())
print(f"AMPOK rank={rank} digest={digest:.6f} "
      f"loss={float(np.asarray(loss.addressable_data(0))):.6f}", flush=True)
