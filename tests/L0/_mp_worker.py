"""Worker for the multiproc e2e test: joins the 2-process cluster set up by
``python -m apex_tpu.parallel.multiproc`` env, runs a cross-process
allgather + a global-mesh psum, prints a checkable line per rank."""
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

initialize_distributed()          # env from the launcher

import jax                        # noqa: E402
import jax.numpy as jnp           # noqa: E402
from jax.experimental import multihost_utils  # noqa: E402

rank = jax.process_index()
world = jax.process_count()
assert world == 2, f"expected 2 processes, got {world}"

# cross-process allgather of each rank's id
gathered = multihost_utils.process_allgather(np.array([rank], np.int32))
assert sorted(np.asarray(gathered).ravel().tolist()) == [0, 1], gathered

# global-mesh psum: every device contributes (global_device_index + 1)
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402
mesh = Mesh(np.array(jax.devices()), ("data",))
n = jax.device_count()
local = np.array([i + 1 for i in range(n)], np.float32)  # same on each host
garr = multihost_utils.host_local_array_to_global_array(
    local[rank * (n // world):(rank + 1) * (n // world)], mesh, P("data"))

from jax import shard_map
import functools                  # noqa: E402


@jax.jit
@functools.partial(shard_map, mesh=mesh, in_specs=P("data"), out_specs=P())
def total(x):
    return jax.lax.psum(jnp.sum(x), "data")


out = float(np.asarray(total(garr).addressable_data(0)))
expect = float(sum(range(1, n + 1)))
print(f"MPOK rank={rank} world={world} psum={out:.0f} expect={expect:.0f}",
      flush=True)
assert out == expect, (out, expect)
