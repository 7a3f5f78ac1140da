"""Minimal data-parallel + amp training (CPU-runnable).

TPU-native rebuild of the reference's
``examples/simple/distributed/distributed_data_parallel.py`` (toy model +
DistributedDataParallel + ``amp.scale_loss``): a 2-layer MLP trained with
amp O1 (per-op autocast + dynamic loss scaling) and the batch sharded over
every visible device through a ``data`` mesh axis.  Where the reference
launches one process per GPU (``torch.distributed.launch``), SPMD drives all
devices from one process; run on CPU with

    PYTHONPATH=. JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/simple/distributed/distributed_data_parallel.py
"""
from __future__ import annotations

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu import amp
from apex_tpu.optimizers import FusedSGD
from apex_tpu.parallel import create_mesh, use_mesh


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch-size", type=int, default=64, help="global batch")
    p.add_argument("--d-in", type=int, default=512)
    p.add_argument("--d-hidden", type=int, default=256)
    p.add_argument("--d-out", type=int, default=32)
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--opt-level", default="O1")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--print-freq", type=int, default=20)
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    devices = jax.devices()
    n_dev = len(devices)
    if args.batch_size % n_dev:
        n_dev = 1      # fall back to single device rather than erroring
        devices = devices[:1]
    mesh = create_mesh({"data": n_dev}, devices=devices)
    print(f"=> {n_dev} device(s) ({jax.default_backend()}), amp "
          f"{args.opt_level}")

    key = jax.random.PRNGKey(args.seed)
    k1, k2, k3 = jax.random.split(key, 3)
    params = {
        "fc1": {"w": jax.random.normal(k1, (args.d_in, args.d_hidden))
                * (2.0 / args.d_in) ** 0.5,
                "b": jnp.zeros((args.d_hidden,))},
        "fc2": {"w": jax.random.normal(k2, (args.d_hidden, args.d_out))
                * (1.0 / args.d_hidden) ** 0.5,
                "b": jnp.zeros((args.d_out,))},
    }
    opt = FusedSGD(lr=args.lr, momentum=0.9)
    state = amp.initialize(params, opt, opt_level=args.opt_level)

    # fixed regression target, like the reference's toy problem
    rng = np.random.RandomState(args.seed)
    X = rng.randn(args.batch_size, args.d_in).astype(np.float32)
    W = rng.randn(args.d_in, args.d_out).astype(np.float32) * 0.1
    Y = X @ W

    batch_sharding = NamedSharding(mesh, P("data"))
    X = jax.device_put(X, batch_sharding)
    Y = jax.device_put(Y, batch_sharding)

    @jax.jit
    def train_step(state, X, Y):
        def loss_fn(p):
            # jnp.matmul autocasts under O1's patched functions
            h = jax.nn.relu(jnp.matmul(state.cast_input(X), p["fc1"]["w"])
                            + p["fc1"]["b"])
            pred = jnp.matmul(h, p["fc2"]["w"]) + p["fc2"]["b"]
            loss = jnp.mean((pred.astype(jnp.float32) - Y) ** 2)
            return amp.scale_loss(loss, state), loss

        grads, loss = jax.grad(loss_fn, has_aux=True)(state.model_params)
        # gradient reduction over the data axis is inserted by XLA from the
        # shardings (the DistributedDataParallel psum; parallel/distributed.py)
        return amp.amp_step(state, grads), loss

    with use_mesh(mesh):
        t0 = time.perf_counter()
        first_loss = None
        for step in range(args.steps):
            state, loss = train_step(state, X, Y)
            if (step + 1) % args.print_freq == 0:
                loss = float(loss)
                if first_loss is None:
                    first_loss = loss
                dt = time.perf_counter() - t0
                print(f"step {step + 1:4d}  loss {loss:.5f}  "
                      f"loss_scale {float(state.loss_scale):.0f}  "
                      f"{args.print_freq * args.batch_size / dt:.0f} "
                      "samples/sec", flush=True)
                t0 = time.perf_counter()
    final = float(loss)
    print(f"=> done: loss {final:.5f}")
    return final


if __name__ == "__main__":
    from apex_tpu.utils.platform import enable_compile_cache
    enable_compile_cache()
    main()
