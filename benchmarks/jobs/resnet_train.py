"""Job adapter: image classification as ``examples/imagenet/main_amp.py``
trains it.

The example's ``train_step`` is a closure of its ``main`` and cannot be
imported, so :func:`build` repeats ``main``'s set-up and step over the same
public calls (``resnet_init``, ``amp.initialize``, ``amp.scale_loss``,
``amp.amp_step``); ``benchmarks/tests/test_jobs.py`` holds the copy to the
example, loss for loss.  What differs on purpose: the batches are made once in
set-up and stay on the device (the example draws a batch with numpy every step
and is bound by that, not by the chip), so the host feed is bypassed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmarks import flops, inputs
from benchmarks.job import (Job, abs_sum, expect_widths, global_norm,
                            load_example, load_module, reference_outcome,
                            scalars)

_WIDTHS = ("block", "stage_sizes", "num_classes", "width")


def build(config: dict, traffic: dict, seed: int, devices,
          reference_path: str):
    from apex_tpu import amp
    from apex_tpu.models import (resnet18_config, resnet50_config,
                                 resnet_apply, resnet_init)
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.parallel import create_mesh, use_mesh

    if len(devices) != 1 or traffic["layout"] != "single":
        raise ValueError("resnet_train drives one device")
    main_amp = load_example(config["entry"]["example"])
    args = main_amp.parse_args(list(config["entry"]["argv"]) + [
        "--batch-size", str(traffic["batch"]), "--seed", str(seed)])
    if args.opt_level != config["amp_opt_level"] or args.optimizer != "adam":
        raise ValueError(f"entry flags {config['entry']['argv']} do not give "
                         f"the configured {config['amp_opt_level']} + adam")

    # -- main_amp.main's set-up ----------------------------------------------
    mesh = create_mesh({"data": 1}, devices=devices)
    cfg_fn = resnet50_config if args.arch == "resnet50" else resnet18_config
    compute_dtype = (jnp.bfloat16 if args.opt_level in
                     ("O1", "O2", "O3", "O4", "O5") else jnp.float32)
    cfg = cfg_fn(dtype=compute_dtype)
    model = dict(config["model"])
    expect_widths("resnet_train",
                  {"block": cfg.block, "stage_sizes": list(cfg.stage_sizes),
                   "num_classes": cfg.num_classes, "width": cfg.width},
                  {k: model[k] for k in _WIDTHS})
    # (the key is an argument, where the example closes over it: a seed
    # baked into the program would make every new seed a new compile)
    params, bn_state = jax.jit(functools.partial(resnet_init, cfg=cfg))(
        jax.random.PRNGKey(args.seed))
    state = amp.initialize(params, FusedAdam(lr=args.lr),
                           opt_level=args.opt_level, verbosity=0)
    state, bn_state = jax.device_put((state, bn_state),
                                     NamedSharding(mesh, P()))

    def scaled_loss(p, state, bn_state, images, labels):
        logits, new_bn = resnet_apply(p, bn_state, images, cfg, train=True)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32))
        loss = -jnp.mean(jnp.take_along_axis(lp, labels[:, None], axis=1))
        acc = jnp.mean(
            (jnp.argmax(logits, axis=1) == labels).astype(jnp.float32))
        return amp.scale_loss(loss, state), (new_bn, loss, acc)

    @jax.jit
    def train_step(state, bn_state, images, labels):
        grads, (new_bn, loss, acc) = jax.grad(scaled_loss, has_aux=True)(
            state.model_params, state, bn_state, images, labels)
        return amp.amp_step(state, grads), new_bn, loss, acc

    def step(carry, batch):
        state, bn_state, loss, _ = train_step(*carry, *batch)
        return (state, bn_state), loss

    # -- inputs: a ring made once, resident on the device --------------------
    host_batches = inputs.make_batches(traffic, model, seed)
    sharding = NamedSharding(mesh, P("data"))
    batches = [tuple(jax.device_put(x, sharding) for x in b)
               for b in host_batches]
    n_sample = traffic["reference_samples"]
    sample = tuple(x[:n_sample] for x in batches[0])

    # -- the plain reference against the system, on the sample ---------------
    reference = load_module(reference_path,
                            "bench_reference_" + config["reference"])

    @jax.jit
    def reference_side(params, batch):
        loss, grads = jax.value_and_grad(reference.loss)(params, batch, model)
        return {"loss": loss, "grad_norm": global_norm(grads),
                "param_abs_sum": abs_sum(params)}

    @jax.jit
    def system_side(state, bn_state, batch):
        grads, (_, loss, _) = jax.grad(scaled_loss, has_aux=True)(
            state.model_params, state, bn_state, *batch)
        return {"loss": loss,
                "grad_norm": global_norm(grads) / state.loss_scale,
                "param_abs_sum": abs_sum(amp.master_params(state))}

    with jax.default_matmul_precision("highest"):
        ref = scalars(reference_side, params, sample)
    outcome = reference_outcome(
        scalars(system_side, state, bn_state, sample), ref,
        config["reference_tolerance"])
    del params

    # -- the optimizer alone, for optimizer_step_ms --------------------------
    update = jax.jit(amp.amp_step, donate_argnums=0)

    def optimizer_probe(carry):
        grads = jax.jit(lambda p: jax.tree_util.tree_map(
            lambda x: jnp.full_like(x, 1e-3), p))(carry[0].model_params)
        return update, carry[0], grads

    leaves = jax.tree_util.tree_leaves(state.model_params)
    facts = {
        "attention": None,
        # Adam: float32 master, two float32 moments
        "optimizer_bytes": flops.optimizer_update_bytes(
            (x.size, x.dtype.itemsize) for x in leaves),
    }
    return Job(
        state=(state, bn_state), step=step, batches=batches,
        samples_per_step=traffic["batch"],
        flops_per_sample=flops.resnet_train_flops_per_sample(
            model, model["image_size"]),
        applied_steps=lambda carry: int(carry[0].opt_state.count),
        skips_allowed=bool(state.scalers[0].dynamic),
        reference=outcome, optimizer_probe=optimizer_probe, facts=facts,
        scope=functools.partial(use_mesh, mesh))
