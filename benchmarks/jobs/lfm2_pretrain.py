"""Job adapter: causal-LM pretraining of the LFM2 decoder through
``examples/bert/pretrain.py`` (``--lfm2 DENSE PERIODS HELD``).

The state and the step are the example's own (``parse_args`` ->
``lfm2_config(args)`` -> ``run_standard(args, cfg, mesh)``): amp O5,
FusedLAMB on the flat engine, the ``shard_map`` step — ``bert_pretrain``'s
path with another model in it.  The program's configuration is held to EVERY
key of the configuration file's ``model`` (the published ones and the cut).

Order of set-up as in ``bert_pretrain``: the float32 reference runs FIRST, on
parameters made from the same seed by the program's own initialiser, and is
freed before the amp state exists.  At the published widths the reference's
attention scores are 2 GiB a sequence of 4096, so it takes the sample one
sequence at a time and sums (``reference.loss_sum``).

``facts["routing_probe"]`` runs the program's routing over a ring of batches
outside the step (why not inside: ``benchmarks/routing.py``); the reference
check also says how many assignments of the sample the system and the
reference gave to different experts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

# what the parent of the PR that added this job lacks: first, so that a
# checkout without the model fails here, in seconds, and not after set-up
from apex_tpu.models import lfm2_init, lfm2_loss, lfm2_routing

from benchmarks import flops, flops_lfm2, inputs_lfm2
from benchmarks.job import (Job, abs_sum, expect_widths, global_norm,
                            load_example, load_module, reference_outcome,
                            scalars)


def _as_configured(value):
    """A configuration field as JSON would hold it."""
    return list(value) if isinstance(value, tuple) else value


def build(config: dict, traffic: dict, seed: int, devices,
          reference_path: str):
    from jax import shard_map
    from apex_tpu import amp
    from apex_tpu.parallel import create_mesh, use_mesh

    pretrain = load_example(config["entry"]["example"])
    if traffic["layout"] != "single" or len(devices) != 1:
        raise ValueError("lfm2_pretrain runs one chip's share on one chip")
    args = pretrain.parse_args(list(config["entry"]["argv"]) + [
        "--seq-len", str(traffic["seq"]), "--batch-size",
        str(traffic["batch"]), "--seed", str(seed)])
    cfg = pretrain.lfm2_config(args)
    model = config["model"]
    expect_widths("lfm2_pretrain", {
        k: _as_configured(getattr(cfg, k)) for k in model}, model)
    if args.opt_level != config["amp_opt_level"]:
        raise ValueError(f"opt level {args.opt_level} != configured "
                         f"{config['amp_opt_level']}")
    mesh = create_mesh({"data": 1}, devices=devices)
    batches = inputs_lfm2.make_batches(traffic, model, seed)
    n_sample = traffic["reference_samples"]
    sample = {k: jnp.asarray(v[:n_sample]) for k, v in batches[0].items()}

    # -- the plain reference, a sequence at a time, before the amp state -----
    reference = load_module(reference_path,
                            "bench_reference_" + config["reference"])
    make_params = jax.jit(lambda key: lfm2_init(key, cfg))
    one_sequence = jax.jit(lambda params, batch: (
        *jax.value_and_grad(reference.loss_sum)(params, batch, model),
        reference.routing(params, batch["tokens"], model)))
    with jax.default_matmul_precision("highest"):
        params = make_params(jax.random.PRNGKey(seed))
        total, grads, ref_chosen = 0.0, None, []
        for i in range(n_sample):
            part, g, chosen = one_sequence(
                params, {k: v[i:i + 1] for k, v in sample.items()})
            total = total + part
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g)
            ref_chosen.append(np.asarray(chosen))
        weight = jnp.sum(sample["weights"])
        ref = scalars(lambda: {
            "loss": total / weight, "grad_norm": global_norm(grads) / weight,
            "param_abs_sum": abs_sum(params)})
    del params, grads, g
    ref_chosen = np.concatenate(ref_chosen, axis=1)     # (layers, B·S, E)

    # -- the system, through the example's own builder -----------------------
    with use_mesh(mesh):
        state, step = pretrain.run_standard(args, cfg, mesh)

    replicated = functools.partial(shard_map, mesh=mesh, check_vma=False)

    @jax.jit
    @functools.partial(replicated, in_specs=(P(), P()), out_specs=P())
    def system_side(state, batch):
        loss, grads = jax.value_and_grad(lfm2_loss)(
            state.model_params, batch, cfg)
        return {"loss": loss, "grad_norm": global_norm(grads),
                "param_abs_sum": abs_sum(amp.master_params(state))}

    outcome = reference_outcome(scalars(system_side, state, sample), ref,
                                config["reference_tolerance"])

    # assignments of the sample that system and reference gave to different
    # experts: a count, reported and not a limit (a score a hair from the
    # fourth largest falls either way in bfloat16)
    routing = jax.jit(lambda params, tokens: lfm2_routing(params, tokens, cfg))
    ids = np.asarray(routing(state.model_params, sample["tokens"])["ids"])
    agreed = np.take_along_axis(ref_chosen, ids, axis=2)
    outcome["routing"] = {"assignments": int(ids.size),
                          "chosen_differently": int((~agreed).sum())}

    # -- the optimizer alone, for optimizer_step_ms --------------------------
    @functools.partial(jax.jit, donate_argnums=0)
    @functools.partial(replicated, in_specs=(P(), P()), out_specs=P())
    def update(state, grads):
        return amp.amp_step(state, grads)

    @jax.jit
    @functools.partial(replicated, in_specs=(P(),), out_specs=P())
    def stand_in_grads(params):
        return jax.tree_util.tree_map(lambda x: jnp.full_like(x, 1e-3), params)

    def optimizer_probe(state):
        return update, state, stand_in_grads(state.model_params)

    def routing_probe(state, ring):
        """``([rows (layers, held)] a batch of the ring, dropped in all)``
        by the program's own routing on the parameters as they stand."""
        records = [jax.device_get(routing(state.model_params,
                                          jnp.asarray(batch["tokens"])))
                   for batch in ring]
        return ([np.asarray(r["rows"]) for r in records],
                int(sum(r["dropped"].sum() for r in records)))

    leaves = jax.tree_util.tree_leaves(state.model_params)
    itemsize = jnp.dtype(cfg.dtype).itemsize
    facts = {
        "attention": {
            "batch_heads": traffic["batch"] * cfg.num_attention_heads,
            "seq": traffic["seq"], "head_dim": cfg.head_dim, "causal": True,
            "itemsize": itemsize},
        "optimizer_bytes": flops.optimizer_update_bytes(
            (x.size, x.dtype.itemsize) for x in leaves),
        "gradient_bytes": flops.allreduce_payload_bytes(
            (x.size, x.dtype.itemsize) for x in leaves),
        # what one expert layer's grouped products are made of
        "experts": {"held": cfg.experts_held[1], "d_model": cfg.hidden_size,
                    "d_ff": cfg.moe_intermediate_size, "itemsize": itemsize,
                    "layers": cfg.num_hidden_layers - cfg.num_dense_layers},
        "routing_probe": routing_probe,
    }
    return Job(
        state=state, step=step, batches=batches,
        samples_per_step=traffic["batch"],
        flops_per_sample=flops_lfm2.train_flops_per_sample(
            model, traffic["seq"]),
        applied_steps=step.optimizer_steps,
        skips_allowed=bool(state.scalers[0].dynamic),
        reference=outcome, optimizer_probe=optimizer_probe, facts=facts,
        scope=functools.partial(use_mesh, mesh))
