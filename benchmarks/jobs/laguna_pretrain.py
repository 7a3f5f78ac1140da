"""Job adapter: causal-LM pretraining of the Laguna-S-2.1 decoder through
``examples/bert/pretrain.py`` (``--laguna EP``).

The state and the step are the example's own (``parse_args`` ->
``laguna_config(args)`` -> ``run_standard(args, cfg, mesh)``): amp O5,
per-leaf FusedLAMB, the ``shard_map`` step — ``glm4_moe_lite_pretrain``'s
path with another model in it.  The program's configuration is held to
EVERY key of the configuration file's ``model`` (the published counts and
what is held).

Order of set-up as in ``glm4_moe_lite_pretrain``: the float32 reference runs
FIRST, on parameters made from the same seed by the program's own
initialiser, and is freed before the amp state exists.  A batch is one
sequence of 8192, so the sample is the first sequence of each of the ring's
first ``reference_samples`` batches, and each sequence is checked on its
own — loss and gradient norm of the system against the reference's, the
worst of them against the limits — at the batch shape the step has: a
sample of two sequences at once would want a second program beside the
12.1 GiB of state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

# what a checkout from before this model lacks: first, so that such a
# checkout fails here, in seconds, and not after set-up
from apex_tpu.models import laguna_init, laguna_loss, laguna_routing

from benchmarks import flops_laguna, inputs_lfm2
from benchmarks.job import (Job, abs_sum, expect_widths, global_norm,
                            load_example, load_module, reference_outcome,
                            scalars)


def _as_configured(value):
    """A configuration field as JSON would hold it."""
    return list(value) if isinstance(value, tuple) else value


def _worst(outcomes):
    """One reference check of several sequences: each must pass, and the
    errors reported are the largest."""
    return {"ok": all(o["ok"] for o in outcomes),
            "errors": {k: max(o["errors"][k] for o in outcomes)
                       for k in outcomes[0]["errors"]},
            "limits": outcomes[0]["limits"],
            "system": [o["system"] for o in outcomes],
            "reference": [o["reference"] for o in outcomes]}


def build(config: dict, traffic: dict, seed: int, devices,
          reference_path: str):
    from jax import shard_map
    from apex_tpu import amp
    from apex_tpu.parallel import create_mesh, use_mesh

    pretrain = load_example(config["entry"]["example"])
    if traffic["layout"] != "single" or len(devices) != 1:
        raise ValueError("laguna_pretrain runs one chip's share on one chip")
    args = pretrain.parse_args(list(config["entry"]["argv"]) + [
        "--seq-len", str(traffic["seq"]), "--batch-size",
        str(traffic["batch"]), "--seed", str(seed)])
    cfg = pretrain.laguna_config(args)
    model = config["model"]
    expect_widths("laguna_pretrain", {
        k: _as_configured(getattr(cfg, k)) for k in model}, model)
    if args.opt_level != config["amp_opt_level"]:
        raise ValueError(f"opt level {args.opt_level} != configured "
                         f"{config['amp_opt_level']}")
    mesh = create_mesh({"data": 1}, devices=devices)
    batches = inputs_lfm2.make_batches(traffic, model, seed)
    sample = [{k: jnp.asarray(v[:1]) for k, v in batches[i].items()}
              for i in range(traffic["reference_samples"])]

    # -- the plain reference, a sequence at a time, before the amp state -----
    reference = load_module(reference_path,
                            "bench_reference_" + config["reference"])
    make_params = jax.jit(lambda key: laguna_init(key, cfg))

    @jax.jit
    def reference_side(params, batch):
        loss, g = jax.value_and_grad(reference.loss)(params, batch, model)
        return ({"loss": loss, "grad_norm": global_norm(g),
                 "param_abs_sum": abs_sum(params)},
                reference.routing(params, batch, model))

    with jax.default_matmul_precision("highest"):
        params = make_params(jax.random.PRNGKey(seed))
        refs, ref_chosen = [], []
        for batch in sample:
            values, chosen = reference_side(params, batch)
            refs.append({k: float(v) for k, v in jax.device_get(
                values).items()})
            ref_chosen.append(np.asarray(chosen))
    del params

    # -- the system, through the example's own run_standard -----------------
    with use_mesh(mesh):
        state, step = pretrain.run_standard(args, cfg, mesh)

    @jax.jit
    @functools.partial(shard_map, mesh=mesh, check_vma=False,
                       in_specs=(P(), P()), out_specs=P())
    def system_side(state, batch):
        loss, grads = jax.value_and_grad(laguna_loss)(
            state.model_params, batch, cfg)
        return {"loss": loss, "grad_norm": global_norm(grads),
                "param_abs_sum": abs_sum(amp.master_params(state))}

    outcome = _worst([reference_outcome(scalars(system_side, state, batch),
                                        ref, config["reference_tolerance"])
                      for batch, ref in zip(sample, refs)])

    # assignments of the sample that system and reference gave to different
    # experts: a count, reported and not a limit (a score a hair from the
    # tenth largest falls either way in bfloat16)
    routing = jax.jit(
        lambda params, tokens: laguna_routing(params, tokens, cfg))
    ids = [np.asarray(routing(state.model_params, batch["tokens"])["ids"])
           for batch in sample]
    agreed = [np.take_along_axis(chosen, i, axis=2)
              for chosen, i in zip(ref_chosen, ids)]
    outcome["routing"] = {
        "assignments": int(sum(i.size for i in ids)),
        "chosen_differently": int(sum((~a).sum() for a in agreed))}

    def optimizer_probe(state):
        """Not in this cell (``optimizer_step_ms`` and ``optimizer_bw_share``
        list the cells that have it): ``amp.amp_step`` alone needs the state
        (10.57 GiB) and a gradient tree (1.51) beside the loaded step's
        scratch (4.07 by the compiler's count); ``update_time_share`` reads
        the update where it runs."""
        raise RuntimeError("the update alone does not fit beside its state "
                           "and the loaded step at 811 M parameters: read "
                           "update_time_share")

    def routing_probe(state, ring):
        """``([rows (sparse layers, held)] a batch of the ring, dropped in
        all)`` by the program's own routing on the parameters as they
        stand."""
        records = [jax.device_get(routing(state.model_params,
                                          jnp.asarray(batch["tokens"])))
                   for batch in ring]
        print("[bench] walks of the dispatch buffer, a layer a batch: "
              + " ".join(str(r["walks"].tolist()) for r in records),
              flush=True)
        return ([np.asarray(r["rows"]) for r in records],
                int(sum(r["dropped"].sum() for r in records)))

    itemsize = jnp.dtype(cfg.dtype).itemsize
    heads = dict(zip(cfg.layer_types, cfg.num_attention_heads_per_layer))
    facts = {
        # the causal flash calls the accepted rooflines read: the full layers
        "attention": {
            "batch_heads": traffic["batch"] * heads["full_attention"],
            "seq": traffic["seq"], "head_dim": cfg.head_dim,
            "causal": True, "itemsize": itemsize},
        # the band kernels of the window layers
        "window": {
            "batch_heads": traffic["batch"] * heads["sliding_attention"],
            "seq": traffic["seq"], "head_dim": cfg.head_dim,
            "window": cfg.sliding_window, "itemsize": itemsize},
        "experts": {"held": cfg.experts_held[1], "d_model": cfg.hidden_size,
                    "d_ff": cfg.moe_intermediate_size, "itemsize": itemsize,
                    "layers": cfg.mlp_layer_types.count("sparse")},
        "routing_probe": routing_probe,
    }
    return Job(
        state=state, step=step, batches=batches,
        samples_per_step=traffic["batch"],
        flops_per_sample=flops_laguna.train_flops_per_sample(
            model, traffic["seq"]),
        applied_steps=step.optimizer_steps,
        skips_allowed=bool(state.scalers[0].dynamic),
        reference=outcome, optimizer_probe=optimizer_probe, facts=facts,
        scope=functools.partial(use_mesh, mesh))
