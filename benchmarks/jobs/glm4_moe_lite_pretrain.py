"""Job adapter: causal-LM pretraining of the GLM-4.7-Flash decoder, its
multi-token-prediction module included, through ``examples/bert/pretrain.py``
(``--glm4-moe-lite EP LAYERS``).

The state and the step are the example's own (``parse_args`` ->
``glm4_moe_lite_config(args)`` -> ``run_standard(args, cfg, mesh)``): amp
O5, per-leaf FusedLAMB, the ``shard_map`` step — ``qwen3_next_pretrain``'s
path with another model in it.  The program's configuration is held to
EVERY key of the configuration file's ``model`` (the published counts and
what is held).

Order of set-up as in ``qwen3_next_pretrain``: the float32 reference runs
FIRST, on parameters made from the same seed by the program's own
initialiser, and is freed before the amp state exists.  Attention there is a
head and a block of queries at a time, so it takes the sample one sequence at
a time, adding each sequence's part of the loss (both terms' denominators
are the whole sample's: ``reference.weight_totals``) and its gradient into
one donated float32 tree.

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

# what a checkout from before this model lacks: first, so that such a
# checkout fails here, in seconds, and not after set-up
from apex_tpu.models import (glm4_moe_lite_init, glm4_moe_lite_loss,
                             glm4_moe_lite_routing)

from benchmarks import flops_glm47_flash_30b_a3b, inputs_lfm2
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
        raise ValueError("glm4_moe_lite_pretrain runs one chip's share on "
                         "one chip")
    args = pretrain.parse_args(list(config["entry"]["argv"]) + [
        "--seq-len", str(traffic["seq"]), "--batch-size",
        str(traffic["batch"]), "--seed", str(seed)])
    cfg = pretrain.glm4_moe_lite_config(args)
    model = config["model"]
    expect_widths("glm4_moe_lite_pretrain", {
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
    make_params = jax.jit(lambda key: glm4_moe_lite_init(key, cfg))
    totals = reference.weight_totals(sample)

    @functools.partial(jax.jit, donate_argnums=1)
    def add_sequence(params, so_far, batch):
        part, g = jax.value_and_grad(reference.loss_part)(params, batch,
                                                          model, totals)
        return ((so_far[0] + part,
                 jax.tree_util.tree_map(jnp.add, so_far[1], g)),
                reference.routing(params, batch, model))

    with jax.default_matmul_precision("highest"):
        params = make_params(jax.random.PRNGKey(seed))
        so_far = (jnp.float32(0.0),
                  jax.tree_util.tree_map(jnp.zeros_like, params))
        ref_chosen = []
        for i in range(n_sample):
            so_far, chosen = add_sequence(
                params, so_far, {k: v[i:i + 1] for k, v in sample.items()})
            ref_chosen.append(np.asarray(chosen))
        ref = scalars(lambda: {
            "loss": so_far[0], "grad_norm": global_norm(so_far[1]),
            "param_abs_sum": abs_sum(params)})
    del params, so_far
    ref_chosen = np.concatenate(ref_chosen, axis=1)     # (layers, B·S, E)

    # -- the system, through the example's own run_standard -----------------
    with use_mesh(mesh):
        state, step = pretrain.run_standard(args, cfg, mesh)

    @jax.jit
    @functools.partial(shard_map, mesh=mesh, check_vma=False,
                       in_specs=(P(), P()), out_specs=P())
    def system_side(state, batch):
        loss, grads = jax.value_and_grad(glm4_moe_lite_loss)(
            state.model_params, batch, cfg)
        return {"loss": loss, "grad_norm": global_norm(grads),
                "param_abs_sum": abs_sum(amp.master_params(state))}

    outcome = reference_outcome(scalars(system_side, state, sample), ref,
                                config["reference_tolerance"])

    # assignments of the sample that system and reference gave to different
    # experts: a count, reported and not a limit (a score a hair from the
    # fourth largest falls either way in bfloat16)
    routing = jax.jit(
        lambda params, batch: glm4_moe_lite_routing(params, batch, cfg))
    ids = np.asarray(routing(state.model_params, sample)["ids"])
    agreed = np.take_along_axis(ref_chosen, ids, axis=2)
    outcome["routing"] = {"assignments": int(ids.size),
                          "chosen_differently": int((~agreed).sum())}

    def optimizer_probe(state):
        """Not in this cell (``optimizer_step_ms`` and ``optimizer_bw_share``
        list the cells that have it): ``amp.amp_step`` alone needs the state
        (9.22 GiB) and a gradient tree (1.32) beside the loaded step's
        scratch (3.14 by the compiler's count), and the step stays loaded;
        ``update_time_share`` reads the update where it runs."""
        raise RuntimeError("the update alone does not fit beside its state "
                           "and the loaded step at 707 M parameters: read "
                           "update_time_share")

    def routing_probe(state, ring):
        """``([rows (sparse layers, held)] a batch of the ring, dropped in
        all)`` by the program's own routing on the parameters as they
        stand; the MTP module's layer is the last row."""
        records = [jax.device_get(routing(
            state.model_params, {k: jnp.asarray(batch[k])
                                 for k in ("tokens", "targets")}))
            for batch in ring]
        print("[bench] walks of the dispatch buffer, a layer a batch: "
              + " ".join(str(r["walks"].tolist()) for r in records),
              flush=True)
        return ([np.asarray(r["rows"]) for r in records],
                int(sum(r["dropped"].sum() for r in records)))

    itemsize = jnp.dtype(cfg.dtype).itemsize
    sparse = cfg.num_hidden_layers - cfg.first_k_dense_replace \
        + cfg.num_nextn_predict_layers
    facts = {
        # the shapes the flash and grouped-product rooflines read here: the
        # QK and V heads are both qk_head_dim wide
        "attention": {
            "batch_heads": traffic["batch"] * cfg.num_attention_heads,
            "seq": traffic["seq"], "head_dim": cfg.qk_head_dim,
            "causal": True, "itemsize": itemsize},
        "experts": {"held": cfg.experts_held[1], "d_model": cfg.hidden_size,
                    "d_ff": cfg.moe_intermediate_size, "itemsize": itemsize,
                    "layers": sparse},
        "routing_probe": routing_probe,
    }
    return Job(
        state=state, step=step, batches=batches,
        samples_per_step=traffic["batch"],
        flops_per_sample=flops_glm47_flash_30b_a3b.train_flops_per_sample(
            model, traffic["seq"]),
        applied_steps=step.optimizer_steps,
        skips_allowed=bool(state.scalers[0].dynamic),
        reference=outcome, optimizer_probe=optimizer_probe, facts=facts,
        scope=functools.partial(use_mesh, mesh))
