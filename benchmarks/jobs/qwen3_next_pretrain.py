"""Job adapter: causal-LM pretraining of the Qwen3-Next decoder through
``examples/bert/pretrain.py`` (``--qwen3-next EP PERIODS``).

The state and the step are the example's own (``parse_args`` ->
``qwen3_next_config(args)`` -> ``run_standard(args, cfg, mesh)``): amp O5,
FusedLAMB on the flat engine, the ``shard_map`` step — ``bert_pretrain``'s,
``lfm2_pretrain``'s and ``nemotron_h_pretrain``'s path with another model in
it.  The program's configuration is held to EVERY key of the configuration
file's ``model`` (the published counts and what is held).

Order of set-up as in ``nemotron_h_pretrain``: the float32 reference runs
FIRST, on parameters made from the same seed by the program's own
initialiser, and is freed before the amp state exists.  The reference is a
sequential recurrence over 4096 steps and attention a head at a time, so it
takes the sample one sequence at a time, adding each sequence's gradient into
one donated float32 tree (``reference.loss_sum``).

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
from apex_tpu.models import (qwen3_next_init, qwen3_next_loss,
                             qwen3_next_routing)

from benchmarks import flops_qwen3_next, inputs_lfm2
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
        raise ValueError("qwen3_next_pretrain runs one chip's share on one "
                         "chip")
    args = pretrain.parse_args(list(config["entry"]["argv"]) + [
        "--seq-len", str(traffic["seq"]), "--batch-size",
        str(traffic["batch"]), "--seed", str(seed)])
    cfg = pretrain.qwen3_next_config(args)
    model = config["model"]
    expect_widths("qwen3_next_pretrain", {
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
    make_params = jax.jit(lambda key: qwen3_next_init(key, cfg))

    @functools.partial(jax.jit, donate_argnums=1)
    def add_sequence(params, so_far, batch):
        part, g = jax.value_and_grad(reference.loss_sum)(params, batch, model)
        return ((so_far[0] + part,
                 jax.tree_util.tree_map(jnp.add, so_far[1], g)),
                reference.routing(params, batch["tokens"], model))

    with jax.default_matmul_precision("highest"):
        params = make_params(jax.random.PRNGKey(seed))
        so_far = (jnp.float32(0.0),
                  jax.tree_util.tree_map(jnp.zeros_like, params))
        ref_chosen = []
        for i in range(n_sample):
            so_far, chosen = add_sequence(
                params, so_far, {k: v[i:i + 1] for k, v in sample.items()})
            ref_chosen.append(np.asarray(chosen))
        weight = jnp.sum(sample["weights"])
        ref = scalars(lambda: {
            "loss": so_far[0] / weight,
            "grad_norm": global_norm(so_far[1]) / weight,
            "param_abs_sum": abs_sum(params)})
    del params, so_far
    ref_chosen = np.concatenate(ref_chosen, axis=1)     # (layers, B·S, E)

    # -- the system, through the example's own builder -----------------------
    with use_mesh(mesh):
        state, step = pretrain.run_standard(args, cfg, mesh)

    @jax.jit
    @functools.partial(shard_map, mesh=mesh, check_vma=False,
                       in_specs=(P(), P()), out_specs=P())
    def system_side(state, batch):
        loss, grads = jax.value_and_grad(qwen3_next_loss)(
            state.model_params, batch, cfg)
        return {"loss": loss, "grad_norm": global_norm(grads),
                "param_abs_sum": abs_sum(amp.master_params(state))}

    outcome = reference_outcome(scalars(system_side, state, sample), ref,
                                config["reference_tolerance"])

    # assignments of the sample that system and reference gave to different
    # experts: a count, reported and not a limit (a score a hair from the
    # tenth largest falls either way in bfloat16)
    routing = jax.jit(
        lambda params, tokens: qwen3_next_routing(params, tokens, cfg))
    ids = np.asarray(routing(state.model_params, sample["tokens"])["ids"])
    agreed = np.take_along_axis(ref_chosen, ids, axis=2)
    outcome["routing"] = {"assignments": int(ids.size),
                          "chosen_differently": int((~agreed).sum())}

    def optimizer_probe(state):
        """Not in this cell (``optimizer_step_ms`` and ``optimizer_bw_share``
        list the cells that have it): ``amp.amp_step`` alone needs the state
        (8.16 GiB), a gradient tree (1.17) and its own transient — the flat
        float32 gradient and LAMB's float32 update direction, 4.66 GiB —
        beside the loaded step's scratch, more than the chip has; inside the
        step that transient shares the step's scratch.
        ``update_time_share`` reads the update where it runs."""
        raise RuntimeError("the update alone does not fit beside its state "
                           "at 626 M parameters: read update_time_share")

    def routing_probe(state, ring):
        """``([rows (layers, held)] a batch of the ring, dropped in all)`` by
        the program's own routing on the parameters as they stand."""
        records = [jax.device_get(routing(state.model_params,
                                          jnp.asarray(batch["tokens"])))
                   for batch in ring]
        print("[bench] walks of the dispatch buffer, a layer a batch: "
              + " ".join(str(r["walks"].tolist()) for r in records),
              flush=True)
        return ([np.asarray(r["rows"]) for r in records],
                int(sum(r["dropped"].sum() for r in records)))

    kinds = cfg.layer_types
    itemsize = jnp.dtype(cfg.dtype).itemsize
    tokens = traffic["batch"] * traffic["seq"]
    facts = {
        # the shapes the flash and grouped-product rooflines read here
        "attention": {
            "batch_heads": traffic["batch"] * cfg.num_attention_heads,
            "seq": traffic["seq"], "head_dim": cfg.head_dim, "causal": True,
            "itemsize": itemsize},
        "experts": {"held": cfg.experts_held[1], "d_model": cfg.hidden_size,
                    "d_ff": cfg.moe_intermediate_size, "itemsize": itemsize,
                    "layers": len(kinds)},
        # what one Gated DeltaNet layer's rule works on
        # (flops_qwen3_next.gated_delta_rule_cost)
        "gdn": {"tokens": tokens, "heads": cfg.linear_num_value_heads,
                "key_heads": cfg.linear_num_key_heads,
                "key_dim": cfg.linear_key_head_dim,
                "value_dim": cfg.linear_value_head_dim, "itemsize": itemsize,
                "layers": kinds.count("linear_attention"),
                "chunk": cfg.chunk_size},
        "routing_probe": routing_probe,
    }
    return Job(
        state=state, step=step, batches=batches,
        samples_per_step=traffic["batch"],
        flops_per_sample=flops_qwen3_next.train_flops_per_sample(
            model, traffic["seq"]),
        applied_steps=step.optimizer_steps,
        skips_allowed=bool(state.scalers[0].dynamic),
        reference=outcome, optimizer_probe=optimizer_probe, facts=facts,
        scope=functools.partial(use_mesh, mesh))
