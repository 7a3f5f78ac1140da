"""Job adapter: masked-LM pretraining through ``examples/bert/pretrain.py``.

The state and the step are the example's own (``parse_args`` ->
``run_standard(args, cfg, mesh)``): amp at the configured level, FusedLAMB on
the flat engine, ``shard_map`` + ``DistributedDataParallel`` over the mesh's
``data`` axis — one path for one chip and for several.  The example builds its
``TransformerConfig`` inside ``main``; those few lines are repeated in
:func:`_model_config`, and the result is held to the sizes in the
configuration file.

Order of set-up matters for memory: the float32 reference runs FIRST, on
parameters made from the same seed by the program's own initialiser, and is
freed before the amp state exists; a checksum then shows both sides held the
same values.  Done the other way round the reference would sit on top of the
state and lift the allocator's high-water mark above the job's own.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from benchmarks import flops, inputs
from benchmarks.job import (Job, abs_sum, expect_widths, global_norm,
                            load_example, load_module, reference_outcome,
                            scalars)

_WIDTHS = ("vocab_size", "max_len", "num_layers", "d_model", "num_heads",
           "d_ff")


def _model_config(pretrain, args):
    """``pretrain.main``'s choice of model configuration."""
    from apex_tpu.models import TransformerConfig, bert_large_config
    if args.bert_large:
        return bert_large_config(dtype=jnp.bfloat16, remat=args.remat,
                                 attn_impl=args.attn)
    return TransformerConfig(
        vocab_size=args.vocab, max_len=args.seq_len, num_layers=args.layers,
        d_model=args.d_model, num_heads=args.heads, d_ff=4 * args.d_model,
        dtype=jnp.bfloat16, remat=args.remat, attn_impl=args.attn)


def build(config: dict, traffic: dict, seed: int, devices,
          reference_path: str):
    from jax import shard_map
    from apex_tpu import amp
    from apex_tpu.models import transformer_init, transformer_loss
    from apex_tpu.parallel import create_mesh, use_mesh

    pretrain = load_example(config["entry"]["example"])
    n_dev = len(devices)
    if (traffic["layout"] == "dp") != (n_dev > 1):
        raise ValueError(f"layout {traffic['layout']!r} on {n_dev} device(s)")
    argv = list(config["entry"]["argv"]) + [
        "--seq-len", str(traffic["seq"]), "--batch-size",
        str(traffic["batch"]), "--seed", str(seed)]
    if n_dev > 1:
        argv.append("--distributed")
    args = pretrain.parse_args(argv)
    cfg = _model_config(pretrain, args)
    model = config["model"]
    expect_widths("bert_pretrain", {k: getattr(cfg, k) for k in _WIDTHS},
                  {k: model[k] for k in _WIDTHS if k in model})
    if args.opt_level != config["amp_opt_level"]:
        raise ValueError(f"opt level {args.opt_level} != configured "
                         f"{config['amp_opt_level']}")
    model = {k: getattr(cfg, k) for k in _WIDTHS}
    mesh = create_mesh({"data": n_dev}, devices=devices)
    batches = inputs.make_batches(traffic, model, seed)
    n_sample = traffic["reference_samples"]
    sample = {k: jnp.asarray(v[:n_sample]) for k, v in batches[0].items()}

    # -- the plain reference, before the amp state exists -------------------
    reference = load_module(reference_path,
                            "bench_reference_" + config["reference"])

    @jax.jit
    def reference_side(key, batch):
        params = transformer_init(key, cfg)
        loss, grads = jax.value_and_grad(reference.loss)(params, batch, model)
        return {"loss": loss, "grad_norm": global_norm(grads),
                "param_abs_sum": abs_sum(params)}

    with jax.default_matmul_precision("highest"):
        ref = scalars(reference_side, jax.random.PRNGKey(seed), sample)

    # -- the system, through the example's own builder ----------------------
    with use_mesh(mesh):
        state, step = pretrain.run_standard(args, cfg, mesh)

    replicated = functools.partial(shard_map, mesh=mesh, check_vma=False)

    @jax.jit
    @functools.partial(replicated, in_specs=(P(), P()), out_specs=P())
    def system_side(state, batch):
        loss, grads = jax.value_and_grad(transformer_loss)(
            state.model_params, batch, cfg)
        return {"loss": loss, "grad_norm": global_norm(grads),
                "param_abs_sum": abs_sum(amp.master_params(state))}

    outcome = reference_outcome(scalars(system_side, state, sample), ref,
                                config["reference_tolerance"])

    # -- the optimizer alone, for optimizer_step_ms --------------------------
    @functools.partial(jax.jit, donate_argnums=0)
    @functools.partial(replicated, in_specs=(P(), P()), out_specs=P())
    def update(state, grads):
        return amp.amp_step(state, grads)

    # gradients as the step leaves them: the parameters' shapes and dtypes,
    # a copy on every device (made on one device they would be sent to the
    # others inside every timed call)
    @jax.jit
    @functools.partial(replicated, in_specs=(P(),), out_specs=P())
    def stand_in_grads(params):
        return jax.tree_util.tree_map(lambda x: jnp.full_like(x, 1e-3), params)

    def optimizer_probe(state):
        return update, state, stand_in_grads(state.model_params)

    # -- replicas: the parameters every device holds must be the same --------
    @jax.jit
    @functools.partial(replicated, in_specs=(P(),), out_specs=P("data"))
    def per_device_checksum(state):
        return abs_sum(state.model_params)[None]

    def replicas_agree(state):
        sums = jax.device_get(per_device_checksum(state))
        return bool((sums == sums[0]).all())

    leaves = jax.tree_util.tree_leaves(state.model_params)
    facts = {
        "attention": {"batch_heads": traffic["batch"] // n_dev * cfg.num_heads,
                      "seq": traffic["seq"], "head_dim": cfg.head_dim,
                      "causal": cfg.causal,
                      "itemsize": jnp.dtype(cfg.dtype).itemsize},
        # LAMB: float32 master, two float32 moments
        "optimizer_bytes": flops.optimizer_update_bytes(
            (x.size, x.dtype.itemsize) for x in leaves),
        # every gradient once in its own dtype: what comm_bytes_per_step,
        # read from the trace, is printed beside
        "gradient_bytes": flops.allreduce_payload_bytes(
            (x.size, x.dtype.itemsize) for x in leaves),
    }
    return Job(
        state=state, step=step, batches=batches,
        samples_per_step=traffic["batch"],
        flops_per_sample=flops.transformer_train_flops_per_sample(
            model, traffic["seq"]),
        applied_steps=step.optimizer_steps,
        skips_allowed=bool(state.scalers[0].dynamic),
        reference=outcome, optimizer_probe=optimizer_probe, facts=facts,
        replicas_agree=replicas_agree if n_dev > 1 else None,
        scope=functools.partial(use_mesh, mesh))
