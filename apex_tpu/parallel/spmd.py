"""GSPMD step engine: materialize ANY auto-parallel :class:`~apex_tpu.
parallel.plan.Plan` as an executable, measurable train step (ISSUE 12;
ROADMAP open item 1).

The PR-10 planner ranks dp x tp(x sp) / ZeRO / update-sharding plans but
could only *run* the dp family — tp/sp/contrib-ZeRO rankings were
modeled, never measured.  This module closes that gap with one engine
per plan family, all behind :func:`build_plan_step`:

``dp`` (tp == sp == 1, no ZeRO)
    The existing shard_map harness
    (:func:`~apex_tpu.parallel.plan.build_flagship_step`): explicit DDP
    psum / weight-update sharding, compressed collective schemes,
    bitwise-proven against hand configuration.
``tp`` (tp > 1) — the consistent-SPMD posture (veScale, arXiv:2509.07003)
    A plain ``jax.jit`` over GLOBAL arrays with ``NamedSharding``
    annotations: params/activations carry the Megatron
    ``transformer_pspecs`` 2-D dp x tp specs, and the fused-flat
    master/moment buffers are sharded 1-D over tp (and additionally
    over dp when the plan shards the update — ZeRO-1 via GSPMD), with
    the flattener's chunk lattice pinned to ``LANE * shard_world`` so
    every tp slice falls on whole 128-lanes.  XLA inserts every
    collective (the dp grad psum, the Megatron activation psums, the
    flat-buffer reshards); single-device semantics are preserved by
    construction — the global loss IS the global-batch mean.  The wire
    is XLA-owned, so compressed schemes don't apply here (the planner
    enumerates tp plans at fp32 wire only) and the collective payloads
    are metered from the *compiled HLO* (``tp.psum`` family) — which is
    also how the alpha-beta comm model is validated against reality.
``sp`` (sp > 1)
    shard_map over (data, seq): activations sequence-sharded, attention
    routed through the existing :func:`~apex_tpu.parallel.sequence.
    ring_attention` / :func:`~apex_tpu.parallel.sequence.
    ulysses_attention` collectives via the ``attn_override`` hook in
    :func:`~apex_tpu.models.transformer_apply` (position embeddings
    sliced at each device's global offset), grads folded over the seq
    axis then reduced over dp on the normal DDP wire (compressed
    schemes and zero1 update sharding both apply).  Compiled
    ``sp.all_to_all`` / ``sp.ppermute`` payloads are metered.
``zero`` (contrib ZeRO)
    shard_map over data with the
    :class:`~apex_tpu.contrib.optimizers.DistributedFusedAdam` route —
    permanently sharded optimizer state, the reduce-scatter /
    allgather wire riding the plan's collective scheme.
``pp`` (pp_stages > 1) — ISSUE 17
    shard_map over (data, pipe): the flagship's stacked layer axis is
    partitioned into S stage slices (one per pipe device, each running
    its local layers under a mini-scan), microbatches stream through
    :func:`~apex_tpu.parallel.pipeline.pipeline_apply`'s fill-drain
    ``ppermute`` schedule, and the embed/head run masked on the last
    stage so the tied-embedding grad is counted exactly once (psum over
    the pipe axis reassembles every dense grad).  Each stage keeps its
    OWN fused-flat Adam over its local param tree (per-stage optimizer
    placement on the lane lattice) with the amp overflow-skip select
    guarding its fp32 master.  The fori_loop schedule hides the
    ``ppermute``s from the compiled-HLO entry walk, so the wire is
    metered from the STATIC schedule (:func:`_pp_schedule_bytes`) —
    2(M + S - 1) hops of one microbatch activation block.
``ep`` (ep > 1) — ISSUE 17
    shard_map over (data, expert): the MoE flagship variant
    (``models.moe_transformer``) with expert FFN weights sharded on
    their leading axis, token routing through ``parallel/expert``'s
    capacity-factored ``all_to_all``.  Dense grads fold over the expert
    axis first (each device's loss covers only its token shard) then
    ride the normal DDP wire over data; expert grads are excluded from
    that dense fold — they are already per-expert-local — and take only
    the data-axis reduction.  The ``ep.all_to_all`` wire is metered
    from the compiled HLO (the python-loop layers keep it in the entry
    computation) with the static schedule as the cross-check.

amp O-level master weights: every fused-flat engine keeps the fp32
master buffer authoritative; ``amp_dtype="bfloat16"`` runs the model
copy (and activations) at bf16 off the same master — the O2 contract —
with the overflow-skip select keeping non-finite steps out of the
master, exactly like the dp harness.

See docs/parallel.md "SPMD step engine".
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from .mesh import DATA_AXIS, MODEL_AXIS, SEQ_AXIS

__all__ = ["build_plan_step", "plan_param_pspecs", "serve_shardings",
           "compiled_collectives", "meter_compiled_collectives",
           "SPMD_FAMILIES"]

#: plan families the engine materializes (Plan.family values)
SPMD_FAMILIES = ("dp", "tp", "sp", "zero", "pp", "ep")


def plan_param_pspecs(cfg, plan):
    """Param PartitionSpec tree for ``cfg`` under ``plan``: the Megatron
    dp x tp specs when tp > 1, fully replicated otherwise (dp grads ride
    the explicit DDP collectives; sp shards activations, not params)."""
    import jax
    from jax.sharding import PartitionSpec as P
    from ..models import transformer_init, transformer_pspecs
    if plan.tp > 1:
        return transformer_pspecs(cfg, dp=DATA_AXIS, tp=MODEL_AXIS)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    return jax.tree_util.tree_map(lambda _: P(), params)


def serve_shardings(mesh, cfg, *, packed):
    """NamedSharding trees for the inference engine's compiled steps
    (``serve.engine.InferenceEngine``): Megatron tensor-parallel param
    specs over the mesh's ``model`` axis plus the KV pools sharded on
    their head axis — ``(L, pages, page_size, H, hd)`` splits dim 3 —
    so each shard scatters/gathers only its own heads and XLA derives
    the attention psums, the PR 12 consistent-SPMD posture.

    ``packed`` is the engine's O-level param pytree.  Only a raw dict
    tree (fp32/bf16) takes the tensor-parallel specs; the int8 packed
    ``(q, scales)`` leaf list replicates — block-scale codes don't
    slice along Megatron dims (an accepted simplification, the pools
    still shard).  Returns ``{"params": ..., "kv": ...}``."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    tp = int(mesh.shape.get(MODEL_AXIS, 1))
    rep = NamedSharding(mesh, P())
    if tp > 1 and cfg.num_heads % tp:
        raise ValueError(f"num_heads {cfg.num_heads} not divisible by "
                         f"model-axis size {tp}")
    if tp > 1 and isinstance(packed, dict):
        from ..models import transformer_pspecs
        pspecs = transformer_pspecs(cfg, dp=DATA_AXIS, tp=MODEL_AXIS)
        params = jax.tree_util.tree_map(
            lambda spec: NamedSharding(mesh, spec), pspecs,
            is_leaf=lambda x: isinstance(x, P))
        kv = NamedSharding(mesh, P(None, None, None, MODEL_AXIS, None))
    else:
        params = jax.tree_util.tree_map(lambda _: rep, packed)
        kv = rep
    return {"params": params, "kv": kv}


# ---------------------------------------------------------------------------
# compiled-HLO collective metering (tp.psum / sp.all_to_all families)
# ---------------------------------------------------------------------------

def compiled_collectives(fn, *args, **kwargs) -> dict:
    """The compiled program's per-opcode collective payloads (AOT — the
    function is lowered and compiled, never executed): ``{opcode:
    {count, logical_bytes}}`` from :func:`~apex_tpu.telemetry.attrib.
    collectives_table`.  Under SPMD the logical bytes are PER-PARTITION
    (each device's payload), which is exactly what the alpha-beta model
    predicts per device — the validation surface."""
    from ..telemetry import attrib
    table = attrib.op_table(fn, *args, **kwargs)
    return {op: {"count": agg["count"],
                 "logical_bytes": agg["logical_bytes"]}
            for op, agg in (table.get("collectives", {})
                            .get("by_opcode", {})).items()}


#: compiled opcode -> (family, op) for the model-parallel meter families.
#: all-reduce under a tp plan is the fused dp-grad + Megatron-activation
#: psum traffic (GSPMD owns the wire; the split is not recoverable from
#: the compiled module, so the family meters the whole all-reduce
#: payload — the quantity the comm model must account for in total).
#: NOTE the entry-computation walk does not see collectives inside
#: while/scan bodies (the layer scan) — the sp engine therefore meters
#: its per-layer ring/ulysses wire from its STATIC schedule instead
#: (:func:`_sp_schedule_bytes`), where layers and shapes are exact.
_METER_OPS = {
    "tp": {"all-reduce": ("tp", "psum")},
    "sp": {"all-to-all": ("sp", "all_to_all"),
           "collective-permute": ("sp", "ppermute")},
    "ep": {"all-to-all": ("ep", "all_to_all")},
}


def _sp_schedule_bytes(cfg, strategy: str, n_dp: int, n_sp: int,
                       global_batch: int) -> dict:
    """Static per-device wire bytes of one sp train step — the engine's
    exact collective schedule (the scan body hides these from the
    compiled-HLO entry walk): ulysses ships 4 all_to_alls of one local
    (B_local, H, S_local, hd) block per layer forward + the mirrored
    backward; ring rotates the K and V blocks around the full ring each
    layer, forward and backward."""
    import jax.numpy as jnp
    esize = jnp.dtype(cfg.dtype).itemsize
    blk = ((global_batch // n_dp) * cfg.num_heads
           * (cfg.max_len // n_sp) * cfg.head_dim * esize)
    layers = max(int(cfg.num_layers), 1)
    if strategy == "ulysses":
        return {"op": "all_to_all",
                "logical_bytes": 8 * layers * blk,
                "per_layer_block_bytes": blk, "layers": layers}
    return {"op": "ppermute",
            "logical_bytes": 4 * layers * n_sp * blk,
            "per_layer_block_bytes": blk, "layers": layers}


def _pp_schedule_bytes(cfg, n_dp: int, n_pp: int, microbatches: int,
                       global_batch: int) -> dict:
    """Static per-device wire bytes of one pp train step — the engine's
    exact ``ppermute`` schedule (the fori_loop body hides it from the
    compiled-HLO entry walk): the fill-drain schedule runs M + S - 1
    ticks, each hopping one microbatch activation block (B_local/M, S,
    D) to the next stage, and the reversed backward mirrors every hop."""
    import jax.numpy as jnp
    esize = jnp.dtype(cfg.dtype).itemsize
    blk = ((global_batch // n_dp) // microbatches
           * cfg.max_len * cfg.d_model * esize)
    ticks = microbatches + n_pp - 1
    return {"op": "ppermute", "logical_bytes": 2 * ticks * blk,
            "per_tick_block_bytes": blk, "ticks": ticks}


def _ep_schedule_bytes(cfg, n_dp: int, n_ep: int, global_batch: int) -> dict:
    """Static per-device wire bytes of one ep train step — the
    capacity-factored router exchange: each MoE layer ships the
    owner-major (E_total * capacity, D) queue out and back (2
    all_to_alls forward), mirrored in backward (4 per layer per step).
    Unlike pp's fori_loop schedule, the python-loop MoE layers keep
    every all_to_all in the compiled entry computation, so this static
    schedule is the engine-independent CROSS-CHECK of the compiled-HLO
    sub-table (which is what gets metered)."""
    tokens_local = (global_batch // (n_dp * n_ep)) * cfg.max_len
    capacity = max(int(cfg.capacity_factor * tokens_local
                       / cfg.num_experts), 1)
    blk = 4 * cfg.num_experts * capacity * cfg.d_model  # f32 queue buffer
    layers = max(int(cfg.num_layers), 1)
    return {"op": "all_to_all", "logical_bytes": 4 * layers * blk,
            "per_layer_block_bytes": blk, "layers": layers,
            "capacity": capacity}


def meter_compiled_collectives(by_opcode: dict, family: str,
                               axis_name: str) -> dict:
    """Record the compiled collective payloads through
    :func:`~apex_tpu.telemetry.events.record_collective` under the
    model-parallel families (``tp.psum`` / ``sp.all_to_all`` /
    ``sp.ppermute``) so a run's tp/sp wire bytes are provable from the
    JSONL exactly like the ddp/zero wires.  Returns the subset of
    ``by_opcode`` that was metered."""
    from ..telemetry import events as _tel_events
    mapping = _METER_OPS.get(family, {})
    metered = {}
    for opcode, agg in (by_opcode or {}).items():
        if opcode not in mapping:
            continue
        fam, op = mapping[opcode]
        _tel_events.record_collective(
            axis_name, int(agg["logical_bytes"]), int(agg["count"]), 0.0,
            wire_bytes=int(agg["logical_bytes"]), scheme="fp32",
            dtype="float32", op=op, family=fam)
        metered[opcode] = dict(agg)
    return metered


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

def build_plan_step(cfg, mesh, plan, *, global_batch: int, lr: float = 1e-2,
                    amp_dtype=None, meter: bool = True):
    """Materialize ``plan`` as an executable train step over ``mesh``.

    Returns ``(carry0, step, info)`` with ``step(carry, tokens) ->
    (carry, loss)`` (tokens ``(global_batch, seq)`` int32, loss the
    scalar global-batch mean) and ``info`` carrying ``family``,
    ``engine``, and — for the tp/sp engines with ``meter=True`` — the
    compiled-HLO ``collectives`` sub-table (also recorded through the
    telemetry ``tp.psum`` / ``sp.all_to_all`` meter families).

    The mesh must carry the plan's axes (``plan.axis_sizes()`` — what
    ``Plan.apply()`` builds); knobs without an engine argument resolve
    through their existing env surfaces, which ``Plan.apply()`` sets.
    ``amp_dtype="bfloat16"`` selects the O2-style bf16 model copy over
    the fp32 master (fused-flat engines only).

    Rebuild semantics: collective-scheme defaults re-resolve at build
    time (``collectives.resolve`` — which consults the controller's
    live override first), so a mid-run ``comm_retune`` or
    ``replan_reshard`` decision (``apex_tpu.control``) lands the next
    time an engine is (re)built — an elastic resume, a fresh jit after
    preempt, or an explicit rebuild; in-flight compiled executables
    keep their traced wire, by design."""
    from .plan import Plan  # noqa: F401  (typing/doc aid; no cycle at import)
    family = plan.family
    if plan.zero:
        return _build_zero_step(cfg, mesh, plan, global_batch, lr, meter)
    if plan.tp > 1:
        return _build_gspmd_step(cfg, mesh, plan, global_batch, lr,
                                 amp_dtype, meter)
    if plan.sp > 1:
        return _build_sp_step(cfg, mesh, plan, global_batch, lr, meter)
    if plan.pp_stages > 1:
        return _build_pp_step(cfg, mesh, plan, global_batch, lr, meter)
    if plan.ep > 1:
        return _build_ep_step(cfg, mesh, plan, global_batch, lr, meter)
    from .plan import build_flagship_step
    # async overlap execution rides the dp engine: resolve the ambient
    # mode here (env APEX_TPU_OVERLAP — what Plan.apply or an A/B run
    # sets) and surface it both to the DDP harness and in the engine
    # info, so a run records which execution actually ran
    from . import overlap as _ov
    ov_mode = _ov.resolve_mode(None)
    ddp_kwargs = {"overlap": ov_mode} if ov_mode != "off" else None
    carry0, step = build_flagship_step(cfg, mesh, global_batch=global_batch,
                                       ddp_kwargs=ddp_kwargs)
    return carry0, step, {"family": family, "engine": "shard_map.dp",
                          "overlap": ov_mode}


def _build_gspmd_step(cfg, mesh, plan, global_batch, lr, amp_dtype, meter):
    """The consistent-SPMD tp engine (see module docstring): one
    ``jax.jit`` over global arrays, shardings by annotation only."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..models import transformer_init, transformer_loss
    from ..multi_tensor_apply.flattener import LANE
    from ..optimizers import FusedAdam

    n_dp = int(mesh.shape[DATA_AXIS])
    n_tp = int(mesh.shape.get(MODEL_AXIS, 1))
    if global_batch % n_dp:
        raise ValueError(f"global batch {global_batch} must divide over "
                         f"the data axis ({n_dp})")
    if cfg.num_heads % n_tp:
        raise ValueError(f"num_heads {cfg.num_heads} must divide over the "
                         f"model axis ({n_tp}) — the attention shard unit")
    # the Pallas attention/xentropy kernels have no GSPMD partitioning
    # rule (they partition under shard_map, which the dp/sp/zero engines
    # use); the consistent-SPMD step runs the XLA paths
    run_cfg = dataclasses.replace(cfg, attn_impl="default", xent_impl="xla")
    if amp_dtype is not None:
        run_cfg = dataclasses.replace(run_cfg, dtype=jnp.dtype(amp_dtype))

    params0 = transformer_init(jax.random.PRNGKey(0), run_cfg)
    pspecs = plan_param_pspecs(run_cfg, plan)
    is_p = lambda x: isinstance(x, P)
    param_sh = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), pspecs, is_leaf=is_p)

    opt = FusedAdam(lr=lr, impl="fused")
    # chunk lattice: the flat total divides into whole 128-lane slices
    # for EVERY axis that shards the flat buffers, so tp (and zero1's
    # dp) slices never split a lane
    flat_world = n_tp * (n_dp if plan.shards_update else 1)
    fl = opt.flattener_for(params0, chunk=LANE * flat_world)
    flat_axes = ((MODEL_AXIS, DATA_AXIS) if plan.shards_update
                 else (MODEL_AXIS,))
    flat_sh = NamedSharding(mesh, P(flat_axes))
    rep_sh = NamedSharding(mesh, P())
    state0 = opt.init(params0)
    state_sh = type(state0)(count=rep_sh, m=flat_sh, v=flat_sh,
                            master=flat_sh)
    state0 = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, s), state0, state_sh)
    tok_sh = NamedSharding(mesh, P(DATA_AXIS))

    def body(state, tokens):
        master = jax.lax.with_sharding_constraint(state.master, flat_sh)
        params = fl.unflatten(master, like=params0,
                              dtype=(amp_dtype if amp_dtype is not None
                                     else None))
        params = jax.lax.with_sharding_constraint(params, param_sh)
        loss, grads = jax.value_and_grad(lambda p: transformer_loss(
            p, {"tokens": tokens, "targets": tokens}, run_cfg))(params)
        flat_g = jax.lax.with_sharding_constraint(fl.flatten(grads),
                                                  flat_sh)
        # amp overflow-skip contract: a non-finite step never reaches
        # the fp32 master (same select as the dp harness)
        ok = jnp.all(jnp.isfinite(flat_g)).astype(jnp.float32)
        new_state = opt.step_flat(state, flat_g)
        new_state = jax.tree_util.tree_map(
            lambda nw, old: jnp.where(ok > 0, nw, old), new_state, state)
        return new_state, loss

    step_jit = jax.jit(body, in_shardings=(state_sh, tok_sh),
                       out_shardings=(state_sh, rep_sh))

    info = {"family": plan.family, "engine": "gspmd",
            "tp": n_tp, "dp": n_dp, "flat_world": flat_world,
            "amp_dtype": (str(jnp.dtype(amp_dtype))
                          if amp_dtype is not None else None)}
    if meter:
        tokens0 = jax.device_put(
            jnp.zeros((global_batch, run_cfg.max_len), jnp.int32), tok_sh)
        info["collectives"] = compiled_collectives(body, state0, tokens0)
        info["metered"] = meter_compiled_collectives(
            info["collectives"], "tp", MODEL_AXIS)

    def step(state, tokens):
        return step_jit(state, tokens)

    return state0, step, info


def _build_sp_step(cfg, mesh, plan, global_batch, lr, meter):
    """The sequence-parallel engine: shard_map over (data, seq), the
    attention core routed through ring/ulysses (``attn_override``), the
    dp wire and zero1 update sharding riding the existing surfaces."""
    import functools
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from ..models import transformer_init, transformer_loss
    from ..optimizers import FusedAdam
    from ..utils.pallas import to_varying
    from .distributed import DistributedDataParallel
    from jax import shard_map
    from .sequence import (ring_attention, ulysses_attention, validate_sp)

    n_dp = int(mesh.shape[DATA_AXIS])
    n_sp = int(mesh.shape.get(SEQ_AXIS, 1))
    strategy = plan.sp_strategy if plan.sp_strategy != "none" else "ring"
    validate_sp(cfg.max_len, cfg.num_heads, n_sp, strategy)
    if global_batch % n_dp:
        raise ValueError(f"global batch {global_batch} must divide over "
                         f"the data axis ({n_dp})")
    s_local = cfg.max_len // n_sp

    params0 = transformer_init(jax.random.PRNGKey(0), cfg)
    opt = FusedAdam(lr=lr, impl="fused")
    ddp = DistributedDataParallel(axis_name=DATA_AXIS)
    su = ddp.weight_update(opt)
    pspec = jax.tree_util.tree_map(lambda _: P(), params0)

    if strategy == "ulysses":
        def attn(q, k, v, *, causal):
            return ulysses_attention(q, k, v, axis_name=SEQ_AXIS,
                                     causal=causal)
    else:
        def attn(q, k, v, *, causal):
            return ring_attention(q, k, v, axis_name=SEQ_AXIS,
                                  causal=causal)

    def grads_of(params, tokens):
        off = jax.lax.axis_index(SEQ_AXIS) * s_local
        pv = jax.tree_util.tree_map(
            lambda p: to_varying(p, (DATA_AXIS, SEQ_AXIS)), params)
        loss, grads = jax.value_and_grad(lambda p: transformer_loss(
            p, {"tokens": tokens, "targets": tokens}, cfg,
            attn_override=attn, pos_offset=off))(pv)
        # fold the seq axis first: each device's grads cover only ITS
        # sequence block's loss terms; /n_sp turns the seq sum into the
        # seq mean, so the dp reduction below needs no extra scaling
        grads = jax.tree_util.tree_map(
            lambda g: jax.lax.psum(g, SEQ_AXIS) / n_sp, grads)
        return jax.lax.pmean(loss, (DATA_AXIS, SEQ_AXIS)), grads

    if su is None:
        state0_local = opt.init(params0)
        sspec = jax.tree_util.tree_map(lambda _: P(), state0_local)

        def body(params, state, tokens):
            loss, grads = grads_of(params, tokens)
            grads = ddp.allreduce_grads(grads)
            fl = opt.flattener_for(params)
            flat = fl.flatten(grads)
            ok = jnp.all(jnp.isfinite(flat)).astype(jnp.float32)
            new_state = opt.step_flat(state, flat)
            new_state = jax.tree_util.tree_map(
                lambda nw, old: jnp.where(ok > 0, nw, old),
                new_state, state)
            return (fl.unflatten(new_state.master, like=params),
                    new_state, loss)
    else:
        sspec = su.state_pspecs(params0, n_dp)
        init_s = jax.jit(shard_map(lambda p: su.init(p), mesh=mesh,
                                   in_specs=(pspec,), out_specs=sspec))

        def body(params, state, tokens):
            loss, grads = grads_of(params, tokens)
            params, state = su.step(state, grads, params)
            return params, state, loss

    step_sm = jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(pspec, sspec, P(DATA_AXIS, SEQ_AXIS)),
        out_specs=(pspec, sspec, P())))
    state0 = opt.init(params0) if su is None else init_s(params0)

    info = {"family": plan.family, "engine": f"shard_map.sp.{strategy}",
            "dp": n_dp, "sp": n_sp}
    if meter:
        from ..telemetry import events as _tel_events
        tokens0 = jnp.zeros((global_batch, cfg.max_len), jnp.int32)
        info["collectives"] = compiled_collectives(
            step_sm, params0, state0, tokens0)
        # the ring/ulysses wire lives inside the layer scan, invisible
        # to the entry-computation walk — meter the engine's exact
        # static schedule instead (sp.all_to_all / sp.ppermute)
        sched = _sp_schedule_bytes(cfg, strategy, n_dp, n_sp,
                                   global_batch)
        info["sp_wire"] = sched
        _tel_events.record_collective(
            SEQ_AXIS, sched["logical_bytes"], sched["layers"], 0.0,
            wire_bytes=sched["logical_bytes"], scheme="fp32",
            dtype=str(jnp.dtype(cfg.dtype)), op=sched["op"],
            family="sp")

    def step(carry, tokens):
        params, state = carry
        params, state, loss = step_sm(params, state, tokens)
        return (params, state), loss

    return (params0, state0), step, info


def _typed_replicated(x, axis_name):
    """``x`` typed as replicated over ``axis_name``.  The pp and ep
    engines keep ONE flat optimizer state per shard — shared (dense)
    weights followed by that shard's stage/expert slice — so a shared
    weight read back out of it is typed varying over the axis although
    every shard computed the same value.  jax has no collective-free
    assertion of replication; ``pmax`` is exact on equal values and
    yields the type a replicated out_spec needs.  It costs one
    all-reduce of the shared weights per step — gone when the engines
    keep shared and sharded state apart (ROADMAP D2)."""
    import jax
    return jax.lax.pmax(x, axis_name)


def _build_pp_step(cfg, mesh, plan, global_batch, lr, meter):
    """The pipeline-parallel engine: shard_map over (data, pipe), the
    flagship's stacked layer axis partitioned into one stage slice per
    pipe device, microbatches streamed through ``pipeline_apply``'s
    fill-drain ppermute schedule.  The embed/head run MASKED on the
    last stage so every dense grad (including the tied-embedding head
    term) is produced exactly once and reassembled by one pipe-axis
    psum; each stage runs its own fused-flat Adam over its local param
    tree (per-stage optimizer placement) with the amp overflow-skip
    select guarding its fp32 master."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from ..contrib.xentropy import softmax_xentropy_loss
    from ..models import transformer_init
    from ..models.transformer import _layer
    from ..normalization.fused_layer_norm import fused_layer_norm_affine
    from ..optimizers import FusedAdam
    from ..utils.pallas import to_varying
    from .distributed import DistributedDataParallel
    from jax import shard_map
    from .pipeline import PIPE_AXIS, pipeline_apply, unstack_local

    n_dp = int(mesh.shape[DATA_AXIS])
    n_pp = int(mesh.shape.get(PIPE_AXIS, 1))
    m_micro = max(int(plan.pp_microbatches), 1)
    n_layers = int(cfg.num_layers)
    if n_pp <= 1:
        raise ValueError("pp plan needs a pipe mesh axis of size >= 2")
    if n_layers % n_pp:
        raise ValueError(f"num_layers {n_layers} must divide into "
                         f"{n_pp} pipeline stages")
    if global_batch % n_dp:
        raise ValueError(f"global batch {global_batch} must divide over "
                         f"the data axis ({n_dp})")
    b_local = global_batch // n_dp
    if b_local % m_micro:
        raise ValueError(f"per-replica batch {b_local} must divide into "
                         f"{m_micro} microbatches")
    if plan.shards_update or plan.zero:
        raise ValueError("the pp engine runs the plain fused-flat update "
                         "(no zero/zero1 composition)")
    l_local = n_layers // n_pp

    params0 = transformer_init(jax.random.PRNGKey(0), cfg)
    # (L, ...) stacked layers -> (S, L/S, ...): P(pipe) on the stage
    # axis gives each device its contiguous layer slice, in order
    params0 = dict(params0)
    params0["layers"] = jax.tree_util.tree_map(
        lambda l: l.reshape((n_pp, l_local) + l.shape[1:]),
        params0["layers"])
    opt = FusedAdam(lr=lr, impl="fused")
    ddp = DistributedDataParallel(axis_name=DATA_AXIS)
    pspec = {
        "embed": jax.tree_util.tree_map(lambda _: P(), params0["embed"]),
        "layers": jax.tree_util.tree_map(lambda _: P(PIPE_AXIS),
                                         params0["layers"]),
        "head": jax.tree_util.tree_map(lambda _: P(), params0["head"]),
    }
    # per-stage optimizer: state shapes come from the LOCAL tree (one
    # stage slice), flat m/v/master concatenate over the pipe axis
    local_template = dict(params0)
    local_template["layers"] = jax.tree_util.tree_map(
        lambda l: l[:1], params0["layers"])
    state_shape = jax.eval_shape(opt.init, local_template)
    sspec = jax.tree_util.tree_map(
        lambda x: P(PIPE_AXIS) if getattr(x, "ndim", 0) >= 1 else P(),
        state_shape)

    def stage_fn(lp, h):
        def lbody(c, layer_p):
            return _layer(c, layer_p, cfg, None, None), None
        h, _ = jax.lax.scan(lbody, h, lp)
        return h

    def local_loss(p, tokens):
        idx = jax.lax.axis_index(PIPE_AXIS)
        dt = cfg.dtype
        emb = p["embed"]
        x = (emb["tok"][tokens].astype(dt)
             + emb["pos"][: tokens.shape[1]][None].astype(dt))
        x = fused_layer_norm_affine(x, emb["ln_g"].astype(dt),
                                    emb["ln_b"].astype(dt), (cfg.d_model,))
        xm = x.reshape(m_micro, b_local // m_micro, cfg.max_len,
                       cfg.d_model)
        out = pipeline_apply(stage_fn, unstack_local(p["layers"]), xm,
                             axis_name=PIPE_AXIS)
        x = out.reshape(b_local, cfg.max_len, cfg.d_model)
        # head + loss run masked on the LAST stage only: every stage
        # holds the replicated pipeline output, and an unmasked head
        # would produce the tied-embedding logit grad once per stage —
        # the pipe psum in grads_of would then overcount it S-fold
        last = idx == n_pp - 1
        x = jnp.where(last, x, jnp.zeros_like(x))
        hd = p["head"]
        x = fused_layer_norm_affine(x, hd["ln_g"].astype(dt),
                                    hd["ln_b"].astype(dt), (cfg.d_model,))
        w_out = (emb["tok"].T if cfg.tie_embeddings
                 else hd["out"]).astype(dt)
        logits = jnp.einsum("bsd,dv->bsv", x, w_out)
        B, S, V = logits.shape
        nll = softmax_xentropy_loss(logits.reshape(B * S, V),
                                    tokens.reshape(B * S),
                                    0.0, -1, False,
                                    cfg.xent_impl).reshape(B, S)
        loss = jnp.where(last, nll.mean(), 0.0)
        return jax.lax.psum(loss, PIPE_AXIS)

    def grads_of(params, tokens):
        pv = jax.tree_util.tree_map(
            lambda p: to_varying(p, (DATA_AXIS, PIPE_AXIS)), params)
        loss, grads = jax.value_and_grad(
            lambda p: local_loss(p, tokens))(pv)
        # dense grads are stage-masked partials (embed injection on
        # stage 0 + tied-head term on the last stage; head on the last
        # stage only) — one pipe psum reassembles each exactly once.
        # Stage-local layer grads take no pipe reduction: each device's
        # slice IS its stage's gradient.
        grads = dict(grads)
        for k in ("embed", "head"):
            grads[k] = jax.tree_util.tree_map(
                lambda g: jax.lax.psum(g, PIPE_AXIS), grads[k])
        return jax.lax.pmean(loss, DATA_AXIS), grads

    def body(params, state, tokens):
        loss, grads = grads_of(params, tokens)
        grads = ddp.allreduce_grads(grads)
        fl = opt.flattener_for(params)
        flat = fl.flatten(grads)
        ok = jnp.all(jnp.isfinite(flat)).astype(jnp.float32)
        ok = jax.lax.pmin(ok, PIPE_AXIS)       # skip on all stages or none
        new_state = opt.step_flat(state, flat)
        new_state = jax.tree_util.tree_map(
            lambda nw, old: jnp.where(ok > 0, nw, old), new_state, state)
        new_params = fl.unflatten(new_state.master, like=params)
        for k in ("embed", "head"):
            new_params[k] = jax.tree_util.tree_map(
                lambda x: _typed_replicated(x, PIPE_AXIS), new_params[k])
        return new_params, new_state, loss

    init_s = jax.jit(shard_map(lambda p: opt.init(p), mesh=mesh,
                               in_specs=(pspec,), out_specs=sspec))
    step_sm = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(pspec, sspec, P(DATA_AXIS)),
        out_specs=(pspec, sspec, P())))
    state0 = init_s(params0)

    ticks = m_micro + n_pp - 1
    info = {"family": plan.family, "engine": "shard_map.pp",
            "dp": n_dp, "pp": n_pp, "microbatches": m_micro,
            "stages_layers": l_local,
            "pipeline_bubble_fraction": (n_pp - 1) / ticks}
    # a guarded pp run's goodput ledger carves the static fill/drain
    # share of each step span into its ``pipeline_bubble`` class —
    # feed the running ledger at build time (no-op when none installed)
    from ..telemetry import goodput as _goodput
    led = _goodput.get_ledger()
    if led is not None:
        led.set_pipeline_bubble(info["pipeline_bubble_fraction"])
    if meter:
        import jax.numpy as _jnp
        from ..telemetry import events as _tel_events
        tokens0 = _jnp.zeros((global_batch, cfg.max_len), _jnp.int32)
        info["collectives"] = compiled_collectives(
            step_sm, params0, state0, tokens0)
        # the ppermute schedule lives inside the fori_loop, invisible
        # to the entry-computation walk — meter the engine's exact
        # static schedule (pp.ppermute), like the sp engine does
        sched = _pp_schedule_bytes(cfg, n_dp, n_pp, m_micro, global_batch)
        info["pp_wire"] = sched
        _tel_events.record_collective(
            PIPE_AXIS, sched["logical_bytes"], 2 * sched["ticks"], 0.0,
            wire_bytes=sched["logical_bytes"], scheme="fp32",
            dtype=str(_jnp.dtype(cfg.dtype)), op=sched["op"],
            family="pp")

    def step(carry, tokens):
        params, state = carry
        params, state, loss = step_sm(params, state, tokens)
        return (params, state), loss

    return (params0, state0), step, info


def _moe_cfg_from(cfg, n_ep: int):
    """The MoE flagship variant an ep plan materializes: the dense
    config's dims with ``EP_DEFAULT_EXPERTS`` switch experts (rounded
    up to a multiple of the expert-axis width) — already-MoE configs
    pass through untouched."""
    from ..models.moe_transformer import MoETransformerConfig
    if isinstance(cfg, MoETransformerConfig):
        return cfg
    from .plan import EP_DEFAULT_EXPERTS
    experts = max(EP_DEFAULT_EXPERTS, n_ep)
    if experts % n_ep:
        experts = n_ep * (experts // n_ep + 1)
    return MoETransformerConfig(
        vocab_size=cfg.vocab_size, max_len=cfg.max_len,
        num_layers=cfg.num_layers, d_model=cfg.d_model,
        num_heads=cfg.num_heads, d_ff=cfg.d_ff, num_experts=experts,
        causal=cfg.causal, dtype=cfg.dtype,
        xent_impl=getattr(cfg, "xent_impl", "auto"))


def _is_expert_leaf(path) -> bool:
    """Expert-sharded leaves of the MoE param tree: the per-layer
    ``w_in``/``w_out`` FFN stacks (leading expert axis).  The router is
    dense — every device routes over the FULL expert width."""
    last = path[-1]
    name = getattr(last, "key", None)
    return name in ("w_in", "w_out")


def _build_ep_step(cfg, mesh, plan, global_batch, lr, meter):
    """The expert-parallel engine: shard_map over (data, expert), the
    MoE flagship variant with expert FFN weights sharded on their
    leading axis and token routing through ``parallel/expert``'s
    capacity-factored all_to_all.  Dense grads fold over the expert
    axis (each device's loss covers only its token shard) then ride
    the normal DDP wire over data; expert grads are EXCLUDED from that
    dense fold — the backward all_to_all already delivered every
    peer's contribution to the owning shard — and take only the mean
    scaling + the data-axis reduction.  ``n_ep == 1`` degrades to the
    dp-MoE baseline (full expert set per device, no exchange): the A/B
    leg's loss-parity oracle."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from ..models.moe_transformer import (moe_transformer_init,
                                          moe_transformer_loss)
    from ..optimizers import FusedAdam
    from ..utils.pallas import to_varying
    from .distributed import DistributedDataParallel
    from .expert import EXPERT_AXIS
    from jax import shard_map

    n_dp = int(mesh.shape[DATA_AXIS])
    n_ep = int(mesh.shape.get(EXPERT_AXIS, 1))
    cfg_moe = _moe_cfg_from(cfg, max(n_ep, 1))
    if cfg_moe.num_experts % max(n_ep, 1):
        raise ValueError(f"{cfg_moe.num_experts} experts must divide over "
                         f"the expert axis ({n_ep})")
    world = n_dp * n_ep
    if global_batch % world:
        raise ValueError(f"global batch {global_batch} must divide over "
                         f"the data x expert axes ({world})")
    if plan.shards_update or plan.zero:
        raise ValueError("the ep engine runs the plain fused-flat update "
                         "(no zero/zero1 composition)")

    params0 = moe_transformer_init(jax.random.PRNGKey(0), cfg_moe,
                                   n_expert_shards=1)
    opt = FusedAdam(lr=lr, impl="fused")
    ddp = DistributedDataParallel(axis_name=DATA_AXIS)
    pspec = jax.tree_util.tree_map_with_path(
        lambda path, _: (P(EXPERT_AXIS) if n_ep > 1
                         and _is_expert_leaf(path) else P()), params0)
    grad_axes = ((DATA_AXIS, EXPERT_AXIS) if n_ep > 1 else (DATA_AXIS,))
    expert_axis = EXPERT_AXIS if n_ep > 1 else None
    tok_spec = (P((DATA_AXIS, EXPERT_AXIS)) if n_ep > 1
                else P(DATA_AXIS))

    # per-device optimizer state over the LOCAL tree (expert leaves are
    # 1/n_ep slices): flat m/v/master concatenate over the expert axis
    e_local = cfg_moe.num_experts // max(n_ep, 1)
    local_template = jax.tree_util.tree_map_with_path(
        lambda path, l: (l[:e_local] if n_ep > 1 and _is_expert_leaf(path)
                         else l), params0)
    state_shape = jax.eval_shape(opt.init, local_template)
    sspec = jax.tree_util.tree_map(
        lambda x: (P(EXPERT_AXIS) if n_ep > 1
                   and getattr(x, "ndim", 0) >= 1 else P()), state_shape)

    def grads_of(params, tokens):
        pv = jax.tree_util.tree_map(
            lambda p: to_varying(p, grad_axes), params)
        loss, grads = jax.value_and_grad(lambda p: moe_transformer_loss(
            p, {"tokens": tokens, "targets": tokens}, cfg_moe,
            expert_axis=expert_axis))(pv)
        if n_ep > 1:
            # dense leaves: psum over expert / n_ep turns the per-shard
            # loss grads into the expert-axis mean (the sp seq-fold
            # posture); expert leaves skip the dense fold — their
            # backward all_to_all already summed every peer's
            # contribution into the owning shard — and keep only the
            # 1/n_ep mean scaling
            grads = jax.tree_util.tree_map_with_path(
                lambda path, g: (g / n_ep if _is_expert_leaf(path)
                                 else jax.lax.psum(g, EXPERT_AXIS) / n_ep),
                grads)
        return jax.lax.pmean(loss, grad_axes), grads

    def body(params, state, tokens):
        loss, grads = grads_of(params, tokens)
        grads = ddp.allreduce_grads(grads)
        fl = opt.flattener_for(params)
        flat = fl.flatten(grads)
        ok = jnp.all(jnp.isfinite(flat)).astype(jnp.float32)
        if n_ep > 1:
            ok = jax.lax.pmin(ok, EXPERT_AXIS)     # skip on all shards or none
        new_state = opt.step_flat(state, flat)
        new_state = jax.tree_util.tree_map(
            lambda nw, old: jnp.where(ok > 0, nw, old), new_state, state)
        new_params = fl.unflatten(new_state.master, like=params)
        if n_ep > 1:
            new_params = jax.tree_util.tree_map_with_path(
                lambda path, x: (x if _is_expert_leaf(path)
                                 else _typed_replicated(x, EXPERT_AXIS)),
                new_params)
        return new_params, new_state, loss

    init_s = jax.jit(shard_map(lambda p: opt.init(p), mesh=mesh,
                               in_specs=(pspec,), out_specs=sspec))
    step_sm = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(pspec, sspec, tok_spec),
        out_specs=(pspec, sspec, P())))
    state0 = init_s(params0)

    info = {"family": plan.family, "engine": "shard_map.ep",
            "dp": n_dp, "ep": n_ep, "experts": cfg_moe.num_experts,
            "capacity_factor": cfg_moe.capacity_factor}
    if meter:
        tokens0 = jnp.zeros((global_batch, cfg_moe.max_len), jnp.int32)
        info["collectives"] = compiled_collectives(
            step_sm, params0, state0, tokens0)
        if n_ep > 1:
            # the python-loop MoE layers keep the router all_to_alls in
            # the entry computation: meter the compiled payloads
            # (ep.all_to_all), with the static capacity-factored
            # schedule carried alongside as the cross-check
            info["metered"] = meter_compiled_collectives(
                info["collectives"], "ep", EXPERT_AXIS)
            info["ep_wire"] = _ep_schedule_bytes(cfg_moe, n_dp, n_ep,
                                                 global_batch)

    def step(carry, tokens):
        params, state = carry
        params, state, loss = step_sm(params, state, tokens)
        return (params, state), loss

    return (params0, state0), step, info


def _build_zero_step(cfg, mesh, plan, global_batch, lr, meter):
    """The contrib-ZeRO engine: shard_map over data, the
    DistributedFusedAdam route (permanently sharded optimizer state,
    predivided reduce-scatter riding the plan's collective scheme via
    the env surface Plan.apply() sets)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from ..contrib.optimizers import DistributedFusedAdam
    from ..models import transformer_init, transformer_loss
    from ..utils.pallas import to_varying
    from jax import shard_map

    n_dp = int(mesh.shape[DATA_AXIS])
    if global_batch % n_dp:
        raise ValueError(f"global batch {global_batch} must divide over "
                         f"the data axis ({n_dp})")
    params0 = transformer_init(jax.random.PRNGKey(0), cfg)
    # impl="xla" on the sharded flat buffers (the contrib default off a
    # tuned profile); the Pallas fused kernels need interpret mode on
    # CPU, which the zero measurement leg must not pay for
    opt = DistributedFusedAdam(lr=lr, shard_axis=DATA_AXIS, impl="xla")
    pspec = jax.tree_util.tree_map(lambda _: P(), params0)
    sspec = opt.state_pspecs()

    init_s = jax.jit(shard_map(lambda p: opt.init(p), mesh=mesh,
                               in_specs=(pspec,), out_specs=sspec))

    def body(params, state, tokens):
        pv = jax.tree_util.tree_map(
            lambda p: to_varying(p, (DATA_AXIS,)), params)
        loss, grads = jax.value_and_grad(lambda p: transformer_loss(
            p, {"tokens": tokens, "targets": tokens}, cfg))(pv)
        new_params, new_state = opt.step(state, grads, params)
        return new_params, new_state, jax.lax.pmean(loss, DATA_AXIS)

    step_sm = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(pspec, sspec, P(DATA_AXIS)),
        out_specs=(pspec, sspec, P())))
    state0 = init_s(params0)

    info = {"family": plan.family, "engine": "shard_map.zero", "dp": n_dp}
    if meter:
        tokens0 = jnp.zeros((global_batch, cfg.max_len), jnp.int32)
        info["collectives"] = compiled_collectives(
            step_sm, params0, state0, tokens0)

    def step(carry, tokens):
        params, state = carry
        params, state, loss = step_sm(params, state, tokens)
        return (params, state), loss

    return (params0, state0), step, info
