"""Auto-parallel planner: cost-model search over the repo's parallelism
axes (ISSUE 10, ROADMAP top open item).

The repo implements every axis — dp x tp meshes (``parallel.mesh``),
ZeRO state sharding (``contrib.optimizers.distributed_fused``),
ring/Ulysses sequence parallelism (``parallel.sequence``), weight-update
sharding (``parallel.weight_update``) and compressed collectives
(``parallel.collectives``) — but until now the user picked the
combination by hand.  AMP (arXiv:2210.07297) and veScale
(arXiv:2509.07003) show that a cost-model-driven search over exactly
this space recovers expert-level plans automatically; this module is
that search, built on the planner-consumable surfaces PRs 2-8 left
behind:

  * **compute time** from :func:`telemetry.attrib.op_table` FLOPs/bytes
    projected against the per-generation roofline ceilings
    (``pyprof.prof.resolve_ceilings`` — the row of the device jax
    reports, ``APEX_TPU_CEILINGS`` overriding), split into a train part
    (fwd+bwd, divides by every axis) and an optimizer-update part
    (replicated under plain DDP, 1/dp under ZeRO / update sharding);
  * an **alpha-beta collective model** (ring allreduce /
    reduce-scatter / allgather / all-to-all, parameterized by axis
    size, link bandwidth, per-hop latency, and the wire-byte ratio of
    the chosen :mod:`~apex_tpu.parallel.collectives` scheme including
    ``int8_blockscale`` — whose quantize/dequant-sum codec passes are
    charged against HBM bandwidth, so compression only wins when the
    wire is actually the bottleneck).  The modeled payloads can be
    calibrated against the compiled program's real collective bytes via
    ``attrib.op_table(...)["collectives"]``;
  * an **HBM feasibility model** from
    :func:`telemetry.memory.memory_model`'s per-class dict —
    params/optimizer/activations/batch/temps scaled per axis (honoring
    ``update_sharding_world`` semantics: optimizer bytes divide by dp
    when the update is sharded) and pruned against the generation's
    capacity ceiling.

:func:`search` enumerates candidate plans for a chip count — mesh
factorizations dp x tp (x sp for long-sequence models), ZeRO on/off,
``update_sharding`` off/zero1, a collective scheme per wire — prunes
the HBM-infeasible ones, and ranks the rest by predicted step time.
Predictions within ``tie_tol`` of the best are tied and broken toward
the SIMPLER plan (fewer knobs engaged): an analytic model cannot
resolve sub-3% deltas, and shipping complexity for noise is how
auto-tuners regress.  The winner is a :class:`Plan` whose
:meth:`Plan.apply` materializes the mesh via
``parallel.mesh.create_mesh``/``use_mesh`` and engages the knobs
through their existing env/arg surfaces — applying a plan is
bitwise-identical to configuring the same run by hand (asserted by
tests/L0/test_plan.py).

CLI::

    python -m apex_tpu.parallel.plan --chips 8 --model flagship
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
from typing import Dict, List, Optional, Sequence, Tuple

from .mesh import DATA_AXIS, MODEL_AXIS, SEQ_AXIS, create_mesh, use_mesh
from .pipeline import PIPE_AXIS
from .expert import EXPERT_AXIS
from . import collectives as _coll
from . import weight_update as _wu

__all__ = [
    "ModelProfile", "Plan", "profile_step", "flagship_profile",
    "collective_time_s", "compute_time_s", "predict", "plan_hbm_bytes",
    "resolve_overlap_fraction", "ENV_OVERLAP",
    "enumerate_plans", "search", "default_plan",
    "build_flagship_step", "format_plans", "PLAN_SCHEMES",
]

#: wire schemes the search enumerates for the dp gradient exchange.
#: ``adasum`` is deliberately absent — it changes the reduction rule
#: (PR-7 posture: never auto-selected).  The param-allgather wire of
#: update-sharded plans likewise stays fp32: quantizing params is an
#: explicit opt-in with no env surface (PR-8's ZeRO posture — exactly
#: why :meth:`Plan.apply`, which is env-only, could not engage it),
#: and its measured winner already persists as
#: ``ddp_update_allgather_scheme``.
PLAN_SCHEMES = ("fp32", "bf16", "int8_blockscale")

#: fused-flat optimizer update cost per parameter: ~10 FLOPs (Adam
#: moment math) and 28 B of HBM traffic (read g/p/m/v + write p/m/v,
#: fp32 — PERF_NOTES' bandwidth-bound flat-step accounting).  Split out
#: of the profiled totals so plans that shard the update (ZeRO /
#: update_sharding) scale ONLY this part by 1/dp while plain DDP keeps
#: it replicated.
UPDATE_FLOPS_PER_PARAM = 10.0
UPDATE_BYTES_PER_PARAM = 28.0

#: predictions within this relative band of the best are ties, broken
#: toward the simpler plan (see module docstring)
DEFAULT_TIE_TOL = 0.03

#: sequence-parallel candidates only make sense for long sequences —
#: below this the per-layer exchange dominates any activation saving
SP_MIN_SEQ = 2048

#: expert count the ep cost model assumes when the profiled model is
#: dense (the flagship): the MoETransformerConfig default — the expert
#: variant the ep engine materializes (``spmd._build_ep_step`` derives
#: its MoE config with this count, so model and engine price the same
#: program)
EP_DEFAULT_EXPERTS = 8

#: env override for the comm model's overlap factor (the measured
#: exposed-comm fraction) — precedence: explicit ``predict`` arg > this
#: env pin > 1.0 (fully synchronous collectives, today's engine reality)
ENV_OVERLAP = "APEX_TPU_OVERLAP_FRACTION"


def resolve_overlap_fraction(explicit: Optional[float] = None) -> float:
    """The dp-comm overlap factor: the fraction of modeled collective
    time the step actually EXPOSES (what ``telemetry.timeline`` measures
    as ``exposed_comm_fraction``).  Clamped to [0, 1]; without a
    measurement handed in the model keeps charging the full wire time —
    exactly the synchronous engine it describes.  Precedence: explicit
    arg > ``APEX_TPU_OVERLAP_FRACTION`` env > 1.0."""
    if explicit is None:
        explicit = float(os.environ.get(ENV_OVERLAP) or 1.0)
    return min(max(float(explicit), 0.0), 1.0)


# ---------------------------------------------------------------------------
# model profile: the planner's view of one training step
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ModelProfile:
    """Cost-model inputs for the GLOBAL training step as a single-chip
    program (global batch, full fwd+bwd+update) — the quantity every
    axis then divides.  Built by :func:`profile_step` from the compiled
    HLO (``attrib.op_table`` + ``memory.memory_model``), or constructed
    directly for hand-computable oracle tests."""
    name: str
    flops: float                  # total step FLOPs
    bytes_accessed: float         # total step HBM traffic
    params_bytes: int             # per memory_model()'s liveness classes
    optimizer_bytes: int
    activations_bytes: int
    batch_bytes: int
    temps_bytes: int
    output_bytes: int
    args_bytes: int = 0
    constants_bytes: int = 0
    peak_hbm_bytes: int = 0       # single-chip compiled peak (sanity floor)
    grad_bytes: int = 0           # dp exchange payload (defaults to params)
    layers: int = 0               # transformer facts for the tp/sp comm model
    act_layer_bytes: int = 0      # one layer's activation tensor (B*S*D*4)
    seq: int = 0
    heads: int = 1
    global_batch: int = 0         # batch facts for the pp microbatch lattice
    experts: int = 0              # MoE expert count (0 = dense profile; the
                                  # ep model assumes EP_DEFAULT_EXPERTS)
    capacity_factor: float = 1.25  # ep router capacity factor
    platform: str = "cpu"
    collective_bytes: dict = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        if self.grad_bytes == 0:
            object.__setattr__(self, "grad_bytes", self.params_bytes)


def profile_step(fn, *args, name: str = "step", cfg=None,
                 global_batch: Optional[int] = None,
                 **kwargs) -> ModelProfile:
    """Compile ``fn(*args, **kwargs)`` AOT (never executed — both walks
    are CPU-deterministic text over the optimized HLO) and distill the
    planner profile: FLOPs/bytes from :func:`attrib.op_table`, the
    per-class HBM model from :func:`memory.memory_model`, and the
    compiled collective payloads for comm-model calibration.

    ``cfg`` (a :class:`~apex_tpu.models.TransformerConfig`) fills the
    transformer facts the tp/sp comm model needs (layers, per-layer
    activation bytes at ``global_batch``)."""
    import jax
    from ..pyprof.prof import ceilings_row
    from ..telemetry import attrib
    from ..telemetry import memory as tmem

    table = attrib.op_table(fn, *args, **kwargs)
    mem = tmem.memory_model(fn, *args, register=False, **kwargs)
    layers = act_layer = seq = experts = 0
    heads = 1
    cap_factor = 1.25
    if cfg is not None:
        layers = int(cfg.num_layers)
        seq = int(cfg.max_len)
        heads = int(cfg.num_heads)
        act_layer = int((global_batch or 1) * seq * cfg.d_model * 4)
        experts = int(getattr(cfg, "num_experts", 0) or 0)
        cap_factor = float(getattr(cfg, "capacity_factor", 1.25))
    coll = {
        op: {"count": agg["count"],
             "logical_bytes": agg["logical_bytes"]}
        for op, agg in (table.get("collectives", {})
                        .get("by_opcode", {})).items()
    }
    return ModelProfile(
        name=name,
        flops=float(table["module_flops"] or table["total_flops"]),
        bytes_accessed=float(table["module_bytes"] or table["total_bytes"]),
        params_bytes=mem["params_bytes"],
        optimizer_bytes=mem["optimizer_bytes"],
        activations_bytes=mem["activations_bytes"],
        batch_bytes=mem["batch_bytes"],
        temps_bytes=mem["temps_bytes"],
        output_bytes=mem["output_bytes"],
        args_bytes=mem.get("args_bytes", 0),
        constants_bytes=mem.get("constants_bytes", 0),
        peak_hbm_bytes=mem["peak_hbm_bytes"],
        layers=layers, act_layer_bytes=act_layer, seq=seq, heads=heads,
        global_batch=int(global_batch or 0), experts=experts,
        capacity_factor=cap_factor,
        platform=ceilings_row(),
        collective_bytes=coll,
    )


def _flagship_cfg(on_tpu: bool, **overrides):
    from ..models import bert_large_config
    if on_tpu:
        return bert_large_config(**overrides)
    # the CPU stand-in: small enough for tier-1, same
    # structure (stacked layers, tied embeddings) as the flagship
    base = dict(num_layers=2, d_model=128, d_ff=512, vocab_size=1024,
                max_len=64, num_heads=4)
    base.update(overrides)
    return bert_large_config(**base)


def flagship_profile(cfg=None, *, global_batch: Optional[int] = None,
                     **overrides) -> Tuple[ModelProfile, object, int]:
    """Profile the flagship transformer train step (fused-flat Adam).
    Returns
    ``(profile, cfg, global_batch)``."""
    import jax
    on_tpu = jax.default_backend() == "tpu"
    if cfg is None:
        cfg = _flagship_cfg(on_tpu, **overrides)
    if global_batch is None:
        global_batch = 32 if on_tpu else 8
    step, step_args = _flagship_step(cfg, global_batch)
    prof = profile_step(step, *step_args, name=f"flagship-{cfg.num_layers}L",
                        cfg=cfg, global_batch=global_batch)
    return prof, cfg, global_batch


def _flagship_step(cfg, global_batch: int):
    """The single-chip global train step the profile describes: plain
    value_and_grad + fused-flat Adam (the same update math the measured
    DDP plans run, minus the collectives the plan itself adds)."""
    import jax
    import jax.numpy as jnp
    from ..models import transformer_init, transformer_loss
    from ..optimizers import FusedAdam

    params = transformer_init(jax.random.PRNGKey(0), cfg)
    opt = FusedAdam(lr=1e-2, impl="fused")
    state = opt.init(params)
    tokens = jnp.zeros((global_batch, cfg.max_len), jnp.int32)

    def step(params, state, tokens):
        loss, grads = jax.value_and_grad(lambda p: transformer_loss(
            p, {"tokens": tokens, "targets": tokens}, cfg))(params)
        fl = opt.flattener_for(params)
        new_state = opt.step_flat(state, fl.flatten(grads))
        return fl.unflatten(new_state.master, like=params), new_state, loss

    return step, (params, state, tokens)


# ---------------------------------------------------------------------------
# analytic cost model
# ---------------------------------------------------------------------------

def _resolve_ceil(ceilings=None, platform: Optional[str] = None) -> dict:
    if ceilings is not None:
        return ceilings
    from ..pyprof.prof import resolve_ceilings
    return resolve_ceilings(platform or "cpu")


def compute_time_s(flops: float, nbytes: float, ceil: dict) -> float:
    """Roofline lower bound: compute-bound or bandwidth-bound,
    whichever binds."""
    return max(flops / ceil["peak_flops"], nbytes / ceil["peak_bw"])


#: ring-algorithm hop counts and per-device traffic factors (classic
#: alpha-beta: allreduce = reduce-scatter + allgather)
_COLL_HOPS = {
    "all_reduce": lambda n: 2 * (n - 1),
    "reduce_scatter": lambda n: n - 1,
    "all_gather": lambda n: n - 1,
    "all_to_all": lambda n: n - 1,
    # stage-to-stage activation hop (the pp engine's wire): one neighbor
    # link, the full payload crosses it
    "ppermute": lambda n: 1,
}
_COLL_TRAFFIC = {
    "all_reduce": lambda n: 2.0 * (n - 1) / n,
    "reduce_scatter": lambda n: (n - 1) / n,
    "all_gather": lambda n: (n - 1) / n,
    "all_to_all": lambda n: (n - 1) / n,
    "ppermute": lambda n: 1.0,
}


def _codec_bytes(scheme: str, logical_bytes: float, world: int,
                 kind: str) -> float:
    """HBM traffic the scheme's codec pays per device: quantize/cast on
    the way out, dequantize(+sum) on the way in.  This is why int8 does
    NOT win on wires as fast as HBM (a CPU-emulated mesh): the
    allreduce lowering gathers every peer's codes and dequant-sums
    ``world`` stacks locally (``collectives._int8_reduce``), while the
    reduce-scatter's all_to_all only dequant-sums shard slices."""
    if scheme == "bf16":
        return 2.0 * logical_bytes
    if scheme == "int8_blockscale":
        if kind == "all_reduce":
            return (1.0 + world) * logical_bytes
        return 2.0 * logical_bytes
    return 0.0


def _ab_time(kind: str, wire: float, world: int, alpha: float,
             bw: float) -> float:
    """One alpha-beta term: hops x launch latency + ring traffic over
    the link (``wire`` = this tier's per-device wire payload)."""
    if world <= 1 or wire <= 0:
        return 0.0
    return (_COLL_HOPS[kind](world) * alpha
            + _COLL_TRAFFIC[kind](world) * wire / bw)


def collective_time_s(kind: str, logical_bytes: float, world: int,
                      ceil: dict, scheme: str = "fp32",
                      block: int = _coll.DEFAULT_BLOCK,
                      slices: int = 1) -> float:
    """Alpha-beta time for one collective of ``logical_bytes`` (fp32
    payload per device) over a ``world``-sized axis: per-hop launch
    latency + ring traffic of the scheme's WIRE representation over the
    link bandwidth + the codec's HBM passes.

    ``slices > 1`` models a multi-slice axis (the dp axis of a
    multislice pod): the collective decomposes hierarchically into the
    intra-slice phase over ``world/slices`` neighbors on ICI plus an
    inter-slice phase over ``slices`` carrying ``1/local`` of the
    payload per device across DCN (``dcn_bw``/``dcn_alpha_s`` ceilings
    — the classic RS-local / AR-across / AG-local schedule).  Slices
    that don't divide the axis fall back to the flat single-tier
    model."""
    if world <= 1 or logical_bytes <= 0:
        return 0.0
    if kind not in _COLL_HOPS:
        raise ValueError(f"unknown collective kind {kind!r}; "
                         f"known: {tuple(_COLL_HOPS)}")
    nelems = int(logical_bytes) // 4
    wire = float(_coll.wire_bytes(scheme, nelems, block))
    slices = int(slices or 1)
    if slices > 1 and world % slices == 0 and world > slices:
        local = world // slices
        dcn_bw = ceil.get("dcn_bw", ceil["ici_bw"])
        dcn_alpha = ceil.get("dcn_alpha_s", ceil["ici_alpha_s"])
        t = (_ab_time(kind, wire, local, ceil["ici_alpha_s"],
                      ceil["ici_bw"])
             + _ab_time(kind, wire / local, slices, dcn_alpha, dcn_bw))
    else:
        t = _ab_time(kind, wire, world, ceil["ici_alpha_s"],
                     ceil["ici_bw"])
    return t + _codec_bytes(scheme, logical_bytes, world,
                            kind) / ceil["peak_bw"]


def _update_costs(profile: ModelProfile) -> Tuple[float, float]:
    """(flops, bytes) of the optimizer-update part of the step, capped
    at half the profiled totals so a degenerate profile (tiny model,
    huge optimizer) can't drive the train part negative."""
    n_params = profile.params_bytes / 4.0
    return (min(UPDATE_FLOPS_PER_PARAM * n_params, 0.5 * profile.flops),
            min(UPDATE_BYTES_PER_PARAM * n_params,
                0.5 * profile.bytes_accessed))


# ---------------------------------------------------------------------------
# Plan
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Plan:
    """One point of the search space: mesh axis sizes + the knob dict,
    with the model's predictions attached.  :meth:`apply` materializes
    it through the existing surfaces; :meth:`knobs` is the serializable
    form."""
    dp: int = 1
    tp: int = 1
    sp: int = 1
    sp_strategy: str = "none"          # none | ring | ulysses
    pp_stages: int = 1                 # GPipe stages (the pipe mesh axis)
    pp_microbatches: int = 1           # M in-flight microbatches per replica
    ep: int = 1                        # expert-parallel width (expert axis)
    zero: bool = False                 # contrib ZeRO optimizer route
    update_sharding: str = "off"       # off | zero1 (parallel.weight_update)
    collective_scheme: str = "fp32"    # dp gradient wire
    allgather_scheme: str = "fp32"     # sharded-update param allgather wire
    predicted_step_ms: float = 0.0
    predicted_hbm_bytes: int = 0
    hbm_by_class: dict = dataclasses.field(default_factory=dict)
    breakdown: dict = dataclasses.field(default_factory=dict)
    feasible: bool = True

    @property
    def chips(self) -> int:
        return self.dp * self.tp * self.sp * self.pp_stages * self.ep

    @property
    def shards_update(self) -> bool:
        """Does the optimizer update run on 1/dp slices?"""
        return self.zero or self.update_sharding == "zero1"

    @property
    def complexity(self) -> int:
        """Knobs engaged — the tie-break rank (simpler wins a tie)."""
        return ((self.tp > 1) + (self.sp > 1) + (self.pp_stages > 1)
                + (self.ep > 1) + 2 * self.zero
                + (self.update_sharding != "off")
                + (self.collective_scheme != "fp32")
                + (self.allgather_scheme != "fp32"))

    @property
    def family(self) -> str:
        """Which step engine (``parallel.spmd``) materializes this
        plan: ``zero`` (contrib ZeRO) / ``tp`` (consistent-SPMD GSPMD
        jit) / ``sp`` (ring/ulysses shard_map) / ``pp`` (GPipe
        microbatched stages) / ``ep`` (switch-MoE expert sharding) /
        ``dp`` (the classic DDP harness)."""
        if self.zero:
            return "zero"
        if self.tp > 1:
            return "tp"
        if self.sp > 1:
            return "sp"
        if self.pp_stages > 1:
            return "pp"
        if self.ep > 1:
            return "ep"
        return "dp"

    @property
    def measurable(self) -> bool:
        """Can this plan be run and timed?  True across the
        whole search space since the ``parallel.spmd`` step engine
        (ISSUE 12; pp/ep families ISSUE 17): every family — dp, dp x tp
        (GSPMD), dp x sp (ring/ulysses), dp x pp (GPipe), dp x ep
        (switch-MoE), contrib-ZeRO — materializes as a runnable step
        via :func:`~apex_tpu.parallel.spmd.build_plan_step`."""
        return self.family in ("dp", "tp", "sp", "zero", "pp", "ep")

    def axis_sizes(self) -> Dict[str, int]:
        """``create_mesh`` axis dict — size-1 axes are omitted (except
        ``data``, always present) so applying a dp-only plan builds the
        exact mesh a hand-configured DDP run would."""
        axes = {DATA_AXIS: self.dp}
        if self.tp > 1:
            axes[MODEL_AXIS] = self.tp
        if self.sp > 1:
            axes[SEQ_AXIS] = self.sp
        if self.pp_stages > 1:
            axes[PIPE_AXIS] = self.pp_stages
        if self.ep > 1:
            axes[EXPERT_AXIS] = self.ep
        return axes

    def knobs(self) -> dict:
        return {
            "dp": self.dp, "tp": self.tp, "sp": self.sp,
            "sp_strategy": self.sp_strategy,
            "pp_stages": self.pp_stages,
            "pp_microbatches": self.pp_microbatches,
            "ep": self.ep, "zero": self.zero,
            "update_sharding": self.update_sharding,
            "collective_scheme": self.collective_scheme,
            "allgather_scheme": self.allgather_scheme,
        }

    def env(self) -> Dict[str, str]:
        """The env-knob rendering of this plan (the subset of knobs
        that have env surfaces).  ``fp32`` wire / ``off`` sharding emit
        NOTHING — the legacy defaults must stay bitwise-untouched."""
        env = {}
        if self.collective_scheme != "fp32":
            env[_coll.ENV_KNOB] = self.collective_scheme
        if self.update_sharding != "off":
            env[_wu.ENV_KNOB] = self.update_sharding
        return env

    def pspecs(self, cfg):
        """PartitionSpec tree for the flagship transformer under this
        plan (replicated when tp == 1 — dp grads ride the DDP psum).
        Single source: the step engine's
        :func:`~apex_tpu.parallel.spmd.plan_param_pspecs`."""
        from . import spmd as _spmd
        return _spmd.plan_param_pspecs(cfg, self)

    @contextlib.contextmanager
    def apply(self, devices=None):
        """Materialize the plan: build the mesh
        (``create_mesh``/``use_mesh``) and engage the knobs through
        their existing env surfaces for the duration of the context.
        Code inside configures NOTHING by hand — a knob-less
        ``DistributedDataParallel()`` / ``weight_update(opt)`` inside
        the context resolves to exactly this plan's choices (and is
        bitwise-identical to passing them explicitly)."""
        mesh = create_mesh(self.axis_sizes(), devices)
        env = self.env()
        saved = {k: os.environ.get(k) for k in env}
        # the knobs this plan leaves at default must ALSO be at default
        # inside the context: an ambient A/B env var would silently
        # override the plan being applied
        for k in (_coll.ENV_KNOB, _wu.ENV_KNOB):
            if k not in env and k in os.environ:
                saved[k] = os.environ.pop(k)
        try:
            os.environ.update(env)
            with use_mesh(mesh):
                yield mesh
        finally:
            for k in set(env) | set(saved):
                os.environ.pop(k, None)
                if saved.get(k) is not None:
                    os.environ[k] = saved[k]

    def describe(self) -> str:
        bits = [f"dp={self.dp}"]
        if self.tp > 1:
            bits.append(f"tp={self.tp}")
        if self.sp > 1:
            bits.append(f"sp={self.sp}:{self.sp_strategy}")
        if self.pp_stages > 1:
            bits.append(f"pp={self.pp_stages}x{self.pp_microbatches}")
        if self.ep > 1:
            bits.append(f"ep={self.ep}")
        if self.zero:
            bits.append("zero")
        if self.update_sharding != "off":
            bits.append(f"us={self.update_sharding}")
        if self.collective_scheme != "fp32":
            bits.append(self.collective_scheme)
        if self.allgather_scheme != "fp32":
            bits.append(f"ag={self.allgather_scheme}")
        return " ".join(bits)


def default_plan(chips: int) -> Plan:
    """The all-defaults baseline: pure data parallelism, legacy fp32
    psum wire, replicated update — what a knob-less run does today."""
    return Plan(dp=int(chips))


# ---------------------------------------------------------------------------
# prediction: step time + HBM per replica for one candidate
# ---------------------------------------------------------------------------

def _ep_geometry(profile: ModelProfile, dp: int, ep: int,
                 sp: int = 1) -> Tuple[int, int, int, int]:
    """(E_total, capacity, d_model, tokens_local) of the ep router under
    the plan's axes — the shapes the capacity-factored all_to_all and
    the per-device expert buffers are built from (``parallel.expert``'s
    own formulas, so model and engine agree)."""
    E = int(profile.experts or EP_DEFAULT_EXPERTS)
    gb = max(int(profile.global_batch or 1), 1)
    seq = max(int(profile.seq), 1)
    tokens_local = max(gb * seq // max(dp * ep * sp, 1), 1)
    capacity = max(int(profile.capacity_factor * tokens_local / E), 1)
    d_model = max(int(profile.act_layer_bytes) // max(gb * seq * 4, 1), 1)
    return E, capacity, d_model, tokens_local


def plan_hbm_bytes(profile: ModelProfile, plan: Plan) -> Tuple[int, dict]:
    """Per-replica HBM at the peak under the plan's axes, scaled from
    ``memory_model()``'s per-class partition: params/optimizer shard
    over tp x pp (pipeline stages each own their layer slice; and
    optimizer additionally over dp when the update is sharded — the
    ``update_sharding_world`` semantics); activations and temps shard
    over every token/layer axis; the batch over dp x sp x ep.  args and
    constants replicate.

    pp adds the GPipe schedule stash (``pp_stash``): the fori_loop
    backward saves one microbatch activation block per tick (M + S - 1
    ticks) plus the M-deep output collection buffer — the "M in-flight
    microbatches" memory the bubble buys throughput with.  ep adds the
    per-device expert-capacity buffers (``ep_buffers``): the dense
    dispatch/combine one-hots (T, E, C) and the owner-major all_to_all
    queues (E, C, D), both ways — the static shapes switch routing pays
    for XLA-friendliness."""
    dp, tp, sp = plan.dp, plan.tp, plan.sp
    pp, ep = plan.pp_stages, plan.ep
    opt_div = tp * pp * (dp if plan.shards_update else 1)
    by = {
        "params": profile.params_bytes // (tp * pp),
        "optimizer": profile.optimizer_bytes // opt_div,
        "activations": profile.activations_bytes // (dp * tp * sp * pp * ep),
        "batch": profile.batch_bytes // (dp * sp * ep),
        "temps": profile.temps_bytes // (dp * tp * sp * ep),
        "output": profile.output_bytes // (dp * ep),
        "args": profile.args_bytes,
        "constants": profile.constants_bytes,
    }
    if pp > 1:
        m = max(int(plan.pp_microbatches), 1)
        ticks = m + pp - 1
        blk = profile.act_layer_bytes // max(dp * m, 1)
        by["pp_stash"] = int((ticks + m) * blk)
    if ep > 1:
        e_total, cap, d_model, t_local = _ep_geometry(profile, dp, ep, sp)
        # dispatch + combine one-hots and both all_to_all queue buffers,
        # fp32 (moe_ffn computes routing in f32)
        by["ep_buffers"] = int(4 * (2 * t_local * e_total * cap
                                    + 2 * e_total * cap * d_model))
    return sum(by.values()), by


def predict(profile: ModelProfile, plan: Plan, ceilings=None,
            platform: Optional[str] = None,
            overlap_fraction: Optional[float] = None) -> Plan:
    """Fill ``plan``'s predicted step time (with per-component
    breakdown), HBM bytes, and feasibility against the ceilings'
    capacity.  Returns the same plan, mutated.

    ``overlap_fraction`` is the comm model's overlap factor (exposed
    dp comm = modeled comm x fraction; see
    :func:`resolve_overlap_fraction` for the default chain) — the step
    is charged only the EXPOSED part of the dp gradient exchange, so a
    measured overlap changes where compression pays: int8's codec cost
    only wins when the wire time it saves was exposed.  The raw
    modeled comm stays visible in ``breakdown["dp_comm_ms"]``;
    ``breakdown["dp_comm_exposed_ms"]`` is what the total charges."""
    ceil = _resolve_ceil(ceilings, platform or profile.platform)
    overlap = resolve_overlap_fraction(overlap_fraction)
    dp, tp, sp = plan.dp, plan.tp, plan.sp
    pp, ep = plan.pp_stages, plan.ep
    shards = dp * tp * sp * pp * ep

    f_upd, b_upd = _update_costs(profile)
    t_train = compute_time_s((profile.flops - f_upd) / shards,
                             (profile.bytes_accessed - b_upd) / shards,
                             ceil)
    upd_div = tp * pp * (dp if plan.shards_update else 1)
    t_update = compute_time_s(f_upd / upd_div, b_upd / upd_div, ceil)

    t_dp = 0.0
    if dp > 1:
        # only the dp axis can span slices (tp/sp are ICI-adjacent by
        # construction — the mesh's fastest axes); a multi-slice pod
        # charges the dp wire its DCN tier (``num_slices`` rides the
        # ceilings: detected from the device topology by search(), or
        # pinned via APEX_TPU_CEILINGS="num_slices=N")
        dp_slices = min(dp, int(ceil.get("num_slices", 1) or 1))
        gbytes = profile.grad_bytes / tp
        if plan.shards_update:
            t_dp = (collective_time_s("reduce_scatter", gbytes, dp, ceil,
                                      plan.collective_scheme,
                                      slices=dp_slices)
                    + collective_time_s("all_gather",
                                        profile.params_bytes / tp, dp,
                                        ceil, plan.allgather_scheme,
                                        slices=dp_slices))
        else:
            t_dp = collective_time_s("all_reduce", gbytes, dp, ceil,
                                     plan.collective_scheme,
                                     slices=dp_slices)

    t_tp = 0.0
    if tp > 1:
        # Megatron column/row pairs: 2 activation allreduces per layer
        # forward + 2 backward
        act = profile.act_layer_bytes / (dp * sp)
        t_tp = 4 * max(profile.layers, 1) * collective_time_s(
            "all_reduce", act, tp, ceil)

    t_sp = 0.0
    if sp > 1:
        act = profile.act_layer_bytes / (dp * tp)
        if plan.sp_strategy == "ulysses":
            # 4 all_to_alls per layer forward (q/k/v in, out back) + the
            # mirrored backward
            t_sp = 8 * max(profile.layers, 1) * collective_time_s(
                "all_to_all", act / sp, sp, ceil)
        else:
            # ring attention: K+V blocks circulate the full ring each
            # layer, forward and backward
            t_sp = 2 * max(profile.layers, 1) * collective_time_s(
                "all_gather", 2 * act / sp, sp, ceil)

    t_bubble = t_pp = 0.0
    if pp > 1:
        m = max(int(plan.pp_microbatches), 1)
        # GPipe fill-drain: the schedule runs M + S - 1 ticks for M
        # microbatches of useful work — the (S-1)/M bubble sits on the
        # critical path (no overlap can hide it; it IS idle hardware)
        t_bubble = t_train * (pp - 1) / m
        # one microbatch activation block hops stage-to-stage per tick,
        # forward + the mirrored backward
        blk = profile.act_layer_bytes / max(dp * m, 1)
        t_pp = 2 * (m + pp - 1) * collective_time_s("ppermute", blk, pp,
                                                    ceil)

    t_ep = 0.0
    if ep > 1:
        coll = (profile.collective_bytes or {}).get("all-to-all")
        if coll and coll.get("logical_bytes"):
            # compiled-HLO sub-table where available: the program's own
            # per-device all_to_all payload (fwd count; backward mirrors)
            count = max(int(coll.get("count", 1)), 1)
            t_ep = 2 * count * collective_time_s(
                "all_to_all", float(coll["logical_bytes"]) / count, ep,
                ceil)
        else:
            # capacity-factored router wire: each device ships its
            # owner-major (E_total * capacity, D) queue both ways per
            # MoE layer, forward + the mirrored backward (4 all_to_alls
            # per layer per step)
            e_total, cap, d_model, _ = _ep_geometry(profile, dp, ep, sp)
            a2a = 4.0 * e_total * cap * d_model
            t_ep = 4 * max(profile.layers, 1) * collective_time_s(
                "all_to_all", a2a, ep, ceil)

    # only the dp wire is overlap-eligible: its collectives are the
    # ones the backward can hide (bucket-by-bucket as grads become
    # ready); tp/sp/pp/ep exchanges sit ON the critical path between
    # layer ops, so they stay fully charged — and the pipeline bubble
    # is idle hardware by construction
    t_dp_exposed = t_dp * overlap
    total_s = (t_train + t_update + t_dp_exposed + t_tp + t_sp
               + t_bubble + t_pp + t_ep)
    hbm, by = plan_hbm_bytes(profile, plan)
    plan.predicted_step_ms = total_s * 1e3
    plan.predicted_hbm_bytes = int(hbm)
    plan.hbm_by_class = by
    plan.breakdown = {
        "train_ms": t_train * 1e3, "update_ms": t_update * 1e3,
        "dp_comm_ms": t_dp * 1e3,
        "dp_comm_exposed_ms": t_dp_exposed * 1e3,
        "overlap_fraction": overlap,
        "tp_comm_ms": t_tp * 1e3,
        "sp_comm_ms": t_sp * 1e3,
        "pp_bubble_ms": t_bubble * 1e3,
        "pp_comm_ms": t_pp * 1e3,
        "ep_comm_ms": t_ep * 1e3,
    }
    plan.feasible = hbm <= ceil["hbm_bytes"]
    return plan


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------

def _factorizations(chips: int):
    """(dp, tp, sp, pp, ep) tuples with dp*tp*sp*pp*ep == chips (the
    classic dp x tp plane enumerates first; pp then ep widen last)."""
    chips = int(chips)
    for ep in range(1, chips + 1):
        if chips % ep:
            continue
        r1 = chips // ep
        for pp in range(1, r1 + 1):
            if r1 % pp:
                continue
            r2 = r1 // pp
            for sp in range(1, r2 + 1):
                if r2 % sp:
                    continue
                rest = r2 // sp
                for tp in range(1, rest + 1):
                    if rest % tp:
                        continue
                    yield rest // tp, tp, sp, pp, ep


def _pp_microbatch_options(profile: ModelProfile, dp: int) -> List[int]:
    """Candidate microbatch counts M for a pp plan at ``dp`` replicas:
    divisors of the per-replica batch (the engine reshapes (B_local,
    ...) -> (M, B_local/M, ...)), capped at 8 — beyond that the bubble
    saving per extra M is <2% while the per-microbatch blocks shrink
    below MXU-friendly shapes."""
    b_rep = int(profile.global_batch or 0) // max(dp, 1)
    if b_rep < 1:
        return []
    return [m for m in (1, 2, 4, 8) if m <= b_rep and b_rep % m == 0]


def enumerate_plans(profile: ModelProfile, chips: int, *,
                    ceilings=None, platform: Optional[str] = None,
                    schemes: Sequence[str] = PLAN_SCHEMES,
                    allow_tp: bool = True, allow_sp: bool = True,
                    allow_pp: bool = True, allow_ep: bool = True,
                    sp_min_seq: int = SP_MIN_SEQ) -> List[Plan]:
    """Every candidate in the space, predicted (feasible and infeasible
    alike — :func:`search` prunes).  Structural constraints: tp only
    for layered models and only up to the head count (the attention
    shard unit); sp only for sequences >= ``sp_min_seq``, dividing the
    sequence, composed with dp only (the repo's SP paths); pp only when
    the stage count divides the layer stack and a microbatch lattice
    exists (M divides the per-replica batch), composed with dp only; ep
    only when the width divides the expert count, composed with dp
    only; schemes and update-sharding variants only where a dp wire
    exists (dp > 1)."""
    ceil = _resolve_ceil(ceilings, platform or profile.platform)
    plans: List[Plan] = []
    for dp, tp, sp, pp, ep in _factorizations(chips):
        if tp > 1 and (not allow_tp or profile.layers <= 0
                       or tp > profile.heads):
            continue
        if sp > 1:
            if (not allow_sp or profile.seq < sp_min_seq
                    or profile.seq % sp or tp > 1 or pp > 1 or ep > 1):
                continue
            strategies = ["ring"]
            if profile.heads % sp == 0:
                strategies.append("ulysses")
        else:
            strategies = ["none"]
        micro_opts = [1]
        if pp > 1:
            # GPipe stages partition the stacked layer axis; the engine
            # composes pp with dp only (one stage slice per pipe device)
            if (not allow_pp or profile.layers <= 0 or pp > profile.layers
                    or profile.layers % pp or tp > 1 or sp > 1 or ep > 1):
                continue
            micro_opts = _pp_microbatch_options(profile, dp)
            if not micro_opts:
                continue
        if ep > 1:
            # expert width must divide the expert count (the dense
            # flagship's ep variant assumes EP_DEFAULT_EXPERTS); the
            # engine composes ep with dp only
            e_total = int(profile.experts or EP_DEFAULT_EXPERTS)
            if (not allow_ep or profile.layers <= 0 or e_total % ep
                    or tp > 1 or sp > 1 or pp > 1):
                continue
        # sharding variants: plain DDP; update-sharded DDP (zero1); the
        # contrib-ZeRO route.  The wire scheme only matters with a dp
        # axis to exchange over.  Engine constraints (parallel.spmd):
        # contrib ZeRO is a shard_map-over-data optimizer — it composes
        # with neither the GSPMD tp step nor the (data, seq) sp step
        # nor the pp/ep shard_map engines; the tp family's dp wire is
        # XLA-owned (consistent-SPMD: collectives by annotation), so
        # compressed schemes don't apply there; and the pp/ep engines
        # run the plain fused-flat update (their stage/expert-local
        # param trees don't fit zero1's replicated-state lattice) — a
        # plan the engine cannot run must not be enumerated, let alone
        # ranked.
        variants = [("off", False)]
        if dp > 1 and pp == 1 and ep == 1:
            variants.append(("zero1", False))
            if tp == 1 and sp == 1:
                variants.append(("off", True))
        dp_schemes = schemes if (dp > 1 and tp == 1) else ("fp32",)
        for strat in strategies:
            for scheme in dp_schemes:
                for us, zero in variants:
                    for m in micro_opts:
                        plans.append(predict(profile, Plan(
                            dp=dp, tp=tp, sp=sp, sp_strategy=strat,
                            pp_stages=pp, pp_microbatches=m, ep=ep,
                            zero=zero, update_sharding=us,
                            collective_scheme=scheme), ceilings=ceil))
    return plans


def search(profile: ModelProfile, chips: int, *,
           ceilings=None, platform: Optional[str] = None,
           capacity_bytes: Optional[int] = None,
           tie_tol: float = DEFAULT_TIE_TOL,
           **enum_kwargs) -> List[Plan]:
    """Ranked feasible plans for ``chips`` devices: enumerate, prune
    everything whose per-replica HBM exceeds the capacity (the
    ceilings' ``hbm_bytes`` unless ``capacity_bytes`` overrides), rank
    by predicted step time with near-ties broken toward the simpler
    plan.  Never returns an HBM-infeasible plan (property-tested).

    Invoked between runs (elastic resume at a new chip count) and
    MID-RUN by the controller's ``replan_reshard`` actuator
    (``apex_tpu.control`` via :func:`apex_tpu.elastic.replan`) — the
    search is pure host arithmetic over the cost model, so an in-run
    call costs milliseconds, no compiles, no device syncs."""
    ceil = dict(_resolve_ceil(ceilings, platform or profile.platform))
    if capacity_bytes is not None:
        ceil["hbm_bytes"] = float(capacity_bytes)
    if "num_slices" not in ceil:
        # multi-slice detection from the live device topology (DCN
        # terms for the dp wire); explicit ceilings/env always win
        from .mesh import num_slices as _num_slices
        try:
            ceil["num_slices"] = _num_slices()
        except Exception:   # pragma: no cover - uninitialized backend
            ceil["num_slices"] = 1
    plans = [p for p in enumerate_plans(profile, chips, ceilings=ceil,
                                        **enum_kwargs) if p.feasible]
    plans.sort(key=lambda p: p.predicted_step_ms)
    if plans:
        best = plans[0].predicted_step_ms
        band = best * (1.0 + tie_tol)
        plans.sort(key=lambda p: (
            p.predicted_step_ms if p.predicted_step_ms > band else best,
            p.complexity, p.predicted_step_ms))
    return plans


# ---------------------------------------------------------------------------
# the dp-family training step (``spmd.build_plan_step``'s dp engine)
# ---------------------------------------------------------------------------

def build_flagship_step(cfg, mesh, *, global_batch: int,
                        ddp_kwargs: Optional[dict] = None):
    """The flagship transformer's DDP + fused-flat-Adam training step
    over ``mesh``'s data axis: ``(carry0, step)`` with
    ``step(carry, tokens) -> (carry, loss)`` (jitted shard_map; tokens
    ``(global_batch, seq)`` sharded over data).

    Knobs resolve through the EXISTING surfaces: ``ddp_kwargs`` passes
    them explicitly (the hand-configured run), or leave it empty inside
    :meth:`Plan.apply` and the env knobs the plan set select the same
    path — the two must be bitwise-identical (tests/L0/test_plan.py).
    ``update_sharding`` resolving to zero1 routes the update through
    :class:`~apex_tpu.parallel.weight_update.ShardedUpdate`."""
    import functools
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from ..models import transformer_init, transformer_loss
    from ..optimizers import FusedAdam
    from ..utils.pallas import to_varying
    from .distributed import DistributedDataParallel
    from jax import shard_map

    n_dev = int(mesh.shape[DATA_AXIS])
    if global_batch % n_dev:
        raise ValueError(f"global batch {global_batch} must divide over "
                         f"the data axis ({n_dev})")
    params0 = transformer_init(jax.random.PRNGKey(0), cfg)
    opt = FusedAdam(lr=1e-2, impl="fused")
    ddp = DistributedDataParallel(axis_name=DATA_AXIS,
                                  **(ddp_kwargs or {}))
    su = ddp.weight_update(opt)
    pspec = jax.tree_util.tree_map(lambda _: P(), params0)

    def grads_of(params, tokens):
        # grads wrt a pcast-varying copy so the dp collectives actually
        # run (wrt replicated params the cotangent rule pre-sums them)
        pv = jax.tree_util.tree_map(
            lambda p: to_varying(p, (DATA_AXIS,)), params)
        return jax.value_and_grad(lambda p: transformer_loss(
            p, {"tokens": tokens, "targets": tokens}, cfg))(pv)

    if su is None:
        state0 = opt.init(params0)
        sspec = jax.tree_util.tree_map(lambda _: P(), state0)

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
                    new_state, jax.lax.pmean(loss, DATA_AXIS))
    else:
        sspec = su.state_pspecs(params0, n_dev)
        init_s = jax.jit(shard_map(lambda p: su.init(p), mesh=mesh,
                                   in_specs=(pspec,), out_specs=sspec))

        def body(params, state, tokens):
            loss, grads = grads_of(params, tokens)
            params, state = su.step(state, grads, params)
            return params, state, jax.lax.pmean(loss, DATA_AXIS)

    # async overlap enabler (parallel.overlap): donate the carry so XLA
    # can retire each bucket's pre-reduction buffer in place and
    # schedule the per-bucket collectives against remaining backward
    # compute without doubling live HBM.  TPU only — the CPU backend
    # ignores donation (with a warning per buffer), and the CPU-mesh
    # A/B tests reuse the un-donated carry across calls.
    jit_kw = {}
    if jax.default_backend() == "tpu":
        jit_kw["donate_argnums"] = (0, 1)
    step_sm = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(pspec, sspec, P(DATA_AXIS)),
        out_specs=(pspec, sspec, P())), **jit_kw)
    state0 = opt.init(params0) if su is None else init_s(params0)

    def step(carry, tokens):
        params, state = carry
        params, state, loss = step_sm(params, state, tokens)
        return (params, state), loss

    return (params0, state0), step


# ---------------------------------------------------------------------------
# rendering / CLI
# ---------------------------------------------------------------------------

def _human_bytes(n) -> str:
    from ..telemetry.memory import _human
    return _human(n, "B")


def format_plans(plans: Sequence[Plan], *, chips: Optional[int] = None,
                 top: int = 12) -> str:
    """The ranked plan table: predicted ms (+ breakdown), HBM/replica,
    knob summary."""
    head = "auto-parallel plans"
    if chips:
        head += f" @ {chips} chips"
    lines = [
        head,
        f"{'rank':<5}{'pred ms':>9} {'HBM/replica':>12}  "
        f"{'comm ms (dp/tp/sp)':>20}  plan",
    ]
    for i, p in enumerate(plans[:top]):
        b = p.breakdown or {}
        comm = (f"{b.get('dp_comm_ms', 0.0):.2f}/"
                f"{b.get('tp_comm_ms', 0.0):.2f}/"
                f"{b.get('sp_comm_ms', 0.0):.2f}")
        lines.append(
            f"{i:<5}{p.predicted_step_ms:>9.3f} "
            f"{_human_bytes(p.predicted_hbm_bytes):>12}  {comm:>20}  "
            f"{p.describe() or 'all-defaults'}")
    if len(plans) > top:
        lines.append(f"... {len(plans) - top} more feasible plans")
    if plans:
        lines.append(f"winner knobs: {plans[0].knobs()}")
    return "\n".join(lines)


def _main(argv=None):   # pragma: no cover - exercised via CLI test
    import argparse

    ap = argparse.ArgumentParser(
        description="Auto-parallel planner: ranked plan table from a "
                    "fresh cost-model run.")
    ap.add_argument("--chips", type=int, default=None,
                    help="device count to plan for (default: visible "
                         "devices)")
    ap.add_argument("--model", default="flagship",
                    help="model to profile (flagship = the BERT-large "
                         "transformer, scaled down off-TPU)")
    ap.add_argument("--layers", type=int)
    ap.add_argument("--batch", type=int, help="GLOBAL batch")
    ap.add_argument("--seq", type=int)
    ap.add_argument("--capacity-gb", type=float,
                    help="override the HBM capacity the feasibility "
                         "check prunes against")
    ap.add_argument("--top", type=int, default=12)
    args = ap.parse_args(argv)

    if args.model != "flagship":
        ap.error(f"unknown model {args.model!r} (only 'flagship')")
    import jax
    from ..pyprof.prof import ceilings_row
    chips = args.chips or len(jax.devices())
    overrides = {}
    if args.layers:
        overrides["num_layers"] = args.layers
    if args.seq:
        overrides["max_len"] = args.seq
    prof, cfg, gb = flagship_profile(global_batch=args.batch, **overrides)
    cap = int(args.capacity_gb * 1e9) if args.capacity_gb else None
    ranked = search(prof, chips, platform=ceilings_row(),
                    capacity_bytes=cap)
    n_all = len(enumerate_plans(prof, chips,
                                platform=ceilings_row()))
    print(f"profiled {prof.name} (global batch {gb}, seq {cfg.max_len}) "
          f"on {prof.platform}: {prof.flops / 1e9:.2f} GFLOP/step, "
          f"peak {_human_bytes(prof.peak_hbm_bytes)}")
    print(f"{n_all} candidates, {len(ranked)} HBM-feasible")
    print(format_plans(ranked, chips=chips, top=args.top))
    return 0


if __name__ == "__main__":   # pragma: no cover
    from ..utils.platform import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(_main())
