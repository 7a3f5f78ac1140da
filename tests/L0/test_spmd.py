"""GSPMD step engine (ISSUE 12) on the 8-device CPU mesh.

Covers the tentpole and its acceptance gates:

  * THE tp acceptance: a dp=4 x tp=2 ``Plan.apply()`` step trains the
    flagship transformer 6 steps to fp32-tolerance loss vs the dp=8
    baseline, with ``tp.psum`` wire bytes metered and MATCHING the
    compiled-HLO collectives sub-table;
  * sp (ring + ulysses), contrib-ZeRO, and GSPMD-zero1 plans all train
    to the same losses — ``Plan.measurable`` is True across the space;
  * the fused-flat state is genuinely sharded under the GSPMD engine
    (per-device shard = total / flat_world, whole 128-lanes);
  * amp O-level master weights: bf16 model copy over the fp32 master;
  * typed ``SequenceShardingError`` for heads/seq divisibility;
  * the multi-slice DCN alpha-beta terms and the ``@artifact``
    ceilings-calibration hook.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.models import TransformerConfig
from apex_tpu.parallel import collectives
from apex_tpu.parallel import plan as pm
from apex_tpu.parallel import sequence as seqmod
from apex_tpu.parallel import spmd
from apex_tpu.parallel import weight_update as wu

N_DEV = 8
GB = 8
CFG = pm._flagship_cfg(False)          # the tier-1 flagship stand-in
TINY = TransformerConfig(vocab_size=64, max_len=16, num_layers=1,
                         d_model=32, num_heads=2, d_ff=64,
                         xent_impl="xla")


@pytest.fixture(autouse=True)
def _clean_env():
    saved = {k: os.environ.pop(k, None)
             for k in (collectives.ENV_KNOB, wu.ENV_KNOB,
                       "APEX_TPU_CEILINGS")}
    yield
    for k, v in saved.items():
        os.environ.pop(k, None)
        if v is not None:
            os.environ[k] = v


def _tokens(cfg=CFG, gb=GB, seed=0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randint(
        0, cfg.vocab_size, (gb, cfg.max_len)).astype("int32"))


def _run(plan, steps=6, cfg=CFG, gb=GB, meter=False, **kw):
    toks = _tokens(cfg, gb)
    with plan.apply() as mesh:
        carry, step, info = spmd.build_plan_step(
            cfg, mesh, plan, global_batch=gb, meter=meter, **kw)
        losses = []
        for _ in range(steps):
            carry, loss = step(carry, toks)
            losses.append(float(loss))
    return losses, carry, info


@pytest.fixture(scope="module")
def baseline6():
    """The dp=8 all-defaults 6-step loss trajectory every family is
    measured against."""
    losses, _, _ = _run(pm.Plan(dp=N_DEV))
    return losses


def _assert_fp32_tolerance(losses, baseline):
    """fp32-tolerance loss parity: the engines change only collective
    *placement*/reduction order, so per-step losses track within the
    accumulated fp32 reassociation drift (loosest at the late, tiny
    losses)."""
    assert losses[-1] < losses[0]                       # actually trains
    for i, (a, b) in enumerate(zip(losses, baseline)):
        assert abs(a - b) <= max(2e-2 * abs(b), 5e-3), \
            f"step {i}: {a} vs baseline {b}"


# ---------------------------------------------------------------------------
# THE tp acceptance: dp4 x tp2 vs dp8, 6 steps, metered == compiled
# ---------------------------------------------------------------------------

def test_dp4_tp2_trains_to_fp32_tolerance_with_metered_psum(baseline6):
    from apex_tpu import telemetry
    from apex_tpu.telemetry import events as tel_events

    sink = telemetry.MemorySink()
    reg = telemetry.Registry(sink=sink, flush_interval=0,
                             rank0_only=False, run_id="t", memory=False)
    prev = tel_events.set_default(reg)
    try:
        losses, carry, info = _run(pm.Plan(dp=4, tp=2), meter=True)
    finally:
        tel_events.set_default(prev)
    _assert_fp32_tolerance(losses, baseline6)

    # the engine's tp.psum meter must MATCH the compiled-HLO
    # collectives sub-table (same numbers, two independent readers)
    sub = info["collectives"]
    assert "all-reduce" in sub and sub["all-reduce"]["logical_bytes"] > 0
    vals = reg.read()
    assert vals["tp.psum_bytes"] == int(sub["all-reduce"]["logical_bytes"])
    assert vals["tp.psum_compressed_bytes"] == \
        int(sub["all-reduce"]["logical_bytes"])
    assert vals["tp.psum_calls"] == 1      # one meter record per build
    assert info["metered"]["all-reduce"] == sub["all-reduce"]
    # and the summary folds the new family into the collective line
    reg.flush()
    from apex_tpu.telemetry import report as treport
    s = treport.summarize(sink.records)
    assert s["collective_bytes"] >= vals["tp.psum_bytes"]


def test_gspmd_flat_state_is_actually_sharded():
    """The fused-flat master/moment buffers are physically 1/flat_world
    per device, on whole 128-lanes (the chunk-lattice pin)."""
    from apex_tpu.multi_tensor_apply.flattener import LANE
    plan = pm.Plan(dp=4, tp=2, update_sharding="zero1")
    with plan.apply() as mesh:
        carry, step, info = spmd.build_plan_step(
            CFG, mesh, plan, global_batch=GB, meter=False)
        assert info["flat_world"] == 8
        master = carry.master
        total = master.shape[0]
        assert total % (LANE * 8) == 0
        shard_shapes = {s.data.shape for s in
                        master.addressable_shards}
        assert shard_shapes == {(total // 8,)}
        carry, loss = step(carry, _tokens())
        assert {s.data.shape for s in carry.master.addressable_shards} \
            == {(total // 8,)}
    assert np.isfinite(float(loss))


# ---------------------------------------------------------------------------
# the other families train to the same losses
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("plan", [
    pm.Plan(dp=4, sp=2, sp_strategy="ring"),
    pm.Plan(dp=2, sp=4, sp_strategy="ulysses"),
    pm.Plan(dp=8, zero=True),
], ids=["sp-ring", "sp-ulysses", "zero"])
def test_family_trains_to_fp32_tolerance(plan, baseline6):
    losses, _, info = _run(plan, steps=6)
    _assert_fp32_tolerance(losses, baseline6)
    assert info["family"] == plan.family


def test_sp_engine_meters_static_schedule():
    """The sp wire is metered from the engine's static schedule (the
    layer scan hides ring/ulysses collectives from the compiled-HLO
    entry walk): ulysses = 8 all_to_alls/layer of one local block,
    ring = 4*n ppermutes/layer."""
    from apex_tpu import telemetry
    from apex_tpu.telemetry import events as tel_events
    sink = telemetry.MemorySink()
    reg = telemetry.Registry(sink=sink, flush_interval=0,
                             rank0_only=False, run_id="t", memory=False)
    prev = tel_events.set_default(reg)
    try:
        _, _, info = _run(pm.Plan(dp=2, sp=4, sp_strategy="ulysses"),
                          steps=1, meter=True)
        _, _, info_r = _run(pm.Plan(dp=4, sp=2, sp_strategy="ring"),
                            steps=1, meter=True)
    finally:
        tel_events.set_default(prev)
    blk = (8 // 2) * CFG.num_heads * (CFG.max_len // 4) \
        * CFG.head_dim * 4
    assert info["sp_wire"]["op"] == "all_to_all"
    assert info["sp_wire"]["logical_bytes"] == \
        8 * CFG.num_layers * blk
    blk_r = (8 // 4) * CFG.num_heads * (CFG.max_len // 2) \
        * CFG.head_dim * 4
    assert info_r["sp_wire"]["logical_bytes"] == \
        4 * CFG.num_layers * 2 * blk_r
    vals = reg.read()
    assert vals["sp.all_to_all_bytes"] == info["sp_wire"]["logical_bytes"]
    assert vals["sp.ppermute_bytes"] == info_r["sp_wire"]["logical_bytes"]


def test_dp4_pp2_trains_to_fp32_tolerance_with_metered_ppermute(baseline6):
    """ACCEPTANCE (ISSUE 17): the GPipe engine (dp=4 x pp=2, M=2)
    trains the flagship 6 steps to fp32-tolerance vs the dp=8
    baseline, with the ``pp.ppermute`` wire metered from the engine's
    exact static schedule (the fori_loop hides the hops from the
    compiled-HLO entry walk, like the sp ring)."""
    from apex_tpu import telemetry
    from apex_tpu.telemetry import events as tel_events
    sink = telemetry.MemorySink()
    reg = telemetry.Registry(sink=sink, flush_interval=0,
                             rank0_only=False, run_id="t", memory=False)
    prev = tel_events.set_default(reg)
    try:
        losses, _, info = _run(
            pm.Plan(dp=4, pp_stages=2, pp_microbatches=2), meter=True)
    finally:
        tel_events.set_default(prev)
    _assert_fp32_tolerance(losses, baseline6)
    assert info["engine"] == "shard_map.pp"
    assert info["stages_layers"] == CFG.num_layers // 2
    assert info["pipeline_bubble_fraction"] == pytest.approx(1 / 3)
    # the static schedule: (M + S - 1) ticks, each hopping one
    # microbatch activation block, and the backward mirrors every hop
    esize = jnp.dtype(CFG.dtype).itemsize
    blk = (GB // 4 // 2) * CFG.max_len * CFG.d_model * esize
    sched = info["pp_wire"]
    assert sched["op"] == "ppermute"
    assert sched["ticks"] == 2 + 2 - 1
    assert sched["per_tick_block_bytes"] == blk
    assert sched["logical_bytes"] == 2 * 3 * blk
    vals = reg.read()
    assert vals["pp.ppermute_bytes"] == sched["logical_bytes"]


def test_dp4_ep2_loss_parity_vs_dp_moe_twin_with_metered_a2a():
    """ACCEPTANCE (ISSUE 17): the switch-MoE engine (dp=4 x ep=2)
    holds per-step loss parity vs the dp-MoE twin — the SAME engine on
    a data-only mesh (full expert set per device, no exchange), the
    identical per-token function — and the compiled ``ep.all_to_all``
    payload equals the static capacity-factored schedule (two
    independent readers of the same wire)."""
    from apex_tpu import telemetry
    from apex_tpu.telemetry import events as tel_events

    def run_twin():
        plan = pm.Plan(dp=N_DEV)
        toks = _tokens()
        with plan.apply() as mesh:
            carry, step, _ = spmd._build_ep_step(
                CFG, mesh, plan, GB, 1e-2, False)
            losses = []
            for _ in range(6):
                carry, loss = step(carry, toks)
                losses.append(float(loss))
        return losses

    sink = telemetry.MemorySink()
    reg = telemetry.Registry(sink=sink, flush_interval=0,
                             rank0_only=False, run_id="t", memory=False)
    prev = tel_events.set_default(reg)
    try:
        losses, _, info = _run(pm.Plan(dp=4, ep=2), meter=True)
    finally:
        tel_events.set_default(prev)
    _assert_fp32_tolerance(losses, run_twin())
    assert info["engine"] == "shard_map.ep"
    assert info["experts"] == pm.EP_DEFAULT_EXPERTS
    a2a = info["metered"]["all-to-all"]
    assert int(a2a["logical_bytes"]) == \
        int(info["ep_wire"]["logical_bytes"])
    vals = reg.read()
    assert vals["ep.all_to_all_bytes"] == int(a2a["logical_bytes"])


def test_amp_bf16_model_copy_over_fp32_master():
    """O2-style master weights through the GSPMD engine: bf16 model
    copy/activations, fp32 master stays authoritative and finite."""
    plan = pm.Plan(dp=4, tp=2)
    with plan.apply() as mesh:
        carry, step, _ = spmd.build_plan_step(
            CFG, mesh, plan, global_batch=GB, meter=False,
            amp_dtype="bfloat16")
        toks = _tokens()
        losses = []
        for _ in range(4):
            carry, loss = step(carry, toks)
            losses.append(float(loss))
    assert carry.master.dtype == jnp.float32
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


# ---------------------------------------------------------------------------
# plan-space surface: measurable everywhere, engine-aware enumeration
# ---------------------------------------------------------------------------

def test_measurable_true_across_families():
    for plan in (pm.Plan(dp=8), pm.Plan(dp=4, tp=2),
                 pm.Plan(dp=4, sp=2, sp_strategy="ring"),
                 pm.Plan(dp=8, zero=True),
                 pm.Plan(dp=4, tp=2, update_sharding="zero1")):
        assert plan.measurable, plan.describe()
    assert pm.Plan(dp=4, tp=2).family == "tp"
    assert pm.Plan(dp=8, zero=True).family == "zero"
    assert pm.Plan(dp=4, sp=2).family == "sp"
    assert pm.Plan(dp=8).family == "dp"


def test_enumeration_matches_engine_constraints():
    """tp plans carry fp32 wire only (GSPMD owns the collectives) and
    never contrib ZeRO; sp plans drop contrib ZeRO but keep the
    compressed dp wire (their dp reduction is the explicit DDP path)."""
    prof = pm.ModelProfile(
        name="synth", flops=1e9, bytes_accessed=1e8, params_bytes=4096,
        optimizer_bytes=12288, activations_bytes=8192, batch_bytes=1024,
        temps_bytes=512, output_bytes=64, peak_hbm_bytes=30000,
        layers=2, act_layer_bytes=4096, seq=4096, heads=8,
        platform="cpu")
    plans = pm.enumerate_plans(prof, N_DEV, platform="cpu", sp_min_seq=64)
    tp_plans = [p for p in plans if p.tp > 1]
    sp_plans = [p for p in plans if p.sp > 1]
    assert tp_plans and sp_plans
    assert all(p.collective_scheme == "fp32" for p in tp_plans)
    assert not any(p.zero for p in tp_plans)
    assert any(p.update_sharding == "zero1" for p in tp_plans)
    assert not any(p.zero for p in sp_plans)
    assert any(p.collective_scheme == "int8_blockscale" for p in sp_plans)
    assert all(p.measurable for p in plans)


# ---------------------------------------------------------------------------
# typed sequence-sharding errors (satellite)
# ---------------------------------------------------------------------------

def test_ulysses_head_divisibility_typed_error():
    with pytest.raises(seqmod.SequenceShardingError,
                       match=r"num_heads 2 does not divide over sp=4"):
        seqmod.validate_sp(16, 2, 4, "ulysses")
    with pytest.raises(seqmod.SequenceShardingError,
                       match=r"sequence length 15 does not chunk"):
        seqmod.validate_sp(15, 4, 4, "ring")
    seqmod.validate_sp(16, 2, 1, "ulysses")     # sp=1 always fine
    # and through the engine, before anything traces
    plan = pm.Plan(dp=2, sp=4, sp_strategy="ulysses")
    with plan.apply() as mesh:
        with pytest.raises(seqmod.SequenceShardingError,
                           match="num_heads"):
            spmd.build_plan_step(TINY, mesh, plan, global_batch=8,
                                 meter=False)


# ---------------------------------------------------------------------------
# multi-slice DCN terms + ceilings calibration hook
# ---------------------------------------------------------------------------

CEIL = {"peak_flops": 1e12, "peak_bw": 1e11, "ici_bw": 1e10,
        "ici_alpha_s": 1e-6, "hbm_bytes": 1e12,
        "dcn_bw": 1e9, "dcn_alpha_s": 1e-4}


def test_multislice_dcn_terms_oracle():
    """8-way allreduce over 2 slices = intra 4-ring on ICI + inter
    2-ring of 1/4 payload on DCN (hand-computed)."""
    logical = 4 * (1 << 20)
    flat = pm.collective_time_s("all_reduce", logical, 8, CEIL)
    two = pm.collective_time_s("all_reduce", logical, 8, CEIL, slices=2)
    intra = (2 * 3 * CEIL["ici_alpha_s"]
             + 2.0 * 3 / 4 * logical / CEIL["ici_bw"])
    inter = (2 * 1 * CEIL["dcn_alpha_s"]
             + 2.0 * 1 / 2 * (logical / 4) / CEIL["dcn_bw"])
    assert two == pytest.approx(intra + inter)
    assert two > flat          # the slow DCN tier costs more
    # slices that don't divide fall back to the flat model
    assert pm.collective_time_s("all_reduce", logical, 8, CEIL,
                                slices=3) == flat
    # and predict() charges the dp wire its DCN tier
    prof = pm.ModelProfile(
        name="s", flops=1e9, bytes_accessed=1e8, params_bytes=1 << 20,
        optimizer_bytes=3 << 20, activations_bytes=8192,
        batch_bytes=1024, temps_bytes=512, output_bytes=64,
        peak_hbm_bytes=1 << 22, platform="cpu")
    p1 = pm.predict(prof, pm.Plan(dp=8), ceilings=dict(CEIL))
    t1 = p1.breakdown["dp_comm_ms"]
    p2 = pm.predict(prof, pm.Plan(dp=8),
                    ceilings=dict(CEIL, num_slices=2))
    assert p2.breakdown["dp_comm_ms"] > t1
