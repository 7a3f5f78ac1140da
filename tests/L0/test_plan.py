"""Auto-parallel planner (ISSUE 10) on the 8-device CPU mesh.

Covers the tentpole and its acceptance gates:

  * cost-model oracles pinned on hand-computable cases (2-chip ring
    allreduce alpha-beta time; a known-FLOPs matmul's roofline);
  * the search: >= 12 candidates enumerated for the flagship at 8
    simulated chips, every HBM-infeasible plan pruned (asserted
    against ``memory_model()``'s numbers), ties broken toward the
    simpler plan;
  * ``Plan.apply()`` reproducing the BITWISE-identical loss/params of
    the same manually-configured run (mesh + env knobs vs explicit
    args);
  * the ranked-table CLI (``python -m apex_tpu.parallel.plan``) from
    a fresh CPU cost-model run.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.parallel import plan as pm
from apex_tpu.parallel import collectives
from apex_tpu.parallel import weight_update as wu
from apex_tpu.parallel.mesh import create_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
N_DEV = 8

#: explicit ceilings for the oracle tests — no env / platform coupling
CEIL = {"peak_flops": 1e12, "peak_bw": 1e11, "ici_bw": 1e10,
        "ici_alpha_s": 1e-6, "hbm_bytes": 1e12}


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


@pytest.fixture(scope="module")
def flagship():
    """(profile, cfg, global_batch, memory_model dict) for the tiny
    flagship step — the memory_model() is recomputed independently so
    the pruning assertions are against ITS numbers, not the profile's
    copy of them."""
    from apex_tpu.telemetry import memory as tmem
    cfg = pm._flagship_cfg(False)
    step, args = pm._flagship_step(cfg, 8)
    prof = pm.profile_step(step, *args, name="flagship-test", cfg=cfg,
                           global_batch=8)
    mm = tmem.memory_model(step, *args, register=False)
    return prof, cfg, 8, mm


def _synth_profile(**kw):
    base = dict(name="synth", flops=1e9, bytes_accessed=1e8,
                params_bytes=4096, optimizer_bytes=12288,
                activations_bytes=8192, batch_bytes=1024,
                temps_bytes=512, output_bytes=64, args_bytes=16,
                constants_bytes=8, peak_hbm_bytes=30000,
                layers=2, act_layer_bytes=4096, seq=64, heads=4,
                platform="cpu")
    base.update(kw)
    return pm.ModelProfile(**base)


# ---------------------------------------------------------------------------
# cost-model oracles
# ---------------------------------------------------------------------------

def test_collective_time_oracle_2chip_ring_allreduce():
    """Hand-computed 2-chip ring allreduce: 2(N-1) hops of alpha +
    2(N-1)/N of the payload over the link."""
    logical = 4 * (1 << 20)            # 1M fp32 elems
    t = pm.collective_time_s("all_reduce", logical, 2, CEIL)
    assert t == pytest.approx(2 * 1e-6 + 1.0 * logical / 1e10)
    # reduce-scatter / allgather: half the hops, half the traffic
    t_rs = pm.collective_time_s("reduce_scatter", logical, 2, CEIL)
    assert t_rs == pytest.approx(1e-6 + 0.5 * logical / 1e10)
    assert pm.collective_time_s("all_gather", logical, 2, CEIL) == t_rs
    # degenerate axes cost nothing
    assert pm.collective_time_s("all_reduce", logical, 1, CEIL) == 0.0
    assert pm.collective_time_s("all_reduce", 0, 8, CEIL) == 0.0
    with pytest.raises(ValueError, match="unknown collective"):
        pm.collective_time_s("gossip", logical, 2, CEIL)


def test_collective_time_scheme_wire_and_codec():
    """int8_blockscale ships the metered wire bytes (codes + scales)
    and pays its dequant-sum codec against HBM bandwidth — so it wins
    on slow wires and loses when the wire is as fast as memory."""
    logical = 4 * (1 << 20)
    nelems = logical // 4
    world = 8
    wire = collectives.wire_bytes("int8_blockscale", nelems)
    expected = (2 * (world - 1) * CEIL["ici_alpha_s"]
                + 2.0 * (world - 1) / world * wire / CEIL["ici_bw"]
                + (1 + world) * logical / CEIL["peak_bw"])
    t8 = pm.collective_time_s("all_reduce", logical, world, CEIL,
                              "int8_blockscale")
    assert t8 == pytest.approx(expected)
    t32 = pm.collective_time_s("all_reduce", logical, world, CEIL)
    assert t8 < t32                    # wire 10x slower than HBM: wins
    fast_wire = dict(CEIL, ici_bw=CEIL["peak_bw"])
    assert pm.collective_time_s(
        "all_reduce", logical, world, fast_wire, "int8_blockscale") > \
        pm.collective_time_s("all_reduce", logical, world, fast_wire)


def test_compute_time_known_flops_matmul():
    """The parse->model chain on a known workload: a 64x64x64 matmul is
    exactly 2*M*N*K FLOPs, and the compute-bound roofline time is
    flops/peak."""
    a = jnp.ones((64, 64), jnp.float32)
    prof = pm.profile_step(lambda x, y: x @ y, a, a, name="matmul")
    assert prof.flops == pytest.approx(2 * 64 ** 3, rel=0.01)
    t = pm.compute_time_s(prof.flops, 0.0, CEIL)
    assert t == pytest.approx(prof.flops / CEIL["peak_flops"])
    # bandwidth-bound when bytes dominate
    assert pm.compute_time_s(0.0, 1e9, CEIL) == pytest.approx(1e9 / 1e11)


def test_profile_step_surfaces_compiled_collectives():
    """The profile carries the compiled program's real collective
    payloads (the attrib sub-table) for comm-model calibration."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    mesh = create_mesh({"data": N_DEV})
    sm = shard_map(lambda x: jax.lax.psum(x, "data"), mesh=mesh,
                   in_specs=(P("data"),), out_specs=P("data"))
    prof = pm.profile_step(sm, jnp.ones((N_DEV, 1024)), name="psum")
    ar = prof.collective_bytes["all-reduce"]
    assert ar["logical_bytes"] == 1024 * 4


# ---------------------------------------------------------------------------
# HBM model + search
# ---------------------------------------------------------------------------

def test_hbm_scaling_semantics():
    """Per-class scaling: tp shards params+optimizer, dp shards the
    optimizer ONLY when the update is sharded (the
    ``update_sharding_world`` semantics), activations/temps shard over
    every axis, batch over dp."""
    prof = _synth_profile()
    total, by = pm.plan_hbm_bytes(prof, pm.Plan(dp=8))
    assert by["params"] == 4096            # replicated over dp
    assert by["optimizer"] == 12288        # replicated: update is not sharded
    assert by["activations"] == 8192 // 8
    assert by["batch"] == 1024 // 8
    assert total == sum(by.values())
    _, by_z = pm.plan_hbm_bytes(prof, pm.Plan(dp=8,
                                              update_sharding="zero1"))
    assert by_z["optimizer"] == 12288 // 8
    _, by_tp = pm.plan_hbm_bytes(prof, pm.Plan(dp=4, tp=2))
    assert by_tp["params"] == 4096 // 2
    assert by_tp["optimizer"] == 12288 // 2
    assert by_tp["activations"] == 8192 // 8


def test_enumerate_flagship_8chips_ge_12_candidates(flagship):
    """ACCEPTANCE: the flagship at 8 simulated chips enumerates >= 12
    candidate plans spanning the axes."""
    prof, _, _, _ = flagship
    plans = pm.enumerate_plans(prof, N_DEV, platform="cpu")
    assert len(plans) >= 12
    assert all(p.chips == N_DEV for p in plans)
    assert any(p.tp > 1 for p in plans)                 # dp x tp plane
    assert any(p.zero for p in plans)                   # ZeRO on/off
    assert any(p.update_sharding == "zero1" for p in plans)
    schemes = {p.collective_scheme for p in plans if p.dp > 1}
    assert schemes == set(pm.PLAN_SCHEMES)
    # short sequences enumerate no SP plans ...
    assert all(p.sp == 1 for p in plans)
    # ... long sequences do (ring always; ulysses when heads divide)
    long = _synth_profile(seq=4096, heads=8)
    sp_plans = [p for p in pm.enumerate_plans(long, N_DEV,
                                              platform="cpu")
                if p.sp > 1]
    assert {p.sp_strategy for p in sp_plans} == {"ring", "ulysses"}


def test_search_prunes_all_infeasible_against_memory_model(flagship):
    """Property: ``search`` NEVER returns an HBM-infeasible plan.  The
    capacity is squeezed until some candidates are infeasible, and
    feasibility is recomputed here from ``memory_model()``'s own
    numbers — not trusted from the search."""
    prof, _, _, mm = flagship
    # the profile's memory facts ARE memory_model()'s (no drift)
    assert prof.params_bytes == mm["params_bytes"]
    assert prof.optimizer_bytes == mm["optimizer_bytes"]
    assert prof.activations_bytes == mm["activations_bytes"]
    all_plans = pm.enumerate_plans(prof, N_DEV, platform="cpu")
    demands = sorted(p.predicted_hbm_bytes for p in all_plans)
    cap = demands[len(demands) // 2]       # median: some must be pruned
    ranked = pm.search(prof, N_DEV, platform="cpu", capacity_bytes=cap)
    assert ranked and len(ranked) < len(all_plans)

    def hbm_from_memory_model(p):
        pp, ep = p.pp_stages, p.ep
        opt_div = p.tp * pp * (p.dp if p.shards_update else 1)
        total = (mm["params_bytes"] // (p.tp * pp)
                 + mm["optimizer_bytes"] // opt_div
                 + mm["activations_bytes"] // (p.dp * p.tp * p.sp * pp * ep)
                 + mm["batch_bytes"] // (p.dp * p.sp * ep)
                 + mm["temps_bytes"] // (p.dp * p.tp * p.sp * ep)
                 + mm["output_bytes"] // (p.dp * ep)
                 + mm["args_bytes"] + mm["constants_bytes"])
        if pp > 1:        # GPipe stash: one block/tick + M output slots
            m = max(int(p.pp_microbatches), 1)
            total += (m + pp - 1 + m) * (
                prof.act_layer_bytes // max(p.dp * m, 1))
        if ep > 1:        # dispatch/combine one-hots + a2a queues, f32
            e, cap_, d, t_loc = pm._ep_geometry(prof, p.dp, ep, p.sp)
            total += 4 * (2 * t_loc * e * cap_ + 2 * e * cap_ * d)
        return total

    for p in ranked:
        assert hbm_from_memory_model(p) <= cap, p.describe()
    assert any(hbm_from_memory_model(p) > cap for p in all_plans)


def test_tie_break_prefers_simpler_plan():
    """Predictions inside the tie band resolve to the SIMPLEST plan:
    with negligible params (no wire, no update to shrink) every dp=8
    variant predicts the same, and the all-defaults baseline must rank
    first."""
    prof = _synth_profile(params_bytes=512, optimizer_bytes=1536,
                          layers=0)
    ranked = pm.search(prof, N_DEV, ceilings=CEIL)
    assert ranked[0].knobs() == pm.default_plan(N_DEV).knobs()


def test_int8_wins_on_tpu_wire_loses_on_cpu(flagship):
    """The codec model makes compression platform-aware: on TPU
    ceilings (ICI far slower than HBM) the int8 dp wire beats fp32; on
    the CPU-emulated mesh (wire ~ memory) it loses."""
    prof, _, _, _ = flagship

    def dp_comm(platform, scheme):
        p = pm.predict(prof, pm.Plan(dp=N_DEV,
                                     collective_scheme=scheme),
                       platform=platform)
        return p.breakdown["dp_comm_ms"]

    assert dp_comm("tpu_v5e", "int8_blockscale") < dp_comm("tpu_v5e", "fp32")
    assert dp_comm("cpu", "int8_blockscale") > dp_comm("cpu", "fp32")


# ---------------------------------------------------------------------------
# pp / ep families (ISSUE 17)
# ---------------------------------------------------------------------------

def test_enumerate_pp_ep_candidates(flagship):
    """ACCEPTANCE: the flagship at 8 chips enumerates >= 2 pp and >= 2
    ep candidates, and every structural constraint holds: stages
    divide the layer stack, M divides the per-replica batch, the ep
    width divides the expert count, both compose with dp only, and
    both run the plain fused-flat update (no zero/zero1 variants — the
    engine cannot run them)."""
    prof, _, _, _ = flagship
    plans = pm.enumerate_plans(prof, N_DEV, platform="cpu")
    pps = [p for p in plans if p.pp_stages > 1]
    eps = [p for p in plans if p.ep > 1]
    assert len(pps) >= 2 and len(eps) >= 2
    for p in pps:
        assert prof.layers % p.pp_stages == 0
        assert (prof.global_batch // p.dp) % p.pp_microbatches == 0
        assert p.tp == p.sp == p.ep == 1
        assert not p.zero and p.update_sharding == "off"
        assert p.family == "pp" and p.measurable
    for p in eps:
        e_total = prof.experts or pm.EP_DEFAULT_EXPERTS
        assert e_total % p.ep == 0
        assert p.tp == p.sp == p.pp_stages == 1
        assert not p.zero and p.update_sharding == "off"
        assert p.family == "ep" and p.measurable
    # the microbatch lattice actually varies — the bubble knob is
    # searched, not pinned
    assert len({p.pp_microbatches for p in pps}) >= 2
    # knob rendering for tables/logs
    assert pm.Plan(dp=4, pp_stages=2,
                   pp_microbatches=2).describe() == "dp=4 pp=2x2"
    assert pm.Plan(dp=4, ep=2).describe() == "dp=4 ep=2"


def test_pp_cost_model_bubble_and_wire_oracle():
    """GPipe oracle: the bubble charges ``t_train * (S-1)/M`` on the
    critical path (shrinking as M grows) and the wire charges
    ``2(M+S-1)`` stage-hop ppermutes of one microbatch activation
    block; dense plans charge nothing."""
    prof = _synth_profile(global_batch=8)
    p = pm.predict(prof, pm.Plan(dp=4, pp_stages=2, pp_microbatches=2),
                   ceilings=CEIL)
    bd = p.breakdown
    assert bd["pp_bubble_ms"] == pytest.approx(bd["train_ms"] / 2)
    blk = prof.act_layer_bytes / (4 * 2)
    want_s = 2 * (2 + 2 - 1) * pm.collective_time_s("ppermute", blk, 2,
                                                    CEIL)
    assert bd["pp_comm_ms"] == pytest.approx(want_s * 1e3)
    p1 = pm.predict(prof, pm.Plan(dp=4, pp_stages=2, pp_microbatches=1),
                    ceilings=CEIL)
    assert p1.breakdown["pp_bubble_ms"] > bd["pp_bubble_ms"]
    dense = pm.predict(prof, pm.Plan(dp=8), ceilings=CEIL).breakdown
    assert dense["pp_bubble_ms"] == dense["pp_comm_ms"] == 0.0


def test_ep_cost_model_capacity_wire_and_hlo_subtable():
    """ep oracle: the router wire charges 4 capacity-factored
    all_to_alls per layer (the owner-major ``(E*C, D)`` queue both
    ways, forward + the mirrored backward); a compiled-HLO all-to-all
    sub-table, when the profile carries one, overrides the analytic
    formula (measured bytes beat modeled bytes)."""
    prof = _synth_profile(global_batch=8, experts=8)
    p = pm.predict(prof, pm.Plan(dp=4, ep=2), ceilings=CEIL)
    e, cap, d_model, _ = pm._ep_geometry(prof, 4, 2)
    a2a = 4.0 * e * cap * d_model
    want_s = 4 * prof.layers * pm.collective_time_s("all_to_all", a2a,
                                                    2, CEIL)
    assert p.breakdown["ep_comm_ms"] == pytest.approx(want_s * 1e3)
    prof2 = _synth_profile(global_batch=8, experts=8, collective_bytes={
        "all-to-all": {"logical_bytes": 1 << 20, "count": 4}})
    p2 = pm.predict(prof2, pm.Plan(dp=4, ep=2), ceilings=CEIL)
    want2_s = 2 * 4 * pm.collective_time_s("all_to_all", (1 << 20) / 4,
                                           2, CEIL)
    assert p2.breakdown["ep_comm_ms"] == pytest.approx(want2_s * 1e3)
    dense = pm.predict(prof, pm.Plan(dp=8), ceilings=CEIL)
    assert dense.breakdown["ep_comm_ms"] == 0.0


def test_hbm_charges_pp_stash_and_ep_buffers():
    """The HBM model charges pp its schedule stash (``(ticks + M)``
    microbatch activation blocks) and ep its expert-capacity buffers
    (dispatch/combine one-hots + both all_to_all queues, fp32); dense
    plans carry neither class; params shard over the stage axis."""
    prof = _synth_profile(global_batch=8, experts=8)
    _, by_pp = pm.plan_hbm_bytes(
        prof, pm.Plan(dp=4, pp_stages=2, pp_microbatches=2))
    ticks = 2 + 2 - 1
    blk = prof.act_layer_bytes // (4 * 2)
    assert by_pp["pp_stash"] == (ticks + 2) * blk
    assert by_pp["params"] == prof.params_bytes // 2
    _, by_ep = pm.plan_hbm_bytes(prof, pm.Plan(dp=4, ep=2))
    e, cap, d_model, t_local = pm._ep_geometry(prof, 4, 2)
    assert by_ep["ep_buffers"] == 4 * (2 * t_local * e * cap
                                       + 2 * e * cap * d_model)
    _, by_d = pm.plan_hbm_bytes(prof, pm.Plan(dp=8))
    assert "pp_stash" not in by_d and "ep_buffers" not in by_d


def test_search_prunes_infeasible_pp_ep(flagship):
    """The never-returns-infeasible property holds with pp/ep in the
    space: squeeze the capacity to the pp/ep demand median and every
    ranked plan — its HBM recomputed incl. the GPipe stash / expert
    buffers — still fits."""
    prof, _, _, _ = flagship
    all_plans = pm.enumerate_plans(prof, N_DEV, platform="cpu")
    ppep = [p for p in all_plans if p.pp_stages > 1 or p.ep > 1]
    assert ppep
    demands = sorted(p.predicted_hbm_bytes for p in ppep)
    assert demands[0] < demands[-1]    # the squeeze can discriminate
    cap = (demands[0] + demands[-1]) // 2
    ranked = pm.search(prof, N_DEV, platform="cpu", capacity_bytes=cap)
    assert ranked
    for p in ranked:
        total, by = pm.plan_hbm_bytes(prof, p)
        assert total <= cap, p.describe()
        if p.pp_stages > 1:
            assert "pp_stash" in by
        if p.ep > 1:
            assert "ep_buffers" in by
    assert any(p.predicted_hbm_bytes > cap for p in ppep)


# ---------------------------------------------------------------------------
# Plan.apply: env round-trip + the bitwise A/B
# ---------------------------------------------------------------------------

def test_apply_env_roundtrip(monkeypatch):
    """apply() engages exactly the plan's env knobs inside the context,
    masks conflicting ambient knobs, and restores everything after."""
    monkeypatch.setenv(collectives.ENV_KNOB, "bf16")   # ambient A/B var
    plan = pm.Plan(dp=N_DEV, update_sharding="zero1")
    with plan.apply() as mesh:
        assert dict(mesh.shape)["data"] == N_DEV
        assert os.environ.get(wu.ENV_KNOB) == "zero1"
        # the plan's fp32 wire means NO collectives knob — the ambient
        # one must not leak into the applied plan
        assert collectives.ENV_KNOB not in os.environ
    assert os.environ.get(collectives.ENV_KNOB) == "bf16"   # restored
    assert wu.ENV_KNOB not in os.environ
    plan8 = pm.Plan(dp=N_DEV, collective_scheme="int8_blockscale")
    with plan8.apply():
        assert os.environ[collectives.ENV_KNOB] == "int8_blockscale"
    assert os.environ.get(collectives.ENV_KNOB) == "bf16"


def _ab_cfg():
    return pm._flagship_cfg(False, num_layers=1, d_model=32, d_ff=64,
                            vocab_size=64, max_len=16, num_heads=2)


def _ab_batch(i):
    rng = np.random.RandomState(1000 + i)
    return jnp.asarray(rng.randint(0, 64, (N_DEV, 16)).astype("int32"))


@pytest.mark.parametrize("ddp_kwargs", [
    {}, {"update_sharding": "zero1"},
], ids=["all-defaults", "zero1"])
def test_apply_reproduces_manual_run_bitwise(ddp_kwargs):
    """ACCEPTANCE: training under ``plan.apply()`` (mesh + env knobs,
    knob-less DDP inside) is BITWISE the same run configured by hand
    (explicit mesh + explicit DDP args) — losses and params."""
    cfg = _ab_cfg()

    def run_manual():
        mesh = create_mesh({"data": N_DEV})
        carry, step = pm.build_flagship_step(cfg, mesh, global_batch=8,
                                             ddp_kwargs=ddp_kwargs)
        losses = []
        for i in range(3):
            carry, loss = step(carry, _ab_batch(i))
            losses.append(float(loss))
        return carry, losses

    def run_plan():
        plan = pm.Plan(dp=N_DEV,
                       update_sharding=ddp_kwargs.get("update_sharding",
                                                      "off"))
        with plan.apply() as mesh:
            carry, step = pm.build_flagship_step(cfg, mesh,
                                                 global_batch=8)
            losses = []
            for i in range(3):
                carry, loss = step(carry, _ab_batch(i))
                losses.append(float(loss))
        return carry, losses

    (pm_, _), lm = run_manual()
    (pp_, _), lp = run_plan()
    assert lm == lp
    assert lm[-1] < lm[0]              # training actually happened
    for (kp_a, a), (kp_b, b) in zip(
            jax.tree_util.tree_leaves_with_path(pm_),
            jax.tree_util.tree_leaves_with_path(pp_)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=str(kp_a))


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_renders_fresh_run():
    """``python -m apex_tpu.parallel.plan`` renders the ranked table
    from a fresh CPU cost-model run."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT}
    r = subprocess.run(
        [sys.executable, "-m", "apex_tpu.parallel.plan",
         "--chips", "8", "--model", "flagship",
         "--layers", "1", "--seq", "16", "--batch", "8"],
        capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr
    assert "HBM-feasible" in r.stdout
    assert "winner knobs" in r.stdout
