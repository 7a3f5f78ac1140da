"""Measured-tuning profile (apex_tpu/utils/tuning.py) and the decision
engine that writes it (tools/apply_perf_results.py).

The round-5 close of the perf loop: on-chip bench JSONs -> profile of
measured winners -> every tunable default consults it.  These tests
drive the chain with synthetic TPU artifacts.
"""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from apex_tpu.utils import tuning


@pytest.fixture
def profile(tmp_path, monkeypatch):
    """Point the tuning profile at a temp file; restore after."""
    path = tmp_path / "tuned.json"

    def write(d):
        path.write_text(json.dumps(d))
        tuning.reload()

    monkeypatch.setenv("APEX_TPU_TUNING_FILE", str(path))
    tuning.reload()
    yield write
    monkeypatch.delenv("APEX_TPU_TUNING_FILE")
    tuning.reload()


def test_get_without_profile_returns_default(profile):
    assert tuning.get("flash_block_q") is None
    assert tuning.get("flash_block_q", 512) == 512


def test_get_reads_profile_and_reload(profile):
    profile({"flash_block_q": 256})
    assert tuning.get("flash_block_q", 512) == 256
    profile({"flash_block_q": 128})
    assert tuning.get("flash_block_q", 512) == 128


def test_corrupt_profile_is_ignored(tmp_path, monkeypatch):
    p = tmp_path / "bad.json"
    p.write_text("{not json")
    monkeypatch.setenv("APEX_TPU_TUNING_FILE", str(p))
    tuning.reload()
    assert tuning.get("anything", "fallback") == "fallback"
    monkeypatch.delenv("APEX_TPU_TUNING_FILE")
    tuning.reload()


@pytest.fixture
def fake_tpu(monkeypatch):
    """Profile values only apply on the TPU backend (get_on_tpu); fake
    it for the consumer tests — nothing here executes a kernel.
    get_on_tpu is also side-effect-free (returns the default when no
    backend is initialized yet), so initialize the CPU backend first."""
    import jax
    jax.devices()                      # ensure backends_initialized()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_profile_ignored_off_tpu(profile):
    """On the CPU backend (the real test env) measured values must NOT
    apply — they would route interpret-mode Pallas (code-review r5)."""
    from apex_tpu.contrib.multihead_attn.flash import (_clamp_blocks,
                                                      DEFAULT_BLOCK_Q)
    from apex_tpu.contrib.optimizers import DistributedFusedAdam
    from apex_tpu.models import bert_large_config
    profile({"flash_block_q": 128, "flash_block_k": 256,
             "zero_impl": "fused", "bert_attn_impl": "fast"})
    bq, _bk = _clamp_blocks(None, None, D=64, esz=2, bias_per_q=False,
                            sq=4096, sk=4096)
    assert bq == DEFAULT_BLOCK_Q
    assert DistributedFusedAdam(lr=1e-3).impl == "xla"
    assert bert_large_config(num_layers=2).attn_impl == "default"


def test_flash_clamp_consults_profile(profile, fake_tpu):
    from apex_tpu.contrib.multihead_attn.flash import _clamp_blocks
    profile({"flash_block_q": 128, "flash_block_k": 256})
    bq, bk = _clamp_blocks(None, None, D=64, esz=2, bias_per_q=False)
    assert (bq, bk) == (128, 256)
    # explicit arguments always win over the profile
    bq, bk = _clamp_blocks(64, 128, D=64, esz=2, bias_per_q=False)
    assert (bq, bk) == (64, 128)
    # the fwd profile does NOT leak into bwd (a partial autotune window
    # may write fwd keys only; the fwd winner measured 17x slow as a bwd
    # config): without bwd keys, bwd uses its own built-in 128-block
    # defaults (the regime jax's flash kernel defaults to)
    from apex_tpu.contrib.multihead_attn import flash as F
    bq, bk = _clamp_blocks(None, None, D=64, esz=2, bias_per_q=False,
                           bwd=True)
    assert (bq, bk) == (F.DEFAULT_BWD_BLOCK_Q, F.DEFAULT_BWD_BLOCK_K)


def test_flash_clamp_bwd_keys_override_fwd(profile, fake_tpu):
    """The recompute-backward kernels have their own measured optimum:
    flash_bwd_block_q/k beat the shared keys for bwd=True only."""
    from apex_tpu.contrib.multihead_attn.flash import _clamp_blocks
    profile({"flash_block_q": 512, "flash_block_k": 1024,
             "flash_bwd_block_q": 128, "flash_bwd_block_k": 256})
    assert _clamp_blocks(None, None, D=64, esz=2,
                         bias_per_q=False) == (512, 1024)
    assert _clamp_blocks(None, None, D=64, esz=2, bias_per_q=False,
                         bwd=True) == (128, 256)


def test_flash_clamp_fwd_env_pin_does_not_shadow_bwd_profile(
        profile, fake_tpu, monkeypatch):
    """A user who pinned the fwd autotune winner via env must still get
    the measured bwd profile for bwd=True: the bwd path consults only
    its own env/profile/built-in chain — fwd keys never leak into bwd
    (code-review r5: leaking re-created the fwd-blocks-on-bwd
    pathology)."""
    from apex_tpu.contrib.multihead_attn.flash import _clamp_blocks
    monkeypatch.setenv("APEX_TPU_FLASH_BLOCK_Q", "512")
    monkeypatch.setenv("APEX_TPU_FLASH_BLOCK_K", "1024")
    profile({"flash_bwd_block_q": 128, "flash_bwd_block_k": 256})
    assert _clamp_blocks(None, None, D=64, esz=2, bias_per_q=False,
                         bwd=True) == (128, 256)
    assert _clamp_blocks(None, None, D=64, esz=2,
                         bias_per_q=False) == (512, 1024)


def test_flash_clamp_bwd_env_pin(profile, fake_tpu, monkeypatch):
    """APEX_TPU_FLASH_BWD_BLOCK_Q/_K pin the bwd blocks (and count as
    pinned — no budget rewrite), while the fwd path ignores them."""
    from apex_tpu.contrib.multihead_attn.flash import _clamp_blocks
    monkeypatch.setenv("APEX_TPU_FLASH_BWD_BLOCK_Q", "256")
    monkeypatch.setenv("APEX_TPU_FLASH_BWD_BLOCK_K", "512")
    monkeypatch.setenv("APEX_TPU_FLASH_VMEM_MB", "0.25")  # would shrink
    assert _clamp_blocks(None, None, D=64, esz=2, bias_per_q=False,
                         bwd=True) == (256, 512)
    fwd = _clamp_blocks(None, None, D=64, esz=2, bias_per_q=False)
    assert fwd != (256, 512)                   # fwd unaffected by bwd pins


def test_layer_norm_auto_uses_profile(profile, fake_tpu, monkeypatch):
    import jax.numpy as jnp
    from apex_tpu.normalization import fused_layer_norm_affine
    from apex_tpu import ops
    profile({"layer_norm_use_pallas": True})
    called = {}
    import apex_tpu.ops.layer_norm as lnmod

    def spy(x, w, b, shape, eps):
        called["pallas"] = True
        return x

    monkeypatch.setattr(lnmod, "layer_norm_pallas", spy)
    x = jnp.ones((4, 8), jnp.float32)
    fused_layer_norm_affine(x, jnp.ones(8), jnp.zeros(8), (8,))
    assert called.get("pallas")
    # explicit False wins over the profile
    called.clear()
    fused_layer_norm_affine(x, jnp.ones(8), jnp.zeros(8), (8,),
                            use_pallas=False)
    assert not called


def test_zero_impl_auto_uses_profile(profile, fake_tpu):
    from apex_tpu.contrib.optimizers import DistributedFusedAdam
    profile({"zero_impl": "fused"})
    assert DistributedFusedAdam(lr=1e-3).impl == "fused"
    profile({})
    assert DistributedFusedAdam(lr=1e-3).impl == "xla"
    assert DistributedFusedAdam(lr=1e-3, impl="xla").impl == "xla"


def test_collective_scheme_resolve_uses_profile(profile, fake_tpu):
    """ISSUE 7: the DDP collective scheme consults the measured profile
    (TPU only, DDP key only) with the standard precedence."""
    from apex_tpu.parallel import collectives
    profile({"ddp_collective_scheme": "int8_blockscale",
             "collective_min_compress_bytes": 2048})
    spec = collectives.resolve(None)
    assert spec is not None and spec.scheme == "int8_blockscale"
    assert spec.min_bytes == 2048
    # explicit arg beats the profile
    assert collectives.resolve("adasum").scheme == "adasum"
    # the ZeRO paths opt out of the DDP tuning key
    assert collectives.resolve(None, tuning_key=None) is None
    profile({})
    assert collectives.resolve(None) is None


def test_bert_config_attn_from_profile(profile, fake_tpu):
    from apex_tpu.models import bert_large_config
    profile({"bert_attn_impl": "fast"})
    assert bert_large_config(num_layers=2).attn_impl == "fast"
    assert bert_large_config(num_layers=2,
                             attn_impl="default").attn_impl == "default"
    profile({})
    assert bert_large_config(num_layers=2).attn_impl == "default"


def test_get_on_tpu_is_side_effect_free_pre_init():
    """Consulting a tuning knob (e.g. constructing DistributedFusedAdam
    before jax.distributed.initialize) must not force backend bring-up
    (code-review r5, third pass)."""
    code = (
        "from apex_tpu.utils import tuning\n"
        "from apex_tpu.utils.platform import backends_initialized\n"
        "assert not backends_initialized()\n"
        "assert tuning.get_on_tpu('zero_impl', 'xla') == 'xla'\n"
        "assert not backends_initialized(), 'get_on_tpu initialized jax!'\n"
        "from apex_tpu.contrib.optimizers import DistributedFusedAdam\n"
        "assert DistributedFusedAdam(lr=1e-3).impl == 'xla'\n"
        "assert not backends_initialized(), 'optimizer ctor initialized jax!'\n"
        "print('SIDE-EFFECT-FREE')\n")
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": ROOT, "JAX_PLATFORMS": "cpu"},
        timeout=120)
    assert r.returncode == 0, r.stderr
    assert "SIDE-EFFECT-FREE" in r.stdout


# ---------------------------------------------------------------------------
# decision engine
# ---------------------------------------------------------------------------

def _load_apply():
    spec = importlib.util.spec_from_file_location(
        "apply_perf_results", os.path.join(ROOT, "tools",
                                           "apply_perf_results.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _tpu_artifacts():
    bench = {"metric": "fused_lamb_step_ms_bert_large", "value": 19.0,
             "vs_baseline": 1.55, "backend": "tpu",
             "detail": {"winner": "fused_flat", "xla_impl_ms": 28.8,
                        "fused_flat_impl_ms": 19.0,
                        "optax_baseline_ms": 29.4}}
    kern = {"metric": "pallas_kernel_microbench", "backend": "tpu",
            "kernels": {
                "flash_autotune": {"best": "256x1024",
                                   "sweep_ms": {"256x1024": 1.2}},
                # the r6 per-kernel ladder: dq and dkv winners differ, the
                # fused strategy beats the split total, and the fair
                # grads(q,k,v) A/B records a Pallas-backward LOSS (the
                # auto-fallback case the loop exists for)
                "flash_bwd_autotune": {
                    "shape": "B8 H16 S1024 D64 causal per-kernel bwd + "
                             "grads(q,k,v) A/B",
                    "best": "128x256",
                    "best_dq": "128x256", "best_dkv": "256x256",
                    "best_fused": "128x256",
                    "sweep_ms": {
                        "dq_128x128": 1.4, "dq_128x256": 1.0,
                        "dkv_128x128": 2.0, "dkv_128x256": 1.9,
                        "dkv_256x256": 1.8,
                        "fused_128x128": 2.9, "fused_128x256": 2.5,
                        "pallas_grads_qkv": 5.0, "xla_grads_qkv": 3.0,
                        "jax_ref_fwdbwd": 11.0}},
                "xentropy_fwdbwd": {"speedup": 1.3},
                "layer_norm_fwdbwd": {"speedup": 0.8},
                "mlp_fwdbwd": {"speedup": 1.1},
                "adam_update": {"speedup": 1.2},
                "lamb_stage1": {"speedup": 0.9},
                "attn_seq_sweep": {"by_seq": {
                    "64": {"speedup": 0.8}, "512": {"speedup": 1.4},
                    "1024": {"speedup": 1.8}, "2048": {"speedup": 2.2}}},
            }}
    return bench, kern


def test_decide_applies_rules():
    mod = _load_apply()
    bench, kern = _tpu_artifacts()
    prof, rows = mod.decide(bench, kern)
    assert prof["flash_block_q"] == 256 and prof["flash_block_k"] == 1024
    assert prof["flash_bwd_block_q"] == 128
    assert prof["flash_bwd_block_k"] == 256
    # per-kernel winners refine the shared keys independently
    assert prof["flash_bwd_dq_block_q"] == 128
    assert prof["flash_bwd_dq_block_k"] == 256
    # best fused (2.5) beats best dq + best dkv (1.0 + 1.8 = 2.8)
    assert prof["flash_bwd_fuse"] is True
    # with fuse=True the dkv keys carry best_FUSED (128x256), not
    # best_dkv (256x256): the fused kernel runs on the dkv grid and reads
    # these keys, and must get the config its win was measured at
    assert prof["flash_bwd_dkv_block_q"] == 128
    assert prof["flash_bwd_dkv_block_k"] == 256
    # the A/B recorded pallas 5.0 vs xla 3.0: auto must route to XLA
    assert prof["flash_bwd_impl"] == "xla"
    assert prof["xent_auto_impl"] == "pallas"
    assert prof["layer_norm_use_pallas"] is False
    assert prof["mlp_use_pallas"] is True
    assert prof["zero_impl"] == "xla"          # lamb_stage1 lost
    assert prof["bert_attn_impl"] == "fast"    # mean(1.4,1.8,2.2) >= 1
    assert any("headline" in r[0] for r in rows)


def test_decide_collective_scheme_from_ab_leg():
    """The bench ``collectives`` A/B leg decides ddp_collective_scheme:
    fastest measured scheme at the top payload; int8 is only eligible
    with its >=3.5x wire ratio intact; a non-fp32 winner pins the
    min-bytes threshold and the profile passes the committed schema."""
    mod = _load_apply()
    bench, kern = _tpu_artifacts()
    bench["detail"]["collectives"] = {
        "leg": "collectives", "world": 8,
        # adasum "fastest": it must still never be auto-selected — it
        # changes the reduction rule, not just the wire format
        "schemes": {"fp32": {"host_ms": 4.0, "ratio": 1.0},
                    "bf16": {"host_ms": 2.4, "ratio": 2.0},
                    "int8_blockscale": {"host_ms": 1.5, "ratio": 3.88},
                    "adasum": {"host_ms": 0.9, "ratio": 1.0}}}
    prof, rows = mod.decide(bench, kern)
    assert prof["ddp_collective_scheme"] == "int8_blockscale"
    assert prof["collective_min_compress_bytes"] == 4096
    assert tuning.schema_violations(
        {k: v for k, v in prof.items()}) == []
    assert any("ddp_collective_scheme" in r[0] for r in rows)
    # a drifted int8 ratio disqualifies it; the next-fastest wins
    bench["detail"]["collectives"]["schemes"]["int8_blockscale"][
        "ratio"] = 2.0
    prof2, _ = mod.decide(bench, kern)
    assert prof2["ddp_collective_scheme"] == "bf16"
    assert any("ratio" in v for v in mod.collective_violations(bench))


def _plan_leg(err=3.0):
    return {
        "leg": "plan", "chips": 8, "candidates_enumerated": 27,
        "feasible": 27, "baseline_step_ms": 2.0,
        "calibration_error_pct": err,
        "telemetry": {"records": [], "summary": {}},
        "plans": [
            {"knobs": {"dp": 8, "tp": 1, "sp": 1,
                       "sp_strategy": "none", "zero": False,
                       "update_sharding": "zero1",
                       "collective_scheme": "fp32",
                       "allgather_scheme": "fp32"},
             "plan": "dp=8 us=zero1",
             "predicted_ms": 1.55, "measured_ms": 1.5},
            {"knobs": {"dp": 8, "tp": 1, "sp": 1,
                       "sp_strategy": "none", "zero": False,
                       "update_sharding": "off",
                       "collective_scheme": "fp32",
                       "allgather_scheme": "fp32"},
             "plan": "all-defaults",
             "predicted_ms": 2.0, "measured_ms": 2.0}]}


def test_decide_plan_from_ab_leg():
    """The bench ``plan`` A/B leg decides the plan_* keys: the MEASURED
    winner's knob dict is persisted (schema-valid), but only while the
    calibration drift guard holds."""
    mod = _load_apply()
    bench, kern = _tpu_artifacts()
    bench["detail"]["plan"] = _plan_leg()
    prof, rows = mod.decide(bench, kern)
    assert prof["plan_dp"] == 8 and prof["plan_tp"] == 1
    assert prof["plan_update_sharding"] == "zero1"
    assert prof["plan_collective_scheme"] == "fp32"
    assert prof["plan_zero"] is False
    assert tuning.schema_violations(dict(prof)) == []
    assert any("plan" in r[0] for r in rows)
    assert mod.plan_violations(bench) == []
    # a drifted model (>25% calibration error) must not persist a plan
    bench["detail"]["plan"] = _plan_leg(err=40.0)
    prof2, _ = mod.decide(bench, kern)
    assert not any(k.startswith("plan_") for k in prof2)
    assert any("calibration error" in v
               for v in mod.plan_violations(bench))
    # a predicted pick measuring >25% behind the measured winner is
    # drift too (the ranked pick is row 0 by the leg's contract)
    leg = _plan_leg()
    leg["plans"][0]["measured_ms"] = 2.8
    assert any("calibration drift" in v
               for v in mod.plan_violations({"plan": leg}))


def test_decide_skips_cpu_tagged_kernels():
    mod = _load_apply()
    bench, kern = _tpu_artifacts()
    kern["backend"] = "mixed"
    kern["kernels"]["xentropy_fwdbwd"]["_backend"] = "cpu"
    prof, _ = mod.decide(bench, kern)
    assert "xent_auto_impl" not in prof        # cpu evidence rejected
    assert prof["flash_block_q"] == 256        # tpu evidence kept


def test_cli_refuses_cpu_artifacts(tmp_path):
    bench = tmp_path / "b.json"
    bench.write_text(json.dumps({"backend": "cpu", "detail": {}}))
    kern = tmp_path / "k.json"
    kern.write_text(json.dumps({"backend": "cpu", "kernels": {}}))
    out = tmp_path / "tuned.json"
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools",
                                      "apply_perf_results.py"),
         "--bench", str(bench), "--kernels", str(kern), "--out", str(out)],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT})
    assert r.returncode == 1
    assert "refusing" in r.stderr
    assert not out.exists()


def test_cli_writes_profile_and_notes(tmp_path):
    mod_bench, mod_kern = _tpu_artifacts()
    bench = tmp_path / "b.json"
    bench.write_text(json.dumps(mod_bench))
    kern = tmp_path / "k.json"
    kern.write_text(json.dumps(mod_kern))
    out = tmp_path / "tuned.json"
    notes = tmp_path / "notes.md"
    notes.write_text("# notes\n")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools",
                                      "apply_perf_results.py"),
         "--bench", str(bench), "--kernels", str(kern), "--out", str(out),
         "--notes", str(notes)],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT})
    assert r.returncode == 0, r.stderr
    prof = json.loads(out.read_text())
    assert prof["flash_block_q"] == 256
    assert prof["_provenance"]["bench"] == "b.json"
    assert "| knob | decision |" in r.stdout
    assert "Measured winners applied" in notes.read_text()
    # re-running (documented as safe) REPLACES the section, no duplicates
    r2 = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools",
                                      "apply_perf_results.py"),
         "--bench", str(bench), "--kernels", str(kern), "--out", str(out),
         "--notes", str(notes)],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT})
    assert r2.returncode == 0, r2.stderr
    txt = notes.read_text()
    assert txt.count("## 8. Measured winners applied") == 1
    assert txt.startswith("# notes")            # preamble preserved
    # a section written under an OLD heading number (pre-r5: "## 7.") is
    # also replaced, not accreted next to the new one
    notes.write_text("# notes\n\n## 7. Measured winners applied (old)\n\n"
                     "| stale | table |\n")
    r3 = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools",
                                      "apply_perf_results.py"),
         "--bench", str(bench), "--kernels", str(kern), "--out", str(out),
         "--notes", str(notes)],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT})
    assert r3.returncode == 0, r3.stderr
    txt = notes.read_text()
    assert "stale" not in txt
    assert txt.count("Measured winners applied") == 1


def test_decide_skips_non_config_winner():
    """A non-config row name landing in a ``best*`` field (e.g. the
    ``jax_ref_fwdbwd`` sanity row) must SKIP the key, not crash decide()
    with a ValueError from int() — ADVICE r5 #3."""
    mod = _load_apply()
    bench, kern = _tpu_artifacts()
    bt = kern["kernels"]["flash_bwd_autotune"]
    bt["best"] = "jax_ref_fwdbwd"
    kern["kernels"]["flash_autotune"]["best"] = "jax_ref_fwdbwd"
    # force the split path (fused rows lose) and poison its winner: the
    # dkv keys must be SKIPPED, not crash decide()
    for c in list(bt["sweep_ms"]):
        if c.startswith("fused_"):
            bt["sweep_ms"][c] = 99.0
    bt["best_dkv"] = "failed: Mosaic"
    prof, _ = mod.decide(bench, kern)          # must not raise
    assert "flash_block_q" not in prof
    assert "flash_bwd_block_q" not in prof
    assert "flash_bwd_dkv_block_q" not in prof
    assert prof["flash_bwd_fuse"] is False
    assert prof["flash_bwd_dq_block_q"] == 128  # valid winners still land


def test_decide_fuse_loses_ships_best_dkv():
    """When the split total wins, the dkv keys carry best_dkv — the split
    kernel is what production runs."""
    mod = _load_apply()
    bench, kern = _tpu_artifacts()
    sweep = kern["kernels"]["flash_bwd_autotune"]["sweep_ms"]
    sweep["fused_128x128"] = 9.0
    sweep["fused_128x256"] = 8.5       # worst fused (8.5) > split (2.8)
    prof, _ = mod.decide(bench, kern)
    assert prof["flash_bwd_fuse"] is False
    assert prof["flash_bwd_dkv_block_q"] == 256   # best_dkv
    assert prof["flash_bwd_dkv_block_k"] == 256


def test_decide_fuse_win_with_unparsable_best_fused_skips_dkv_keys():
    """fuse=true must never ship dkv keys taken from best_dkv: when
    best_fused is absent/unparsable the keys are skipped entirely (the
    runtime falls back to its 128x128 built-in — a config the fused
    ladder DID measure — rather than a split-only winner it didn't)."""
    mod = _load_apply()
    bench, kern = _tpu_artifacts()
    kern["kernels"]["flash_bwd_autotune"]["best_fused"] = "stale-garbage"
    prof, _ = mod.decide(bench, kern)
    assert prof["flash_bwd_fuse"] is True
    assert "flash_bwd_dkv_block_q" not in prof
    assert "flash_bwd_dkv_block_k" not in prof


def test_decide_failed_dq_ladder_with_fused_measured_pins_fuse_true():
    """Every dq row failed while dkv+fused measured (ROADMAP deferral a):
    the split total is unmeasurable, so flash_bwd_fuse must be pinned
    True (fused is the only strategy with on-chip evidence) and the dkv
    keys must carry best_fused — previously the key stayed unwritten
    while best_dkv shipped, letting the runtime byte-cap heuristic pair
    a fused pick with split-measured blocks."""
    mod = _load_apply()
    bench, kern = _tpu_artifacts()
    bt = kern["kernels"]["flash_bwd_autotune"]
    for c in list(bt["sweep_ms"]):
        if c.startswith("dq_"):
            bt["sweep_ms"][c] = "failed: Mosaic lowering"
    bt["best_dq"] = None
    prof, rows = mod.decide(bench, kern)
    assert prof["flash_bwd_fuse"] is True
    # dkv keys carry the measured FUSED winner, not the split dkv one
    assert prof["flash_bwd_dkv_block_q"] == 128
    assert prof["flash_bwd_dkv_block_k"] == 256
    assert "flash_bwd_dq_block_q" not in prof
    assert any("only" in e and "measured" in e for _, _, e in rows)


def test_decide_failed_fused_ladder_records_fuse_false():
    """A fused ladder with no measured row must write flash_bwd_fuse=False:
    leaving the key absent would let the runtime byte-cap heuristic
    re-enable the kernel that just failed on this chip."""
    mod = _load_apply()
    bench, kern = _tpu_artifacts()
    bt = kern["kernels"]["flash_bwd_autotune"]
    for c in list(bt["sweep_ms"]):
        if c.startswith("fused_"):
            bt["sweep_ms"][c] = "failed: Mosaic lowering"
    bt["best_fused"] = None
    prof, _ = mod.decide(bench, kern)
    assert prof["flash_bwd_fuse"] is False
    assert prof["flash_bwd_dkv_block_q"] == 256   # split keys still land


def _good_telemetry_block():
    return {"records": [
        {"kind": "metric", "ts": "2026-08-04T00:00:00Z", "step": 0,
         "name": "step_time_ms", "type": "histogram",
         "stats": {"count": 1, "sum": 5.0, "min": 5.0, "max": 5.0,
                   "mean": 5.0}, "cum_count": 1}],
        "summary": {"steps": 0}}


def test_apply_perf_results_audits_embedded_telemetry(tmp_path, capsys):
    """Bench artifacts embedding telemetry records are schema-checked by
    the same tool that audits them for tuning decisions: valid blocks
    pass silently, drifted records are surfaced as warnings without
    blocking the (telemetry-independent) profile write."""
    mod = _load_apply()
    bench, kern = _tpu_artifacts()
    bench["detail"]["bert_e2e"] = {"step_ms": 5.0,
                                   "telemetry": _good_telemetry_block()}
    assert mod.telemetry_violations(bench) == []
    assert mod.telemetry_violations(kern) == []

    bench["detail"]["bert_e2e"]["telemetry"]["records"].append(
        {"kind": "metric", "name": "x"})        # off-schema
    bad = mod.telemetry_violations(bench)
    assert bad and "bert_e2e" in bad[0]

    # blocks nested under LIST-valued nodes are audited too
    listed = {"detail": {"sweep": [
        {"telemetry": {"records": [{"kind": "bogus"}], "summary": {}}}]}}
    bad2 = mod.telemetry_violations(listed)
    assert bad2 and "sweep[0]" in bad2[0]

    bpath = tmp_path / "b.json"
    bpath.write_text(json.dumps(bench))
    kpath = tmp_path / "k.json"
    kpath.write_text(json.dumps(kern))
    out = tmp_path / "tuned.json"
    rc = mod.main(["--bench", str(bpath), "--kernels", str(kpath),
                   "--out", str(out)])
    assert rc == 0                              # tuning write unaffected
    assert out.exists()
    assert "WARNING bench" in capsys.readouterr().err


def test_schema_violations():
    """The committed profile schema: unknown keys and ill-typed values are
    violations; ``_``-prefixed metadata is exempt."""
    good = {"flash_block_q": 128, "flash_bwd_dq_block_q": 256,
            "flash_bwd_impl": "xla", "flash_bwd_fuse": True,
            "_provenance": {"ts": "2026"}}
    assert tuning.schema_violations(good) == []
    assert tuning.schema_violations({"mystery_knob": 1})
    assert tuning.schema_violations({"flash_block_q": True})  # bool != block
    assert tuning.schema_violations({"flash_block_q": -8})
    assert tuning.schema_violations({"flash_bwd_impl": "cuda"})
    assert tuning.schema_violations({"flash_bwd_fuse": 1})    # int != bool
    # ISSUE 7: the per-bucket collective-scheme keys
    assert tuning.schema_violations(
        {"ddp_collective_scheme": "int8_blockscale",
         "collective_min_compress_bytes": 4096}) == []
    assert tuning.schema_violations({"ddp_collective_scheme": "zstd"})
    assert tuning.schema_violations({"collective_min_compress_bytes": 0})


def test_cli_schema_gate_blocks_drifted_profile(tmp_path, monkeypatch):
    """A decision engine emitting a key the consumers don't know must fail
    the write, not ship a profile the training run silently ignores."""
    mod = _load_apply()
    bench, kern = _tpu_artifacts()
    bpath = tmp_path / "b.json"
    bpath.write_text(json.dumps(bench))
    kpath = tmp_path / "k.json"
    kpath.write_text(json.dumps(kern))
    out = tmp_path / "tuned.json"
    monkeypatch.setattr(mod, "decide",
                        lambda b, k: ({"mystery_knob": 1},
                                      [("mystery_knob", "1", "synthetic")]))
    rc = mod.main(["--bench", str(bpath), "--kernels", str(kpath),
                   "--out", str(out)])
    assert rc == 1
    assert not out.exists()


_FLASH_ENV = ("APEX_TPU_FLASH_BLOCK_Q", "APEX_TPU_FLASH_BLOCK_K",
              "APEX_TPU_FLASH_BWD_BLOCK_Q", "APEX_TPU_FLASH_BWD_BLOCK_K",
              "APEX_TPU_FLASH_BWD_DQ_BLOCK_Q", "APEX_TPU_FLASH_BWD_DQ_BLOCK_K",
              "APEX_TPU_FLASH_BWD_DKV_BLOCK_Q",
              "APEX_TPU_FLASH_BWD_DKV_BLOCK_K",
              "APEX_TPU_FLASH_BWD_IMPL", "APEX_TPU_FLASH_BWD_FUSE",
              "APEX_TPU_FLASH_VMEM_MB")


def test_flash_clamp_per_kernel_chains(profile, fake_tpu, monkeypatch):
    """The dq/dkv backward kernels resolve blocks through their own chains:
    argument > per-kernel env > shared bwd env > per-kernel profile >
    shared bwd profile > built-in.  The fused kernel rides the dkv chain
    (it runs on the dkv grid)."""
    from apex_tpu.contrib.multihead_attn.flash import _clamp_blocks
    for var in _FLASH_ENV:
        monkeypatch.delenv(var, raising=False)
    profile({"flash_bwd_block_q": 128, "flash_bwd_block_k": 128,
             "flash_bwd_dq_block_q": 256, "flash_bwd_dq_block_k": 256})
    # per-kernel profile beats the shared profile key...
    assert _clamp_blocks(None, None, 64, 2, False, bwd="dq") == (256, 256)
    # ...while a kernel without per-kernel keys falls back to shared
    assert _clamp_blocks(None, None, 64, 2, False, bwd="dkv") == (128, 128)
    assert _clamp_blocks(None, None, 64, 2, False, bwd="fused") == (128, 128)
    # legacy shared-model callers (bwd=True) see shared keys only
    assert _clamp_blocks(None, None, 64, 2, False, bwd=True) == (128, 128)
    # a shared bwd env pin beats the per-kernel PROFILE (env > profile)
    monkeypatch.setenv("APEX_TPU_FLASH_BWD_BLOCK_Q", "512")
    monkeypatch.setenv("APEX_TPU_FLASH_BWD_BLOCK_K", "512")
    assert _clamp_blocks(None, None, 64, 2, False, bwd="dq") == (512, 512)
    # a per-kernel env pin beats the shared env pin, for its kernel only
    monkeypatch.setenv("APEX_TPU_FLASH_BWD_DQ_BLOCK_Q", "128")
    monkeypatch.setenv("APEX_TPU_FLASH_BWD_DQ_BLOCK_K", "128")
    assert _clamp_blocks(None, None, 64, 2, False, bwd="dq") == (128, 128)
    assert _clamp_blocks(None, None, 64, 2, False, bwd="dkv") == (512, 512)
    # the fwd chain never sees any of it
    assert _clamp_blocks(None, None, 64, 2, False) == (512, 1024)


def test_resolve_fuse_chain(profile, fake_tpu, monkeypatch):
    """Fused-vs-split: explicit arg > env > profile > buffer-cap
    heuristic."""
    from apex_tpu.contrib.multihead_attn import flash as F
    monkeypatch.delenv("APEX_TPU_FLASH_BWD_FUSE", raising=False)
    monkeypatch.delenv("APEX_TPU_FLASH_BWD_FUSE_MB", raising=False)
    # heuristic: small dq-partials buffer -> fuse; past the cap -> split
    assert F._resolve_fuse(None, 4, 128, 128, 64, 128) is True
    assert F._resolve_fuse(None, 64, 16384, 16384, 64, 128) is False
    # 'off'/'no' disable, same vocabulary as telemetry's _env_enabled
    # (they used to read as truthy — ROADMAP deferral b)
    for off in ("off", "no", "0", "false"):
        monkeypatch.setenv("APEX_TPU_FLASH_BWD_FUSE", off)
        assert F._resolve_fuse(None, 4, 128, 128, 64, 128) is False, off
    monkeypatch.delenv("APEX_TPU_FLASH_BWD_FUSE")
    monkeypatch.setenv("APEX_TPU_FLASH_BWD_FUSE_MB", "0.001")
    assert F._resolve_fuse(None, 4, 128, 128, 64, 128) is False
    monkeypatch.delenv("APEX_TPU_FLASH_BWD_FUSE_MB")
    # profile beats the heuristic
    profile({"flash_bwd_fuse": False})
    assert F._resolve_fuse(None, 4, 128, 128, 64, 128) is False
    # env beats the profile
    monkeypatch.setenv("APEX_TPU_FLASH_BWD_FUSE", "1")
    assert F._resolve_fuse(None, 4, 128, 128, 64, 128) is True
    # explicit argument beats everything
    assert F._resolve_fuse(False, 4, 128, 128, 64, 128) is False


def test_tuning_loop_closes_end_to_end(tmp_path, fake_tpu, monkeypatch):
    """The full produce -> decide -> consume cycle on CPU: a synthetic
    BENCH_KERNELS_*.json flows through the apply_perf_results CLI into a
    schema-valid tuned_defaults.json, whose dq/dkv block keys and
    flash_bwd_impl route _clamp_blocks and backward="auto" — with env
    pins still beating the written profile (the documented precedence)."""
    for var in _FLASH_ENV:
        monkeypatch.delenv(var, raising=False)
    bench, kern = _tpu_artifacts()
    bpath = tmp_path / "BENCH_TPU_x.json"
    bpath.write_text(json.dumps(bench))
    kpath = tmp_path / "BENCH_KERNELS_TPU_x.json"
    kpath.write_text(json.dumps(kern))
    out = tmp_path / "tuned_defaults.json"
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools",
                                      "apply_perf_results.py"),
         "--bench", str(bpath), "--kernels", str(kpath), "--out", str(out)],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT})
    assert r.returncode == 0, r.stderr

    # the written artifact carries the documented schema
    prof = json.loads(out.read_text())
    assert tuning.schema_violations(prof) == []
    assert prof["flash_bwd_dq_block_q"] == 128
    assert prof["flash_bwd_dq_block_k"] == 256
    # fuse won, so the dkv keys (which the fused kernel reads) carry the
    # measured fused winner, not the split dkv winner
    assert prof["flash_bwd_dkv_block_q"] == 128
    assert prof["flash_bwd_dkv_block_k"] == 256
    assert prof["flash_bwd_fuse"] is True
    assert prof["flash_bwd_impl"] == "xla"
    assert prof["_provenance"]["kernels"] == "BENCH_KERNELS_TPU_x.json"

    # the consumers pick the written keys up (on the TPU backend)
    monkeypatch.setenv("APEX_TPU_TUNING_FILE", str(out))
    tuning.reload()
    from apex_tpu.contrib.multihead_attn import flash as F
    assert F._clamp_blocks(None, None, 64, 2, False, bwd="dq") == (128, 256)
    assert F._clamp_blocks(None, None, 64, 2, False, bwd="dkv") == (128, 256)
    # the recorded Pallas-backward loss provably flips auto to XLA
    assert F._resolve_backward("auto") == "xla"
    # the measured fuse decision beats the byte-cap heuristic
    assert F._resolve_fuse(None, 64, 16384, 16384, 64, 128) is True

    # env pins still win over the written profile
    monkeypatch.setenv("APEX_TPU_FLASH_BWD_DQ_BLOCK_Q", "512")
    monkeypatch.setenv("APEX_TPU_FLASH_BWD_DQ_BLOCK_K", "512")
    assert F._clamp_blocks(None, None, 64, 2, False, bwd="dq") == (512, 512)
    assert F._clamp_blocks(None, None, 64, 2, False, bwd="dkv") == (128, 256)
    monkeypatch.setenv("APEX_TPU_FLASH_BWD_IMPL", "pallas")
    assert F._resolve_backward("auto") == "pallas"
