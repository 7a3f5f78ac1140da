"""Incremental bench-leg persistence: a bench run that dies midway
must not lose completed measurements.

Covers the three layers of the recovery pipeline:
  1. ``apex_tpu.utils.bench_legs`` — flush/read/assemble primitives;
  2. ``bench.run_bench(legs_dir=...)`` flushes the headline leg after
     EVERY sub-measurement (simulated mid-run wedge keeps earlier ones);
  3. ``assemble`` rebuilds a driver-shaped (partial) payload from
     whatever legs landed, and never reports vs_baseline off-TPU.
"""
import importlib.util
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from apex_tpu.utils.bench_legs import (assemble, flush_leg, make_flusher,
                                       read_legs)


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(ROOT, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_flush_and_read_roundtrip(tmp_path):
    d = str(tmp_path / "legs")
    flush_leg(d, "headline", {"xla_impl_ms": 1.5}, backend="tpu")
    flush_leg(d, "rn50", {"images_per_sec": 10.0}, backend="tpu")
    # re-flush overwrites (accreting legs)
    flush_leg(d, "headline", {"xla_impl_ms": 1.5, "winner": "xla"},
              backend="tpu")
    legs = read_legs(d)
    assert set(legs) == {"headline", "rn50"}
    assert legs["headline"]["data"]["winner"] == "xla"
    assert legs["headline"]["backend"] == "tpu"
    assert legs["headline"]["ts"].endswith("Z")
    # no tmp debris from the atomic writes
    assert not [f for f in os.listdir(d) if f.startswith(".")]


def test_flush_none_dir_is_noop(tmp_path):
    flush_leg(None, "headline", {"x": 1}, backend="cpu")
    flush_leg("", "headline", {"x": 1}, backend="cpu")


def test_read_legs_skips_corrupt_file(tmp_path):
    d = str(tmp_path)
    flush_leg(d, "good", {"v": 1}, backend="tpu")
    with open(os.path.join(d, "bad.json"), "w") as f:
        f.write("{truncated")
    legs = read_legs(d)
    assert set(legs) == {"good"}


def test_assemble_bench_partial_headline_only(tmp_path):
    """A window that wedged after the xla timing still yields a usable
    payload: value from the one finished impl, partial=true, and
    vs_baseline stays null (no baseline was timed)."""
    d = str(tmp_path)
    flush_leg(d, "headline", {"n_params": 100, "complete": False,
                              "xla_impl_ms": 28.8}, backend="tpu")
    out = assemble(d, "bench")
    assert out["partial"] is True
    assert out["value"] == 28.8
    assert out["vs_baseline"] is None
    assert out["backend"] == "tpu"
    assert out["leg_timestamps"]["headline"]
    assert out["detail"]["xla_impl_ms"] == 28.8


def test_assemble_bench_full_legs(tmp_path):
    d = str(tmp_path)
    flush_leg(d, "headline", {"n_params": 100, "complete": True,
                              "xla_impl_ms": 28.8,
                              "fused_flat_impl_ms": 19.0,
                              "optax_baseline_ms": 29.4,
                              "winner": "fused_flat"}, backend="tpu")
    flush_leg(d, "rn50", {"images_per_sec": 800.0, "batch": 128},
              backend="tpu")
    flush_leg(d, "bert_e2e", {"step_ms": 900.0}, backend="tpu")
    out = assemble(d, "bench")
    assert out["value"] == 19.0
    assert out["vs_baseline"] == pytest.approx(29.4 / 19.0, abs=1e-3)
    assert out["detail"]["rn50"]["images_per_sec"] == 800.0
    assert out["detail"]["bert_e2e"]["step_ms"] == 900.0
    assert out["partial"] is True        # assembled => documents a kill


def test_assemble_bench_cpu_backend_never_reports_vs_baseline(tmp_path):
    """round-4 verdict weak #3: a CPU ratio must not surface as
    vs_baseline even through the assembler path."""
    d = str(tmp_path)
    flush_leg(d, "headline", {"xla_impl_ms": 16.7,
                              "optax_baseline_ms": 21.0}, backend="cpu")
    out = assemble(d, "bench")
    assert out["value"] == 16.7
    assert out["vs_baseline"] is None


def test_assemble_kernels_merges_sections(tmp_path):
    d = str(tmp_path)
    flush_leg(d, "attention", {"flash_attn_fwd": {"pallas_ms": 1.0,
                                                  "xla_ms": 2.0}},
              backend="tpu")
    # intra-leg flush mid-sweep, then the section flush overwrote it with
    # one more row — the assembler sees only the latest
    flush_leg(d, "attn_seq_sweep",
              {"attn_seq_sweep": {"by_seq": {"64": {"speedup": 0.9}}}},
              backend="tpu")
    flush_leg(d, "attn_seq_sweep",
              {"attn_seq_sweep": {"by_seq": {"64": {"speedup": 0.9},
                                             "128": {"speedup": 1.1}}}},
              backend="tpu")
    out = assemble(d, "kernels")
    assert out["metric"] == "pallas_kernel_microbench"
    assert out["compiled"] is True
    assert out["kernels"]["flash_attn_fwd"]["xla_ms"] == 2.0
    assert set(out["kernels"]["attn_seq_sweep"]["by_seq"]) == {"64", "128"}
    assert out["partial"] is True


def test_assemble_empty_dir(tmp_path):
    """No legs => backend 'none' (not 'mixed'): nothing was measured on
    ANY backend, and downstream tooling treats 'mixed' as partially
    TPU-backed."""
    out = assemble(str(tmp_path), "bench")
    assert out["value"] is None and out["detail"] == {}
    assert out["backend"] == "none"
    out_k = assemble(str(tmp_path / "missing"), "kernels")
    assert out_k["kernels"] == {} and out_k["backend"] == "none"


def test_assemble_cli_prints_json(tmp_path):
    import subprocess
    import sys
    d = str(tmp_path)
    flush_leg(d, "headline", {"xla_impl_ms": 3.0}, backend="tpu")
    r = subprocess.run(
        [sys.executable, "-m", "apex_tpu.utils.bench_legs", d,
         "--kind", "bench"],
        capture_output=True, text=True, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr
    payload = json.loads(r.stdout.strip().splitlines()[-1])
    assert payload["value"] == 3.0 and payload["partial"] is True


def test_merge_flush_keeps_prior_window_measurements(tmp_path):
    """A second recovery window that wedges EARLIER than the first must
    not destroy the first window's captured timings (code-review r5)."""
    d = str(tmp_path)
    # window 1 got as far as the fused timing
    flush_leg(d, "headline", {"xla_impl_ms": 28.8,
                              "fused_flat_impl_ms": 19.0,
                              "complete": False}, backend="tpu")
    # window 2 re-measured xla (fresher value wins) then died
    flush_leg(d, "headline", {"xla_impl_ms": 27.9, "complete": False},
              backend="tpu", merge=True)
    head = read_legs(d)["headline"]["data"]
    assert head["xla_impl_ms"] == 27.9          # fresh value wins
    assert head["fused_flat_impl_ms"] == 19.0   # old survives
    out = assemble(d, "bench")
    assert out["value"] == 19.0


def test_merge_flush_deep_merges_sweep_rows(tmp_path):
    """Kernel sweep legs: a re-run that wedged earlier keeps the rows a
    previous window captured (code-review r5, second pass)."""
    d = str(tmp_path)
    flush_leg(d, "attn_seq_sweep",
              {"attn_seq_sweep": {"by_seq": {"64": 1.0, "128": 2.0,
                                             "256": 3.0}}},
              backend="tpu")
    flush_leg(d, "attn_seq_sweep",
              {"attn_seq_sweep": {"by_seq": {"64": 0.9}}},
              backend="tpu", merge=True)
    rows = read_legs(d)["attn_seq_sweep"]["data"]["attn_seq_sweep"]["by_seq"]
    assert rows == {"64": 0.9, "128": 2.0, "256": 3.0}


def test_merge_flush_never_mixes_backends(tmp_path):
    """A CPU re-run must neither inherit NOR destroy TPU-backend legs:
    the TPU measurement is the perf story, the CPU record is noise."""
    d = str(tmp_path)
    flush_leg(d, "headline", {"xla_impl_ms": 28.8}, backend="tpu")
    flush_leg(d, "headline", {"fused_flat_impl_ms": 52.0}, backend="cpu",
              merge=True)
    head = read_legs(d)["headline"]
    assert head["backend"] == "tpu"             # tpu leg preserved
    assert head["data"] == {"xla_impl_ms": 28.8}
    # and the same protection without merge (plain overwrite attempt)
    flush_leg(d, "headline", {"fused_flat_impl_ms": 52.0}, backend="cpu")
    assert read_legs(d)["headline"]["backend"] == "tpu"
    # a TPU re-run may of course overwrite a CPU leg (upgrade)
    flush_leg(d, "rn50", {"ips": 1.0}, backend="cpu")
    flush_leg(d, "rn50", {"ips": 900.0}, backend="tpu")
    assert read_legs(d)["rn50"]["data"]["ips"] == 900.0


def test_assemble_mixed_backends_tags_every_leg(tmp_path):
    """CPU and TPU legs in one dir: every merged
    value must carry its backend and no headline metric may surface from
    the CPU leg."""
    d = str(tmp_path)
    flush_leg(d, "headline", {"xla_impl_ms": 16.7,
                              "optax_baseline_ms": 21.0}, backend="cpu")
    flush_leg(d, "rn50", {"images_per_sec": 800.0}, backend="tpu")
    out = assemble(d, "bench")
    assert out["backend"] == "mixed"
    assert out["value"] is None                 # cpu headline: not the metric
    assert out["vs_baseline"] is None
    assert out["detail"]["_backend"] == "cpu"   # tagged headline fields
    assert out["detail"]["rn50"]["_backend"] == "tpu"

    out_k_dir = str(tmp_path / "k")
    flush_leg(out_k_dir, "attention",
              {"flash_attn_fwd": {"pallas_ms": 1.0}}, backend="tpu")
    flush_leg(out_k_dir, "xentropy",
              {"xentropy_fwd": {"pallas_ms": 9.0}}, backend="cpu")
    out_k = assemble(out_k_dir, "kernels")
    assert out_k["backend"] == "mixed"
    assert out_k["kernels"]["flash_attn_fwd"]["_backend"] == "tpu"
    assert out_k["kernels"]["xentropy_fwd"]["_backend"] == "cpu"


def test_bench_telemetry_records_schema_checked(tmp_path):
    """bench legs that embed telemetry records (bert_e2e does, via
    bench.telemetry_summary) must carry records valid against the
    committed telemetry SCHEMA, and the block must survive the
    leg-flush/assemble recovery path intact (ISSUE 3 satellite)."""
    import pytest as _pytest
    from apex_tpu.telemetry import records_violations
    bench = _load_bench()
    tel = bench.telemetry_summary([12.5], counters={"examples": 8})
    assert records_violations(tel["records"]) == []
    assert tel["summary"]["step_time_ms"]["count"] == 1
    assert tel["summary"]["step_time_ms"]["mean"] == _pytest.approx(12.5)
    # examples / (step time): the ready-made items/sec the summary carries
    assert tel["summary"]["items_per_sec"] == _pytest.approx(640.0)

    d = str(tmp_path)
    flush_leg(d, "bert_e2e", {"step_ms": 12.5, "telemetry": tel},
              backend="tpu")
    out = assemble(d, "bench")
    embedded = out["detail"]["bert_e2e"]["telemetry"]
    assert records_violations(embedded["records"]) == []
    # and the apply_perf_results auditor sees a clean artifact
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "apply_perf_results", os.path.join(ROOT, "tools",
                                           "apply_perf_results.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.telemetry_violations(out) == []


def test_leg_telemetry_lifts_mfu_and_hbm_into_gauges(tmp_path):
    """ISSUE 6 satellite: every leg embeds MFU + peak-HBM evidence as
    schema-valid gauges (bench.leg_telemetry), and the
    apply_perf_results perf-field audit accepts a leg that carries them
    and flags one that doesn't."""
    from apex_tpu.telemetry import records_violations
    bench = _load_bench()
    fields = {"mfu_pct": 41.2, "hbm_compiled_peak_bytes": 123456,
              "hbm_temp_bytes": 456}
    tel = bench.leg_telemetry([10.0], fields, counters={"examples": 4})
    assert records_violations(tel["records"]) == []
    gauges = {r["name"]: r["value"] for r in tel["records"]
              if r.get("type") == "gauge"}
    assert gauges["mfu_pct"] == 41.2
    assert gauges["mem.compiled_peak_bytes"] == 123456
    # the summary's memory line rides the same gauges
    assert tel["summary"]["mem_peak_bytes"] == 123456

    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "apply_perf_results", os.path.join(ROOT, "tools",
                                           "apply_perf_results.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    good = {"backend": "tpu",
            "detail": {"bert_e2e": {"step_ms": 10.0, "mfu_pct": 41.2,
                                    "hbm_compiled_peak_bytes": 123456,
                                    "telemetry": tel}}}
    assert mod.perf_field_violations(good) == []
    # gauges alone (no leg-dict fields) also satisfy the audit
    gauges_only = {"backend": "tpu",
                   "detail": {"bert_e2e": {"step_ms": 10.0,
                                           "telemetry": tel}}}
    assert mod.perf_field_violations(gauges_only) == []
    bare = {"backend": "tpu",
            "detail": {"bert_e2e": {
                "step_ms": 10.0,
                "telemetry": bench.telemetry_summary([10.0])}}}
    bad = mod.perf_field_violations(bare)
    assert any("peak-HBM" in v for v in bad)
    assert any("MFU" in v for v in bad)
    # hbm_util_pct is a RATIO, not the footprint — it must not satisfy
    # the byte-evidence requirement (the round-5 regression the audit
    # exists to catch)
    ratio_only = {"backend": "tpu",
                  "detail": {"bert_e2e": {
                      "step_ms": 10.0, "mfu_pct": 41.2,
                      "hbm_util_pct": 55.0,
                      "telemetry": bench.telemetry_summary([10.0])}}}
    assert any("peak-HBM" in v
               for v in mod.perf_field_violations(ratio_only))
    # CPU stand-in legs inside a mixed artifact are tagged _backend and
    # skipped — they honestly carry no MFU
    mixed = {"backend": "mixed",
             "detail": {"rn50": {
                 "step_ms": 10.0, "_backend": "cpu",
                 "telemetry": bench.telemetry_summary([10.0])}}}
    assert mod.perf_field_violations(mixed) == []


def test_mem_fields_compiled_footprint_on_cpu():
    """bench._mem_fields embeds the compiled memory_analysis footprint
    even on CPU (the allocator counters are TPU-only), so CPU runs and
    tier-1 exercise the exact field path the TPU legs emit."""
    import jax
    import jax.numpy as jnp
    bench = _load_bench()
    jitted = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    jitted(x)
    out = bench._mem_fields(jitted, (x,))
    assert "mem_error" not in out, out
    assert out["hbm_compiled_peak_bytes"] > 0
    assert out["hbm_args_bytes"] == 64 * 64 * 4
    # CPU allocator reports nothing -> no device fields, no error
    assert "hbm_device_process_peak_bytes" not in out


# ---------------------------------------------------------------------------
# run_bench integration: the flush sequence under a simulated mid-run wedge
# ---------------------------------------------------------------------------

class _Wedge(Exception):
    """Stands in for the run dying mid-bench (in reality: SIGKILL)."""


def _stub_timings(bench, monkeypatch, wedge_at=None):
    """Replace the slow timing fns with constants; ``wedge_at`` names the
    one that simulates the run dying mid-measurement."""
    vals = {"time_apex_xla": 28.8, "time_apex_fused_flat": 19.0,
            "time_optax": 29.4}

    def mk(name, v):
        def f(*a, **k):
            if name == wedge_at:
                raise _Wedge(name)
            return v
        return f

    for name, v in vals.items():
        monkeypatch.setattr(bench, name, mk(name, v))
    monkeypatch.setattr(bench, "bench_rn50",
                        mk("bench_rn50",
                           {"images_per_sec": 1.0, "batch": 4}))
    monkeypatch.setattr(bench, "bench_rn50_native_baseline",
                        mk("bench_rn50_native_baseline",
                           {"images_per_sec": 0.8, "batch": 4}))
    monkeypatch.setattr(bench, "bench_bert_e2e",
                        mk("bench_bert_e2e", {"step_ms": 2.0}))
    monkeypatch.setattr(bench, "bench_collectives",
                        mk("bench_collectives",
                           {"leg": "collectives",
                            "schemes": {"int8_blockscale":
                                        {"host_ms": 1.0, "ratio": 3.88}}}))
    monkeypatch.setattr(bench, "bench_update_sharding",
                        mk("bench_update_sharding",
                           {"leg": "update_sharding", "world": 8,
                            "opt_state_shrink": 7.9,
                            "modes": {"off": {"step_ms": 2.0},
                                      "zero1": {"step_ms": 1.5}}}))
    monkeypatch.setattr(bench, "bench_spmd",
                        mk("bench_spmd",
                           {"leg": "spmd", "chips": 8,
                            "families": {"dp_tp": {"step_ms": 2.0}}}))
    monkeypatch.setattr(bench, "bench_goodput",
                        mk("bench_goodput",
                           {"leg": "goodput", "steps": 10,
                            "goodput_fraction": 0.9}))
    monkeypatch.setattr(bench, "bench_overlap",
                        mk("bench_overlap",
                           {"leg": "overlap", "scheme": "fp32",
                            "parity_ok": True,
                            "logical_bytes_equal": True,
                            "modes": {"off": {"step_ms": 2.0},
                                      "bucketed": {"step_ms": 1.8}}}))
    monkeypatch.setattr(bench, "bench_ppep",
                        mk("bench_ppep",
                           {"leg": "ppep", "parity_ok": True,
                            "families": {"pp": {"parity_ok": True},
                                         "ep": {"parity_ok": True}}}))
    monkeypatch.setattr(bench, "bench_serve",
                        mk("bench_serve",
                           {"leg": "serve", "requests": 16,
                            "variants": [{"olevel": "bf16",
                                          "decode_width": 8,
                                          "tokens_per_sec": 1500.0}],
                            "winner": {"olevel": "bf16",
                                       "decode_width": 8,
                                       "tokens_per_sec": 1500.0}}))
    monkeypatch.setattr(bench, "bench_plan",
                        mk("bench_plan",
                           {"leg": "plan", "chips": 8,
                            "candidates_enumerated": 27,
                            "calibration_error_pct": 3.0,
                            "plans": [{"knobs": {"dp": 8},
                                       "predicted_ms": 1.9,
                                       "measured_ms": 2.0},
                                      {"knobs": {"dp": 8,
                                                 "update_sharding":
                                                 "zero1"},
                                       "predicted_ms": 1.6,
                                       "measured_ms": 1.5}]}))


def test_run_bench_flushes_headline_incrementally(tmp_path, monkeypatch):
    """Wedge during the fused timing: the already-measured xla number is
    on disk, complete=false, and no later leg files exist."""
    bench = _load_bench()
    _stub_timings(bench, monkeypatch, wedge_at="time_apex_fused_flat")
    d = str(tmp_path / "legs")
    with pytest.raises(_Wedge):
        bench.run_bench(legs_dir=d)
    legs = read_legs(d)
    assert set(legs) == {"headline"}
    head = legs["headline"]["data"]
    assert head["xla_impl_ms"] == 28.8
    assert head["complete"] is False
    assert "fused_flat_impl_ms" not in head
    # and the assembler turns the wreckage into a driver-shaped payload
    out = assemble(d, "bench")
    assert out["value"] == 28.8 and out["partial"] is True


def test_run_bench_full_flush_sequence(tmp_path, monkeypatch):
    """No wedge: headline (complete=true) + rn50 + bert legs all land,
    and the returned payload matches the legs.  Off-TPU, vs_baseline is
    null at top level with the ratio kept as an explicit cpu proxy."""
    import jax
    bench = _load_bench()
    _stub_timings(bench, monkeypatch)
    d = str(tmp_path / "legs")
    payload = bench.run_bench(legs_dir=d)
    legs = read_legs(d)
    rn50_key = ("rn50" if jax.default_backend() == "tpu"
                else "rn50_cpu_standin_resnet18")
    assert set(legs) == {"headline", rn50_key, "bert_e2e", "collectives",
                         "update_sharding", "plan", "spmd", "overlap",
                         "ppep", "goodput", "serve"}
    assert legs["ppep"]["data"]["leg"] == "ppep"
    assert legs["serve"]["data"]["leg"] == "serve"
    assert legs["collectives"]["data"]["leg"] == "collectives"
    assert legs["goodput"]["data"]["leg"] == "goodput"
    assert legs["overlap"]["data"]["leg"] == "overlap"
    assert legs["update_sharding"]["data"]["leg"] == "update_sharding"
    assert legs["plan"]["data"]["leg"] == "plan"
    assert legs["spmd"]["data"]["leg"] == "spmd"
    assert legs["headline"]["data"]["complete"] is True
    assert legs["headline"]["data"]["winner"] == "fused_flat"
    assert payload["value"] == 19.0
    assert payload["vs_baseline"] is None          # CPU in tests
    assert payload["detail"]["vs_baseline_cpu_proxy"] == pytest.approx(
        29.4 / 19.0, abs=1e-3)
    rn50 = payload["detail"][rn50_key]
    assert rn50["images_per_sec"] == 1.0
    # the same-batch native-optax baseline rides inside the rn50 leg with
    # the ready-made ratio (BASELINE's ">=90% of native" check)
    assert rn50["native_optax_baseline"]["images_per_sec"] == 0.8
    assert rn50["vs_native_baseline"] == pytest.approx(1.25, abs=1e-3)


def test_run_bench_without_legs_dir_still_returns_payload(monkeypatch):
    bench = _load_bench()
    _stub_timings(bench, monkeypatch)
    payload = bench.run_bench()     # legs_dir=None: flushing is a no-op
    assert payload["metric"] == "fused_lamb_step_ms_bert_large"
    assert payload["value"] == 19.0


# ---------------------------------------------------------------------------
# bench_kernels section-level resume: a fresh run skips already-captured
# sections instead of restarting at bench_attention
# ---------------------------------------------------------------------------

def _load_kernels():
    spec = importlib.util.spec_from_file_location(
        "bench_kernels", os.path.join(ROOT, "bench_kernels.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ab_rec(p, x):
    return {"pallas_ms": p, "xla_ms": x}


_SEQ_LABEL = "B8 H16 D64 fwd+bwd grads(q,k,v)"   # bench_kernels.ATTN_SWEEP_LABEL

_COMPLETE_LEGS = {
    "attention": {"flash_attn_fwd": _ab_rec(1.0, 1.5),
                  "flash_attn_fwdbwd": _ab_rec(2.0, 2.5),
                  "flash_attn_fwdbwd_qkv": _ab_rec(3.0, 3.5)},
    "xentropy": {"xentropy_fwd": _ab_rec(1.4, 2.7),
                 "xentropy_fwdbwd": _ab_rec(2.8, 5.4)},
    "layer_norm": {"layer_norm_fwd": _ab_rec(1.0, 1.0),
                   "layer_norm_fwdbwd": _ab_rec(1.0, 1.0)},
    "mlp": {"mlp_fwd": _ab_rec(1.0, 1.0), "mlp_fwdbwd": _ab_rec(1.0, 1.0)},
    "multi_tensor": {"l2norm": _ab_rec(1.0, 1.0),
                     "scale_flagged": _ab_rec(1.0, 1.0),
                     "axpby_flagged": _ab_rec(1.0, 1.0),
                     "adam_update": _ab_rec(1.0, 1.0),
                     "lamb_stage1": _ab_rec(1.0, 1.0)},
    # the sweep sections (flash_autotune, flash_bwd_autotune,
    # attn_seq_sweep) are injected per-test from the loaded module's own
    # ladder constants (drift guard: the bench loop, the completeness
    # row names, and this fixture share one constant — ADVICE r5 #2)
    "flash_vmem_probe": {"flash_vmem_probe": {"rows": []}},
}

_SECTION_FNS = ("bench_attention", "bench_xentropy",
                "bench_flash_bwd_autotune", "bench_layer_norm", "bench_mlp",
                "bench_multi_tensor", "bench_flash_autotune",
                "bench_attn_seq_sweep", "bench_flash_vmem_probe")


def _bwd_autotune_rec(bk, sweep):
    return {"shape": bk.FLASH_BWD_LABEL, "sweep_ms": sweep,
            "best": "128x128", "best_dq": "128x128",
            "best_dkv": "128x128", "best_fused": "128x128"}


def _complete_legs(bk):
    legs = dict(_COMPLETE_LEGS)
    assert bk.ATTN_SWEEP_LABEL == _SEQ_LABEL
    legs["attn_seq_sweep"] = {"attn_seq_sweep": {
        "shape": bk.ATTN_SWEEP_LABEL,
        "by_seq": {str(s): _ab_rec(1.0, 1.0)
                   for s in bk.ATTN_SWEEP_SEQS}}}
    legs["flash_autotune"] = {"flash_autotune": {
        "sweep_ms": {c: 1.0 for c in bk.FLASH_AUTOTUNE_LADDER},
        "best": "128x512"}}
    legs["flash_bwd_autotune"] = {"flash_bwd_autotune": _bwd_autotune_rec(
        bk, {r: 1.0 for r in bk.FLASH_BWD_ROWS})}
    return legs


def _patch_sections(bk, monkeypatch, calls):
    for name in _SECTION_FNS:
        def rec(results, on_tpu, flush=None, _n=name):
            calls.append(_n)
        rec.__name__ = name   # run() derives the leg name from fn.__name__
        monkeypatch.setattr(bk, name, rec)


def test_kernel_bench_resume_skips_complete_sections(tmp_path, monkeypatch):
    bk = _load_kernels()
    monkeypatch.setattr(bk.jax, "default_backend", lambda: "tpu")
    d = str(tmp_path / "legs")
    for leg, data in _complete_legs(bk).items():
        flush_leg(d, leg, data, backend="tpu")
    calls = []
    _patch_sections(bk, monkeypatch, calls)
    out = bk.run(legs_dir=d)
    assert calls == []                       # every section skipped
    assert out["kernels"]["xentropy_fwd"] == _ab_rec(1.4, 2.7)
    assert out["backend"] == "tpu"


def test_kernel_bench_resume_reruns_incomplete_sweep(tmp_path, monkeypatch):
    bk = _load_kernels()
    monkeypatch.setattr(bk.jax, "default_backend", lambda: "tpu")
    d = str(tmp_path / "legs")
    legs = _complete_legs(bk)
    # seq sweep captured only 3 of 6 rows; attention leg predates the
    # fwdbwd_qkv key (the r5 first capture's exact shape)
    legs["attn_seq_sweep"] = {"attn_seq_sweep": {
        "shape": _SEQ_LABEL,
        "by_seq": {"64": _ab_rec(1.0, 1.0), "128": _ab_rec(1.0, 1.0),
                   "256": _ab_rec(1.0, 1.0)}}}
    legs["attention"] = {"flash_attn_fwd": {"pallas_ms": 0.0},
                         "flash_attn_fwdbwd": {"pallas_ms": 192.9}}
    for leg, data in legs.items():
        flush_leg(d, leg, data, backend="tpu")
    calls = []
    _patch_sections(bk, monkeypatch, calls)

    def remeasuring_attention(results, on_tpu, flush=None):
        calls.append("bench_attention")
        results["flash_attn_fwd"] = {"pallas_ms": 5.5}   # repaired reading
    remeasuring_attention.__name__ = "bench_attention"
    monkeypatch.setattr(bk, "bench_attention", remeasuring_attention)
    bk.run(legs_dir=d)
    assert calls == ["bench_attention", "bench_attn_seq_sweep"]
    # a re-run section re-flushes its declared keys: the stale 0.0 ms
    # reading in the leg file must be repaired, not frozen forever by
    # the resume seeding (code-review r5)
    att = read_legs(d)["attention"]["data"]
    assert att["flash_attn_fwd"] == {"pallas_ms": 5.5}


def test_kernel_bench_cpu_run_ignores_tpu_legs(tmp_path, monkeypatch):
    """A CPU fallback must not seed TPU numbers into its own payload."""
    bk = _load_kernels()
    d = str(tmp_path / "legs")
    for leg, data in _complete_legs(bk).items():
        flush_leg(d, leg, data, backend="tpu")
    calls = []
    _patch_sections(bk, monkeypatch, calls)
    out = bk.run(legs_dir=d)                 # ambient backend = cpu
    assert len(calls) == len(_SECTION_FNS)   # nothing skipped
    assert "xentropy_fwd" not in out["kernels"]


def test_kernel_bench_transient_failure_rows_do_not_settle(tmp_path,
                                                           monkeypatch):
    """A mid-sweep transient failure recorded as an error row must re-run on
    the next run; a permanent (Mosaic/compile) failure must not."""
    bk = _load_kernels()
    monkeypatch.setattr(bk.jax, "default_backend", lambda: "tpu")
    d = str(tmp_path / "legs")
    legs = _complete_legs(bk)
    sweep = {r: 1.0 for r in bk.FLASH_BWD_ROWS}
    flaky_row = bk.FLASH_BWD_ROWS[0]
    sweep[flaky_row] = "failed: XlaRuntimeError('INTERNAL: stream closed')"
    legs["flash_bwd_autotune"] = {
        "flash_bwd_autotune": _bwd_autotune_rec(bk, sweep)}
    for leg, data in legs.items():
        flush_leg(d, leg, data, backend="tpu")
    calls = []
    _patch_sections(bk, monkeypatch, calls)
    bk.run(legs_dir=d)
    assert calls == ["bench_flash_bwd_autotune"]    # transient -> retry

    # flip the row to a permanent Mosaic failure: now settled, no re-run
    sweep[flaky_row] = "failed: Mosaic lowering: RESOURCE_EXHAUSTED vmem"
    flush_leg(d, "flash_bwd_autotune", {
        "flash_bwd_autotune": _bwd_autotune_rec(bk, sweep)}, backend="tpu")
    calls.clear()
    bk.run(legs_dir=d)
    assert calls == []


def test_kernel_bench_ladder_revision_reopens_sweep(tmp_path, monkeypatch):
    """A leg captured by an OLDER ladder (enough settled rows to fool a
    count, but different row names/label) must not freeze the section
    "complete" — completeness keys on the current ladder's row NAMES
    (ADVICE r5 #2: the r5 gate counted 8 settled rows, so the r5-shaped
    record below would have skipped the rebuilt per-kernel sweep forever)."""
    bk = _load_kernels()
    monkeypatch.setattr(bk.jax, "default_backend", lambda: "tpu")
    d = str(tmp_path / "legs")
    legs = _complete_legs(bk)
    legs["flash_bwd_autotune"] = {"flash_bwd_autotune": {
        "shape": "B8 H16 S1024 D64 causal bwd-only(dq,dk,dv)",
        "sweep_ms": {c: 1.0 for c in ("128x128", "128x256", "256x256",
                                      "256x512", "512x512", "512x1024",
                                      "1024x1024", "jax_ref_fwdbwd")},
        "best": "128x128"}}
    for leg, data in legs.items():
        flush_leg(d, leg, data, backend="tpu")
    calls = []
    _patch_sections(bk, monkeypatch, calls)
    bk.run(legs_dir=d)
    assert calls == ["bench_flash_bwd_autotune"]


def test_kernel_bench_seq_sweep_stale_semantics_reset(tmp_path, monkeypatch):
    """by_seq rows measured by an older revision (different shape label)
    must not satisfy completeness nor leak into the new sweep."""
    bk = _load_kernels()
    monkeypatch.setattr(bk.jax, "default_backend", lambda: "tpu")
    d = str(tmp_path / "legs")
    legs = _complete_legs(bk)
    legs["attn_seq_sweep"] = {"attn_seq_sweep": {
        "shape": "B8 H16 D64 fwd+bwd(dq)",          # the r4 measurement
        "by_seq": {str(s): _ab_rec(1.0, 1.0)
                   for s in (64, 128, 256, 512, 1024, 2048)}}}
    for leg, data in legs.items():
        flush_leg(d, leg, data, backend="tpu")
    calls = []
    _patch_sections(bk, monkeypatch, calls)
    bk.run(legs_dir=d)
    assert calls == ["bench_attn_seq_sweep"]
