"""The ``laguna_pretrain`` job, its counts and its readers, on the CPU.

- the cell's code path end to end at a tiny size, from a manifest of its own
  (``tiny_laguna/``): the adapter drives the example's ``--laguna`` preset,
  so the test — not an option of the program — swaps the preset's
  published widths for tiny ones;
- ``flops_laguna.py`` agrees with the count by hand;
- the band's readers read the kernels and the scope they name, and nothing
  from a program without them.
"""
import collections
import json
import os
import types

import pytest

from benchmarks import flops_laguna, run, scopes
from benchmarks.job import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "tiny_laguna", "BENCHMARK.json")
CELL = "laguna_s_2_1.ep32_s8192"

#: per-layer metrics that are counts, and so may be reported off the chip
COUNTS = {"amp_skipped_steps", "expert_load_max_over_mean"}
#: what the share holds is the cut's, not a width
HELD = ("vocab_size", "experts_held")


def _tiny_config():
    with open(os.path.join(HERE, "tiny_laguna", "cells", "configs",
                           "tiny_laguna.json")) as f:
        return json.load(f)


def _published_model():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "laguna_s_2_1.json")) as f:
        return json.load(f)["model"]


@pytest.fixture
def tiny_widths(monkeypatch):
    """``--laguna`` builds ``laguna_s_2_1_config(5, **the cut)``: give that
    name tiny widths and a tiny five-layer pattern (the cut's share of the
    experts is read off the whole model's count, so that is kept too)."""
    import apex_tpu.models
    from apex_tpu.models import LagunaConfig
    widths = {k: tuple(v) if isinstance(v, list) else v
              for k, v in _tiny_config()["model"].items() if k not in HELD}
    monkeypatch.setattr(
        apex_tpu.models, "laguna_s_2_1_config",
        lambda layers=5, **cut: LagunaConfig(**dict(widths, **cut)))


@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearses(trace, tiny_widths, capfd):
    result = run.run_cell("tiny_laguna.s64", 0, 0.5, trace,
                          manifest_path=TINY, rehearse=True)
    json.dumps(result)
    assert result["correct"] is True, result
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    logged = capfd.readouterr().out
    assert '"chosen_differently"' in logged
    assert ("routing probe over the ring" in logged) == trace
    if trace:
        assert "dropped 0" in logged and "walks of the dispatch" in logged
        assert "[1, 1, 1, 1]" in logged        # four sparse layers, a batch
        # off the chip: counts only, never a time, a rate or a share
        assert set(result["metrics"]) == COUNTS
    else:
        assert result["metrics"] == {}


def test_job_holds_the_program_to_every_key_of_the_model(tiny_widths):
    import jax
    manifest = run.Manifest(TINY)
    config = _tiny_config()
    config["model"]["sliding_window"] = 16
    adapter = load_module(manifest.find("jobs", "laguna_pretrain.py"),
                          "laguna_pretrain_under_test")
    with pytest.raises(ValueError, match="sliding_window"):
        adapter.build(config, manifest.load_json(
            "workloads", "tiny_laguna.s64.json"), 0, jax.devices()[:1],
            manifest.find("reference", "laguna_s_2_1.py"))


def test_the_manifest_names_the_cell_and_its_readers():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    cell = next(w for w in doc["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["chips"]) == ("laguna_s_2_1", 1)
    mine = [m["name"] for m in doc["per_layer"]
            if m.get("workloads") == [CELL]]
    assert mine == ["window_flash_fwd_roofline", "window_flash_bwd_roofline",
                    "window_attn_time_share"]
    joined = {m["name"] for m in doc["per_layer"]
              if CELL in m.get("workloads", ())}
    assert joined == {"moe_time_share", "expert_matmul_roofline",
                      "expert_load_max_over_mean", "flash_fwd_roofline",
                      "flash_bwd_roofline", "recompute_time_share",
                      "head_loss_time_share", "scope_coverage_share", *mine}
    for name in joined:
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", name + ".py"))
    with open(os.path.join(ROOT, "benchmarks", "workloads",
                           CELL + ".json")) as f:
        traffic = json.load(f)
    assert (traffic["batch"], traffic["seq"], traffic["ring"],
            traffic["sync_every"], traffic["reference_samples"]) == (
        1, 8192, 8, 2, 2)


def test_flops_per_token_by_hand():
    """The cut at the published widths: a full layer's attention 44.19 M in
    products, a window layer's 63.14 M, a sparse FFN 13.17 M met by a token
    (router 0.79 + shared expert 9.44 and its gate + 10·8/256 of a 9.44 M
    expert), the dense FFN 113.2 M, the head 38.5 M; the causal cores
    8192·48·256 a full layer, a window layer's over the band's 4 063 488
    pairs a head."""
    model = _published_model()
    d = 3072
    full = 2 * d * 6144 + 2 * d * 1024 + d * 48
    window = 2 * d * 9216 + 2 * d * 1024 + d * 72
    ffn = d * 256 + 3 * d * 1024 + d + 10 * 8 / 256 * 3 * d * 1024
    n = full + 3 * d * 12288 + 3 * (window + ffn) + full + ffn + 12544 * d
    assert (full, window) == (44_187_648, 63_135_744)
    assert flops_laguna.matmul_params_per_token(model) == n
    pairs = 512 * 513 // 2 + (8192 - 512) * 512
    assert flops_laguna.band_pairs(8192, 512) == pairs == 4_063_488
    cores = 2 * 8192 * 48 * 256 + 3 * 4 * 128 * 72 * pairs / 8192
    assert flops_laguna.attention_flops_per_token(model, 8192) == \
        pytest.approx(cores)
    # 1.22 GFLOP forward a token, of which the window layers' mixers 36 %
    assert 2 * n + cores == pytest.approx(1.220e9, rel=2e-3)
    assert 3 * (2 * window + 4 * 128 * 72 * pairs / 8192) / (2 * n + cores) \
        == pytest.approx(0.356, abs=2e-3)
    # the band against a causal kernel at the cell's shape: a forward call
    # is 150 GFLOP over 72 heads, 8.3 x fewer pairs than the causal half
    fwd, nbytes = flops_laguna.band_kernel_cost(72, 8192, 128, 512, "fwd")
    assert fwd == pytest.approx(149.8e9, rel=1e-3)
    assert nbytes == 4 * 72 * 8192 * 128 * 2
    assert 8192 * 8193 / 2 / pairs == pytest.approx(8.26, abs=0.01)


def _fake_run(selfs, paths, facts=None, n_steps=8):
    """A run whose one chip spent ``selfs`` [(event, ns)] and nothing else."""
    names = scopes.Names(paths, frozenset())
    busy = sum(ns for _, ns in selfs)

    def count(predicate):
        return sum(1 for ev, _ in selfs if predicate(ev))

    def self_ns(predicate):
        return sum(ns for ev, ns in selfs if predicate(ev))
    dev = types.SimpleNamespace(count=count, self_ns=self_ns)
    trace = types.SimpleNamespace(
        n_steps=n_steps, devices=[dev], share_of_busy=lambda predicate:
        100.0 * self_ns(predicate) / busy)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    return types.SimpleNamespace(
        trace=trace, peaks=peaks,
        job=types.SimpleNamespace(facts=facts or {})), names


Event = collections.namedtuple("Event", "name start_ns dur_ns")


def _read(name):
    return load_module(os.path.join(ROOT, "benchmarks", "layer_metrics",
                                    name + ".py"), name).read


def test_the_band_readers(monkeypatch, capsys):
    """Three band forwards of 1 ms, three backwards of 2 + 1 ms, one causal
    forward of 3 ms: the least time of a call over the time it took; the
    causal kernels are not the band's, and a program without the band
    kernels (the parent's) reads nothing, never 0."""
    facts = {"window": {"batch_heads": 72, "seq": 8192, "head_dim": 128,
                        "window": 512, "itemsize": 2},
             "attention": {"batch_heads": 48, "seq": 8192, "head_dim": 128,
                           "causal": True, "itemsize": 2}}
    ev = {k: Event(f"%{k}.1 = bf16[8]{{0}} custom-call(%a)", 0, 0)
          for k in ("apex_flash_window_fwd", "apex_flash_window_bwd_dq",
                    "apex_flash_window_bwd_dkv", "apex_flash_fwd")}
    selfs = [(ev["apex_flash_window_fwd"], 1e6)] * 3 \
        + [(ev["apex_flash_window_bwd_dq"], 2e6)] * 3 \
        + [(ev["apex_flash_window_bwd_dkv"], 1e6)] * 3 \
        + [(ev["apex_flash_fwd"], 3e6)]
    run_, _ = _fake_run(selfs, {}, facts)
    peaks = run_.peaks
    least = {p: max(f / peaks["bf16_flops_per_s"], b / peaks["hbm_bytes_per_s"])
             for p in ("fwd", "bwd") for f, b in [
                 flops_laguna.band_kernel_cost(72, 8192, 128, 512, p)]}
    assert _read("window_flash_fwd_roofline")(run_) == pytest.approx(
        100 * least["fwd"] / 1e-3)
    assert _read("window_flash_bwd_roofline")(run_) == pytest.approx(
        100 * least["bwd"] / 3e-3)
    logged = capsys.readouterr().out
    assert "13.89 us a head" in logged            # 1 ms over 72 heads
    assert "62.50 us a head" in logged            # the causal 3 ms over 48
    parent, _ = _fake_run(selfs[-1:], {}, facts)
    assert _read("window_flash_fwd_roofline")(parent) is None
    assert _read("window_flash_bwd_roofline")(parent) is None


def test_the_window_attention_reader(monkeypatch):
    """A step of 100 ns: a window mixer's band kernel 20, its projections
    15, its glue 10; the full layers' mixer 5, the rest 50."""
    paths = {
        "band": "jit(step)/apex.swa/apex.flash/apex_flash_window_fwd",
        "proj": "jit(step)/transpose(jvp(apex.swa))/dot_general",
        "glue": "jit(step)/apex.swa/mul",
        "full": "jit(step)/apex.attn/dot_general",
        "rest": "jit(step)/apex.moe/add"}
    events = {k: Event(f"%{k} = f32[8]{{0}} fusion(%a)", 0, 0) for k in paths}
    names_of = {v.name.split(" ")[0][1:]: paths[k] for k, v in events.items()}
    selfs = [(events["band"], 20), (events["proj"], 15),
             (events["glue"], 10), (events["full"], 5), (events["rest"], 50)]
    run_, names = _fake_run(selfs, names_of)
    monkeypatch.setattr(scopes, "seen", lambda run: names)
    read = _read("window_attn_time_share")
    assert read(run_) == pytest.approx(45.0)
    run_, names = _fake_run(selfs[-2:], names_of)
    assert read(run_) is None
    monkeypatch.setattr(scopes, "seen", lambda run: None)
    assert read(run_) is None


def test_the_e4m3_control_goes_through_the_cells_own_check(tiny_widths):
    """``reference/laguna_s_2_1_e4m3.py``: rounds parameters and the input
    of every norm to 3 mantissa bits, passes the gradient, and takes the
    plain reference's place in the job's own check (what it reads at the
    published widths is the chip's to say)."""
    control = load_module(os.path.join(
        ROOT, "benchmarks", "reference", "laguna_s_2_1_e4m3.py"),
        "laguna_e4m3_under_test")
    check = control.outcome(0, TINY, "tiny_laguna.s64")
    assert set(check) >= {"ok", "errors", "limits", "routing"}
    assert check["errors"]["param_abs_sum_rel"] == 0.0
    assert check["errors"]["loss_rel"] > 0.0
    # the plain reference of the adapter's own copy stayed plain
    assert control._plain._rms is control._plain_rms
