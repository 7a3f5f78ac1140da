"""The ``glm4_moe_lite_pretrain`` job, its counts and its readers, on the CPU.

- the cell's code path end to end at a tiny size, from a manifest of its own
  (``tiny_glm4_moe_lite/``): the adapter drives the example's
  ``--glm4-moe-lite`` preset, so the test — not an option of the program —
  swaps the preset's published widths for tiny ones;
- ``flops_glm47_flash_30b_a3b.py`` agrees with the count by hand;
- the three readers of the latent-attention and MTP blocks read the scopes
  they name, and nothing from a program without them.
"""
import collections
import json
import os
import types

import jax
import pytest

from benchmarks import flops_glm47_flash_30b_a3b as flops_glm, run, scopes
from benchmarks.job import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "tiny_glm4_moe_lite", "BENCHMARK.json")
CELL = "glm47_flash_30b_a3b.ep8_s4096"

#: per-layer metrics that are counts, and so may be reported off the chip
COUNTS = {"amp_skipped_steps", "expert_load_max_over_mean"}
#: what the share holds is the cut's, not a width
HELD = ("vocab_size", "num_hidden_layers", "experts_held")


def _tiny_config():
    with open(os.path.join(HERE, "tiny_glm4_moe_lite", "cells", "configs",
                           "tiny_glm4_moe_lite.json")) as f:
        return json.load(f)


def _published_model():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "glm47_flash_30b_a3b.json")) as f:
        return json.load(f)["model"]


@pytest.fixture
def tiny_widths(monkeypatch):
    """``--glm4-moe-lite`` builds ``glm47_flash_config(**the cut)``: give
    that name tiny widths (the cut's share of the experts and its depth are
    read off the whole model's counts, so those are kept too)."""
    import apex_tpu.models
    from apex_tpu.models import Glm4MoeLiteConfig
    widths = {k: v for k, v in _tiny_config()["model"].items()
              if k not in HELD}
    monkeypatch.setattr(
        apex_tpu.models, "glm47_flash_config",
        lambda **cut: Glm4MoeLiteConfig(**dict(widths, **cut)))


@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearses(trace, tiny_widths, capfd):
    result = run.run_cell("tiny_glm4_moe_lite.s64", 0, 0.5, trace,
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
        # the trunk's two sparse layers and the MTP module's, a batch
        assert "[1, 1, 1]" in logged
        # off the chip: counts only, never a time, a rate or a share
        assert set(result["metrics"]) == COUNTS
        assert result["metrics"]["expert_load_max_over_mean"]["value"] >= 1.0
    else:
        assert result["metrics"] == {}


def test_job_holds_the_program_to_every_key_of_the_model(tiny_widths):
    manifest = run.Manifest(TINY)
    config = _tiny_config()
    config["model"]["mtp_loss_weight"] = 0.5
    adapter = load_module(manifest.find("jobs", "glm4_moe_lite_pretrain.py"),
                          "glm4_moe_lite_pretrain_under_test")
    with pytest.raises(ValueError, match="mtp_loss_weight"):
        adapter.build(config, manifest.load_json(
            "workloads", "tiny_glm4_moe_lite.s64.json"), 0,
            jax.devices()[:1],
            manifest.find("reference", "glm47_flash_30b_a3b.py"))


def test_the_e4m3_control_goes_through_the_cells_own_check(tiny_widths):
    """``reference/glm47_flash_30b_a3b_e4m3.py``: rounds parameters and the
    input of every norm to 3 mantissa bits, passes the gradient, and takes
    the plain reference's place in the job's own ``reference_outcome``
    (what it reads at the published widths is the chip's to say)."""
    control = load_module(os.path.join(
        ROOT, "benchmarks", "reference", "glm47_flash_30b_a3b_e4m3.py"),
        "glm47_flash_e4m3_under_test")
    check = control.outcome(0, TINY, "tiny_glm4_moe_lite.s64")
    assert set(check) >= {"ok", "errors", "limits", "routing"}
    assert check["errors"]["param_abs_sum_rel"] == 0.0
    assert check["errors"]["loss_rel"] > 0.0
    # the plain reference of the adapter's own copy stayed plain
    assert control._plain._rms is control._plain_rms


def test_the_manifest_names_the_cell_and_its_readers():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    cell = next(w for w in doc["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["chips"]) == ("glm47_flash_30b_a3b", 1)
    mine = [m["name"] for m in doc["per_layer"]
            if m.get("workloads") == [CELL]]
    assert mine == ["mla_time_share", "mla_glue_share", "mtp_time_share"]
    joined = {m["name"] for m in doc["per_layer"]
              if CELL in m.get("workloads", ())}
    assert joined == {"moe_time_share", "expert_matmul_roofline",
                      "expert_load_max_over_mean", "latent_dispatch_share",
                      "flash_fwd_roofline", "flash_bwd_roofline",
                      "recompute_time_share", "head_loss_time_share",
                      "scope_coverage_share", *mine}
    for name in joined:
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", name + ".py"))
    with open(os.path.join(ROOT, "benchmarks", "workloads",
                           CELL + ".json")) as f:
        traffic = json.load(f)
    assert (traffic["batch"], traffic["seq"], traffic["ring"],
            traffic["sync_every"], traffic["reference_samples"]) == (
        4, 4096, 8, 2, 2)


def test_flops_per_token_by_hand():
    """The cut at the published widths: an MLA mixer 21.757 952 M in
    products, a sparse FFN 10.617 M met by a token (router 0.131 + shared
    expert 9.437 + 4·8/64 of a 9.437 M expert), the dense FFN 62.9 M, the
    MTP join 8.39 M, the head 39.85 M twice; the causal cores 2·4096·20·256
    a layer, six layers."""
    model = _published_model()
    d = 2048
    mla = d * 768 + 768 * 5120 + d * 576 + 512 * 8960 + 5120 * d
    ffn = d * 64 + 3 * d * 1536 + 4 * 8 / 64 * 3 * d * 1536
    head = 19456 * d
    n = 5 * mla + 3 * d * 10240 + 4 * ffn + head \
        + 2 * d * d + mla + ffn + head
    assert mla == 21_757_952
    assert flops_glm.matmul_params_per_token(model) == n
    causal = 6 * 4096 * 20 * 512
    assert flops_glm.attention_flops_per_token(model, 4096) == causal
    assert flops_glm.train_flops_per_token(model, 4096) == \
        3 * (2 * n + causal)
    # 957 M forward FLOPs a token; 47.1 TFLOP a step of 4 x 4096
    assert 2 * n + causal == pytest.approx(957e6, rel=2e-3)
    assert 4 * flops_glm.train_flops_per_sample(model, 4096) == \
        pytest.approx(47.05e12, rel=2e-3)


def _fake_run(selfs, paths, n_steps=8):
    """A run whose one chip spent ``selfs`` [(event, ns)] and nothing else."""
    names = scopes.Names(paths, frozenset())
    busy = sum(ns for _, ns in selfs)
    trace = types.SimpleNamespace(
        n_steps=n_steps, share_of_busy=lambda predicate: 100.0 * sum(
            ns for ev, ns in selfs if predicate(ev)) / busy)
    return types.SimpleNamespace(
        trace=trace, job=types.SimpleNamespace(facts={})), names


def test_the_latent_attention_and_mtp_readers(monkeypatch):
    """A step of 100 ns: the MLA mixer's flash kernel 20, its projections
    15, its glue 10 — of which 4 in the MTP module's mixer —, the MTP join
    5, the rest 50."""
    Event = collections.namedtuple("Event", "name start_ns dur_ns")
    paths = {
        "flash": "jit(step)/apex.mla/apex.flash/apex_flash_fwd",
        "proj": "jit(step)/transpose(jvp(apex.mla))/dot_general",
        "glue": "jit(step)/apex.mla/concatenate",
        "mtp_glue": "jit(step)/apex.mtp/checkpoint/apex.mla/mul",
        "join": "jit(step)/apex.mtp/dot_general",
        "rest": "jit(step)/apex.moe/add"}
    kinds = {"flash": "%apex_flash_fwd.3 = bf16[8]{0} custom-call(%a)",
             "proj": "%dot.1 = bf16[8]{0} dot(%a, %b)",
             "join": "%dot.2 = bf16[8]{0} dot(%a, %b)"}
    events = {k: Event(kinds.get(k, f"%{k} = f32[8]{{0}} fusion(%a)"), 0, 0)
              for k in paths}
    names_of = {v.name.split(" ")[0][1:]: paths[k] for k, v in events.items()}
    selfs = [(events["flash"], 20), (events["proj"], 15),
             (events["glue"], 6), (events["mtp_glue"], 4),
             (events["join"], 5), (events["rest"], 50)]
    run_, names = _fake_run(selfs, names_of)
    monkeypatch.setattr(scopes, "seen", lambda run: names)
    read = {name: load_module(os.path.join(
        ROOT, "benchmarks", "layer_metrics", name + ".py"), name).read
        for name in ("mla_time_share", "mla_glue_share", "mtp_time_share")}
    assert read["mla_time_share"](run_) == pytest.approx(45.0)
    assert read["mla_glue_share"](run_) == pytest.approx(10.0)
    assert read["mtp_time_share"](run_) == pytest.approx(9.0)
    # a program without the scopes (the parent's): nothing, never 0
    run_, names = _fake_run(selfs[-1:], names_of)
    assert all(r(run_) is None for r in read.values())
    monkeypatch.setattr(scopes, "seen", lambda run: None)
    assert all(r(run_) is None for r in read.values())
