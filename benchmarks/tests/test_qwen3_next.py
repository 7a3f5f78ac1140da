"""The ``qwen3_next_pretrain`` job, its counts and its readers, on the CPU.

- the cell's code path end to end at a tiny size, from a manifest of its own
  (``tiny_qwen3_next/``): the adapter drives the example's ``--qwen3-next``
  preset, so the test — not an option of the program — swaps the preset's
  published widths for tiny ones;
- ``flops_qwen3_next.py`` agrees with the count by hand;
- ``gated_delta_rule_cost`` is the recurrence's work, whatever implements the
  rule, and ``gdn_rule_roofline`` reads the passes from the trace's phases.
"""
import collections
import inspect
import json
import os
import types

import jax
import pytest

from benchmarks import flops_qwen3_next, run, scopes
from benchmarks.job import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "tiny_qwen3_next", "BENCHMARK.json")
CELL = "qwen3_next_80b_a3b.ep16_s4096"

#: per-layer metrics that are counts, and so may be reported off the chip
COUNTS = {"amp_skipped_steps", "expert_load_max_over_mean"}
#: what the share holds is the cut's, not a width
HELD = ("vocab_size", "num_hidden_layers", "experts_held")


def _tiny_model():
    with open(os.path.join(HERE, "tiny_qwen3_next", "cells", "configs",
                           "tiny_qwen3_next.json")) as f:
        return json.load(f)["model"]


def _published_model():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "qwen3_next_80b_a3b.json")) as f:
        return json.load(f)["model"]


@pytest.fixture
def tiny_widths(monkeypatch):
    """``--qwen3-next`` builds ``qwen3_next_80b_a3b_config(**the cut)``: give
    that name tiny widths (the cut's share of the experts is read off the
    whole model's count, so that is kept too)."""
    import apex_tpu.models
    from apex_tpu.models import Qwen3NextConfig
    widths = {k: v for k, v in _tiny_model().items() if k not in HELD}
    monkeypatch.setattr(
        apex_tpu.models, "qwen3_next_80b_a3b_config",
        lambda **cut: Qwen3NextConfig(**dict(widths, **cut)))


@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearses(trace, tiny_widths, capfd):
    result = run.run_cell("tiny_qwen3_next.s64", 0, 0.5, trace,
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
        # off the chip: counts only, never a time, a rate or a share
        assert set(result["metrics"]) == COUNTS
        assert result["metrics"]["expert_load_max_over_mean"]["value"] >= 1.0
    else:
        assert result["metrics"] == {}


def test_job_holds_the_program_to_every_key_of_the_model(tiny_widths):
    manifest = run.Manifest(TINY)
    with open(os.path.join(manifest.root, "cells", "configs",
                           "tiny_qwen3_next.json")) as f:
        config = json.load(f)
    config["model"]["linear_key_head_dim"] = 16
    adapter = load_module(manifest.find("jobs", "qwen3_next_pretrain.py"),
                          "qwen3_next_pretrain_under_test")
    with pytest.raises(ValueError, match="linear_key_head_dim"):
        adapter.build(config, manifest.load_json(
            "workloads", "tiny_qwen3_next.s64.json"), 0, jax.devices()[:1],
            manifest.find("reference", "qwen3_next_80b_a3b.py"))


def test_the_e4m3_control_goes_through_the_cells_own_check(tiny_widths):
    """``reference/qwen3_next_80b_a3b_e4m3.py``: rounds to 3 mantissa bits,
    passes the gradient, and takes the plain reference's place in the job's
    own ``reference_outcome`` (what it reads at the published widths is the
    chip's to say: the tiny cell's limits are loose on purpose)."""
    import jax.numpy as jnp
    control = load_module(os.path.join(
        ROOT, "benchmarks", "reference", "qwen3_next_80b_a3b_e4m3.py"),
        "qwen3_next_e4m3_under_test")
    x = jnp.asarray([1.0, 1.0625, 1.07, 0.3, 300.0, 0.022])
    got = control.e4m3(x)
    assert got[:4].tolist() == [1.0, 1.0, 1.125, 0.3125]
    assert got[5] != x[5] and abs(float(got[5]) - 0.022) < 0.002
    assert jax.grad(lambda x: jnp.sum(control.e4m3(x) ** 2))(x)[2] \
        == pytest.approx(2 * 1.125)
    check = control.outcome(0, TINY, "tiny_qwen3_next.s64")
    assert set(check) >= {"ok", "errors", "limits", "routing"}
    assert check["errors"]["param_abs_sum_rel"] == 0.0
    assert check["errors"]["loss_rel"] > 0.0
    # the plain reference of the adapter's own copy stayed plain
    assert control._plain._rms is control._plain_rms


def test_the_manifest_names_the_cells_and_their_readers():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    cell = next(w for w in doc["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["chips"]) == ("qwen3_next_80b_a3b", 1)
    assert "bert_large.s128_b544" in [w["name"] for w in doc["workloads"]]
    # no pin to the manifest's tail or to a list's whole content: a later PR
    # appends metrics and cells (such pins went red in test_scopes.py with
    # PR 27 and in test_nemotron_h.py with this PR)
    mine = [m["name"] for m in doc["per_layer"]
            if m.get("workloads") == [CELL]]
    assert mine == ["gdn_time_share", "gdn_rule_roofline"]
    joined = {m["name"] for m in doc["per_layer"]
              if CELL in m.get("workloads", ())}
    assert joined >= {"moe_time_share", "expert_matmul_roofline",
                      "expert_load_max_over_mean", "latent_dispatch_share",
                      "flash_fwd_roofline", "flash_bwd_roofline",
                      "recompute_time_share", "head_loss_time_share",
                      "scope_coverage_share", *mine}
    assert not joined & {"optimizer_step_ms", "optimizer_bw_share"}
    for name in joined:
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", name + ".py"))
    with open(os.path.join(ROOT, "benchmarks", "workloads",
                           CELL + ".json")) as f:
        traffic = json.load(f)
    assert (traffic["seq"], traffic["ring"], traffic["sync_every"],
            traffic["reference_samples"]) == (4096, 8, 2, 2)
    with open(os.path.join(ROOT, "benchmarks", "workloads",
                           "bert_large.s128_b544.json")) as f:
        b544 = json.load(f)
    with open(os.path.join(ROOT, "benchmarks", "workloads",
                           "bert_large.s128.json")) as f:
        s128 = json.load(f)
    told = ("batch", "job", "exercises", "bypasses")
    assert b544["batch"] == 544
    assert {k: v for k, v in b544.items() if k not in told} \
        == {k: v for k, v in s128.items() if k not in told}


def test_flops_per_token_by_hand():
    """The cut at the published widths: a Gated DeltaNet mixer 33.69 M in
    products (W_qkvz 2048 x 12 288, W_ba 2048 x 64, W_out 4096 x 2048), the
    attention mixer 27.26 M, a sparse FFN 6.16 M met by a token (router 1.05
    + shared expert 3.15 + its gate + 10·32/512 of a 3.146 M expert), head
    39.06 M."""
    model = _published_model()
    d = 2048
    gdn = d * 12288 + d * 64 + 4096 * d
    attn = d * 8192 + 2 * d * 512 + 4096 * d
    ffn = d * 512 + 3 * d * 512 + d + 10 * 32 / 512 * 3 * d * 512
    n = 3 * gdn + attn + 4 * ffn + 19072 * d
    assert flops_qwen3_next.matmul_params_per_token(model) == n
    assert gdn == pytest.approx(33.69e6, rel=1e-3)
    assert ffn == pytest.approx(6.16e6, rel=2e-3)
    rule = 3 * 22 * 32 * 128 * 128
    assert flops_qwen3_next.rule_flops_per_token(model) == rule
    assert flops_qwen3_next.train_flops_per_token(model, 4096) == \
        6 * n + 3 * 2 * 4096 * 4096 + rule
    assert flops_qwen3_next.train_flops_per_sample(model, 4096) == \
        4096 * (6 * n + 3 * 2 * 4096 * 4096 + rule)


def test_rule_cost_is_the_recurrences_whatever_implements_it():
    """No chunk, block or implementation among its arguments; it follows the
    tokens, and the backward moves twice the forward's bytes."""
    f1, b1 = flops_qwen3_next.gated_delta_rule_cost(
        32768, 32, 16, 128, 128, "fwd")
    assert f1 == 7 * 32768 * 32 * 128 * 128
    assert b1 == 32768 * ((2 * 16 * 128 + 2 * 32 * 128) * 2 + 8 * 32)
    f2, b2 = flops_qwen3_next.gated_delta_rule_cost(
        65536, 32, 16, 128, 128, "fwd")
    assert (f2, b2) == (2 * f1, 2 * b1)
    fb, bb = flops_qwen3_next.gated_delta_rule_cost(
        32768, 32, 16, 128, 128, "bwd")
    assert (fb, bb) == (15 * 32768 * 32 * 128 * 128, 2 * b1)
    assert list(inspect.signature(
        flops_qwen3_next.gated_delta_rule_cost).parameters) == [
        "tokens", "heads", "key_heads", "key_dim", "value_dim", "passes",
        "itemsize"]
    with pytest.raises(ValueError):
        flops_qwen3_next.gated_delta_rule_cost(1, 1, 1, 1, 1, "both")


def _fake_run(selfs, facts, paths, n_steps=8):
    names = scopes.Names(paths, frozenset())
    dev = types.SimpleNamespace(selfs=selfs, busy_ns=sum(
        ns for _, ns in selfs))
    run_ = types.SimpleNamespace(
        trace=types.SimpleNamespace(devices=[dev], n_steps=n_steps),
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        job=types.SimpleNamespace(facts=facts))
    return run_, names


def test_rule_roofline_counts_the_passes_the_trace_shows(monkeypatch):
    """Compute-bound: 32 768 tokens are 120 GFLOP a forward pass, 0.610 ms at
    197 TFLOP/s (their 814 MB would take 0.99 ms: memory-bound, in fact).
    Forward, remat's second forward and a backward a layer and step; a rule
    that took ten times its least reads 10 %, and one whose trace shows no
    recompute is held to two passes."""
    Event = collections.namedtuple("Event", "name start_ns dur_ns")
    gdn = {"tokens": 32768, "heads": 32, "key_heads": 16, "key_dim": 128,
           "value_dim": 128, "itemsize": 2, "layers": 3, "chunk": 64}
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    from benchmarks import flops
    fwd = flops.roofline_seconds(*flops_qwen3_next.gated_delta_rule_cost(
        32768, 32, 16, 128, 128, "fwd"), peaks)[0]
    bwd = flops.roofline_seconds(*flops_qwen3_next.gated_delta_rule_cost(
        32768, 32, 16, 128, 128, "bwd"), peaks)[0]
    rule = "jit(step)/{}apex.gdn/apex.gdn_rule/mul"
    paths = {"fwd": rule.format(""), "bwd": rule.format("transpose(jvp())/"),
             "again": rule.format("checkpoint/rematted_computation/"),
             "proj": "jit(step)/apex.gdn/dot_general"}
    events = {k: Event(f"%{k} = f32[8]{{0}} fusion(%a)", 0, 0) for k in paths}
    reader = load_module(os.path.join(
        ROOT, "benchmarks", "layer_metrics", "gdn_rule_roofline.py"), "grr")
    scale = 10 * 8 * 3 * 1e9
    selfs = [(events["fwd"], fwd * scale), (events["again"], fwd * scale),
             (events["bwd"], bwd * scale), (events["proj"], 7e9)]
    run_, names = _fake_run(selfs, {"gdn": gdn}, paths)
    monkeypatch.setattr(scopes, "seen", lambda run: names)
    assert reader.read(run_) == pytest.approx(10.0, rel=1e-6)
    run_, names = _fake_run([s for s in selfs if s[0] is not events["again"]],
                            {"gdn": gdn}, paths)
    assert reader.read(run_) == pytest.approx(10.0, rel=1e-6)
    # a program without the scope (the parent's), or no trace: nothing
    run_, names = _fake_run(selfs[-1:], {"gdn": gdn}, paths)
    assert reader.read(run_) is None
    run_, _ = _fake_run(selfs, {}, paths)
    assert reader.read(run_) is None
    monkeypatch.setattr(scopes, "seen", lambda run: None)
    assert reader.read(run_) is None
