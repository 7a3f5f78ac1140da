"""The ``nemotron_h_pretrain`` job, its counts against the program and its
readers, on the CPU.

- the cell's code path end to end at a tiny size, from a manifest of its own
  (``tiny_nemotron_h/``): the adapter drives the example's ``--nemotron-h``
  preset, so the test — not an option of the program — swaps the preset's
  published widths for tiny ones;
- ``flops_nemotron_h.py`` agrees with the count by hand and with the dot
  FLOPs ``telemetry.attrib.op_table`` reads out of the compiled HLO;
- ``ssd_scan_cost`` is the recurrence's work, whatever implements the scan,
  and ``ssm_scan_roofline`` reads the passes from the trace's phases.
"""
import collections
import json
import os
import types

import jax
import jax.numpy as jnp
import pytest

from benchmarks import flops_nemotron_h, inputs_lfm2, run, scopes
from benchmarks.job import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "tiny_nemotron_h", "BENCHMARK.json")
CELL = "nemotron3_super_120b_a12b.tp8_ep64_s8192"

#: per-layer metrics that are counts, and so may be reported off the chip
COUNTS = {"amp_skipped_steps", "latent_expert_load_max_over_mean"}
#: what the share holds is the cut's, not a width
HELD = ("vocab_size", "hybrid_override_pattern", "mamba_heads_held",
        "attention_heads_held", "experts_held")


def _tiny_model():
    with open(os.path.join(HERE, "tiny_nemotron_h", "cells", "configs",
                           "tiny_nemotron_h.json")) as f:
        return json.load(f)["model"]


def _published_model():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "nemotron3_super_120b_a12b.json")) as f:
        return json.load(f)["model"]


@pytest.fixture
def tiny_widths(monkeypatch):
    """``--nemotron-h`` builds ``nemotron3_super_120b_a12b_config(**the
    cut)``: give that name tiny widths."""
    import apex_tpu.models
    from apex_tpu.models import NemotronHConfig
    widths = {k: v for k, v in _tiny_model().items() if k not in HELD}
    monkeypatch.setattr(
        apex_tpu.models, "nemotron3_super_120b_a12b_config",
        lambda **cut: NemotronHConfig(**dict(widths, **cut)))


@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearses(trace, tiny_widths, capfd):
    result = run.run_cell("tiny_nemotron_h.s64", 0, 0.5, trace,
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
        assert result["metrics"]["latent_expert_load_max_over_mean"][
            "value"] >= 1.0
    else:
        assert result["metrics"] == {}


def test_job_holds_the_program_to_every_key_of_the_model(tiny_widths):
    manifest = run.Manifest(TINY)
    with open(os.path.join(manifest.root, "cells", "configs",
                           "tiny_nemotron_h.json")) as f:
        config = json.load(f)
    config["model"]["ssm_state_size"] = 32
    adapter = load_module(manifest.find("jobs", "nemotron_h_pretrain.py"),
                          "nemotron_h_pretrain_under_test")
    with pytest.raises(ValueError, match="ssm_state_size"):
        adapter.build(config, manifest.load_json(
            "workloads", "tiny_nemotron_h.s64.json"), 0, jax.devices()[:1],
            manifest.find("reference", "nemotron3_super_120b_a12b.py"))


def test_the_manifest_names_the_cell_and_its_readers():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    cell = next(w for w in doc["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["chips"]) == ("nemotron3_super_120b_a12b", 1)
    mine = [m for m in doc["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == [
        "ssm_time_share", "ssm_scan_roofline", "latent_moe_time_share",
        "latent_dispatch_share", "latent_expert_load_max_over_mean"]
    assert mine == doc["per_layer"][-5:]
    for m in mine:
        assert m["moves"] == "samples_per_s"
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", m["name"] + ".py"))
    with open(os.path.join(ROOT, "benchmarks", "workloads",
                           CELL + ".json")) as f:
        traffic = json.load(f)
    assert (traffic["batch"], traffic["seq"], traffic["ring"],
            traffic["sync_every"], traffic["reference_samples"]) == (
        2, 8192, 8, 2, 2)


def test_flops_per_token_by_hand():
    """The cut at the published widths: M 13.7 M (W_in 4096 x 2320, W_out
    1024 x 4096), * 5.2 M, E 56.4 M met by a token (router 2.1 + latent 8.4 +
    shared 44.0 + 22·8/512 of a 5.505 M expert), head 67.1 M: 423 M = 846
    MFLOP a token forward."""
    model = _published_model()
    d = 4096
    m = d * 2320 + 1024 * d
    a = 2 * d * 512 + 2 * d * 128
    e = (d * 512 + 2 * d * 1024 + 2 * d * 5376
         + 22 * 8 / 512 * 2 * 1024 * 2688)
    n = 5 * m + a + 5 * e + 16384 * d
    assert flops_nemotron_h.matmul_params_per_token(model) == n
    assert 2 * n == pytest.approx(846e6, rel=2e-3)
    scan = 5 * 16 * 64 * 128 * 16
    assert flops_nemotron_h.scan_flops_per_token(model) == scan
    assert flops_nemotron_h.train_flops_per_token(model, 8192) == \
        6 * n + 3 * 2 * 8192 * 512 + scan
    assert 16384 * flops_nemotron_h.train_flops_per_token(model, 8192) == \
        pytest.approx(41.6e12 + 16384 * (3 * 2 * 8192 * 512 + scan),
                      rel=2e-3)


def test_scan_cost_is_the_recurrences_whatever_implements_it():
    """No chunk, block or implementation among its arguments; it follows the
    tokens, and the backward moves twice the forward's bytes."""
    f1, b1 = flops_nemotron_h.ssd_scan_cost(16384, 16, 64, 1, 128, "fwd")
    assert f1 == 5 * 16384 * 16 * 64 * 128
    assert b1 == 16384 * ((2 * 16 * 64 + 2 * 128) * 2 + 4 * 16)
    f2, b2 = flops_nemotron_h.ssd_scan_cost(32768, 16, 64, 1, 128, "fwd")
    assert (f2, b2) == (2 * f1, 2 * b1)
    fb, bb = flops_nemotron_h.ssd_scan_cost(16384, 16, 64, 1, 128, "bwd")
    assert (fb, bb) == (11 * 16384 * 16 * 64 * 128, 2 * b1)
    import inspect
    assert list(inspect.signature(
        flops_nemotron_h.ssd_scan_cost).parameters) == [
        "tokens", "heads", "head_dim", "groups", "state", "passes",
        "itemsize"]
    with pytest.raises(ValueError):
        flops_nemotron_h.ssd_scan_cost(1, 1, 1, 1, 1, "both")


def test_flops_match_the_compiled_program():
    """XLA attention, no remat, every head, expert and id held.  What the
    CPU's program does and the count does not: XLA's dense attention saves
    nothing by the causal mask (the other half of QKᵀ and PV); the scan is
    the chunked form — four products a chunk — where the count is the
    recurrence's elementwise work; and the routed experts' products sit in
    ``while`` bodies, which ``op_table`` does not enter.  With those put on
    top, or taken off, the dots add up to ``flops_nemotron_h``'s count."""
    from apex_tpu.models import (NemotronHConfig, nemotron_h_init,
                                 nemotron_h_loss)
    from apex_tpu.telemetry.attrib import op_table
    model = dict(_tiny_model(), mamba_heads_held=[0, 16],
                 attention_heads_held=[0, 8], experts_held=[0, 32])
    cfg = NemotronHConfig(xent_impl="xla", **{
        k: tuple(v) if isinstance(v, list) else v for k, v in model.items()})
    params = nemotron_h_init(jax.random.PRNGKey(0), cfg)
    batch, seq = 4, 64
    data = {k: jnp.asarray(v) for k, v in inputs_lfm2.next_token_batches(
        0, 1, batch=batch, seq=seq, vocab=256, common_share=8,
        common_mass=0.9, follow=0.5)[0].items()}
    table = op_table(jax.grad(lambda p: nemotron_h_loss(p, data, cfg)),
                     params)
    got = sum(table["by_class"].get(c, {"flops": 0.0})["flops"]
              for c in ("blas", "conv"))
    whole = flops_nemotron_h.train_flops_per_sample(model, seq)
    routed = whole - flops_nemotron_h.train_flops_per_sample(
        dict(model, experts_held=[0, 0]), seq)
    assert 0.02 * whole < routed < 0.5 * whole
    recurrence = seq * flops_nemotron_h.scan_flops_per_token(model)
    masked_half = 3 * 2.0 * seq * 8 * 16 * seq
    # the chunked form's products a token and M layer, forward: C Bᵀ (a
    # group), (scores ∘ decay) x, the chunk states, C · entering state
    q, heads, p, groups, n = 16, 16, 8, 8, 16
    chunked = 5 * 3 * seq * 2.0 * (groups * q * n + heads * q * p
                                   + 2 * heads * p * n)
    want = batch * (whole - routed - recurrence + masked_half + chunked)
    assert got == pytest.approx(want, rel=0.03)


def _fake_run(selfs, facts, paths, n_steps=8):
    names = scopes.Names(paths, frozenset())
    dev = types.SimpleNamespace(selfs=selfs, busy_ns=sum(
        ns for _, ns in selfs))
    run_ = types.SimpleNamespace(
        trace=types.SimpleNamespace(devices=[dev], n_steps=n_steps),
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        job=types.SimpleNamespace(facts=facts),
        metric=lambda name: 99.0)
    return run_, names


def test_scan_roofline_counts_the_passes_the_trace_shows(monkeypatch):
    """Memory-bound: 16 384 tokens move 76.5 MB a forward pass, 0.0935 ms at
    819 GB/s.  Forward, remat's second forward and a backward (two passes'
    bytes) a layer and step are four of them; a scan that took ten times
    that reads 10 %, and one whose trace shows no recompute is held to
    three."""
    Event = collections.namedtuple("Event", "name start_ns dur_ns")
    ssm = {"tokens": 16384, "heads": 16, "head_dim": 64, "groups": 1,
           "state": 128, "itemsize": 2, "layers": 5, "chunk": 128}
    a_pass = 16384 * ((2 * 16 * 64 + 2 * 128) * 2 + 4 * 16) / 819e9
    assert a_pass * 1e3 == pytest.approx(0.0935, rel=1e-2)
    scan = "jit(step)/{}apex.ssm/apex.ssm_scan/mul"
    paths = {"fwd": scan.format(""), "bwd": scan.format("transpose(jvp())/"),
             "again": scan.format("checkpoint/rematted_computation/"),
             "proj": "jit(step)/apex.ssm/dot_general"}
    events = {k: Event(f"%{k} = f32[8]{{0}} fusion(%a)", 0, 0) for k in paths}
    reader = load_module(os.path.join(
        ROOT, "benchmarks", "layer_metrics", "ssm_scan_roofline.py"), "ssr")
    took = 10 * 4 * a_pass * 8 * 5 * 1e9
    selfs = [(events["fwd"], took / 4), (events["again"], took / 4),
             (events["bwd"], took / 2), (events["proj"], 7e9)]
    run_, names = _fake_run(selfs, {"ssm": ssm}, paths)
    monkeypatch.setattr(scopes, "seen", lambda run: names)
    assert reader.read(run_) == pytest.approx(10.0, rel=1e-6)
    run_, names = _fake_run([s for s in selfs if s[0] is not events["again"]],
                            {"ssm": ssm}, paths)
    assert reader.read(run_) == pytest.approx(10.0, rel=1e-6)
    # a program without the scope (the parent's), or no trace: nothing
    run_, names = _fake_run(selfs[-1:], {"ssm": ssm}, paths)
    assert reader.read(run_) is None
    monkeypatch.setattr(scopes, "seen", lambda run: None)
    assert reader.read(run_) is None


def test_latent_readers_build_on_the_accepted_ones():
    run_, _ = _fake_run([], {}, {})
    for name in ("latent_moe_time_share", "latent_expert_load_max_over_mean"):
        reader = load_module(os.path.join(
            ROOT, "benchmarks", "layer_metrics", name + ".py"), name)
        assert reader.read(run_) == 99.0


def test_dispatch_share_leaves_out_products_latent_and_shared(monkeypatch):
    Event = collections.namedtuple("Event", "name start_ns dur_ns")
    paths = {"sort": "jit(step)/apex.moe/sort",
             "router": "jit(step)/apex.moe/apex.router/dot_general",
             "gate": "jit(step)/apex.moe/while/body/apex.experts/mul",
             "down": "jit(step)/apex.moe/apex.latent/dot_general",
             "shared": "jit(step)/apex.moe/apex.shared_expert/dot_general",
             "scan": "jit(step)/apex.ssm/apex.ssm_scan/mul"}
    events = {k: Event(f"%{k} = f32[8]{{0}} fusion(%a)", 0, 0) for k in paths}
    events["ragged-dot-none.7"] = Event(
        "%ragged-dot-none.7 = bf16[8,8]{1,0} custom-call(%a, %b)", 0, 0)
    selfs = [(ev, 1e6) for ev in events.values()]
    run_, names = _fake_run(selfs, {}, paths)
    run_.trace.share_of_busy = lambda pred: 100.0 * sum(
        ns for ev, ns in selfs if pred(ev)) / len(selfs) / 1e6
    monkeypatch.setattr(scopes, "seen", lambda run: names)
    reader = load_module(os.path.join(
        ROOT, "benchmarks", "layer_metrics", "latent_dispatch_share.py"),
        "lds")
    assert reader.read(run_) == pytest.approx(100.0 * 2 / 7)
