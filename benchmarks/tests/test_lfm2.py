"""The ``lfm2_pretrain`` job, its generator and its counts against the
program, on the CPU.

- the cell's code path end to end at a tiny size, from a manifest of its own
  (``tiny_lfm2/``: ``tiny/BENCHMARK.json`` is the first benchmark's and is not
  edited): the adapter drives the example's ``--lfm2`` preset, so the test —
  not an option of the program — swaps the preset's published widths for
  tiny ones;
- the copied generator is the example's;
- ``flops_lfm2.py`` agrees with the dot FLOPs ``telemetry.attrib.op_table``
  reads out of the compiled HLO, and with the count by hand.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import flops_lfm2, inputs_lfm2, run
from benchmarks.job import load_example

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "tiny_lfm2", "BENCHMARK.json")

#: per-layer metrics that are counts, and so may be reported off the chip
COUNTS = {"amp_skipped_steps", "expert_load_max_over_mean"}


def _tiny_model():
    with open(os.path.join(HERE, "tiny_lfm2", "cells", "configs",
                           "tiny_lfm2.json")) as f:
        return json.load(f)["model"]


@pytest.fixture
def tiny_widths(monkeypatch):
    """``--lfm2`` builds ``lfm2_24b_a2b_config(**the cut)``: give that name
    tiny widths."""
    import apex_tpu.models
    from apex_tpu.models import Lfm2Config
    widths = {k: v for k, v in _tiny_model().items() if k not in (
        "vocab_size", "num_dense_layers", "layer_types", "experts_held")}
    monkeypatch.setattr(
        apex_tpu.models, "lfm2_24b_a2b_config",
        lambda **cut: Lfm2Config(**dict(widths, **cut)))


@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearses(trace, tiny_widths, capfd):
    result = run.run_cell("tiny_lfm2.s64", 0, 0.5, trace, manifest_path=TINY,
                          rehearse=True)
    json.dumps(result)
    assert result["correct"] is True, result
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    logged = capfd.readouterr().out
    assert '"chosen_differently"' in logged
    assert ("routing probe over the ring" in logged) == trace
    if trace:
        assert "dropped 0" in logged
    if trace:
        # off the chip: counts only, never a time, a rate or a share
        assert set(result["metrics"]) == COUNTS
        assert result["metrics"]["expert_load_max_over_mean"]["value"] >= 1.0
    else:
        assert result["metrics"] == {}


def test_job_holds_the_program_to_every_key_of_the_model(tiny_widths):
    manifest = run.Manifest(TINY)
    with open(os.path.join(manifest.root, "cells", "configs",
                           "tiny_lfm2.json")) as f:
        config = json.load(f)
    config["model"]["num_key_value_heads"] = 4
    from benchmarks.job import load_module
    adapter = load_module(manifest.find("jobs", "lfm2_pretrain.py"),
                          "lfm2_pretrain_under_test")
    with pytest.raises(ValueError, match="num_key_value_heads"):
        adapter.build(config, manifest.load_json(
            "workloads", "tiny_lfm2.s64.json"), 0, jax.devices()[:1],
            manifest.find("reference", "lfm2_24b_a2b.py"))


def test_next_token_generator_is_the_examples():
    pretrain = load_example("examples/bert/pretrain.py")
    rng = np.random.RandomState(7)
    share, mass = pretrain._SYN_COMMON
    ours = inputs_lfm2.next_token_batches(
        7, 3, batch=4, seq=48, vocab=512, common_share=share,
        common_mass=mass, follow=pretrain._SYN_FOLLOW)
    for batch in ours:
        tokens, targets, weights = pretrain.synthetic_next_token(
            rng, 4, 48, 512)
        np.testing.assert_array_equal(batch["tokens"], tokens)
        np.testing.assert_array_equal(batch["targets"], targets)
        np.testing.assert_array_equal(batch["weights"], weights)


def test_generator_takes_the_drivers_large_seeds():
    a, b = (inputs_lfm2.next_token_batches(
        seed, 1, batch=2, seq=16, vocab=64, common_share=8, common_mass=0.9,
        follow=0.5)[0]["tokens"] for seed in (2 ** 31 + 11, 2 ** 31 + 12))
    assert a.shape == (2, 16) and np.any(a != b)


def test_flops_match_the_compiled_program():
    """Every expert held, so that every assignment is a row and the expected
    count is the count; XLA attention, no remat.  Two things the CPU's
    program does that the algorithm does not need: the causal mask saves the
    algorithm half of QKᵀ and PV and XLA's dense attention none; and off the
    TPU ``ragged_dot`` is lowered as one dense product a group over the whole
    buffer, masked — 16 times the rows' own FLOPs here (on the TPU it is a
    kernel over the rows, which ``expert_matmul_roofline`` times).  With
    both put on top the dots add up to ``flops_lfm2``'s count.  Margin 2%."""
    from apex_tpu.models import Lfm2Config, lfm2_init, lfm2_loss
    from apex_tpu.telemetry.attrib import op_table
    model = dict(_tiny_model(), experts_held=[0, 16])
    cfg = Lfm2Config(xent_impl="xla", **{
        k: tuple(v) if isinstance(v, list) else v for k, v in model.items()})
    params = lfm2_init(jax.random.PRNGKey(0), cfg)
    batch = {k: jnp.asarray(v) for k, v in inputs_lfm2.next_token_batches(
        0, 1, batch=4, seq=64, vocab=256, common_share=8, common_mass=0.9,
        follow=0.5)[0].items()}
    table = op_table(jax.grad(lambda p: lfm2_loss(p, batch, cfg)), params)
    got = sum(table["by_class"].get(c, {"flops": 0.0})["flops"]
              for c in ("blas", "conv"))
    masked_half = 3 * 2.0 * 64 * model["hidden_size"] * 64
    whole = flops_lfm2.train_flops_per_sample(model, 64)
    experts = whole - flops_lfm2.train_flops_per_sample(
        dict(model, experts_held=[0, 0]), 64)
    assert 0.1 * whole < experts < 0.5 * whole
    want = 4 * (whole + masked_half + (16 - 1) * experts)
    assert got == pytest.approx(want, rel=0.02)


def test_lfm2_flops_per_token_by_hand():
    """The cut at the published widths: 89.1 M (dense layer) + 60.8 M (the
    period's mixers) + 0.5 M (routers) + 4 x 0.5 x 9.44 M (held experts, 4·8/64
    of a token's assignments) + 16.8 M (head) = 186.1 M; 6·N + 3·2·S·d for the
    one causal attention layer."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "lfm2_24b_a2b.json")) as f:
        model = json.load(f)["model"]
    d, f_, m = 2048, 11776, 1536
    conv, attn = 4 * d * d, 2 * d * d + 2 * d * 512
    n = ((conv + 3 * d * f_) + (attn + 3 * conv) + 4 * d * 64
         + 4 * 0.5 * 3 * d * m + 8192 * d)
    assert flops_lfm2.matmul_params_per_token(model) == n
    assert n == pytest.approx(186.1e6, rel=1e-3)
    assert flops_lfm2.train_flops_per_token(model, 4096) == \
        6 * n + 3 * 2 * 4096 * d
    assert flops_lfm2.train_flops_per_sample(model, 4096) == \
        pytest.approx(4.78e12, rel=1e-3)


def test_grouped_product_cost_follows_the_rows():
    f1, b1 = flops_lfm2.grouped_ffn_cost(16384, 8, 2048, 1536, "fwd")
    f2, b2 = flops_lfm2.grouped_ffn_cost(32768, 8, 2048, 1536, "fwd")
    assert f1 == 6 * 16384 * 2048 * 1536 and f2 == 2 * f1
    weights = 8 * 3 * 2048 * 1536 * 2
    assert b1 == 2 * 16384 * 2048 * 2 + weights
    assert b2 - b1 == 2 * 16384 * 2048 * 2          # the weights once
    fb, bb = flops_lfm2.grouped_ffn_cost(16384, 8, 2048, 1536, "bwd")
    assert fb == 2 * f1 and bb == 3 * 16384 * 2048 * 2 + 2 * weights
    assert flops_lfm2.grouped_ffn_cost(0, 8, 2048, 1536, "fwd")[0] == 0


def test_expert_roofline_reads_the_kernel_by_name_and_the_rows_sent():
    """Two forward passes and one backward a layer (8 kernel calls), 16384
    rows a layer: compute-bound, 6.28 ms a layer and step at the v5e's
    peak.  A kernel that took twice that reads 50 %."""
    import collections
    import types
    from benchmarks import routing
    from benchmarks.job import load_module
    Event = collections.namedtuple("Event", "name start_ns dur_ns")
    kernel = Event("%ragged-dot-none.7 = bf16[131072,3072]{1,0} "
                   "custom-call(%a, %b)", 0, 0)
    other = Event("%fusion.3 = bf16[8]{0} fusion(%a), kind=kLoop", 0, 0)
    assert routing.is_grouped_product(kernel)
    assert not routing.is_grouped_product(other)
    assert not routing.is_grouped_product(Event(
        "%ragged-dot-metadata.1 = (s32[9]{0}, s32[263]{0}) "
        "custom-call(%sizes)", 0, 0))
    steps, layers = 8, 4
    rows = [np.full((layers, 8), 2048) for _ in range(steps)]
    least_ms = steps * layers * (2 * 6 + 12) * 16384 * 2048 * 1536 / 197e12 * 1e3
    assert least_ms == pytest.approx(8 * 4 * 6.28, rel=1e-2)
    selfs = [(kernel, 2 * least_ms * 1e6 / 256)] * 256 + [(other, 5e6)] * 9
    dev = types.SimpleNamespace(
        self_ns=lambda pred: sum(ns for ev, ns in selfs if pred(ev)),
        count=lambda pred: sum(1 for ev, _ in selfs if pred(ev)))
    run_ = types.SimpleNamespace(
        trace=types.SimpleNamespace(devices=[dev], n_steps=steps),
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        ring_rows=rows,
        job=types.SimpleNamespace(facts={
            "routing_probe": None, "experts": {
                "held": 8, "d_model": 2048, "d_ff": 1536, "itemsize": 2,
                "layers": layers}}))
    run_.job.facts["routing_probe"] = lambda state, ring: (rows, 0)
    reader = load_module(os.path.join(ROOT, "benchmarks", "layer_metrics",
                                      "expert_matmul_roofline.py"), "emr")
    assert reader.read(run_) == pytest.approx(50.0, rel=1e-6)
    load = load_module(os.path.join(ROOT, "benchmarks", "layer_metrics",
                                    "expert_load_max_over_mean.py"), "elm")
    assert load.read(run_) == 1.0
