"""Laguna-S-2.1 (``apex_tpu.models.laguna`` over the flash kernels' band and
``parallel.expert.routed_experts``) against its plain float32 reference
(``benchmarks/reference/laguna_s_2_1.py``) on seeded random weights at a
small size: d 64; a dense full-attention layer, a sliding-window layer and
a sparse full layer (each kind of the published pattern once), 4 query
heads on a full layer and 6 on a window layer over 2 key-value heads of 16,
a window of 8 over 37 positions, YaRN on half of a full layer's head; 32
experts top-4, softmax scores x 2.5, a gated shared expert; and a share of
the experts (8..15) with a non-zero first.  Then the band kernels against the XLA band, YaRN
against its formula, the shares against the uncut layer and the cut
against the published configuration.
"""
import dataclasses
import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.contrib.multihead_attn import flash as F
from apex_tpu.models import (LagunaConfig, laguna_init, laguna_loss,
                             laguna_routing, laguna_s_2_1_config,
                             laguna_share)
from apex_tpu.models import laguna
from apex_tpu.parallel import create_mesh, use_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _load(rel_path, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, rel_path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load("benchmarks/reference/laguna_s_2_1.py", "laguna_reference")

WHOLE = LagunaConfig(
    vocab_size=128, hidden_size=64, intermediate_size=96,
    layer_types=("full_attention", "sliding_attention", "full_attention"),
    mlp_layer_types=("dense", "sparse", "sparse"),
    num_attention_heads_per_layer=(4, 6, 4), num_key_value_heads=2,
    head_dim=16, sliding_window=8, rope_theta=100.0,
    original_max_position_embeddings=32, yarn_factor=4.0,
    attention_factor=0.1 * math.log(4.0) + 1, num_experts=32,
    num_experts_per_tok=4, moe_intermediate_size=16,
    shared_expert_intermediate_size=16, experts_held=(0, 32),
    xent_impl="xla")
CFG = dataclasses.replace(WHOLE, experts_held=(8, 8))
SEQ = 37        # no multiple of any block, nor of the window


def _model(cfg):
    """The configuration as the reference reads it: a plain dict."""
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def _params(cfg, seed=0):
    """The initialiser's tree with every norm's gain drawn away from 1, so
    that each takes part."""
    params = laguna_init(jax.random.PRNGKey(seed), cfg)
    key = jax.random.PRNGKey(seed + 100)
    for layer in params["layers"] + [params["head"]]:
        for name in layer:
            if name.endswith("norm"):
                key, k = jax.random.split(key)
                layer[name] = layer[name] + 0.3 * jax.random.normal(
                    k, layer[name].shape)
    return params


def _batch(cfg, batch=2, seq=SEQ, seed=0):
    tokens = np.random.RandomState(seed).randint(
        0, cfg.vocab_size, size=(batch, seq)).astype(np.int32)
    weights = np.ones((batch, seq), np.float32)
    weights[:, -1] = 0.0
    return {"tokens": jnp.asarray(tokens),
            "targets": jnp.asarray(np.roll(tokens, -1, axis=1)),
            "weights": jnp.asarray(weights)}


@pytest.fixture(autouse=True)
def _float32_products():
    with jax.default_matmul_precision("highest"):
        yield


def _close(got, want, what="", tol=2e-5):
    """float32 on both sides: only the order of sums differs.  ``tol`` is of
    the largest magnitude; bfloat16 (8 bits of mantissa, 4e-3 a rounding)
    is 200 times over the default."""
    np.testing.assert_allclose(
        got, want, rtol=1e-4, atol=tol * float(jnp.max(jnp.abs(want))),
        err_msg=what)


# ---------------------------------------------------------------------------
# (a) the whole model against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("attn", ["default", "fast"])
def test_loss_and_every_gradient_leaf_match_the_reference(attn):
    """Float32 on both sides, the XLA core (``default``) or the flash
    kernels interpreted (``fast``: the window layers take the XLA band at
    these widths).  The loss to 1e-5: float32 sums in another order; every
    leaf to 1e-3 of its largest value: the gradient of a softmax router's
    top-4 renormalisation and the gates' products lose a few bits more than
    the loss — bfloat16 would be off by 4e-3 of a value in ONE rounding."""
    cfg = dataclasses.replace(CFG, attn_impl=attn)
    params, batch = _params(cfg), _batch(cfg)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p, b: laguna_loss(p, b, cfg)))(params, batch)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p, b: reference.loss(p, b, _model(cfg))))(params, batch)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    flat = jax.tree_util.tree_leaves_with_path(want_grads)
    assert len(flat) == len(jax.tree_util.tree_leaves(grads))
    for (path, leaf), got in zip(flat, jax.tree_util.tree_leaves(grads)):
        name = jax.tree_util.keystr(path)
        assert np.any(leaf), name
        _close(got, leaf, name, tol=1e-3)


def test_remat_changes_nothing():
    params, batch = _params(CFG), _batch(CFG)
    plain, again = (jax.jit(jax.value_and_grad(
        lambda p, b, cfg=cfg: laguna_loss(p, b, cfg)))(params, batch)
        for cfg in (CFG, dataclasses.replace(CFG, remat=True)))
    for a, b in zip(jax.tree_util.tree_leaves(plain),
                    jax.tree_util.tree_leaves(again)):
        _close(a, b)


def test_routing_record_covers_every_sparse_layer():
    params, batch = _params(CFG), _batch(CFG)
    record = jax.jit(lambda p, t: laguna_routing(p, t, CFG))(
        params, batch["tokens"])
    tokens = batch["tokens"].size
    assert record["ids"].shape == (2, tokens, CFG.num_experts_per_tok)
    assert record["rows"].shape == (2, 8)
    chosen = np.asarray(jax.jit(lambda p, b: reference.routing(
        p, b, _model(CFG)))(params, batch))
    assert np.take_along_axis(chosen, np.asarray(record["ids"]), 2).all()
    assert not np.any(record["dropped"])


@pytest.mark.parametrize("attn", ["default", "fast"])
def test_a_window_layer_sees_its_last_window_keys_and_no_more(attn):
    """Position i of a window layer's output moves with the input at i - 7
    .. i and not at i - 8 or before (nor after i); a full layer's moves with
    every earlier position.  Each is the reference's mixer."""
    cfg = dataclasses.replace(CFG, attn_impl=attn)
    params = _params(cfg)
    u = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, cfg.hidden_size))
    moved = u.at[:, 10].add(1.0)
    for i, kind in ((1, "sliding_attention"), (2, "full_attention")):
        lp, heads = params["layers"][i], cfg.num_attention_heads_per_layer[i]
        mixer = jax.jit(lambda u, lp: laguna._attention_mixer(
            u, lp, cfg, kind, heads))
        got = mixer(u, lp)
        _close(got, jax.jit(lambda u, lp: reference._attention(
            u, lp, _model(cfg), kind, heads))(u, lp))
        diff = np.abs(np.asarray(mixer(moved, lp) - got)).max(axis=(0, 2))
        reach = range(10, 18) if kind == "sliding_attention" \
            else range(10, SEQ)
        assert all(diff[p] > 1e-4 for p in reach), (kind, diff)
        assert all(diff[p] == 0 for p in range(SEQ) if p not in reach), (
            kind, diff)


# ---------------------------------------------------------------------------
# (b) the band kernels against the XLA band
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seq,window,block", [
    (192, 32, 64),        # a window under the block
    (192, 64, 64),        # a window of one block
    (150, 100, 64),       # a window no multiple of the block, S of neither
])
def test_the_band_kernels_are_the_xla_band(seq, window, block):
    """``apex_flash_window_fwd`` and the ``_bwd_dq`` / ``_bwd_dkv`` pair
    (interpreted) against the dense-masked XLA band, in float32: forward to
    1e-5 of the largest value and each gradient to 1e-4 (the online
    softmax and the recompute reorder float32 sums; a bfloat16 rounding is
    4e-3).  The grid walks the band alone."""
    bh, d = 2, 16
    keys = jax.random.split(jax.random.PRNGKey(seq + window), 4)
    q, k, v, do = (jax.random.normal(kk, (bh, seq, d)) for kk in keys)
    q = q * d ** -0.5
    bias = jnp.zeros((1, 1, seq), jnp.float32)
    out, lse = F._band_fwd(q, k, v, bias, window, bh, block, block)
    _close(out, F._xla_reference(q, k, v, bias, True, 0.0, 0, bh, window),
           "out", tol=1e-5)
    got = F._band_bwd(q, k, v, bias, window, bh, out, lse, do, block, block)
    want = F._xla_bwd(q, k, v, bias, True, 0.0, 0, bh, out, lse, do, window)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        _close(a, b, name, tol=1e-4)
    # the band's grid: at most the key blocks a q block's band meets
    n = -(-seq // block)
    steps, _ = F._band_axis(lambda i, lib: F._band_keys(
        i, block, block, window, n, lib), n)
    assert steps == min(n, -(-(window - 1) // block) + 1)


def test_the_band_at_the_cells_shape_visits_two_key_blocks_a_q_block():
    """S 8192, W 512, D 128, bfloat16 is admitted: the forward's grid is
    (BH, 16, 2), dq's (BH, 16, 2) and dk / dv's (BH, 16, 2) — 32 tiles a
    head where the causal forward's 512 x 1024 grid has 136 below the
    diagonal's reach — and nothing of the causal kernels runs."""
    bh, s = 72, 8192
    x = jax.ShapeDtypeStruct((bh, s, 128), jnp.bfloat16)
    bias = jnp.zeros((1, 1, s), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: F.flash_attention(
        q, k, v, bias, 0, True, 0.0, bh, "auto", 512).astype(
            jnp.float32).sum(), argnums=(0, 1, 2)))(x, x, x)
    assert _pallas_grids(jaxpr) == {
        "apex_flash_window_fwd": (bh, 16, 2),
        "apex_flash_window_bwd_dq": (bh, 16, 2),
        "apex_flash_window_bwd_dkv": (bh, 16, 2)}


def _pallas_grids(jaxpr):
    def walk(j):
        for e in j.eqns:
            yield e
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from walk(sub)
    return {e.params["name"]: tuple(e.params["grid_mapping"].grid)
            for e in walk(jaxpr.jaxpr) if e.primitive.name == "pallas_call"}


def test_a_window_wants_causal_attention():
    x = jnp.ones((2, 16, 8))
    with pytest.raises(ValueError, match="causal=True"):
        F.flash_attention(x, x, x, jnp.zeros((1, 1, 16)), 0, False, 0.0, 2,
                          "auto", 4)


# ---------------------------------------------------------------------------
# (c) YaRN against its formula
# ---------------------------------------------------------------------------

def test_yarn_is_the_formula_at_the_published_numbers():
    """64 rotary dims of a full layer at θ 5e5, factor 128 over 8192,
    β 32 / 1: the ramp runs from dim pair 9 (8192 / (2π·32) turns:
    64 ln(40.7) / 2 ln(5e5) = 9.04, rounded down) to 18 (17.49, rounded
    up); pairs below it keep θ^(-2i/64), pairs from it on are divided by
    128, between them the blend; the attention factor is 0.1 ln 128 + 1."""
    cfg = laguna_s_2_1_config()
    got = laguna.yarn_inv_freq(cfg)
    theta = 5e5 ** (-np.arange(0, 64, 2) / 64)
    ramp = np.clip((np.arange(32) - 9) / 9, 0, 1)
    np.testing.assert_allclose(got, theta * (1 - ramp) + theta / 128 * ramp,
                               rtol=1e-12)
    np.testing.assert_allclose(got[:10], theta[:10], rtol=1e-12)
    np.testing.assert_allclose(got[18:], theta[18:] / 128, rtol=1e-12)
    assert cfg.attention_factor == pytest.approx(0.1 * math.log(128) + 1,
                                                 rel=1e-15)
    want, scale = reference.yarn(_model(cfg))
    np.testing.assert_allclose(got, want, rtol=1e-12)
    assert scale == cfg.attention_factor


def test_the_full_layers_rotate_half_their_heads_scaled():
    """Position p of a full layer's q: dims 0..7 rotated by p·inv_freq and
    scaled by the attention factor, dims 8..15 as they were; a window
    layer's rotates all 16 unscaled."""
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 5, 2, 16))
    for kind, rot in (("full_attention", 8), ("sliding_attention", 16)):
        inv_freq, scale = laguna._rotary(CFG, kind)
        got = laguna._rope(x, inv_freq, scale)
        np.testing.assert_array_equal(got[..., rot:], x[..., rot:])
        half = rot // 2
        angle = 3 * inv_freq
        a, b = x[0, 3, :, :half], x[0, 3, :, half:rot]
        want = np.concatenate([a * np.cos(angle) - b * np.sin(angle),
                               b * np.cos(angle) + a * np.sin(angle)], -1)
        _close(got[0, 3, :, :rot], scale * want)
    assert laguna._rotary(CFG, "sliding_attention")[1] == 1.0


# ---------------------------------------------------------------------------
# (d) the shares add up
# ---------------------------------------------------------------------------

def test_the_32_shares_add_up_to_the_uncut_references_layer():
    """All 32 shares of a tiny sparse layer's 32 experts, one expert each
    (the published 256 over 32 chips is 8 each): their routed parts, with
    what every chip computes alike — the gated shared expert — counted
    once, are the uncut reference's layer; a share alone is the reference
    given the same share."""
    params = _params(WHOLE)
    lp = params["layers"][2]
    h = jax.random.normal(jax.random.PRNGKey(7), (2, SEQ, WHOLE.hidden_size))
    # one program for every share: the first expert held is traced, as it
    # is under a bound expert axis
    ffn = jax.jit(lambda h, cut, first: laguna._sparse_ffn(
        h, cut, dataclasses.replace(WHOLE, experts_held=(first, 1))))
    total, rows = 0.0, 0
    for first in range(32):
        cfg = dataclasses.replace(WHOLE, experts_held=(first, 1))
        cut = laguna_share(params, cfg)["layers"][2]
        out, record = ffn(h, cut, first)
        total += out
        rows += int(record["rows"].sum())
        if first == 8:
            _close(out, reference._sparse_ffn(h, cut, _model(cfg))[0])
    alike = reference._sigmoid(h @ lp["shared_gate"]) * reference._gated(
        h, lp["shared_w13"], lp["shared_w2"])
    _close(total - 31 * alike,
           reference._sparse_ffn(h, lp, _model(WHOLE))[0])
    assert rows == h.shape[0] * h.shape[1] * WHOLE.num_experts_per_tok


def test_a_share_of_the_whole_models_parameters_is_a_shares_tree():
    params = _params(WHOLE)
    small = dataclasses.replace(CFG, vocab_size=64)
    cut = laguna_share(params, small)
    like = laguna_init(jax.random.PRNGKey(0), small)
    assert jax.tree_util.tree_map(jnp.shape, cut) \
        == jax.tree_util.tree_map(jnp.shape, like)
    np.testing.assert_array_equal(cut["layers"][2]["w2"],
                                  params["layers"][2]["w2"][8:16])
    # the dense layer, attention, router and shared expert are every chip's
    for name in ("w13", "wq", "w_gate"):
        np.testing.assert_array_equal(cut["layers"][0][name],
                                      params["layers"][0][name])
    np.testing.assert_array_equal(cut["layers"][1]["router"],
                                  params["layers"][1]["router"])
    np.testing.assert_array_equal(cut["head"]["out"],
                                  params["head"]["out"][:, :64])


# ---------------------------------------------------------------------------
# (e) the published configuration, the example's preset, the standard path
# ---------------------------------------------------------------------------

def _config_file():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "laguna_s_2_1.json")) as f:
        return json.load(f)


#: the published config.json of Laguna-S-2.1
#: (https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json), the
#: keys that state its shape
PUBLISHED = {
    "vocab_size": 100352, "hidden_size": 3072, "intermediate_size": 12288,
    "num_hidden_layers": 48, "num_attention_heads": 48,
    "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 1048576, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 10,
    "moe_intermediate_size": 1024, "shared_expert_intermediate_size": 1024,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [0],
    "tie_word_embeddings": False, "gating": "per-head", "sliding_window": 512,
    "moe_routed_scaling_factor": 2.5, "moe_router_logit_softcapping": 0,
    "moe_apply_router_weight_on_input": False}


def test_config_file_holds_the_published_numbers():
    doc = _config_file()
    assert doc["source"].startswith(
        "https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json")
    for key, value in PUBLISHED.items():
        if key in doc["reduced"]:
            assert doc["published"][key] == value, key
            assert doc[key] != value, key
        else:
            assert doc[key] == value, key
    assert doc["reduced"] == ["num_hidden_layers", "layer_types",
                              "mlp_layer_types",
                              "num_attention_heads_per_layer", "num_experts",
                              "vocab_size"]
    full = doc["rope_parameters"]["full_attention"]
    assert (full["rope_theta"], full["factor"],
            full["original_max_position_embeddings"], full["beta_fast"],
            full["beta_slow"], full["partial_rotary_factor"]) == (
        500000, 128, 8192, 32, 1, 0.5)
    assert doc["rope_parameters"]["sliding_attention"]["rope_theta"] == 10000
    assert doc["num_attention_heads_per_layer"] == [48, 72, 72, 72, 48]


def test_preset_is_the_published_configuration():
    """Every width as published; the cut the configuration runs is what
    ``--laguna 32`` builds, and its tree counts 811.0 M parameters: 157.43 M
    in layer 0, 148.87 M a window layer, 129.91 M in layer 4, 77.07 M of
    embedding and head."""
    doc = _config_file()
    cfg = laguna_s_2_1_config()
    full = doc["rope_parameters"]["full_attention"]
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.num_key_value_heads,
            cfg.head_dim, cfg.sliding_window, cfg.num_experts,
            cfg.num_experts_per_tok, cfg.moe_intermediate_size,
            cfg.shared_expert_intermediate_size, cfg.rms_norm_eps,
            cfg.moe_routed_scaling_factor, cfg.vocab_size,
            cfg.num_hidden_layers) == (
        3072, 12288, 8, 128, 512, 256, 10, 1024, 1024, 1e-6, 2.5, 100352,
        48)
    assert (cfg.rope_theta, cfg.yarn_factor,
            cfg.original_max_position_embeddings, cfg.beta_fast,
            cfg.beta_slow, cfg.attention_factor,
            cfg.partial_rotary_factor) == (
        full["rope_theta"], full["factor"],
        full["original_max_position_embeddings"], full["beta_fast"],
        full["beta_slow"], full["attention_factor"],
        full["partial_rotary_factor"])
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "laguna_s_2_1.json")) as f:
        assert json.load(f)["published"]["num_experts"] == cfg.num_experts
    pretrain = _load("examples/bert/pretrain.py", "pretrain_for_laguna")
    cut = pretrain.laguna_config(pretrain.parse_args(doc["entry"]["argv"]))
    for key, value in doc["model"].items():
        got = getattr(cut, key)
        assert (list(got) if isinstance(got, tuple) else got) == value, key
    assert (cut.experts_held[1], cut.vocab_size, cut.num_hidden_layers,
            list(cut.layer_types), list(cut.mlp_layer_types)) == (
        doc["num_experts"], doc["vocab_size"], doc["num_hidden_layers"],
        doc["layer_types"], doc["mlp_layer_types"])
    shapes = jax.eval_shape(lambda k: laguna_init(k, cut),
                            jax.random.PRNGKey(0))
    count = lambda t: sum(x.size for x in jax.tree_util.tree_leaves(t))
    assert count(shapes) == 811_030_784
    layers = [count(lp) for lp in shapes["layers"]]
    assert [round(n / 1e4) / 100 for n in layers] == [
        157.44, 148.87, 148.87, 148.87, 129.92]
    assert count(shapes["embed"]) + shapes["head"]["out"].size == 77_070_336
    window = shapes["layers"][1]
    assert (window["wq"].shape, window["wk"].shape, window["w_gate"].shape,
            window["w13"].shape, window["router"].shape) == (
        (3072, 9216), (3072, 1024), (3072, 72), (8, 3072, 2048),
        (3072, 256))
    assert shapes["layers"][0]["w13"].shape == (3072, 24576)


def test_the_whole_step_trains_through_the_example():
    """``parse_args`` -> ``run_standard`` under O5 with per-leaf FusedLAMB,
    the path the benchmark drives: finite, falling, no step skipped."""
    pretrain = _load("examples/bert/pretrain.py", "pretrain_for_laguna_step")
    args = pretrain.parse_args(["--laguna", "4", "--vocab", "128",
                                "--seq-len", "48", "--batch-size", "2",
                                "--attn", "fast", "--remat", "--lr", "1e-2"])
    assert args.opt_level == "O5"
    cfg = dataclasses.replace(
        WHOLE, experts_held=(0, 8), dtype=jnp.bfloat16, remat=args.remat,
        attn_impl=args.attn, xent_impl="auto")
    mesh = create_mesh({"data": 1}, devices=jax.devices()[:1])
    rng = np.random.RandomState(0)
    steps, losses = 8, []
    with use_mesh(mesh):
        state, step = pretrain.run_standard(args, cfg, mesh)
        for _ in range(steps):
            tokens, targets, weights = pretrain.synthetic_next_token(
                rng, args.batch_size, args.seq_len, cfg.vocab_size)
            state, loss = step(state, {"tokens": tokens, "targets": targets,
                                       "weights": weights})
            losses.append(float(loss))
    assert np.all(np.isfinite(losses))
    assert np.mean(losses[-2:]) < np.mean(losses[:2]) - 0.02, losses
    assert step.optimizer_steps(state) == steps


def test_the_flag_is_a_preset_of_the_standard_path_alone():
    pretrain = _load("examples/bert/pretrain.py", "pretrain_for_laguna_flag")
    with pytest.raises(SystemExit, match="--laguna is a model preset"):
        pretrain.main(["--laguna", "32", "--zero"])
