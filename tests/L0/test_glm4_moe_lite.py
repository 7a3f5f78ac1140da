"""GLM-4.7-Flash (``apex_tpu.models.glm4_moe_lite`` over
``parallel.expert.routed_experts``) against its plain float32 reference
(``benchmarks/reference/glm47_flash_30b_a3b.py``) on seeded random weights at
a small size: d 64; a dense layer, then two sparse ones; 4 heads of
multi-head latent attention (q through a 24-wide latent, k / v through a
16-wide one, 12 + 4 wide query and key heads, 16-wide value heads); 32
experts top-4, sigmoid scores with a correction bias, x 1.8, a shared
expert; the MTP module; and a share of the experts (8..15) with a non-zero
first.
"""
import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import (Glm4MoeLiteConfig, glm47_flash_config,
                             glm4_moe_lite_apply, glm4_moe_lite_init,
                             glm4_moe_lite_loss, glm4_moe_lite_routing,
                             glm4_moe_lite_share)
from apex_tpu.models import glm4_moe_lite
from apex_tpu.models.lfm2 import causal_lm_loss
from apex_tpu.parallel import create_mesh, expert, use_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
#: the published config.json of GLM-4.7-Flash
#: (https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json),
#: the keys that state its shape
PUBLISHED = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 768,
    "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "v_head_dim": 256, "vocab_size": 154880}


def _load(rel_path, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, rel_path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load("benchmarks/reference/glm47_flash_30b_a3b.py",
                  "glm47_flash_reference")

WHOLE = Glm4MoeLiteConfig(
    vocab_size=128, hidden_size=64, num_hidden_layers=3,
    intermediate_size=96, num_attention_heads=4, q_lora_rank=24,
    kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16,
    num_experts=32, num_experts_per_tok=4, moe_intermediate_size=16,
    experts_held=(0, 32), xent_impl="xla")
CFG = dataclasses.replace(WHOLE, experts_held=(8, 8))
SEQ = 37        # no multiple of any flash block


def _model(cfg):
    """The configuration as the reference reads it: a plain dict."""
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def _params(cfg, seed=0):
    """The initialiser's tree with every norm's gain drawn away from 1 and
    the correction bias away from 0, so that each takes part."""
    params = glm4_moe_lite_init(jax.random.PRNGKey(seed), cfg)
    key = jax.random.PRNGKey(seed + 100)

    def perturb(leaf, scale):
        nonlocal key
        key, k = jax.random.split(key)
        return leaf + scale * jax.random.normal(k, leaf.shape)
    for layer in params["layers"] + params["mtp"]:
        for name in layer:
            if name.endswith("norm"):
                layer[name] = perturb(layer[name], 0.3)
        if "expert_bias" in layer:
            layer["expert_bias"] = perturb(layer["expert_bias"], 0.1)
    params["head"]["norm"] = perturb(params["head"]["norm"], 0.3)
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
    np.testing.assert_allclose(
        got, want, rtol=1e-4, atol=tol * float(jnp.max(jnp.abs(want))),
        err_msg=what)


# ---------------------------------------------------------------------------
# (a) the whole model, MTP on
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("attn", ["default", "fast"])
def test_loss_and_every_gradient_leaf_match_the_reference(attn):
    """Float32 on both sides, the MTP module and its loss term included;
    every leaf but the correction bias (a buffer: no gradient) takes part."""
    cfg = dataclasses.replace(CFG, attn_impl=attn)
    params, batch = _params(cfg), _batch(cfg)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p, b: glm4_moe_lite_loss(p, b, cfg)))(params, batch)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p, b: reference.loss(p, b, _model(cfg))))(params, batch)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    flat = jax.tree_util.tree_leaves_with_path(want_grads)
    assert len(flat) == len(jax.tree_util.tree_leaves(grads))
    for (path, leaf), got in zip(flat, jax.tree_util.tree_leaves(grads)):
        name = jax.tree_util.keystr(path)
        assert np.any(leaf) != name.endswith("['expert_bias']"), name
        _close(got, leaf, name, tol=1e-3)


def test_remat_changes_nothing():
    params, batch = _params(CFG), _batch(CFG)
    plain, again = (jax.jit(jax.value_and_grad(
        lambda p, b, cfg=cfg: glm4_moe_lite_loss(p, b, cfg)))(params, batch)
        for cfg in (CFG, dataclasses.replace(CFG, remat=True)))
    for a, b in zip(jax.tree_util.tree_leaves(plain),
                    jax.tree_util.tree_leaves(again)):
        _close(a, b)


def test_routing_record_covers_every_sparse_layer_and_the_mtp_module():
    params, batch = _params(CFG), _batch(CFG)
    record = jax.jit(lambda p, b: glm4_moe_lite_routing(p, b, CFG))(
        params, batch)
    tokens = batch["tokens"].size
    assert record["ids"].shape == (3, tokens, CFG.num_experts_per_tok)
    assert record["rows"].shape == (3, 8)
    chosen = np.asarray(reference.routing(params, batch, _model(CFG)))
    ids = np.asarray(record["ids"])
    assert np.take_along_axis(chosen, ids, axis=2).all()
    first, held = CFG.experts_held
    for layer in range(3):
        want = [(ids[layer] == first + e).sum() for e in range(held)]
        np.testing.assert_array_equal(record["rows"][layer], want)
    assert not np.any(record["dropped"])
    assert np.all(np.asarray(record["walks"]) == 1)


# ---------------------------------------------------------------------------
# (b) latent attention is multi-head attention on the assembled q and k
# ---------------------------------------------------------------------------

def _mha_by_hand(u, lp, cfg):
    """Project to the latents, norm them, expand a head at a time, rotate,
    assemble q_h = q_nope_h ‖ RoPE(q_pe_h) and k_h = k_nope_h ‖ RoPE(k_pe),
    then softmax attention head by head with a causal mask."""
    eps, nope = cfg.rms_norm_eps, cfg.qk_nope_head_dim
    heads, hd, vd = cfg.num_attention_heads, cfg.qk_head_dim, cfg.v_head_dim
    seq = u.shape[1]
    c_q = reference._rms(u @ lp["q_a"], lp["q_a_norm"], eps)
    latent = u @ lp["kv_a"]
    c_kv = reference._rms(latent[..., :cfg.kv_lora_rank], lp["kv_a_norm"],
                          eps)
    k_pe = reference._rope(latent[..., cfg.kv_lora_rank:], cfg.rope_theta)
    mask = np.tril(np.ones((seq, seq), bool))
    out = []
    for h in range(heads):
        q_h = c_q @ lp["q_b"][:, h * hd:(h + 1) * hd]
        kv_h = c_kv @ lp["kv_b"][:, h * (nope + vd):(h + 1) * (nope + vd)]
        q_h = jnp.concatenate(
            [q_h[..., :nope], reference._rope(q_h[..., nope:],
                                              cfg.rope_theta)], -1)
        k_h = jnp.concatenate([kv_h[..., :nope], k_pe], -1)
        scores = jnp.einsum("bqd,bkd->bqk", q_h, k_h) / np.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        out.append(probs @ kv_h[..., nope:])
    return jnp.concatenate(out, -1) @ lp["o"]


@pytest.mark.parametrize("attn", ["default", "fast"])
def test_mla_is_attention_on_the_assembled_heads_and_is_causal(attn):
    cfg = dataclasses.replace(CFG, attn_impl=attn)
    lp = _params(cfg)["layers"][1]
    u = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, cfg.hidden_size))
    got = glm4_moe_lite._mla_mixer(u, lp, cfg)
    _close(got, _mha_by_hand(u, lp, cfg))
    _close(got, reference._mla(u, lp, _model(cfg)))
    later = u.at[:, 20:].add(1.0)
    _close(glm4_moe_lite._mla_mixer(later, lp, cfg)[:, :20], got[:, :20])


def test_every_head_reads_the_same_rotated_key(monkeypatch):
    """The key the core is handed: a head's own 12-wide part, then ONE
    4-wide rotated part that is the same in every head — the rotation of
    ``k_pe``, the last columns of ``u W_kva``."""
    seen = {}

    def core(q, k, v, impl):
        seen.update(q=q, k=k, v=v)
        return jnp.zeros(q.shape[:1] + q.shape[2:3]
                         + (q.shape[1] * v.shape[3],), q.dtype)
    monkeypatch.setattr(glm4_moe_lite, "causal_attention", core)
    lp = _params(CFG)["layers"][1]
    u = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, CFG.hidden_size))
    glm4_moe_lite._mla_mixer(u, lp, CFG)
    k, nope = seen["k"], CFG.qk_nope_head_dim
    assert k.shape == (2, 4, SEQ, 16) and seen["v"].shape == (2, 4, SEQ, 16)
    rotated = k[..., nope:]
    for h in range(1, 4):
        np.testing.assert_array_equal(rotated[:, h], rotated[:, 0])
    want = reference._rope((u @ lp["kv_a"])[..., CFG.kv_lora_rank:],
                           CFG.rope_theta)
    _close(rotated[:, 0], want)
    # position 0 is not rotated; the heads' own parts differ
    _close(rotated[:, 0, 0], (u @ lp["kv_a"])[:, 0, CFG.kv_lora_rank:])
    assert not np.allclose(k[:, 0, :, :nope], k[:, 1, :, :nope])


def test_unequal_qk_and_v_widths_are_refused():
    cfg = dataclasses.replace(CFG, v_head_dim=8)
    lp = glm4_moe_lite_init(jax.random.PRNGKey(0), cfg)["layers"][0]
    with pytest.raises(ValueError, match="QK width 16 != V width 8"):
        glm4_moe_lite._mla_mixer(jnp.ones((1, 4, 64)), lp, cfg)


# ---------------------------------------------------------------------------
# (c) the shares add up
# ---------------------------------------------------------------------------

def _shares(parts=4):
    each = WHOLE.num_experts // parts
    return [dataclasses.replace(WHOLE, experts_held=(i * each, each))
            for i in range(parts)]


@pytest.mark.parametrize("where", ["trunk", "mtp"])
def test_the_shares_add_up_to_the_uncut_references_layer(where):
    """All 4 shares of the experts of a tiny sparse layer — the trunk's and
    the MTP module's: their routed parts, with what every chip computes
    alike — the shared expert — counted once, are the uncut reference's
    layer."""
    params = _params(WHOLE)
    pick = (lambda p: p["layers"][2]) if where == "trunk" \
        else (lambda p: p["mtp"][0])
    lp = pick(params)
    h = jax.random.normal(jax.random.PRNGKey(7), (2, SEQ, WHOLE.hidden_size))
    total, rows = 0.0, 0
    for cfg in _shares():
        cut = pick(glm4_moe_lite_share(params, WHOLE, cfg))
        out, record = glm4_moe_lite._sparse_ffn(h, cut, cfg)
        total += out
        rows += int(record["rows"].sum())
        if cfg.experts_held[0] == 8:
            # a share alone is the reference given the same share
            _close(out, reference._sparse_ffn(h, cut, _model(cfg))[0])
    alike = reference._gated(h, lp["shared_w13"], lp["shared_w2"])
    _close(total - 3 * alike, reference._sparse_ffn(h, lp, _model(WHOLE))[0])
    assert rows == h.shape[0] * h.shape[1] * WHOLE.num_experts_per_tok


def test_a_share_of_the_whole_models_parameters_is_a_shares_tree():
    params = _params(WHOLE)
    small = dataclasses.replace(CFG, vocab_size=64)
    cut = glm4_moe_lite_share(params, WHOLE, small)
    like = glm4_moe_lite_init(jax.random.PRNGKey(0), small)
    assert jax.tree_util.tree_map(jnp.shape, cut) \
        == jax.tree_util.tree_map(jnp.shape, like)
    np.testing.assert_array_equal(cut["layers"][2]["w2"],
                                  params["layers"][2]["w2"][8:16])
    np.testing.assert_array_equal(cut["mtp"][0]["w13"],
                                  params["mtp"][0]["w13"][8:16])
    # the dense layer and the MTP join are every chip's
    np.testing.assert_array_equal(cut["layers"][0]["w13"],
                                  params["layers"][0]["w13"])
    np.testing.assert_array_equal(cut["mtp"][0]["eh_proj"],
                                  params["mtp"][0]["eh_proj"])
    # with everything held the cut is the whole
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(
                        glm4_moe_lite_share(params, WHOLE, WHOLE))):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# (d) the MTP term
# ---------------------------------------------------------------------------

def test_the_mtp_targets_are_two_ahead_and_the_last_position_is_masked():
    batch = _batch(CFG)
    mtp = glm4_moe_lite.mtp_batch(batch)
    tokens = np.asarray(batch["tokens"])
    np.testing.assert_array_equal(mtp["targets"][:, :-2], tokens[:, 2:])
    want = np.ones((2, SEQ), np.float32)
    want[:, -2:] = 0.0     # t_{S} and t_{S+1} do not exist
    np.testing.assert_array_equal(mtp["weights"], want)
    np.testing.assert_array_equal(
        mtp["weights"], reference.mtp_weights(batch["weights"]))
    # a position with no next token has no token two ahead either
    holed = dict(batch, weights=batch["weights"].at[0, 10].set(0.0))
    w = np.asarray(glm4_moe_lite.mtp_batch(holed)["weights"])
    assert w[0, 9] == 0.0 and w[0, 10] == 0.0 and w[0, 11] == 1.0


def test_the_mtp_term_is_lambda_times_its_cross_entropy():
    params, batch = _params(CFG), _batch(CFG)
    logits, mtp_logits, _ = glm4_moe_lite._forward(
        params, batch["tokens"], batch["targets"], CFG)
    main = causal_lm_loss(logits, batch, "xla")
    term = causal_lm_loss(mtp_logits, glm4_moe_lite.mtp_batch(batch), "xla")
    assert float(glm4_moe_lite_loss(params, batch, CFG)) == pytest.approx(
        float(main + 0.3 * term), rel=1e-6)
    # the MTP module's position i reads the token at i + 1: changing the
    # LAST token moves only the last position's MTP logits (masked)
    later = dict(batch, targets=batch["targets"].at[:, -1].set(3))
    _, moved, _ = glm4_moe_lite._forward(params, batch["tokens"],
                                         later["targets"], CFG)
    _close(moved[:, :-1], mtp_logits[:, :-1])
    assert not np.allclose(moved[:, -1], mtp_logits[:, -1])


@pytest.mark.parametrize("off", ["lambda_0", "no_module"])
def test_without_the_mtp_term_the_loss_is_the_main_loss_exactly(off):
    cfg = (dataclasses.replace(CFG, mtp_loss_weight=0.0) if off == "lambda_0"
           else dataclasses.replace(CFG, num_nextn_predict_layers=0))
    params, batch = _params(cfg), _batch(cfg)
    main = causal_lm_loss(glm4_moe_lite_apply(params, batch["tokens"], cfg),
                          batch, "xla")
    assert float(glm4_moe_lite_loss(params, batch, cfg)) == float(main)
    if off == "no_module":
        assert params["mtp"] == []
        assert float(reference.loss(params, batch, _model(cfg))) \
            == pytest.approx(float(main), rel=1e-6)


# ---------------------------------------------------------------------------
# (e) the router at routed_scaling_factor 1.8
# ---------------------------------------------------------------------------

def test_route_top_k_is_a_direct_top_4_of_sigmoid_plus_bias_times_1_8():
    x = jax.random.normal(jax.random.PRNGKey(0), (50, 64))
    router = jax.random.normal(jax.random.PRNGKey(1), (64, 64)) / 8.0
    bias = 0.2 * jax.random.normal(jax.random.PRNGKey(2), (64,))
    ids, weights = expert.route_top_k(x, router, bias, 4,
                                      routed_scaling_factor=1.8)
    scores = jax.nn.sigmoid(x @ router)
    _, want_ids = jax.lax.top_k(scores + bias, 4)
    np.testing.assert_array_equal(ids, want_ids)
    chosen = jnp.take_along_axis(scores, want_ids, axis=-1)
    _close(weights, 1.8 * chosen / jnp.sum(chosen, -1, keepdims=True))
    _close(jnp.sum(weights, -1), jnp.full((50,), 1.8))
    # the bias chooses and never weighs: the reference's rule agrees
    chosen_ref, w_ref = reference._route(
        x, {"router": router, "expert_bias": bias}, _model(CFG))
    assert np.take_along_axis(np.asarray(chosen_ref), np.asarray(ids),
                              axis=1).all()
    _close(jnp.take_along_axis(w_ref, ids, axis=1), weights)


# ---------------------------------------------------------------------------
# the published configuration, the example's preset, the standard path
# ---------------------------------------------------------------------------

def _config_file():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "glm47_flash_30b_a3b.json")) as f:
        return json.load(f)


def test_config_file_holds_the_published_numbers():
    doc = _config_file()
    assert doc["source"].startswith(
        "https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json")
    assert len(doc["source"]) <= 200
    for key, value in PUBLISHED.items():
        if key in doc["reduced"]:
            assert doc["published"][key] == value, key
            assert doc[key] != value, key
        else:
            assert doc[key] == value, key
    assert doc["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]


def test_preset_is_the_published_configuration():
    doc = _config_file()
    cfg = glm47_flash_config()
    published = dict(doc, **doc["published"])
    names = {"num_experts": "n_routed_experts"}
    for key in ("hidden_size", "num_hidden_layers", "first_k_dense_replace",
                "intermediate_size", "num_attention_heads", "q_lora_rank",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "rope_theta", "num_experts",
                "num_experts_per_tok", "moe_intermediate_size",
                "n_shared_experts", "routed_scaling_factor",
                "norm_topk_prob", "rms_norm_eps", "num_nextn_predict_layers",
                "vocab_size"):
        assert getattr(cfg, key) == published[names.get(key, key)], key
    assert cfg.qk_head_dim == cfg.v_head_dim == 256
    # the cut the configuration runs is what the example's flag builds
    pretrain = _load("examples/bert/pretrain.py", "pretrain_for_glm4")
    cut = pretrain.glm4_moe_lite_config(
        pretrain.parse_args(doc["entry"]["argv"]))
    for key, value in doc["model"].items():
        got = getattr(cut, key)
        assert (list(got) if isinstance(got, tuple) else got) == value, key
    # ... and the top-level counts are what it holds
    assert (cut.experts_held[1], cut.vocab_size, cut.num_hidden_layers) == (
        doc["n_routed_experts"], doc["vocab_size"], doc["num_hidden_layers"])
    shapes = jax.eval_shape(lambda k: glm4_moe_lite_init(k, cut),
                            jax.random.PRNGKey(0))
    count = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    # the issue's 706 911 744 and the five correction biases (64 each)
    assert count == 706_911_744 + 5 * 64
    dense, sparse = shapes["layers"][0], shapes["layers"][1]
    assert dense["w13"].shape == (2048, 20480)
    assert sparse["q_a"].shape == (2048, 768)
    assert sparse["q_b"].shape == (768, 20 * 256)
    assert sparse["kv_a"].shape == (2048, 512 + 64)
    assert sparse["kv_b"].shape == (512, 20 * (192 + 256))
    assert sparse["o"].shape == (20 * 256, 2048)
    assert sparse["w13"].shape == (8, 2048, 3072)
    assert shapes["mtp"][0]["eh_proj"].shape == (4096, 2048)
    assert shapes["head"]["out"].shape == (2048, 19456)


def test_init_is_the_public_implementations():
    cfg = dataclasses.replace(WHOLE, hidden_size=256, vocab_size=2048)
    params = glm4_moe_lite_init(jax.random.PRNGKey(0), cfg)
    lp, mp = params["layers"][1], params["mtp"][0]
    stds = {"tok": (params["embed"]["tok"], 256 ** -0.5),
            "head": (params["head"]["out"], 256 ** -0.5),
            "q_a": (lp["q_a"], 256 ** -0.5), "q_b": (lp["q_b"], 24 ** -0.5),
            "kv_b": (lp["kv_b"], 16 ** -0.5), "o": (lp["o"], 64 ** -0.5),
            "router": (lp["router"], 256 ** -0.5),
            "w2": (lp["w2"], 16 ** -0.5),
            "eh_proj": (mp["eh_proj"], 512 ** -0.5)}
    for name, (leaf, want) in stds.items():
        assert float(jnp.std(leaf)) == pytest.approx(want, rel=0.06), name
    for layer in params["layers"] + params["mtp"]:
        for name, leaf in layer.items():
            if name.endswith("norm"):
                assert np.all(leaf == 1), name
    assert not np.any(lp["expert_bias"]) and "router" not in \
        params["layers"][0]


def test_the_whole_step_trains_through_the_example():
    """``parse_args`` -> ``run_standard`` under O5 with per-leaf FusedLAMB,
    the path the benchmark drives, the MTP term included: finite, falling,
    no step skipped."""
    pretrain = _load("examples/bert/pretrain.py", "pretrain_for_glm4_step")
    args = pretrain.parse_args(["--glm4-moe-lite", "4", "2", "--vocab",
                                "128", "--seq-len", "48", "--batch-size",
                                "4", "--attn", "fast", "--remat", "--lr",
                                "1e-2"])
    assert args.opt_level == "O5"
    cfg = dataclasses.replace(
        WHOLE, experts_held=(0, 8), dtype=jnp.bfloat16, remat=args.remat,
        attn_impl=args.attn, xent_impl="auto")
    mesh = create_mesh({"data": 1}, devices=jax.devices()[:1])
    rng = np.random.RandomState(0)
    steps, losses = 16, []
    with use_mesh(mesh):
        state, step = pretrain.run_standard(args, cfg, mesh)
        for _ in range(steps):
            tokens, targets, weights = pretrain.synthetic_next_token(
                rng, args.batch_size, args.seq_len, cfg.vocab_size)
            state, loss = step(state, {"tokens": tokens, "targets": targets,
                                       "weights": weights})
            losses.append(float(loss))
    assert np.all(np.isfinite(losses))
    assert np.mean(losses[-4:]) < np.mean(losses[:4]) - 0.02, losses
    assert step.optimizer_steps(state) == steps
    # the correction bias is a buffer: LAMB leaves it where it started
    assert not np.any(state.model_params["layers"][1]["expert_bias"])


def test_the_flag_is_a_preset_of_the_standard_path_alone():
    pretrain = _load("examples/bert/pretrain.py", "pretrain_for_glm4_flag")
    with pytest.raises(SystemExit, match="--glm4-moe-lite is a model preset"):
        pretrain.main(["--glm4-moe-lite", "8", "4", "--zero"])
