"""Nemotron-H (``apex_tpu.models.nemotron_h`` over
``parallel.expert.routed_experts``) against its plain float32 reference
(``benchmarks/reference/nemotron3_super_120b_a12b.py``) on seeded random
weights at a small size: d 64; 16 Mamba heads of 8 in 8 groups, state 16,
chunk 16; 8 query and 2 key/value heads of 16; 32 experts top-4 in a 32-wide
latent, a shared expert; and a share of each (heads 4..7, query heads 2..3,
experts 8..15) with non-zero firsts.  The selection bias is random and
NON-ZERO, so that "chooses but does not weigh" is part of every comparison.
"""
import dataclasses
import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import amp
from apex_tpu.models import (NemotronHConfig, nemotron3_super_120b_a12b_config,
                             nemotron_h_cut_pattern, nemotron_h_init,
                             nemotron_h_loss, nemotron_h_routing,
                             nemotron_h_share)
from apex_tpu.models import nemotron_h
from apex_tpu.parallel import create_mesh, expert, use_mesh
from apex_tpu.parallel.expert import routed_experts
from apex_tpu.telemetry import MemorySink, Registry, events

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _load(rel_path, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, rel_path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load("benchmarks/reference/nemotron3_super_120b_a12b.py",
                  "nemotron_h_reference")

WHOLE = NemotronHConfig(
    vocab_size=256, hidden_size=64, hybrid_override_pattern="ME*E",
    mamba_num_heads=16, mamba_head_dim=8, n_groups=8, ssm_state_size=16,
    conv_kernel=4, chunk_size=16, num_attention_heads=8,
    num_key_value_heads=2, head_dim=16, n_routed_experts=32,
    num_experts_per_tok=4, moe_latent_size=32, moe_intermediate_size=48,
    moe_shared_expert_intermediate_size=96, mamba_heads_held=(0, 16),
    attention_heads_held=(0, 8), experts_held=(0, 32), xent_impl="xla")
CFG = dataclasses.replace(WHOLE, mamba_heads_held=(4, 4),
                          attention_heads_held=(2, 2), experts_held=(8, 8))
SEQ = 37        # no multiple of the chunk nor of any flash block


def _model(cfg):
    """The configuration as the reference reads it: a plain dict."""
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def _params(cfg, seed=0):
    params = nemotron_h_init(jax.random.PRNGKey(seed), cfg)
    key = jax.random.PRNGKey(seed + 100)
    for layer in params["layers"]:
        for name, scale in (("expert_bias", 0.3), ("conv_b", 0.5)):
            if name in layer:
                key, k = jax.random.split(key)
                layer[name] = scale * jax.random.normal(k, layer[name].shape)
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


def _close(got, want, what=""):
    np.testing.assert_allclose(
        got, want, rtol=1e-4, atol=2e-5 * float(jnp.max(jnp.abs(want))),
        err_msg=what)


# ---------------------------------------------------------------------------
# the whole model
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("attn", ["default", "fast"])
def test_loss_and_every_gradient_leaf_match_the_reference(attn):
    cfg = dataclasses.replace(CFG, attn_impl=attn)
    params, batch = _params(cfg), _batch(cfg)
    loss, grads = jax.value_and_grad(nemotron_h_loss)(params, batch, cfg)
    want, want_grads = jax.value_and_grad(reference.loss)(
        params, batch, _model(cfg))
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    flat = jax.tree_util.tree_leaves_with_path(want_grads)
    for (path, leaf), got in zip(flat, jax.tree_util.tree_leaves(grads)):
        name = jax.tree_util.keystr(path)
        assert np.any(leaf) != name.endswith("['expert_bias']"), name
        _close(got, leaf, name)


def test_remat_changes_nothing():
    params, batch = _params(CFG), _batch(CFG)
    plain = jax.value_and_grad(nemotron_h_loss)(params, batch, CFG)
    again = jax.value_and_grad(nemotron_h_loss)(
        params, batch, dataclasses.replace(CFG, remat=True))
    for a, b in zip(jax.tree_util.tree_leaves(plain),
                    jax.tree_util.tree_leaves(again)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


def test_routing_record_covers_every_expert_layer():
    params, batch = _params(CFG), _batch(CFG)
    record = nemotron_h_routing(params, batch["tokens"], CFG)
    tokens = batch["tokens"].size
    assert record["ids"].shape == (2, tokens, CFG.num_experts_per_tok)
    assert record["rows"].shape == (2, 8)
    assert not np.any(record["dropped"]) and np.all(record["walks"] == 1)
    chosen = np.asarray(reference.routing(params, batch["tokens"],
                                          _model(CFG)))
    assert np.take_along_axis(chosen, np.asarray(record["ids"]), 2).all()
    held = (record["ids"] >= 8) & (record["ids"] < 16)
    assert int(record["rows"].sum()) == int(held.sum())
    # the fullest token's held assignments, a layer: what the sum back to
    # the tokens adds as neighbours
    np.testing.assert_array_equal(record["slots"],
                                  held.sum(axis=2).max(axis=1))
    assert record["slots"].shape == (2,)


# ---------------------------------------------------------------------------
# the Mamba-2 layer
# ---------------------------------------------------------------------------

def _scan_inputs(seq, heads=4, p=8, groups=2, n=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (2, seq, heads, p)),
            jax.nn.softplus(jax.random.normal(ks[1], (2, seq, heads)) - 1.0),
            -jnp.exp(jax.random.uniform(ks[2], (heads,), minval=0.0,
                                        maxval=2.5)),
            jax.random.normal(ks[3], (2, seq, groups, n)),
            jax.random.normal(ks[4], (2, seq, groups, n)))


@pytest.mark.parametrize("seq", [5, 16, 37, 64])
def test_chunked_scan_is_the_sequential_recurrence(seq):
    """Under one chunk, one chunk, several and a ragged last one, several
    whole ones: the output and the gradient of every input."""
    args = _scan_inputs(seq)
    probe = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

    def chunked(x, delta, a, b_in, c_out):
        return jnp.sum(nemotron_h.ssd_scan(x, delta, a, b_in, c_out, 16)
                       * probe)

    def stepwise(x, delta, a, b_in, c_out):
        per = x.shape[2] // b_in.shape[2]
        return jnp.sum(reference._recurrence(
            x, delta, a, jnp.repeat(b_in, per, axis=2),
            jnp.repeat(c_out, per, axis=2)) * probe)

    _close(nemotron_h.ssd_scan(*args, 16), reference._recurrence(
        *args[:3], jnp.repeat(args[3], 2, axis=2),
        jnp.repeat(args[4], 2, axis=2)))
    got = jax.grad(chunked, argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(stepwise, argnums=(0, 1, 2, 3, 4))(*args)
    for name, g, w in zip(("x", "delta", "a", "B", "C"), got, want):
        assert np.any(w), name
        _close(g, w, name)


def test_scan_keeps_its_statistics_in_float32_on_bfloat16_operands():
    args = _scan_inputs(48)
    half = tuple(a.astype(jnp.bfloat16) if i in (0, 3, 4) else a
                 for i, a in enumerate(args))
    out = nemotron_h.ssd_scan(*half, 16)
    assert out.dtype == jnp.float32
    want = nemotron_h.ssd_scan(*args, 16)
    assert float(jnp.max(jnp.abs(out - want))) < 0.03 * float(
        jnp.max(jnp.abs(want)))


def test_causal_conv_is_causal_and_is_the_references():
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(ks[0], (2, SEQ, 24))
    w, b = jax.random.normal(ks[1], (4, 24)), jax.random.normal(ks[2], (24,))
    out = nemotron_h._causal_conv(x, w, b)
    _close(out, reference._causal_conv(x, w, b))
    later = x.at[:, 20:].set(0.0)
    np.testing.assert_array_equal(
        nemotron_h._causal_conv(later, w, b)[:, :20], out[:, :20])
    assert np.any(nemotron_h._causal_conv(later, w, b)[:, 20] != out[:, 20])


def test_mamba_mixer_is_the_references_and_is_causal():
    params = _params(CFG)
    lp = params["layers"][0]
    u = jax.random.normal(jax.random.PRNGKey(4), (2, SEQ, CFG.hidden_size))
    out = nemotron_h._mamba_mixer(u, lp, CFG)
    _close(out, reference._mamba_mixer(u, lp, _model(CFG)))
    later = u.at[:, 20:].set(0.0)
    np.testing.assert_allclose(nemotron_h._mamba_mixer(later, lp, CFG)[:, :20],
                               out[:, :20], rtol=1e-5, atol=1e-6)


def test_held_heads_must_be_whole_groups():
    with pytest.raises(ValueError, match="whole groups"):
        dataclasses.replace(WHOLE, mamba_heads_held=(1, 4)).groups_held
    assert CFG.groups_held == (2, 2) and CFG.kv_heads_held == (0, 1)
    assert dataclasses.replace(
        WHOLE, attention_heads_held=(3, 2)).kv_heads_held == (0, 2)


def test_scan_layout_is_recorded_once_a_traced_layer():
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    prev = events.set_default(reg)
    try:
        lp = _params(CFG)["layers"][0]
        jax.eval_shape(lambda u: nemotron_h._mamba_mixer(u, lp, CFG),
                       jnp.zeros((2, SEQ, CFG.hidden_size)))
        records = [r["fields"] for r in reg.flush()
                   if r.get("name") == "ssm.layout"]
    finally:
        events.set_default(prev)
    assert records == [{"heads": 4, "chunk": 16, "chunks": 3}]


# ---------------------------------------------------------------------------
# attention without positions
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("attn", ["default", "fast"])
def test_attention_has_no_positions_and_is_the_references(attn):
    cfg = dataclasses.replace(CFG, attn_impl=attn)
    lp = _params(cfg)["layers"][2]
    u = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, cfg.hidden_size))
    out = nemotron_h._attention_mixer(u, lp, cfg)
    _close(out, reference._attention_mixer(u, lp, _model(cfg)))
    # the last position sees the SET of the earlier ones, in any order
    order = np.r_[np.random.RandomState(0).permutation(SEQ - 1), SEQ - 1]
    np.testing.assert_allclose(
        nemotron_h._attention_mixer(u[:, order], lp, cfg)[:, -1], out[:, -1],
        rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# the latent expert layer
# ---------------------------------------------------------------------------

def _latent_layer(cfg, tokens=96, seed=1):
    lp = _params(cfg, seed)["layers"][1]
    u = jax.random.normal(jax.random.PRNGKey(seed), (tokens, cfg.hidden_size))
    return lp, u


def _routed_part(u, lp, cfg, rows_a_walk=None):
    """The held experts' ``r`` (T, latent) and the routing record."""
    fn = routed_experts if rows_a_walk is None else functools.partial(
        expert._routed_experts, rows_a_walk=rows_a_walk)
    return fn(u, lp["router"], lp["expert_bias"], lp["w1"], lp["w2"],
              top_k=cfg.num_experts_per_tok, first=cfg.experts_held[0],
              routed_scaling_factor=cfg.routed_scaling_factor, form="relu2",
              rows=u @ lp["latent_down"], axis_name=None)


def _reference_routed_part(u, lp, cfg):
    model = _model(cfg)
    _, weights = reference._route(u, lp, model)
    latent = u @ lp["latent_down"]
    first, count = cfg.experts_held
    return sum(weights[:, first + e, None] * (
        reference._relu2(latent @ lp["w1"][e]) @ lp["w2"][e])
        for e in range(count))


@pytest.mark.parametrize("walks", [1, 3])
def test_squared_relu_experts_on_latent_rows_through_routed_experts(walks):
    """Output and every gradient — rows, router, W1, W2 — at one walk of the
    buffer and at a forced three; the rows are narrower than the router's
    input."""
    lp, u = _latent_layer(CFG)
    sent = int(_routed_part(u, lp, CFG)[1]["rows"].sum())
    rows_a_walk = None if walks == 1 else -(-sent // walks)
    probe = jax.random.normal(jax.random.PRNGKey(11),
                              (u.shape[0], CFG.moe_latent_size))
    leaves = ("router", "latent_down", "w1", "w2")

    def system(u, *ws):
        out, record = _routed_part(u, dict(lp, **dict(zip(leaves, ws))), CFG,
                                   rows_a_walk)
        return jnp.sum(out * probe), (out, record)

    def plain(u, *ws):
        out = _reference_routed_part(u, dict(lp, **dict(zip(leaves, ws))),
                                     CFG)
        return jnp.sum(out * probe), out

    args = (u, *(lp[k] for k in leaves))
    (_, (out, record)), got = jax.value_and_grad(
        jax.checkpoint(system), argnums=range(5), has_aux=True)(*args)
    (_, want_out), want = jax.value_and_grad(
        plain, argnums=range(5), has_aux=True)(*args)
    assert int(record["walks"]) == walks and int(record["dropped"]) == 0
    assert out.shape == (u.shape[0], CFG.moe_latent_size)
    _close(out, want_out)
    for name, g, w in zip(("u",) + leaves, got, want):
        assert np.any(w), name
        _close(g, w, name)


def test_an_unknown_expert_form_is_refused():
    lp, u = _latent_layer(CFG)
    with pytest.raises(ValueError, match="form"):
        routed_experts(u, lp["router"], lp["expert_bias"], lp["w1"],
                       lp["w2"], top_k=4, form="gelu", axis_name=None)


def test_latent_layer_is_the_references():
    lp, u = _latent_layer(CFG)
    out, record = nemotron_h._latent_moe(u[None], lp, CFG)
    want, chosen = reference._latent_moe(u[None], lp, _model(CFG))
    _close(out, want)
    assert np.take_along_axis(np.asarray(chosen), np.asarray(record["ids"]),
                              1).all()


# ---------------------------------------------------------------------------
# the shares add up
# ---------------------------------------------------------------------------

def _shares():
    """Eight head shares (2 Mamba heads = one group, one query head each),
    each holding a different eighth of the experts."""
    return [dataclasses.replace(
        WHOLE, mamba_heads_held=(2 * i, 2), attention_heads_held=(i, 1),
        experts_held=(4 * i, 4)) for i in range(8)]


def test_the_shares_add_up_to_the_uncut_references_layers():
    """All 8 shares of heads and experts of a tiny model: their parts of
    each layer's sum, with what every chip computes alike — the shared
    expert — counted once, are the uncut reference's layer."""
    params = _params(WHOLE)
    model = _model(WHOLE)
    u = jax.random.normal(jax.random.PRNGKey(7), (2, SEQ, WHOLE.hidden_size))
    m, e, a = (params["layers"][i] for i in (0, 1, 2))
    parts = {"M": 0.0, "*": 0.0, "E": 0.0}
    rows = 0
    for cfg in _shares():
        cut = nemotron_h_share(params, WHOLE, cfg)["layers"]
        parts["M"] += nemotron_h._mamba_mixer(u, cut[0], cfg)
        parts["*"] += nemotron_h._attention_mixer(u, cut[2], cfg)
        out, record = nemotron_h._latent_moe(u, cut[1], cfg)
        parts["E"] += out
        rows += int(record["rows"].sum())
        if cfg.experts_held[0] == 4:
            # a share alone is the reference given the same share
            _close(out, reference._latent_moe(u, cut[1], _model(cfg))[0])
    alike = reference._relu2(u @ e["shared_w1"]) @ e["shared_w2"]
    _close(parts["M"], reference._mamba_mixer(u, m, model), "M")
    _close(parts["*"], reference._attention_mixer(u, a, model), "*")
    _close(parts["E"] - 7 * alike, reference._latent_moe(u, e, model)[0], "E")
    assert rows == u.shape[0] * u.shape[1] * WHOLE.num_experts_per_tok


def test_a_share_of_the_whole_models_parameters_is_a_shares_tree():
    params = _params(WHOLE)
    cut = nemotron_h_share(params, WHOLE, dataclasses.replace(
        CFG, vocab_size=64))
    like = nemotron_h_init(jax.random.PRNGKey(0), dataclasses.replace(
        CFG, vocab_size=64))
    assert jax.tree_util.tree_map(jnp.shape, cut) \
        == jax.tree_util.tree_map(jnp.shape, like)
    # with everything held the cut is the whole
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(
                        nemotron_h_share(params, WHOLE, WHOLE))):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# the published configuration, the example's preset, the standard path
# ---------------------------------------------------------------------------

def _config_file():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "nemotron3_super_120b_a12b.json")) as f:
        return json.load(f)


def test_config_file_holds_the_catalog_rows_numbers():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog of architectures is not on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows
               if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    doc = _config_file()
    assert doc["source"].startswith(row["source_url"])
    for key, value in row["config"].items():
        if key in doc["reduced"]:
            assert doc["published"][key] == value, key
            assert doc[key] != value, key
        else:
            assert doc[key] == value, key
    # no width among the cuts
    assert not [k for k in doc["reduced"] if k.endswith(("_dim", "_size"))
                and k != "vocab_size"]


def test_preset_is_the_published_configuration():
    doc = _config_file()
    cfg = nemotron3_super_120b_a12b_config()
    published = dict(doc, **doc["published"])
    for key in ("hidden_size", "hybrid_override_pattern", "mamba_num_heads",
                "mamba_head_dim", "n_groups", "ssm_state_size", "conv_kernel",
                "chunk_size", "time_step_min", "time_step_max",
                "time_step_floor", "num_attention_heads",
                "num_key_value_heads", "head_dim", "n_routed_experts",
                "num_experts_per_tok", "moe_latent_size",
                "moe_intermediate_size",
                "moe_shared_expert_intermediate_size", "norm_topk_prob",
                "routed_scaling_factor", "layer_norm_epsilon", "vocab_size",
                "num_hidden_layers"):
        assert getattr(cfg, key) == published[key], key
    assert cfg.mamba_num_heads * cfg.mamba_head_dim \
        == doc["expand"] * cfg.hidden_size
    assert (cfg.hybrid_override_pattern.count("M"),
            cfg.hybrid_override_pattern.count("*"),
            cfg.hybrid_override_pattern.count("E")) == (40, 8, 40)
    # the period kept: layers 28-38 of the 88, and three more times after
    assert cfg.hybrid_override_pattern[27:38] == nemotron_h_cut_pattern(1)
    assert cfg.hybrid_override_pattern.count(nemotron_h_cut_pattern(1)) == 4
    # the cut the configuration runs is what the example's flag builds
    pretrain = _load("examples/bert/pretrain.py", "pretrain_for_nemotron_h")
    cut = pretrain.nemotron_h_config(
        pretrain.parse_args(doc["entry"]["argv"]))
    for key, value in doc["model"].items():
        got = getattr(cut, key)
        assert (list(got) if isinstance(got, tuple) else got) == value, key
    # ... and the top-level counts are what it holds
    assert (cut.mamba_heads_held[1], cut.groups_held[1],
            cut.attention_heads_held[1], cut.kv_heads_held[1],
            cut.experts_held[1], cut.vocab_size, cut.num_hidden_layers) == (
        doc["mamba_num_heads"], doc["n_groups"], doc["num_attention_heads"],
        doc["num_key_value_heads"], doc["n_routed_experts"],
        doc["vocab_size"], doc["num_hidden_layers"])
    shapes = jax.eval_shape(lambda k: nemotron_h_init(k, cut),
                            jax.random.PRNGKey(0))
    count = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert count == pytest.approx(701e6, rel=2e-3)


def test_init_rescales_what_writes_into_the_residual_stream():
    """N(0, 1/fan_in) but: the four matrices that write into the stream are
    1/sqrt(rescale_layers) smaller — the published depth, not the cut's — and
    the embedding is N(0, 1)."""
    cfg = dataclasses.replace(WHOLE, hidden_size=256, vocab_size=2048)
    assert cfg.rescale_layers == 88 and cfg.num_hidden_layers == 4
    params = nemotron_h_init(jax.random.PRNGKey(0), cfg)
    m, e, a, _ = params["layers"]
    stds = {"tok": (params["embed"]["tok"], 1.0),
            "head": (params["head"]["out"], 256 ** -0.5),
            "in_proj": (m["in_proj"], 256 ** -0.5),
            "out_proj": (m["out_proj"], (128 * 88) ** -0.5),
            "wq": (a["wq"], 256 ** -0.5), "wo": (a["wo"], (128 * 88) ** -0.5),
            "router": (e["router"], 256 ** -0.5),
            "latent_down": (e["latent_down"], 256 ** -0.5),
            "latent_up": (e["latent_up"], (32 * 88) ** -0.5),
            "w2": (e["w2"], 48 ** -0.5),
            "shared_w1": (e["shared_w1"], 256 ** -0.5),
            "shared_w2": (e["shared_w2"], (96 * 88) ** -0.5)}
    for name, (leaf, want) in stds.items():
        assert float(jnp.std(leaf)) == pytest.approx(want, rel=0.05), name
    assert np.all(m["D"] == 1) and not np.any(m["conv_b"])
    step = jax.nn.softplus(m["dt_bias"])
    assert np.all((step >= 1e-3 * 0.999) & (step <= 0.1 * 1.001))
    assert np.all((m["A_log"] >= 0) & (m["A_log"] <= np.log(16.0)))


def _tiny_step(argv=()):
    pretrain = _load("examples/bert/pretrain.py", "pretrain_for_nemotron_step")
    args = pretrain.parse_args(["--nemotron-h", "4", "4", "1", "--vocab",
                                "256", "--seq-len", "48", "--batch-size", "4",
                                "--attn", "fast", "--remat", *argv])
    cfg = dataclasses.replace(
        WHOLE, hybrid_override_pattern=nemotron_h_cut_pattern(1),
        mamba_heads_held=(0, 4), attention_heads_held=(0, 2),
        experts_held=(0, 8), dtype=jnp.bfloat16, remat=args.remat,
        attn_impl=args.attn, xent_impl="auto")
    return pretrain, args, cfg


def test_the_whole_step_trains_through_the_example():
    """``parse_args`` -> ``run_standard`` under O5 with per-leaf FusedLAMB,
    the path the benchmark drives: finite, falling, no step skipped."""
    pretrain, args, cfg = _tiny_step(["--lr", "1e-2"])
    assert args.opt_level == "O5"
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
    for layer in state.model_params["layers"]:
        if "expert_bias" in layer:
            assert not np.any(layer["expert_bias"])


def test_run_standard_builds_in_one_program_the_state_amp_initialize_builds():
    """Set-up makes parameters and amp state in ONE jitted program, placed on
    the mesh where it is written: the values, leaf for leaf, of ``amp.initialize`` of the initialiser's
    float32 parameters."""
    pretrain, args, cfg = _tiny_step()
    mesh = create_mesh({"data": 1}, devices=jax.devices()[:1])
    with use_mesh(mesh):
        state, _ = pretrain.run_standard(args, cfg, mesh)
    want = amp.initialize(
        nemotron_h_init(jax.random.PRNGKey(args.seed), cfg),
        state.optimizer, opt_level=args.opt_level, verbosity=0)
    got_leaves = jax.tree_util.tree_leaves(state)
    assert len(got_leaves) == len(jax.tree_util.tree_leaves(want))
    # one program may round a fused product differently from two: the float32
    # leaves to a few ulps, a bfloat16 copy to one of its own
    for a, b in zip(got_leaves, jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype
        assert a.sharding.is_equivalent_to(
            jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
            a.ndim)
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=1e-2 if a.dtype == jnp.bfloat16 else 1e-6, atol=1e-7)
