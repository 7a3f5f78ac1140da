"""Qwen3-Next (``apex_tpu.models.qwen3_next`` over
``parallel.expert.routed_experts``) against its plain float32 reference
(``benchmarks/reference/qwen3_next_80b_a3b.py``) on seeded random weights at a
small size: d 64; pattern (linear, linear, linear, full); 2 key and 4 value
heads of 8 in the Gated DeltaNet layers, chunk 16; 4 query and 2 key/value
heads of 16 with a quarter rotary; 32 experts top-4 with softmax scores, a
gated shared expert; and a share of the experts (8..15) with a non-zero first.
"""
import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import (Qwen3NextConfig, qwen3_next_80b_a3b_config,
                             qwen3_next_init, qwen3_next_loss,
                             qwen3_next_routing, qwen3_next_share)
from apex_tpu.models import qwen3_next
from apex_tpu.parallel import create_mesh, expert, use_mesh
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


reference = _load("benchmarks/reference/qwen3_next_80b_a3b.py",
                  "qwen3_next_reference")

WHOLE = Qwen3NextConfig(
    vocab_size=256, hidden_size=64, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    linear_num_key_heads=2, linear_key_head_dim=8, linear_num_value_heads=4,
    linear_value_head_dim=8, chunk_size=16, num_experts=32,
    num_experts_per_tok=4, moe_intermediate_size=24,
    shared_expert_intermediate_size=24, experts_held=(0, 32),
    xent_impl="xla")
CFG = dataclasses.replace(WHOLE, experts_held=(8, 8))
SEQ = 37        # no multiple of the chunk nor of any flash block


def _model(cfg):
    """The configuration as the reference reads it: a plain dict."""
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def _params(cfg, seed=0):
    """The initialiser's tree with the zero-centred norms' ``w`` drawn away
    from 0 and the gated norm's gain from 1, so that each takes part."""
    params = qwen3_next_init(jax.random.PRNGKey(seed), cfg)
    key = jax.random.PRNGKey(seed + 100)

    def perturb(leaf):
        nonlocal key
        key, k = jax.random.split(key)
        return leaf + 0.3 * jax.random.normal(k, leaf.shape)
    for layer in params["layers"]:
        for name in ("input_norm", "ffn_norm", "q_norm", "k_norm",
                     "gate_norm"):
            if name in layer:
                layer[name] = perturb(layer[name])
    params["head"]["norm"] = perturb(params["head"]["norm"])
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
# (a) the whole model
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("attn", ["default", "fast"])
def test_loss_and_every_gradient_leaf_match_the_reference(attn):
    """Float32 on both sides.  The gradient's limit is 1e-3 of a leaf's
    largest element: against the reference in float64 the system's leaves
    are off by up to 2.3e-4 and the float32 reference's by up to 2.5e-4 (the
    first layer's, where four layers' rounding has gathered; the last
    layer's by 2e-5) — the two float32 computations are as far from each
    other as either is from the truth."""
    cfg = dataclasses.replace(CFG, attn_impl=attn)
    assert cfg.layer_types == ("linear_attention",) * 3 + ("full_attention",)
    params, batch = _params(cfg), _batch(cfg)
    loss, grads = jax.value_and_grad(qwen3_next_loss)(params, batch, cfg)
    want, want_grads = jax.value_and_grad(reference.loss)(
        params, batch, _model(cfg))
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    flat = jax.tree_util.tree_leaves_with_path(want_grads)
    assert len(flat) == len(jax.tree_util.tree_leaves(grads))
    for (path, leaf), got in zip(flat, jax.tree_util.tree_leaves(grads)):
        name = jax.tree_util.keystr(path)
        assert np.any(leaf), name
        _close(got, leaf, name, tol=1e-3)


def test_remat_changes_nothing():
    params, batch = _params(CFG), _batch(CFG)
    plain = jax.value_and_grad(qwen3_next_loss)(params, batch, CFG)
    again = jax.value_and_grad(qwen3_next_loss)(
        params, batch, dataclasses.replace(CFG, remat=True))
    for a, b in zip(jax.tree_util.tree_leaves(plain),
                    jax.tree_util.tree_leaves(again)):
        _close(a, b)


def test_routing_record_covers_every_layer():
    params, batch = _params(CFG), _batch(CFG)
    record = jax.jit(lambda p, t: qwen3_next_routing(p, t, CFG))(
        params, batch["tokens"])
    tokens = batch["tokens"].size
    assert record["ids"].shape == (4, tokens, CFG.num_experts_per_tok)
    assert record["rows"].shape == (4, 8)
    chosen = np.asarray(reference.routing(params, batch["tokens"],
                                          _model(CFG)))
    ids = np.asarray(record["ids"])
    assert np.take_along_axis(chosen, ids, axis=2).all()
    first, held = CFG.experts_held
    for layer in range(4):
        want = [(ids[layer] == first + e).sum() for e in range(held)]
        np.testing.assert_array_equal(record["rows"][layer], want)
    assert not np.any(record["dropped"])
    assert np.all(np.asarray(record["walks"]) == 1)


# ---------------------------------------------------------------------------
# (b) the chunked rule is the sequential recurrence
# ---------------------------------------------------------------------------

def _rule_inputs(seq, decay, seed=0):
    """q, k normalised as the mixer hands them; ``g = -decay · softplus``."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    bsz, groups, per, dk, dv = 2, 2, 2, 8, 12
    q = qwen3_next._l2_norm(jax.random.normal(ks[0], (bsz, seq, groups, dk))
                            ) * dk ** -0.5
    k = qwen3_next._l2_norm(jax.random.normal(ks[1], (bsz, seq, groups, dk)))
    v = jax.random.normal(ks[2], (bsz, seq, groups * per, dv))
    g = -decay * jax.nn.softplus(
        jax.random.normal(ks[3], (bsz, seq, groups * per)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (bsz, seq, groups * per)))
    probe = jax.random.normal(ks[5], (bsz, seq, groups * per, dv))
    return (q, k, v, g, beta), probe


def _sequential(q, k, v, g, beta):
    per = v.shape[2] // q.shape[2]
    return reference._delta_recurrence(
        jnp.repeat(q, per, axis=2), jnp.repeat(k, per, axis=2), v, g, beta)


@pytest.mark.parametrize("seq,decay", [
    (5, 1.0),       # shorter than a chunk
    (16, 1.0),      # one whole chunk
    (37, 1.0),      # no multiple of the chunk
    (64, 0.05),     # four chunks, a state that hardly decays
    (37, 40.0),     # strongly negative g: e^γ underflows inside a chunk
])
def test_chunked_rule_is_the_sequential_recurrence(seq, decay):
    args, probe = _rule_inputs(seq, decay)
    got = qwen3_next.gated_delta_rule(*args, 16)
    want = _sequential(*args)
    assert got.shape == want.shape and got.dtype == jnp.float32
    _close(got, want)
    grads = jax.grad(lambda *a: jnp.sum(
        qwen3_next.gated_delta_rule(*a, 16) * probe), argnums=range(5))(*args)
    want_grads = jax.grad(lambda *a: jnp.sum(_sequential(*a) * probe),
                          argnums=range(5))(*args)
    for name, a, b in zip("q k v g beta".split(), grads, want_grads):
        assert np.all(np.isfinite(a)), name
        _close(a, b, name)


def test_the_chunk_length_changes_nothing():
    args, _ = _rule_inputs(50, 1.0, seed=3)
    want = qwen3_next.gated_delta_rule(*args, 64)
    for chunk in (8, 16, 32):
        _close(qwen3_next.gated_delta_rule(*args, chunk), want)


def test_unit_lower_inverse_and_its_reverse_rule():
    a = 0.3 * jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (3, 16, 16)),
                       -1)
    eye = jnp.eye(16)
    t = qwen3_next._unit_lower_inverse(a)
    _close(t @ (eye - a), jnp.broadcast_to(eye, a.shape), tol=1e-5)
    probe = jax.random.normal(jax.random.PRNGKey(1), a.shape)
    got = jax.grad(lambda a: jnp.sum(
        qwen3_next._unit_lower_inverse(a) * probe))(a)
    want = jax.grad(lambda a: jnp.sum(jnp.linalg.inv(eye - a) * probe))(a)
    _close(got, want, tol=1e-5)


def test_rule_keeps_its_statistics_in_float32_on_bfloat16_operands():
    (q, k, v, g, beta), _ = _rule_inputs(48, 1.0, seed=2)
    half = [t.astype(jnp.bfloat16) for t in (q, k, v)]
    got = qwen3_next.gated_delta_rule(*half, g, beta, 16)
    assert got.dtype == jnp.bfloat16
    want = _sequential(*(t.astype(jnp.float32) for t in half), g, beta)
    np.testing.assert_allclose(got, want, atol=0.05 * float(
        jnp.max(jnp.abs(want))))


@pytest.mark.parametrize("bsz,seq", [
    (1, SEQ), (2, SEQ), (3, SEQ), (4, SEQ), (1, 5), (2, 5), (3, 64), (4, 64),
    (2, 70)])
def test_the_mixer_takes_a_sequence_at_a_time(bsz, seq):
    """The rule runs under ``lax.map`` over the batch's sequences: whatever
    the batch, the mixer is the reference's, and a sequence's output is what
    it is alone (the gradients: the test of every leaf above, at batch 2)."""
    lp = _params(CFG)["layers"][0]
    u = jax.random.normal(jax.random.PRNGKey(5), (bsz, seq, CFG.hidden_size))
    got = qwen3_next._gdn_mixer(u, lp, CFG)
    _close(got, reference._gdn_mixer(u, lp, _model(CFG)))
    _close(got[-1:], qwen3_next._gdn_mixer(u[-1:], lp, CFG))


def test_gdn_mixer_is_the_references_and_is_causal():
    params = _params(CFG)
    lp = params["layers"][0]
    u = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, CFG.hidden_size))
    got = qwen3_next._gdn_mixer(u, lp, CFG)
    _close(got, reference._gdn_mixer(u, lp, _model(CFG)))
    later = u.at[:, 20:].add(1.0)
    np.testing.assert_array_equal(
        qwen3_next._gdn_mixer(later, lp, CFG)[:, :20], got[:, :20])


# ---------------------------------------------------------------------------
# (c) the shares add up
# ---------------------------------------------------------------------------

def _shares(parts=4):
    each = WHOLE.num_experts // parts
    return [dataclasses.replace(WHOLE, experts_held=(i * each, each))
            for i in range(parts)]


def test_the_shares_add_up_to_the_uncut_references_layer():
    """All 4 shares of the experts of a tiny layer: their routed parts, with
    what every chip computes alike — the gated shared expert — counted once,
    are the uncut reference's layer."""
    params = _params(WHOLE)
    lp = params["layers"][1]
    model = _model(WHOLE)
    h = jax.random.normal(jax.random.PRNGKey(7), (2, SEQ, WHOLE.hidden_size))
    total, rows = 0.0, 0
    for cfg in _shares():
        cut = qwen3_next_share(params, WHOLE, cfg)["layers"][1]
        out, record = qwen3_next._sparse_ffn(h, cut, cfg)
        total += out
        rows += int(record["rows"].sum())
        if cfg.experts_held[0] == 8:
            # a share alone is the reference given the same share
            _close(out, reference._sparse_ffn(h, cut, _model(cfg))[0])
    alike = reference._shared_expert(h.reshape(-1, h.shape[-1]), lp
                                     ).reshape(h.shape)
    _close(total - 3 * alike, reference._sparse_ffn(h, lp, model)[0])
    assert rows == h.shape[0] * h.shape[1] * WHOLE.num_experts_per_tok


def test_a_share_of_the_whole_models_parameters_is_a_shares_tree():
    params = _params(WHOLE)
    small = dataclasses.replace(CFG, vocab_size=64)
    cut = qwen3_next_share(params, WHOLE, small)
    like = qwen3_next_init(jax.random.PRNGKey(0), small)
    assert jax.tree_util.tree_map(jnp.shape, cut) \
        == jax.tree_util.tree_map(jnp.shape, like)
    np.testing.assert_array_equal(cut["layers"][2]["w2"],
                                  params["layers"][2]["w2"][8:16])
    # with everything held the cut is the whole
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(
                        qwen3_next_share(params, WHOLE, WHOLE))):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# (d) the quarter rotary, the zero-centred norm, the gated attention
# ---------------------------------------------------------------------------

def test_partial_rotary_touches_only_the_first_quarter_of_a_head():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 4, 16))
    got = qwen3_next._partial_rope(x, CFG)
    assert CFG.rotary_dim == 4
    np.testing.assert_array_equal(got[..., 4:], x[..., 4:])
    np.testing.assert_array_equal(got[:, 0], x[:, 0])         # position 0
    assert np.all(np.asarray(got[:, 1:, :, :4] != x[:, 1:, :, :4]).any(-1))
    _close(got, reference._partial_rope(x, _model(CFG)))
    # a rotation: the rotated quarter keeps its length
    _close(jnp.sum(got[..., :4] ** 2, -1), jnp.sum(x[..., :4] ** 2, -1))


def test_zero_centred_norm_at_w_zero_is_a_plain_norm():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 64)) * 3.0
    plain = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    _close(qwen3_next._rms0(x, jnp.zeros((64,)), 1e-6), plain)
    w = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (64,))
    _close(qwen3_next._rms0(x, w, 1e-6), (1.0 + w) * plain)
    _close(qwen3_next._rms0(x, w, 1e-6), reference._rms0(x, w, 1e-6))


@pytest.mark.parametrize("attn", ["default", "fast"])
def test_gated_attention_is_the_references_and_is_causal(attn):
    cfg = dataclasses.replace(CFG, attn_impl=attn)
    lp = _params(cfg)["layers"][3]
    u = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, cfg.hidden_size))
    got = qwen3_next._attention_mixer(u, lp, cfg)
    _close(got, reference._attention_mixer(u, lp, _model(cfg)))
    later = u.at[:, 20:].add(1.0)
    _close(qwen3_next._attention_mixer(later, lp, cfg)[:, :20], got[:, :20])
    # the gate is on the context: gates of 0 (sigmoid ½) halve the output
    # of gates at +40 (sigmoid 1), whatever the queries are
    gates = jnp.tile(jnp.arange(32) >= 16, 4)           # a head: query | gate
    half = dict(lp, wq=jnp.where(gates, 0.0, lp["wq"]))
    ones = jnp.concatenate([u, jnp.full(u.shape[:2] + (1,), 40.0)], -1)
    wide = {k: (jnp.concatenate([v, jnp.zeros((1, v.shape[1]))])
                if k in ("wq", "wk", "wv") else v) for k, v in half.items()}
    wide["wq"] = wide["wq"].at[-1].set(gates.astype(jnp.float32))
    _close(qwen3_next._attention_mixer(u, half, cfg),
           0.5 * qwen3_next._attention_mixer(ones, wide, cfg))


# ---------------------------------------------------------------------------
# (e) the router's score function
# ---------------------------------------------------------------------------

def test_softmax_scores_are_a_direct_top_k_of_a_softmax():
    x = jax.random.normal(jax.random.PRNGKey(0), (50, 64))
    router = jax.random.normal(jax.random.PRNGKey(1), (64, 512)) / 8.0
    ids, weights = expert.route_top_k(x, router, None, 10, score="softmax")
    probs = jax.nn.softmax(x @ router, axis=-1)
    top, want_ids = jax.lax.top_k(probs, 10)
    np.testing.assert_array_equal(ids, want_ids)
    _close(weights, top / jnp.sum(top, -1, keepdims=True))
    _close(jnp.sum(weights, -1), jnp.ones((50,)))
    _, bare = expert.route_top_k(x, router, None, 10, score="softmax",
                                 norm_topk_prob=False)
    _close(bare, top)


def test_sigmoid_default_is_bit_for_bit_what_it_was():
    x = jax.random.normal(jax.random.PRNGKey(0), (50, 64)).astype(
        jnp.bfloat16)
    router = jax.random.normal(jax.random.PRNGKey(1), (64, 32)) / 8.0
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(2), (32,))
    ids, weights = expert.route_top_k(x, router, bias, 4,
                                      routed_scaling_factor=2.5)
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router, precision=jax.lax.Precision.HIGHEST))
    _, want_ids = jax.lax.top_k(scores + bias, 4)
    chosen = jnp.take_along_axis(scores, want_ids, axis=-1)
    want = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-6) * 2.5
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_array_equal(weights, want)
    again = expert.route_top_k(x, router, bias, 4, routed_scaling_factor=2.5,
                               score="sigmoid")
    np.testing.assert_array_equal(again[1], weights)


def test_an_unknown_score_function_is_refused():
    with pytest.raises(ValueError, match="score must be one of"):
        expert.route_top_k(jnp.zeros((4, 8)), jnp.zeros((8, 16)), None, 2,
                           score="tanh")


@pytest.mark.parametrize("walks", [1, 3])
def test_softmax_routed_experts_through_the_buffer(walks):
    """The layer through ``routed_experts`` (one walk of its buffer and a
    forced three) is the reference's: output and every gradient."""
    lp = _params(CFG)["layers"][0]
    h = jax.random.normal(jax.random.PRNGKey(3), (60, CFG.hidden_size))
    probe = jax.random.normal(jax.random.PRNGKey(4), h.shape)
    first, held = CFG.experts_held

    def system(h, router, w13, w2, rows_a_walk=None):
        out, record = expert._routed_experts(
            h, router, None, w13, w2, top_k=CFG.num_experts_per_tok,
            first=first, score="softmax", axis_name=None,
            rows_a_walk=rows_a_walk)
        return jnp.sum(out * probe), record

    def ref(h, router, w13, w2):
        p = dict(lp, router=router, w13=w13, w2=w2)
        out, _ = reference._sparse_ffn(h, p, _model(CFG))
        return jnp.sum((out - reference._shared_expert(h, p)) * probe)

    args = (h, lp["router"], lp["w13"], lp["w2"])
    sent = int(system(*args)[1]["rows"].sum())
    rows_a_walk = None if walks == 1 else -(-sent // 3)
    (value, record), grads = jax.value_and_grad(
        lambda *a: system(*a, rows_a_walk=rows_a_walk), argnums=range(4),
        has_aux=True)(*args)
    assert int(record["walks"]) == walks and not int(record["dropped"])
    want, want_grads = jax.value_and_grad(ref, argnums=range(4))(*args)
    assert float(value) == pytest.approx(float(want), rel=1e-4)
    for name, a, b in zip(("h", "router", "w13", "w2"), grads, want_grads):
        _close(a, b, name)


def test_sparse_ffn_is_the_references():
    lp = _params(CFG)["layers"][2]
    h = jax.random.normal(jax.random.PRNGKey(9), (2, SEQ, CFG.hidden_size))
    out, _ = qwen3_next._sparse_ffn(h, lp, CFG)
    _close(out, reference._sparse_ffn(h, lp, _model(CFG))[0])


def test_routing_layout_is_recorded_once_a_traced_layer():
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    prev = events.set_default(reg)
    try:
        jax.make_jaxpr(lambda p, t: qwen3_next.qwen3_next_apply(p, t, CFG))(
            _params(CFG), _batch(CFG)["tokens"])
        layouts = [r["fields"] for r in reg.flush()
                   if r.get("name") == "moe.layout"]
    finally:
        events.set_default(prev)
    assert len(layouts) == 4
    assert {(l["experts"], l["held"], l["top_k"]) for l in layouts} \
        == {(32, 8, 4)}


# ---------------------------------------------------------------------------
# the published configuration, the example's preset, the standard path
# ---------------------------------------------------------------------------

def _config_file():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "qwen3_next_80b_a3b.json")) as f:
        return json.load(f)


def test_config_file_holds_the_catalog_rows_numbers():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog of architectures is not on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    doc = _config_file()
    assert doc["source"].startswith(row["source_url"])
    assert len(doc["source"]) <= 200
    for key, value in row["config"].items():
        if key in doc["reduced"]:
            assert doc["published"][key] == value, key
            assert doc[key] != value, key
        else:
            assert doc[key] == value, key
    assert doc["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]


def test_preset_is_the_published_configuration():
    doc = _config_file()
    cfg = qwen3_next_80b_a3b_config()
    published = dict(doc, **doc["published"])
    for key in ("hidden_size", "num_hidden_layers", "full_attention_interval",
                "num_attention_heads", "num_key_value_heads", "head_dim",
                "partial_rotary_factor", "rope_theta", "linear_num_key_heads",
                "linear_key_head_dim", "linear_num_value_heads",
                "linear_value_head_dim", "linear_conv_kernel_dim",
                "num_experts", "num_experts_per_tok", "moe_intermediate_size",
                "shared_expert_intermediate_size", "norm_topk_prob",
                "rms_norm_eps", "vocab_size"):
        assert getattr(cfg, key) == published[key], key
    assert cfg.rotary_dim == 64
    assert (cfg.layer_types.count("linear_attention"),
            cfg.layer_types.count("full_attention")) == (36, 12)
    assert cfg.layer_types[:4] == ("linear_attention",) * 3 + (
        "full_attention",)
    # the cut the configuration runs is what the example's flag builds
    pretrain = _load("examples/bert/pretrain.py", "pretrain_for_qwen3_next")
    cut = pretrain.qwen3_next_config(
        pretrain.parse_args(doc["entry"]["argv"]))
    for key, value in doc["model"].items():
        got = getattr(cut, key)
        assert (list(got) if isinstance(got, tuple) else got) == value, key
    # ... and the top-level counts are what it holds
    assert (cut.experts_held[1], cut.vocab_size, cut.num_hidden_layers) == (
        doc["num_experts"], doc["vocab_size"], doc["num_hidden_layers"])
    shapes = jax.eval_shape(lambda k: qwen3_next_init(k, cut),
                            jax.random.PRNGKey(0))
    count = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert count == 625_994_816
    layer = shapes["layers"][0]
    assert layer["in_proj_qkvz"].shape == (2048, 12288)
    assert layer["in_proj_ba"].shape == (2048, 64)
    assert layer["conv_w"].shape == (4, 8192)
    assert shapes["layers"][3]["wq"].shape == (2048, 8192)
    assert layer["w13"].shape == (32, 2048, 1024)


def test_init_is_the_public_implementations():
    cfg = dataclasses.replace(WHOLE, hidden_size=256, vocab_size=2048)
    params = qwen3_next_init(jax.random.PRNGKey(0), cfg)
    gdn, full = params["layers"][0], params["layers"][3]
    stds = {"tok": (params["embed"]["tok"], 256 ** -0.5),
            "head": (params["head"]["out"], 256 ** -0.5),
            "in_proj_qkvz": (gdn["in_proj_qkvz"], 256 ** -0.5),
            "out_proj": (gdn["out_proj"], 32 ** -0.5),
            "conv_w": (gdn["conv_w"], 0.5),
            "wq": (full["wq"], 256 ** -0.5), "wo": (full["wo"], 64 ** -0.5),
            "router": (gdn["router"], 256 ** -0.5),
            "w2": (gdn["w2"], 24 ** -0.5),
            "shared_w13": (gdn["shared_w13"], 256 ** -0.5)}
    for name, (leaf, want) in stds.items():
        assert float(jnp.std(leaf)) == pytest.approx(want, rel=0.06), name
    assert np.all(gdn["dt_bias"] == 1) and np.all(gdn["gate_norm"] == 1)
    assert np.all(np.asarray(gdn["A_log"]) <= np.log(16.0))
    assert np.all(np.isfinite(gdn["A_log"]))
    for name in ("input_norm", "ffn_norm"):
        assert not np.any(gdn[name]) and not np.any(full[name])
    assert not np.any(full["q_norm"]) and not np.any(params["head"]["norm"])


def test_the_whole_step_trains_through_the_example():
    """``parse_args`` -> ``run_standard`` under O5 with per-leaf FusedLAMB,
    the path the benchmark drives: finite, falling, no step skipped."""
    pretrain = _load("examples/bert/pretrain.py", "pretrain_for_qwen3_step")
    args = pretrain.parse_args(["--qwen3-next", "4", "1", "--vocab", "256",
                                "--seq-len", "48", "--batch-size", "4",
                                "--attn", "fast", "--remat", "--lr", "1e-2"])
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


def test_the_flag_is_a_preset_of_the_standard_path_alone():
    pretrain = _load("examples/bert/pretrain.py", "pretrain_for_qwen3_flag")
    with pytest.raises(SystemExit, match="--qwen3-next is a model preset"):
        pretrain.main(["--qwen3-next", "16", "1", "--zero"])
