"""LFM2-MoE (``apex_tpu.models.lfm2`` over ``parallel.expert.routed_experts``)
against its plain float32 reference (``benchmarks/reference/lfm2_24b_a2b.py``)
on seeded random weights at a small size: d 64, 2 key/value and 8 query heads
of 8, 16 experts top-4 of which 4 are held, expert width 32, one dense layer
and one period.  The expert bias is random and NON-ZERO everywhere, so that
"chooses but does not weigh" is part of every comparison.
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
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu import telemetry
from apex_tpu.models import (Lfm2Config, lfm2_24b_a2b_config,
                             lfm2_cut_layer_types, lfm2_init, lfm2_loss,
                             lfm2_routing)
from apex_tpu.models import lfm2 as lfm2_module
from apex_tpu.parallel import create_mesh, use_mesh
from apex_tpu.parallel import expert
from apex_tpu.parallel.expert import route_top_k, routed_experts
from apex_tpu.telemetry import events

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _load(rel_path, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, rel_path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load("benchmarks/reference/lfm2_24b_a2b.py", "lfm2_reference")

CFG = Lfm2Config(vocab_size=256, hidden_size=64, intermediate_size=160,
                 moe_intermediate_size=32, num_experts=16,
                 num_experts_per_tok=4, num_dense_layers=1,
                 layer_types=lfm2_cut_layer_types(1, 1),
                 num_attention_heads=8, num_key_value_heads=2,
                 experts_held=(4, 4), xent_impl="xla")
SEQ = 37        # no multiple of any flash block


def _model(cfg):
    """The configuration as the reference reads it: a plain dict."""
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def _params(cfg, seed=0):
    params = lfm2_init(jax.random.PRNGKey(seed), cfg)
    key = jax.random.PRNGKey(seed + 100)
    for layer in params["layers"]:
        if "expert_bias" in layer:
            key, k = jax.random.split(key)
            layer["expert_bias"] = 0.3 * jax.random.normal(
                k, layer["expert_bias"].shape)
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


@pytest.mark.parametrize("attn", ["default", "fast"])
def test_loss_and_every_gradient_leaf_match_the_reference(attn):
    cfg = dataclasses.replace(CFG, attn_impl=attn)
    params, batch = _params(cfg), _batch(cfg)
    loss, grads = jax.value_and_grad(lfm2_loss)(params, batch, cfg)
    want, want_grads = jax.value_and_grad(reference.loss)(
        params, batch, _model(cfg))
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    got = dict(jax.tree_util.tree_leaves_with_path(grads))
    for path, leaf in jax.tree_util.tree_leaves_with_path(want_grads):
        name = jax.tree_util.keystr(path)
        if "expert_bias" in name:
            # a buffer: no gradient reaches it, on either side
            assert not np.any(got[path]) and not np.any(leaf), name
            continue
        assert np.any(leaf), name
        np.testing.assert_allclose(
            got[path], leaf, rtol=2e-3,
            atol=2e-5 * float(jnp.max(jnp.abs(leaf))), err_msg=name)


def test_remat_changes_nothing():
    params, batch = _params(CFG), _batch(CFG)
    plain = jax.grad(lfm2_loss)(params, batch, CFG)
    remat = jax.grad(lfm2_loss)(params, batch,
                                dataclasses.replace(CFG, remat=True))
    for a, b in zip(jax.tree_util.tree_leaves(plain),
                    jax.tree_util.tree_leaves(remat)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


def _expert_layer(cfg, seed=3, tokens=50):
    """One expert layer's parameters with ALL experts, and its input."""
    whole = dataclasses.replace(cfg, experts_held=(0, cfg.num_experts))
    layer = _params(whole, seed)["layers"][-1]
    h = jax.random.normal(jax.random.PRNGKey(seed), (tokens, cfg.hidden_size))
    return whole, layer, h


def _share(layer, first, count):
    return dict(layer, w13=layer["w13"][first:first + count],
                w2=layer["w2"][first:first + count])


def _routed(h, layer, first, rows_a_walk=None, **kw):
    """``routed_experts`` — or, where ``rows_a_walk`` pins the buffer, the
    function behind it."""
    fn = routed_experts if rows_a_walk is None else functools.partial(
        expert._routed_experts, rows_a_walk=rows_a_walk)
    return fn(h, layer["router"], layer["expert_bias"], layer["w13"],
              layer["w2"], top_k=CFG.num_experts_per_tok, first=first, **kw)


def _pinned(h, layer, first, walks):
    """The buffer under which the share's rows take ``walks`` walks; None
    (the rule's buffer, at these sizes all T·k rows: one walk) for None."""
    if walks is None:
        return None
    sent = int(_routed(h, layer, first, axis_name=None)[1]["rows"].sum())
    return -(-sent // walks)


WALKS = [None, 2, 4]     # the rule's buffer (one walk), and pinned ones


@pytest.mark.parametrize("walks", WALKS)
def test_the_shares_sum_to_the_uncut_references_whole_layer(walks):
    whole, layer, h = _expert_layer(CFG)
    want, _ = reference._expert_ffn(h, layer, _model(whole))
    parts, rows = 0.0, 0
    for first in range(0, 16, 4):
        share = _share(layer, first, 4)
        out, routing = _routed(h, share, first,
                               _pinned(h, share, first, walks),
                               axis_name=None)
        assert int(routing["walks"]) == (walks or 1)
        assert int(routing["dropped"]) == 0
        model = _model(dataclasses.replace(CFG, experts_held=(first, 4)))
        np.testing.assert_allclose(
            out, reference._expert_ffn(h, _share(layer, first, 4), model)[0],
            rtol=1e-4, atol=1e-6)
        parts, rows = parts + out, rows + int(routing["rows"].sum())
    np.testing.assert_allclose(parts, want, rtol=1e-4, atol=1e-6)
    assert rows == h.shape[0] * CFG.num_experts_per_tok


def _layer_gradients(h, layer, first, walks):
    """``(out, {leaf: gradient})`` of a probed sum of the share's part, by
    the system under ``jax.checkpoint`` and by the reference."""
    probe = jax.random.normal(jax.random.PRNGKey(11), h.shape)
    model = _model(dataclasses.replace(
        CFG, experts_held=(first, layer["w13"].shape[0])))
    leaves = ("h", "router", "w13", "w2")
    rows_a_walk = _pinned(h, layer, first, walks)

    def system(h, router, w13, w2):
        out, _ = _routed(h, dict(layer, router=router, w13=w13, w2=w2),
                         first, rows_a_walk, axis_name=None)
        return jnp.sum(out * probe), out

    def plain(h, router, w13, w2):
        out, _ = reference._expert_ffn(
            h, dict(layer, router=router, w13=w13, w2=w2), model)
        return jnp.sum(out * probe), out

    args = (h, layer["router"], layer["w13"], layer["w2"])
    both = []
    for fn in (jax.checkpoint(system), plain):
        (_, out), grads = jax.value_and_grad(
            fn, argnums=(0, 1, 2, 3), has_aux=True)(*args)
        both.append((out, dict(zip(leaves, grads))))
    return both


def _assert_same(got, want):
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4, atol=1e-6)
    for name, leaf in want[1].items():
        assert np.any(leaf), name
        np.testing.assert_allclose(
            got[1][name], leaf, rtol=1e-4,
            atol=1e-5 * float(jnp.max(jnp.abs(leaf))), err_msg=name)


@pytest.mark.parametrize("walks", WALKS)
def test_every_gradient_leaf_of_a_walked_layer_is_the_references(walks):
    """x, w13, w2 and — through the weights — the router, whether the held
    rows fit the buffer or the same body walks them in 2 or 4 pieces."""
    _, layer, h = _expert_layer(CFG)
    share = _share(layer, 4, 4)
    # the rule's buffer (every assignment) sums back to the tokens a slot at
    # a time, a pinned one in row space: both forms are held to the reference
    buffer = _pinned(h, share, 4, walks) or expert.buffer_rows(50, 4, 16, 4)
    assert expert.sums_in_row_space(50, 4, 4, buffer) == (walks is not None)
    _assert_same(*_layer_gradients(h, share, 4, walks))


def test_a_walk_with_nothing_to_do_takes_no_trip():
    """No token takes a held expert: no walk, a zero part, zero gradients."""
    _, layer, h = _expert_layer(CFG)
    layer = dict(_share(layer, 4, 4),
                 expert_bias=jnp.zeros((16,)).at[4:8].set(-50.0))
    out, routing = _routed(h, layer, 4, axis_name=None)
    assert int(routing["walks"]) == 0 and not np.any(routing["rows"])
    assert not np.any(out)
    grads = jax.grad(lambda h, w13: _routed(
        h, dict(layer, w13=w13), 4, axis_name=None)[0].sum(),
        argnums=(0, 1))(h, layer["w13"])
    assert not np.any(grads[0]) and not np.any(grads[1])


def test_bound_to_an_axis_the_devices_hold_the_shares_and_sum_them():
    whole, layer, h = _expert_layer(CFG)
    want, _ = reference._expert_ffn(h, layer, _model(whole))
    mesh = Mesh(np.array(jax.devices()[:4]), ("expert",))
    shared = {k: P() for k in layer}
    shared.update(w13=P("expert"), w2=P("expert"))

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(P(), shared),
                       out_specs=(P(), P("expert")), check_vma=False)
    def layer_over_the_axis(h, layer):
        out, routing = _routed(h, layer, 0, axis_name="expert")
        return out, routing["rows"]

    out, rows = layer_over_the_axis(h, layer)
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-6)
    assert int(rows.sum()) == h.shape[0] * CFG.num_experts_per_tok


@pytest.mark.parametrize("walks", [None, 4])
@pytest.mark.parametrize("favourite", [0, 3])
def test_no_assignment_is_dropped_at_total_imbalance(favourite, walks):
    """Every token's four choices are the four held experts, so each of them
    takes a row from EVERY token: T·4 rows held, none dropped, and the
    result and its gradients are the reference's — in T·k / C walks: one
    through the rule's buffer (all T·k rows at this size), four through a
    quarter of it, as the cell's 32 768 rows would take its 131 072.  The
    bias alone does it (the router's scores are whatever they are), so it
    also shows the bias choosing."""
    _, layer, h = _expert_layer(CFG, tokens=64)
    first = 4
    bias = jnp.full((16,), -5.0).at[first:first + 4].set(5.0)
    bias = bias.at[first + favourite].add(1.0)
    layer = dict(_share(layer, first, 4), expert_bias=bias)
    out, routing = _routed(h, layer, first, _pinned(h, layer, first, walks),
                           axis_name=None)
    assert routing["rows"].tolist() == [64, 64, 64, 64]
    assert int(routing["dropped"]) == 0
    buffer = expert.buffer_rows(64, 4, 16, 4) // (walks or 1)
    assert int(routing["walks"]) == 64 * 4 // buffer == (walks or 1)
    assert sorted(np.unique(routing["ids"]).tolist()) == [4, 5, 6, 7]
    got, want = _layer_gradients(h, layer, first, walks)
    np.testing.assert_array_equal(out, got[0])
    _assert_same(got, want)


def _plain_token_sum(src, at, mine, scale=None):
    """The sum back to the tokens as its plain statement: ``Σ_j src[at[t,
    j]]`` over the slots a walk holds (times ``scale[t, j]``, in its dtype)
    — a gather of T rows a slot, k of them."""
    total = 0
    for j in range(at.shape[1]):
        term = jnp.where(mine[:, j, None], src[at[:, j]], 0)
        total = total + (term if scale is None
                         else term.astype(scale.dtype) * scale[:, j, None])
    return total


def _plain_dispatch(ids, first, held, c):
    """``(order, place, here, ends)`` of ``routed_experts``' sort, in numpy:
    the assignments by held expert with the absent ones last, padded to
    whole walks of ``c`` rows."""
    tokens, top_k = ids.shape
    local = (ids - first).reshape(-1)
    here = (local >= 0) & (local < held)
    group = np.where(here, local, held)
    order = np.argsort(group, kind="stable").astype(np.int32)
    place = np.argsort(order).astype(np.int32).reshape(tokens, top_k)
    ends = np.cumsum(np.bincount(group, minlength=held + 1)[:held])
    return (jnp.asarray(np.pad(order, (0, -len(order) % c))),
            jnp.asarray(place), jnp.asarray(here.reshape(tokens, top_k)),
            jnp.asarray(ends.astype(np.int32)))


def _hand_routed_ids(tokens, imbalance, first=4, held=4, top_k=4, experts=16):
    """Distinct experts a token, as ``top_k`` gives them.  ``imbalance``:
    every token takes the same held experts; else token t holds 0, 1,
    ``min(top_k, held)`` or 2 of them by t % 4, the rest absent ones."""
    rng = np.random.RandomState(5)
    inside = np.arange(first, first + held)
    outside = np.setdiff1d(np.arange(experts), inside)
    ids = np.empty((tokens, top_k), np.int32)
    for t in range(tokens):
        n = min(top_k, held)
        if not imbalance:
            n = (0, 1, n, 2)[t % 4]
        ids[t] = rng.permutation(np.concatenate([
            rng.choice(inside, n, replace=False),
            rng.choice(outside, top_k - n, replace=False)]))
    return ids


@pytest.mark.parametrize("form, width", [("gated_silu", None), ("relu2", 24)])
@pytest.mark.parametrize("scaled", [False, True], ids=["plain", "scaled"])
@pytest.mark.parametrize("imbalance", [False, True],
                         ids=["mixed", "imbalance"])
@pytest.mark.parametrize("walks", [1, 3])
def test_the_row_space_sum_is_a_gather_a_slot(walks, imbalance, scaled, form,
                                              width):
    """``_row_sum`` — rows sorted by token, neighbours added, one gather
    of T rows — against a gather of T rows a slot, walk by walk, over the
    rows the held experts computed with the buffer's rows past the groups
    set to NaN, as a TPU leaves them: tokens holding 0, 1 and min(k, held)
    rows in one walk, or every token the same held experts; with the
    assignment's weight in float32 and without; gated SiLU on the model's
    rows and squared ReLU on narrower ones."""
    tokens, top_k, first, held = 48, 4, 4, 4
    _, layer, h = _expert_layer(CFG, tokens=tokens)
    rows = h if width is None else h[:, :width]
    r, f = rows.shape[1], CFG.moe_intermediate_size
    w13 = layer["w13"][first:first + held, :r,
                       :2 * f if form == "gated_silu" else f]
    w2 = layer["w2"][first:first + held, :, :r]
    ids = _hand_routed_ids(tokens, imbalance)
    weights = jax.random.uniform(jax.random.PRNGKey(7), ids.shape)
    sent = int(((ids >= first) & (ids < first + held)).sum())
    c = -(-sent // walks) + 3       # the last walk's buffer is never full
    assert -(-sent // c) == walks
    order, place, here, ends = _plain_dispatch(ids, first, held, c)
    slots = min(top_k, held)
    counts = set()
    for i in range(walks):
        lo, token, assignment, sizes, valid = expert._walk(
            i, order, ends, top_k, c)
        src = jnp.where(valid[:, None], expert._expert_ffn(
            form, rows[token], w13, w2, sizes), jnp.nan)
        at, mine = expert._slots(place, here, lo, c)
        counts |= set(np.sum(mine, axis=1).tolist())
        want = _plain_token_sum(src, at, mine, weights if scaled else None)
        got = expert._row_sum(
            src, token, valid, mine, slots,
            weights.reshape(-1)[assignment] if scaled else None)
        assert got.shape == (tokens, r) and np.all(np.isfinite(got))
        assert bool(jnp.all(valid)) == (i < walks - 1)
        np.testing.assert_allclose(
            got, want, rtol=1e-6, atol=1e-6 * float(jnp.max(jnp.abs(want))))
    assert max(counts) <= slots
    if walks == 1:
        assert counts == ({slots} if imbalance else {0, 1, 2, slots})


@pytest.mark.parametrize("first", [0, 4, 12])
def test_slots_is_the_fullest_tokens_held_assignments(first):
    _, layer, h = _expert_layer(CFG)
    _, routing = _routed(h, _share(layer, first, 4), first, axis_name=None)
    ids = np.asarray(routing["ids"])
    held = (ids >= first) & (ids < first + 4)
    assert int(routing["slots"]) == held.sum(axis=1).max() <= 4
    assert routing["slots"].dtype == jnp.int32


def _avals(jaxpr):
    for eqn in jaxpr.eqns:
        yield from (v.aval for v in (*eqn.invars, *eqn.outvars))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _avals(sub)


def test_no_array_of_t_k_rows_at_the_cells_shape():
    """Traced at the benchmark cell's shape (abstract: nothing runs), the
    expert layer and its gradient hold the 32 768-row buffer and no array of
    T·k = 131 072 rows by D or by 2F — in any loop body or reverse rule."""
    tokens, d, f, experts, held, top_k = 32768, 2048, 1536, 64, 8, 4
    sds = jax.ShapeDtypeStruct
    args = (sds((tokens, d), jnp.bfloat16), sds((d, experts), jnp.float32),
            sds((held, d, 2 * f), jnp.bfloat16),
            sds((held, f, d), jnp.bfloat16))

    def layer(x, router, w13, w2):
        out, _ = routed_experts(x, router, jnp.zeros((experts,)), w13, w2,
                                top_k=top_k, axis_name=None)
        return jnp.sum(out.astype(jnp.float32))

    buffer = expert.buffer_rows(tokens, top_k, experts, held)
    assert buffer == 32768 and tokens * top_k == 4 * buffer
    for fn in (layer, jax.grad(jax.checkpoint(layer), argnums=(0, 1, 2, 3))):
        shapes = {tuple(a.shape) for a in _avals(jax.make_jaxpr(fn)(*args).jaxpr)
                  if hasattr(a, "shape")}
        assert (buffer, d) in shapes and (buffer, 2 * f) in shapes
        wide = {s for s in shapes if len(s) >= 2 and tokens * top_k in s
                and set(s) & {d, f, 2 * f}}
        assert not wide, wide


def test_the_bias_chooses_and_does_not_weigh():
    _, layer, h = _expert_layer(CFG)
    ids, weights = route_top_k(h, layer["router"], layer["expert_bias"], 4)
    scores = jax.nn.sigmoid(h @ layer["router"])
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    np.testing.assert_allclose(
        weights, picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-5)
    # another bias, other experts; the same bias everywhere, the same experts
    other, _ = route_top_k(h, layer["router"], -layer["expert_bias"], 4)
    assert np.any(np.sort(ids, -1) != np.sort(other, -1))
    same, w = route_top_k(h, layer["router"], layer["expert_bias"] + 7.0, 4)
    assert np.array_equal(ids, same) and np.allclose(w, weights)
    # and no gradient reaches it
    g = jax.grad(lambda b: _routed(h, dict(layer, expert_bias=b), 0,
                                   axis_name=None)[0].sum())(
        layer["expert_bias"])
    assert not np.any(g)


def test_conv_mixer_is_causal_and_is_the_references():
    layer = _params(CFG)["layers"][0]
    u = jax.random.normal(jax.random.PRNGKey(1), (2, SEQ, 64))
    out = lfm2_module._conv_mixer(u, layer, CFG)
    assert CFG.conv_L_cache == 3
    np.testing.assert_allclose(
        out, reference._conv_mixer(u, layer, _model(CFG)), rtol=1e-5,
        atol=1e-6)
    t = 20
    later = u.at[:, t + 1:].set(jax.random.normal(
        jax.random.PRNGKey(2), (2, SEQ - t - 1, 64)))
    changed = lfm2_module._conv_mixer(later, layer, CFG)
    np.testing.assert_array_equal(out[:, :t + 1], changed[:, :t + 1])
    assert np.any(out[:, t + 1:] != changed[:, t + 1:])


@pytest.mark.parametrize("attn", ["default", "fast"])
def test_grouped_query_attention_with_qk_norm_and_rope_is_the_references(attn):
    cfg = dataclasses.replace(CFG, attn_impl=attn)
    layer = dict(_params(cfg)["layers"][1])
    # gains that are not 1, so the per-head norm's gain is in the comparison
    layer["q_norm"] = 1.0 + 0.1 * jnp.arange(8.0)
    layer["k_norm"] = 1.5 - 0.1 * jnp.arange(8.0)
    u = jax.random.normal(jax.random.PRNGKey(1), (2, SEQ, 64))
    np.testing.assert_allclose(
        lfm2_module._attention_mixer(u, layer, cfg),
        reference._attention_mixer(u, layer, _model(cfg)), rtol=2e-4,
        atol=2e-5)


def test_the_routing_meter_counts_once_a_forward_pass_under_remat():
    cfg = dataclasses.replace(CFG, remat=True)
    params, batch = _params(cfg), _batch(cfg)
    registry = telemetry.Registry(enabled=True, memory=False)
    previous = events.set_default(registry)
    try:
        before = len(events.expert_rows())
        jax.block_until_ready(jax.jit(jax.grad(
            lambda p: lfm2_loss(p, batch, cfg)))(params))
        jax.effects_barrier()
        assert len(events.expert_rows()) == before + 1
        rows = events.expert_rows()[-1]
        assert sum(registry.counter("moe.walks")._pending_values()) == 4
        fullest = list(registry.histogram("moe.slots_max")._pending_values())
    finally:
        events.set_default(previous)
    routing = lfm2_routing(params, batch["tokens"], cfg)
    np.testing.assert_array_equal(rows, routing["rows"])
    assert rows.shape == (4, 4) and not np.any(routing["dropped"])
    assert routing["walks"].tolist() == [1, 1, 1, 1]
    held = (routing["ids"] >= 4) & (routing["ids"] < 8)
    np.testing.assert_array_equal(routing["slots"],
                                  held.sum(axis=2).max(axis=1))
    assert fullest == [int(routing["slots"].max())]
    layouts = [e["fields"] for e in registry._events
               if e["name"] == "moe.layout"]
    assert {"experts": 16, "held": 4, "top_k": 4, "buffer_rows": 2 * SEQ * 4,
            "sum_rows": 2 * SEQ * 4} in layouts
    # off, the step holds no callback at all
    text = jax.jit(lambda p: lfm2_loss(p, batch, cfg)).lower(params).as_text()
    assert "callback" not in text


@pytest.mark.parametrize("slots", [None, [2, 3]], ids=["without", "with"])
def test_the_step_side_of_the_meter_takes_slots_or_goes_without(slots):
    """``record_expert_rows`` as the benchmark calls it — rows, dropped,
    walks — and as the models do, with each layer's fullest token."""
    registry = telemetry.Registry(enabled=True, memory=False)
    previous = events.set_default(registry)
    try:
        rows, more = np.array([[3, 1], [2, 2]]), {}
        if slots is not None:
            more["slots"] = np.asarray(slots)
        events.record_expert_rows(rows, 0, np.array([1, 1]), **more)
        np.testing.assert_array_equal(events.expert_rows()[-1], rows)
        assert sum(registry.counter("moe.rows_held")._pending_values()) == 8
        assert list(registry.histogram("moe.load_max_over_mean")
                    ._pending_values()) == [1.5]
        assert list(registry.histogram("moe.slots_max")._pending_values()) \
            == ([] if slots is None else [3.0])
    finally:
        events.set_default(previous)


def test_preset_is_the_published_configuration():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "lfm2_24b_a2b.json")) as f:
        doc = json.load(f)
    cfg = lfm2_24b_a2b_config()
    published = dict(doc, **doc["published"])
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_experts", "num_experts_per_tok", "num_dense_layers",
                "num_attention_heads", "num_key_value_heads", "conv_L_cache",
                "norm_eps", "norm_topk_prob", "use_expert_bias",
                "routed_scaling_factor", "vocab_size", "num_hidden_layers"):
        assert getattr(cfg, key) == published[key], key
    assert cfg.rope_theta == doc["rope_parameters"]["rope_theta"]
    assert cfg.head_dim == 64
    assert cfg.layer_types[:6] == ("conv", "conv", "full_attention", "conv",
                                   "conv", "conv")
    assert cfg.layer_types[-2:] == ("full_attention", "conv")
    assert cfg.layer_types.count("full_attention") == 10
    # the cut the configuration runs is what the example's flag builds
    pretrain = _load("examples/bert/pretrain.py", "pretrain_for_lfm2")
    cut = pretrain.lfm2_config(pretrain.parse_args(doc["entry"]["argv"]))
    for key, value in doc["model"].items():
        got = getattr(cut, key)
        assert (list(got) if isinstance(got, tuple) else got) == value, key


def test_the_whole_step_trains_through_the_example():
    """``parse_args`` -> ``run_standard`` under O5 with per-leaf FusedLAMB,
    the path the benchmark drives: finite, falling, no step skipped."""
    pretrain = _load("examples/bert/pretrain.py", "pretrain_for_lfm2_step")
    args = pretrain.parse_args(["--lfm2", "1", "1", "4", "--vocab", "256",
                                "--seq-len", "64", "--batch-size", "8",
                                "--attn", "fast", "--remat"])
    assert args.opt_level == "O5"
    cfg = dataclasses.replace(
        CFG, experts_held=(0, 4), dtype=jnp.bfloat16, remat=args.remat,
        attn_impl=args.attn, xent_impl="auto")
    mesh = create_mesh({"data": 1}, devices=jax.devices()[:1])
    rng = np.random.RandomState(0)
    steps, losses = 24, []
    with use_mesh(mesh):
        state, step = pretrain.run_standard(args, cfg, mesh)
        for _ in range(steps):
            tokens, targets, weights = pretrain.synthetic_next_token(
                rng, args.batch_size, args.seq_len, cfg.vocab_size)
            state, loss = step(state, {"tokens": tokens, "targets": targets,
                                       "weights": weights})
            losses.append(float(loss))
    assert np.all(np.isfinite(losses))
    assert np.mean(losses[-6:]) < np.mean(losses[:6]) - 0.02, losses
    assert step.optimizer_steps(state) == steps
    # the bias stayed what it was: no gradient, and LAMB moves nothing at 0
    for layer in state.model_params["layers"]:
        if "expert_bias" in layer:
            assert not np.any(layer["expert_bias"])


def test_next_token_corpus_covers_the_vocabulary_and_follows_its_rule():
    pretrain = _load("examples/bert/pretrain.py", "pretrain_for_lfm2_data")
    tokens, targets, weights = pretrain.synthetic_next_token(
        np.random.RandomState(0), 16, 512, 1024)
    assert tokens.dtype == np.int32 and tokens.min() >= 0
    assert len(np.unique(tokens)) > 600            # not a pool of 64
    np.testing.assert_array_equal(targets[:, :-1], tokens[:, 1:])
    assert not weights[:, -1].any() and weights[:, :-1].all()
    common, rare, successor = pretrain._syn_rule(1024)
    assert np.isin(common, tokens).all() and np.isin(rare, tokens).mean() > 0.4
    followed = np.mean(successor[tokens[:, :-1]] == tokens[:, 1:])
    assert 0.45 < followed < 0.56
    assert 0.87 < np.isin(tokens, common).mean() < 0.93
    # no id carries more than about its 0.9 / (vocab / 8) of the tokens
    assert np.bincount(tokens.ravel(), minlength=1024).max() \
        < 2 * 0.9 / 128 * tokens.size
