"""Laguna-S-2.1 decoder (poolside ``laguna``; docs/laguna.md): every layer is
``x <- x + attn(rms(x)); x <- x + ffn(rms(x))``, then a final ``rms`` and an
untied head.  The layer pattern says which attention a layer has
(``layer_types``), which FFN (``mlp_layer_types``) and how many query heads
(``num_attention_heads_per_layer``).

- attention: ``q = u W_q``, ``k = u W_k``, ``v = u W_v`` (the
  ``num_key_value_heads`` key-value heads repeated for their query heads),
  RMSNorm over each head of q and k, rotate-half RoPE, causal softmax at
  ``head_dim^-½``, and ONE gate a head on its context: ``out = (ctx ⊙
  sigmoid(u W_g)) W_o``, ``W_g`` (d, H).  A ``full_attention`` layer rotates
  the first ``partial_rotary_factor`` of each head with YaRN's frequencies
  (:func:`yarn_inv_freq`), cos and sin times ``attention_factor``, and sees
  every earlier key; a ``sliding_attention`` layer rotates the whole head
  at ``sliding_rope_theta`` and sees the last ``sliding_window`` keys
  (under ``attn_impl="fast"`` the flash kernel's band).
- dense FFN ``W2 (silu(W1 h) ⊙ W3 h)``; sparse FFN
  :func:`apex_tpu.parallel.expert.routed_experts`: softmax scores over ALL
  ``num_experts``, top ``num_experts_per_tok`` renormalised and times
  ``moe_routed_scaling_factor``, gated-SiLU experts; plus a shared expert
  behind a scalar sigmoid gate on every token.

A chip's share is part of the configuration — ``experts_held`` ``(first,
count)`` and ``vocab_size`` rows of embedding and head: it computes ITS
experts' part of each layer's sum and nothing stands in for the absent
chips; mixers, norms, router and shared expert are whole on every chip.
The defaults hold everything; :func:`laguna_share` cuts a share out of the
whole model's parameters.

Layers are a python loop (they differ in shape), each under
``jax.checkpoint`` where ``remat``.  Plain ``jax.numpy`` around the flash
kernels, the grouped products and the loss kernel; XLA fuses the rest.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.expert import routed_experts
from ..pyprof import annotate
from ..telemetry import events as _tel_events
from .lfm2 import _normal, _rms_norm, causal_attention, causal_lm_loss
from .qwen3_next import _shared_expert

_PERIOD = ("full_attention", "sliding_attention", "sliding_attention",
           "sliding_attention")


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int = 100352            # the rows of embedding and head held
    hidden_size: int = 3072
    intermediate_size: int = 12288      # the dense layer's FFN
    layer_types: Tuple[str, ...] = _PERIOD * 12
    mlp_layer_types: Tuple[str, ...] = ("dense",) + ("sparse",) * 47
    num_attention_heads_per_layer: Tuple[int, ...] = (48, 72, 72, 72) * 12
    num_key_value_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512
    rope_theta: float = 500000.0        # the full layers', with YaRN
    partial_rotary_factor: float = 0.5  # of a full layer's head
    yarn_factor: float = 128.0
    original_max_position_embeddings: int = 8192
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.4852030263919618
    sliding_rope_theta: float = 10000.0  # the window layers', whole head
    num_experts: int = 256              # what the router scores
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 1024   # one routed expert's
    shared_expert_intermediate_size: int = 1024
    norm_topk_prob: bool = True
    moe_routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    experts_held: Tuple[int, int] = (0, 256)   # (first id, count) held here
    dtype: Any = jnp.float32
    remat: bool = False
    attn_impl: str = "default"    # as TransformerConfig's
    xent_impl: str = "auto"       # as TransformerConfig's

    @property
    def num_hidden_layers(self) -> int:
        return len(self.layer_types)


def laguna_s_2_1_config(layers: int = 48, **overrides) -> LagunaConfig:
    """Laguna-S-2.1 as published (the defaults above), or its first
    ``layers`` layers: a cut keeps the leading dense layer and whole
    periods of (full, window, window, window) after it, ``experts_held``
    and a slice of the vocabulary — see ``examples/bert/pretrain.py
    --laguna``."""
    whole = LagunaConfig()
    cut = {name: getattr(whole, name)[:layers] for name in (
        "layer_types", "mlp_layer_types", "num_attention_heads_per_layer")}
    return LagunaConfig(**dict(cut, **overrides))


def yarn_inv_freq(cfg: LagunaConfig) -> np.ndarray:
    """The full layers' rotary frequencies (``rotary_dim // 2`` of them):
    YaRN's blend of the interpolated ``θ^(-2i/dim) / factor`` and the
    extrapolated ``θ^(-2i/dim)`` by a linear ramp between the dims that turn
    ``beta_fast`` and ``beta_slow`` times over ``original_max_position_
    embeddings`` — the public construction (``transformers``'
    ``_compute_yarn_parameters``, its ``truncate`` default)."""
    dim = int(cfg.head_dim * cfg.partial_rotary_factor)
    base, factor = cfg.rope_theta, cfg.yarn_factor
    original = cfg.original_max_position_embeddings

    def correction_dim(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(cfg.beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    extrapolated = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    keep = 1.0 - ramp               # the share of the extrapolated frequency
    return extrapolated / factor * (1.0 - keep) + extrapolated * keep


def _rotary(cfg: LagunaConfig, kind: str):
    """(inverse frequencies, scale of cos and sin) of a layer kind."""
    if kind == "full_attention":
        return yarn_inv_freq(cfg), cfg.attention_factor
    half = cfg.head_dim // 2
    return 1.0 / cfg.sliding_rope_theta ** (np.arange(half) / half), 1.0


def _rope(x, inv_freq, scale):
    """Rotate-half RoPE over the first ``2·len(inv_freq)`` dims of every head
    of ``x`` (B, S, H, hd) at positions 0..S-1, cos and sin times ``scale``;
    the other dims pass.  The table is made on the host in float64."""
    half = inv_freq.shape[0]
    angle = np.arange(x.shape[1])[:, None] * inv_freq[None]
    cos, sin = (jnp.asarray(np.concatenate([f(angle)] * 2, axis=-1) * scale,
                            jnp.float32)[None, :, None, :]
                for f in (np.cos, np.sin))
    x32 = x[..., :2 * half].astype(jnp.float32)
    rotated = jnp.concatenate([-x32[..., half:], x32[..., :half]], axis=-1)
    out = (x32 * cos + rotated * sin).astype(x.dtype)
    return jnp.concatenate([out, x[..., 2 * half:]], axis=-1)


def _block_params(key, cfg: LagunaConfig, heads: int, dense: bool) -> dict:
    d, hd, held = cfg.hidden_size, cfg.head_dim, cfg.experts_held[1]
    q, kv = heads * hd, cfg.num_key_value_heads * hd
    k = jax.random.split(key, 12)
    layer = {"input_norm": jnp.ones((d,), jnp.float32),
             "ffn_norm": jnp.ones((d,), jnp.float32),
             "wq": _normal(k[0], (d, q), d), "wk": _normal(k[1], (d, kv), d),
             "wv": _normal(k[2], (d, kv), d), "wo": _normal(k[3], (q, d), q),
             "w_gate": _normal(k[4], (d, heads), d),
             "q_norm": jnp.ones((hd,), jnp.float32),
             "k_norm": jnp.ones((hd,), jnp.float32)}
    if dense:
        f = cfg.intermediate_size
        layer.update(w13=_normal(k[5], (d, 2 * f), d),
                     w2=_normal(k[6], (f, d), f))
        return layer
    m, ms = cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size
    layer.update(router=_normal(k[7], (d, cfg.num_experts), d),
                 w13=_normal(k[8], (held, d, 2 * m), d),
                 w2=_normal(k[9], (held, m, d), m),
                 shared_w13=_normal(k[10], (d, 2 * ms), d),
                 shared_w2=_normal(k[11], (ms, d), ms),
                 shared_gate=_normal(k[5], (d, 1), d))
    return layer


def laguna_init(key, cfg: LagunaConfig):
    """Parameter tree of the held share: ``embed/tok``, a list of layers
    (``input_norm``, ``ffn_norm``, ``wq``, ``wk``, ``wv``, ``wo``,
    ``w_gate`` (d, H), ``q_norm``, ``k_norm``; ``w13``, ``w2`` dense or
    ``router``, ``w13`` (held, d, 2m), ``w2`` (held, m, d), ``shared_w13``,
    ``shared_w2``, ``shared_gate`` (d, 1) sparse), ``head/norm`` and
    ``head/out``.  Matrices are N(0, 1/fan_in) — 0.018 at d 3072, the
    family's ``initializer_range`` 0.02; norm gains 1."""
    kinds = {"dense", "sparse"}
    for name, types, known in (
            ("layer_types", cfg.layer_types, set(_PERIOD)),
            ("mlp_layer_types", cfg.mlp_layer_types, kinds)):
        if len(types) != cfg.num_hidden_layers or not set(types) <= known:
            raise ValueError(f"{name} {types!r}: one of {sorted(known)} a "
                             f"layer, {cfg.num_hidden_layers} layers")
    if len(cfg.num_attention_heads_per_layer) != cfg.num_hidden_layers:
        raise ValueError("num_attention_heads_per_layer wants one count a "
                         "layer")
    d = cfg.hidden_size
    key, k_tok, k_out = jax.random.split(key, 3)
    layers = []
    for heads, mlp in zip(cfg.num_attention_heads_per_layer,
                          cfg.mlp_layer_types):
        key, k = jax.random.split(key)
        layers.append(_block_params(k, cfg, heads, mlp == "dense"))
    return {"embed": {"tok": _normal(k_tok, (cfg.vocab_size, d), d)},
            "layers": layers,
            "head": {"norm": jnp.ones((d,), jnp.float32),
                     "out": _normal(k_out, (d, cfg.vocab_size), d)}}


def laguna_share(params, cfg: LagunaConfig):
    """The parameters ``cfg``'s share holds, cut out of the whole model's:
    its experts of every sparse layer and the first ``vocab_size`` ids.
    What every chip holds alike (mixers, gates, norms, router, the shared
    expert and its gate, the dense layer) is copied."""
    e0, e = cfg.experts_held

    def cut(lp):
        if "router" not in lp:
            return lp
        return dict(lp, w13=lp["w13"][e0:e0 + e], w2=lp["w2"][e0:e0 + e])
    return {"embed": {"tok": params["embed"]["tok"][:cfg.vocab_size]},
            "layers": [cut(lp) for lp in params["layers"]],
            "head": {"norm": params["head"]["norm"],
                     "out": params["head"]["out"][:, :cfg.vocab_size]}}


def _attention_mixer(u, lp, cfg: LagunaConfig, kind: str, heads: int):
    dt = u.dtype
    bsz, seq, _ = u.shape
    kv_heads, hd, eps = cfg.num_key_value_heads, cfg.head_dim, \
        cfg.rms_norm_eps
    q = (u @ lp["wq"].astype(dt)).reshape(bsz, seq, heads, hd)
    k = (u @ lp["wk"].astype(dt)).reshape(bsz, seq, kv_heads, hd)
    v = (u @ lp["wv"].astype(dt)).reshape(bsz, seq, kv_heads, hd)
    gate = jax.nn.sigmoid(u @ lp["w_gate"].astype(dt))        # (B, S, H)
    inv_freq, scale = _rotary(cfg, kind)
    q = _rope(_rms_norm(q, lp["q_norm"], eps), inv_freq, scale)
    k = _rope(_rms_norm(k, lp["k_norm"], eps), inv_freq, scale)
    # (B, H, S, hd); a key/value head serves heads // kv_heads query heads
    q = (q * hd ** -0.5).astype(dt).transpose(0, 2, 1, 3)
    k = jnp.repeat(k.transpose(0, 2, 1, 3), heads // kv_heads, axis=1)
    v = jnp.repeat(v.transpose(0, 2, 1, 3), heads // kv_heads, axis=1)
    window = cfg.sliding_window if kind == "sliding_attention" else None
    ctx = causal_attention(q, k, v, cfg.attn_impl, window)
    ctx = ctx.reshape(bsz, seq, heads, hd) * gate[..., None]
    return ctx.reshape(bsz, seq, heads * hd) @ lp["wo"].astype(dt)


def _sparse_ffn(h, lp, cfg: LagunaConfig):
    """``(out, routing)``: the held experts' part of the routed sum, plus the
    gated shared expert."""
    flat = h.reshape(-1, h.shape[-1])
    routed, routing = routed_experts(
        flat, lp["router"], None, lp["w13"], lp["w2"],
        top_k=cfg.num_experts_per_tok, first=cfg.experts_held[0],
        norm_topk_prob=cfg.norm_topk_prob,
        routed_scaling_factor=cfg.moe_routed_scaling_factor, score="softmax")
    return (routed + _shared_expert(flat, lp)).reshape(h.shape), routing


def _block(x, lp, *, cfg: LagunaConfig, kind: str, heads: int, dense: bool):
    """One layer: ``kind`` picks the attention, ``dense`` the FFN.  Returns
    ``(y, routing)``: ``routed_experts``' record, None from a dense
    layer."""
    dt, eps = x.dtype, cfg.rms_norm_eps
    with annotate("apex.swa" if kind == "sliding_attention" else "apex.attn"):
        x = x + _attention_mixer(_rms_norm(x, lp["input_norm"], eps), lp,
                                 cfg, kind, heads)
    if dense:
        with annotate("apex.mlp"):
            h = _rms_norm(x, lp["ffn_norm"], eps)
            gate, up = jnp.split(h @ lp["w13"].astype(dt), 2, axis=-1)
            return x + (jax.nn.silu(gate) * up) @ lp["w2"].astype(dt), None
    with annotate("apex.moe"):
        out, routing = _sparse_ffn(_rms_norm(x, lp["ffn_norm"], eps), lp, cfg)
        return x + out, routing


def _forward(params, tokens, cfg: LagunaConfig):
    """``(logits, routing)``: ``routing`` stacks every sparse layer's record
    (``ids`` (L, T, k), ``rows`` (L, held), ``dropped`` (L,), ``walks``
    (L,), ``slots`` (L,))."""
    if cfg.attn_impl not in ("default", "fast"):
        raise ValueError(
            f"attn_impl must be 'default' or 'fast', got {cfg.attn_impl!r}")
    if len(params["layers"]) != cfg.num_hidden_layers:
        raise ValueError(f"{len(params['layers'])} layers of parameters for "
                         f"{cfg.num_hidden_layers} layer_types")
    dt = cfg.dtype
    with annotate("apex.embed"):
        x = params["embed"]["tok"].astype(dt)[tokens]
    records = []
    for kind, heads, mlp, lp in zip(cfg.layer_types,
                                    cfg.num_attention_heads_per_layer,
                                    cfg.mlp_layer_types, params["layers"]):
        block = functools.partial(_block, cfg=cfg, kind=kind, heads=heads,
                                  dense=mlp == "dense")
        if cfg.remat:
            block = jax.checkpoint(block)
        x, record = block(x, lp)
        if record is not None:
            records.append(record)
    routing = jax.tree_util.tree_map(lambda *r: jnp.stack(r), *records) \
        if records else None
    if records and _tel_events.active():
        # the routing meter, as models.lfm2 has it: once a forward pass,
        # outside the checkpoints so remat's second forward does not count
        jax.debug.callback(_tel_events.record_expert_rows, routing["rows"],
                           jnp.sum(routing["dropped"]), routing["walks"],
                           slots=routing["slots"])
    with annotate("apex.head"):
        x = _rms_norm(x, params["head"]["norm"], cfg.rms_norm_eps)
        return x @ params["head"]["out"].astype(dt), routing


def laguna_apply(params, tokens, cfg: LagunaConfig):
    """tokens (B, S) int32 -> logits (B, S, V) over the held vocabulary."""
    return _forward(params, tokens, cfg)[0]


def laguna_routing(params, tokens, cfg: LagunaConfig):
    """What the forward pass over ``tokens`` routed, every sparse layer
    stacked: ``models.lfm2.lfm2_routing``'s record."""
    return _forward(params, tokens, cfg)[1]


def laguna_loss(params, batch, cfg: LagunaConfig):
    """Next-token cross entropy over the held vocabulary, as ``lfm2_loss``."""
    return causal_lm_loss(laguna_apply(params, batch["tokens"], cfg), batch,
                          cfg.xent_impl)
