"""LFM2-MoE decoder (LiquidAI ``lfm2_moe``; docs/lfm2.md): a layer pattern
of gated short-convolution and grouped-query attention mixers, a dense
gated FFN in the leading layers and routed experts in the others.

    h = x + mixer(rms(x));   y = h + ffn(rms(h))

- ``conv`` mixer: ``[B, C, X] = split(W_in u)``, ``z = B ⊙ X``, a causal
  depthwise convolution over ``conv_L_cache`` time steps of ``z``, ``out =
  W_out (C ⊙ c)``.  No positions, no softmax.
- ``full_attention`` mixer: grouped-query heads, RMSNorm over each head of
  q and k, rotate-half RoPE, causal softmax; under ``attn_impl="fast"`` the
  flash kernel with every key/value head repeated for its query heads.
- dense FFN ``W2 (silu(W1 h) ⊙ W3 h)``; expert FFN
  :func:`apex_tpu.parallel.expert.routed_experts`: sigmoid router over ALL
  ``num_experts``, top ``num_experts_per_tok``, and the ``experts_held``
  that live here — a chip's share of an expert-parallel group computes its
  own experts' part of the sum and nothing stands in for the rest.

One block function takes its mixer and FFN kind from the layer pattern;
layers are a python loop (they differ in shape), each under
``jax.checkpoint`` where ``remat``.  Plain ``jax.numpy`` around the flash
kernel, the grouped products and the loss kernel; XLA fuses the rest.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from ..parallel.expert import routed_experts
from ..pyprof import annotate
from ..telemetry import events as _tel_events

_PERIOD = ("full_attention", "conv", "conv", "conv")


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776      # the leading dense layers' FFN
    moe_intermediate_size: int = 1536   # one expert's
    num_experts: int = 64               # what the router scores
    num_experts_per_tok: int = 4
    num_dense_layers: int = 2
    layer_types: Tuple[str, ...] = ("conv", "conv") + _PERIOD * 9 + (
        "full_attention", "conv")
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    conv_L_cache: int = 3
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    experts_held: Tuple[int, int] = (0, 64)   # (first id, count) held here
    dtype: Any = jnp.float32
    remat: bool = False
    attn_impl: str = "default"    # as TransformerConfig's
    xent_impl: str = "auto"       # as TransformerConfig's

    @property
    def head_dim(self) -> int:
        assert self.hidden_size % self.num_attention_heads == 0
        return self.hidden_size // self.num_attention_heads

    @property
    def num_hidden_layers(self) -> int:
        return len(self.layer_types)


def lfm2_24b_a2b_config(**overrides) -> Lfm2Config:
    """LFM2-24B-A2B as published (the defaults above).  A cut keeps the
    leading ``num_dense_layers`` and whole periods of the pattern after
    them, ``experts_held`` and a slice of the vocabulary: see
    ``examples/bert/pretrain.py --lfm2``."""
    return Lfm2Config(**overrides)


def lfm2_cut_layer_types(dense: int, periods: int) -> Tuple[str, ...]:
    """``dense`` leading conv layers, then ``periods`` whole periods
    (full_attention, conv, conv, conv) of the published pattern."""
    return ("conv",) * dense + _PERIOD * periods


def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5


def lfm2_init(key, cfg: Lfm2Config):
    """Parameter tree: ``embed/tok``, a list of layers and ``head/norm``;
    the head is the embedding, transposed.  Matrices are N(0, 1/fan_in) —
    0.022 at the published width, the family's 0.02 — so a block's output
    is of the size of its input at every width."""
    d, f, m = cfg.hidden_size, cfg.intermediate_size, cfg.moe_intermediate_size
    hd, kv = cfg.head_dim, cfg.num_key_value_heads * cfg.head_dim
    held = cfg.experts_held[1]
    key, k_tok = jax.random.split(key)
    layers = []
    for i, kind in enumerate(cfg.layer_types):
        key, k1, k2, k3, k4, k5, k6, k7 = jax.random.split(key, 8)
        layer = {"op_norm": jnp.ones((d,), jnp.float32),
                 "ffn_norm": jnp.ones((d,), jnp.float32)}
        if kind == "conv":
            layer.update(
                in_proj=_normal(k1, (d, 3 * d), d),
                conv=_normal(k2, (cfg.conv_L_cache, d), cfg.conv_L_cache),
                out_proj=_normal(k3, (d, d), d))
        elif kind == "full_attention":
            layer.update(
                wq=_normal(k1, (d, d), d), wk=_normal(k2, (d, kv), d),
                wv=_normal(k3, (d, kv), d), wo=_normal(k4, (d, d), d),
                q_norm=jnp.ones((hd,), jnp.float32),
                k_norm=jnp.ones((hd,), jnp.float32))
        else:
            raise ValueError(f"layer_types[{i}] = {kind!r}")
        if i < cfg.num_dense_layers:
            layer.update(w13=_normal(k5, (d, 2 * f), d),
                         w2=_normal(k6, (f, d), f))
        else:
            layer.update(
                router=_normal(k7, (d, cfg.num_experts), d),
                # chooses, never weighs, and no gradient reaches it: zero,
                # and it stays zero (the source gives no update rule)
                expert_bias=jnp.zeros((cfg.num_experts,), jnp.float32),
                w13=_normal(k5, (held, d, 2 * m), d),
                w2=_normal(k6, (held, m, d), m))
        layers.append(layer)
    return {"embed": {"tok": _normal(k_tok, (cfg.vocab_size, d), d)},
            "layers": layers,
            "head": {"norm": jnp.ones((d,), jnp.float32)}}


def _rms_norm(x, gain, eps):
    x32 = x.astype(jnp.float32)
    x32 = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                              + eps)
    return (x32 * gain.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    """Rotate-half RoPE over x (B, S, H, hd) at positions 0..S-1."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq[None]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    rotated = jnp.concatenate([-x32[..., half:], x32[..., :half]], axis=-1)
    return (x32 * cos + rotated * sin).astype(x.dtype)


def _conv_mixer(u, lp, cfg):
    dt = u.dtype
    b, c, x = jnp.split(u @ lp["in_proj"].astype(dt), 3, axis=-1)
    z = b * x
    taps, seq = cfg.conv_L_cache, z.shape[1]
    z = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
    w = lp["conv"].astype(dt)
    conv = sum(w[j] * z[:, j:j + seq] for j in range(taps))
    return (c * conv) @ lp["out_proj"].astype(dt)


def _attention_mixer(u, lp, cfg):
    dt = u.dtype
    bsz, seq, _ = u.shape
    heads, kv_heads, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                           cfg.head_dim)
    q = (u @ lp["wq"].astype(dt)).reshape(bsz, seq, heads, hd)
    k = (u @ lp["wk"].astype(dt)).reshape(bsz, seq, kv_heads, hd)
    v = (u @ lp["wv"].astype(dt)).reshape(bsz, seq, kv_heads, hd)
    q = _rope(_rms_norm(q, lp["q_norm"], cfg.norm_eps), cfg.rope_theta)
    k = _rope(_rms_norm(k, lp["k_norm"], cfg.norm_eps), cfg.rope_theta)
    # (B, H, S, hd); a key/value head serves heads // kv_heads query heads
    q = (q * hd ** -0.5).astype(dt).transpose(0, 2, 1, 3)
    k = jnp.repeat(k.transpose(0, 2, 1, 3), heads // kv_heads, axis=1)
    v = jnp.repeat(v.transpose(0, 2, 1, 3), heads // kv_heads, axis=1)
    return causal_attention(q, k, v, cfg.attn_impl) @ lp["wo"].astype(dt)


def causal_attention(q, k, v, impl: str, window=None):
    """Causal softmax attention of ``q`` (pre-scaled), ``k``, ``v`` (B, H, S,
    hd), every key/value head already repeated for its query heads: the flash
    kernel under ``impl="fast"``, else the XLA core; ``window`` limits a
    query to that many keys, itself the last.  Returns (B, S, H·hd)."""
    bsz, heads, seq, hd = q.shape
    no_bias = jnp.zeros((1, 1, seq), jnp.float32)
    if impl == "fast":
        from ..contrib.multihead_attn.flash import flash_attention
        ctx = flash_attention(
            q.reshape(bsz * heads, seq, hd), k.reshape(bsz * heads, seq, hd),
            v.reshape(bsz * heads, seq, hd), no_bias, causal=True, heads=heads,
            window=window).reshape(bsz, heads, seq, hd)
    else:
        from ..contrib.multihead_attn.functional import attention_core
        ctx = attention_core(q, k, v, no_bias, causal=True, window=window)
    return ctx.astype(q.dtype).transpose(0, 2, 1, 3).reshape(
        bsz, seq, heads * hd)


def _block(x, lp, *, cfg: Lfm2Config, kind: str, dense: bool):
    """One layer: ``kind`` picks the mixer, ``dense`` the FFN.  Returns
    ``(y, routing)``: ``routed_experts``' record, None from a dense layer."""
    dt = x.dtype
    with annotate("apex.conv" if kind == "conv" else "apex.attn"):
        u = _rms_norm(x, lp["op_norm"], cfg.norm_eps)
        mixer = _conv_mixer if kind == "conv" else _attention_mixer
        x = x + mixer(u, lp, cfg)
    if dense:
        with annotate("apex.mlp"):
            h = _rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
            gate, up = jnp.split(h @ lp["w13"].astype(dt), 2, axis=-1)
            x = x + (jax.nn.silu(gate) * up) @ lp["w2"].astype(dt)
        return x, None
    with annotate("apex.moe"):
        h = _rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
        bias = (lp["expert_bias"] if cfg.use_expert_bias
                else jnp.zeros_like(lp["expert_bias"]))
        out, routing = routed_experts(
            h.reshape(-1, h.shape[-1]), lp["router"], bias, lp["w13"],
            lp["w2"], top_k=cfg.num_experts_per_tok,
            first=cfg.experts_held[0], norm_topk_prob=cfg.norm_topk_prob,
            routed_scaling_factor=cfg.routed_scaling_factor)
        return x + out.reshape(x.shape), routing


def _forward(params, tokens, cfg: Lfm2Config):
    """``(logits, routing)``: ``routing`` stacks every expert layer's record
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
    for i, (kind, lp) in enumerate(zip(cfg.layer_types, params["layers"])):
        block = functools.partial(_block, cfg=cfg, kind=kind,
                                  dense=i < cfg.num_dense_layers)
        if cfg.remat:
            block = jax.checkpoint(block)
        x, record = block(x, lp)
        if record is not None:
            records.append(record)
    routing = jax.tree_util.tree_map(lambda *r: jnp.stack(r), *records) \
        if records else None
    if records and _tel_events.active():
        # the routing meter: once a forward pass, outside the checkpoint so
        # remat's second forward does not count twice
        jax.debug.callback(_tel_events.record_expert_rows, routing["rows"],
                           jnp.sum(routing["dropped"]), routing["walks"],
                           slots=routing["slots"])
    with annotate("apex.head"):
        x = _rms_norm(x, params["head"]["norm"], cfg.norm_eps)
        return x @ params["embed"]["tok"].astype(dt).T, routing


def lfm2_apply(params, tokens, cfg: Lfm2Config):
    """tokens (B, S) int32 -> logits (B, S, V) over the held vocabulary."""
    return _forward(params, tokens, cfg)[0]


def lfm2_routing(params, tokens, cfg: Lfm2Config):
    """What the forward pass over ``tokens`` routed, every expert layer
    stacked: ``ids`` (L, B·S, k) the experts each token took, ``rows``
    (L, held) the assignments each held expert was sent, ``dropped`` (L,)
    those that found no row in the buffer (0), ``walks`` (L,) the times the
    layer went over its buffer (1 where the load fit it), ``slots`` (L,) the
    most held assignments any token has."""
    return _forward(params, tokens, cfg)[1]


def causal_lm_loss(logits, batch, xent_impl: str = "auto"):
    """Next-token cross entropy of ``logits`` (B, S, V): ``batch["targets"]``
    are the tokens shifted by one, ``batch["weights"]`` (optional) 0 where a
    position has no target.  Through the contrib xentropy kernel, as
    ``transformer_loss``."""
    from ..contrib.xentropy import softmax_xentropy_loss
    bsz, seq, vocab = logits.shape
    with annotate("apex.loss"):
        nll = softmax_xentropy_loss(
            logits.reshape(bsz * seq, vocab),
            batch["targets"].reshape(bsz * seq), 0.0, -1, False,
            xent_impl).reshape(bsz, seq)
        w = batch.get("weights")
        if w is None:
            return nll.mean()
        return (nll * w).sum() / jnp.maximum(w.sum(), 1.0)


def lfm2_loss(params, batch, cfg: Lfm2Config):
    """:func:`causal_lm_loss` of the decoder's logits over ``batch``."""
    return causal_lm_loss(lfm2_apply(params, batch["tokens"], cfg), batch,
                          cfg.xent_impl)
