"""GLM-4.7-Flash decoder (``glm4_moe_lite``; docs/glm4_moe_lite.md): every
layer is ``x <- x + MLA(rms(x)); x <- x + ffn(rms(x))``; the leading
``first_k_dense_replace`` layers have a dense gated FFN, the others routed
experts beside a shared expert; then a final ``rms`` and an untied head.
One **multi-token-prediction** module (DeepSeek-V3's, arXiv:2412.19437 §2.2)
joins the trunk's last hidden state to the next token's embedding, runs one
more block and predicts the token two ahead through the same head.

- ``MLA``, multi-head latent attention: ``c_q = rms(u W_qa)``, ``q = c_q
  W_qb`` a head ``q_nope ‖ q_pe``; ``(c_kv ‖ k_pe) = u W_kva``, ``(k_nope ‖
  v) = rms(c_kv) W_kvb`` a head; rotate-half RoPE on ``q_pe`` of every head
  and on the ONE ``k_pe`` that every head shares; ``k = k_nope ‖ RoPE(k_pe)``,
  ``q = q_nope ‖ RoPE(q_pe)``; causal softmax at ``(nope + rope)^-½`` (under
  ``attn_impl="fast"`` the flash kernel: the QK and V widths must be equal,
  256 as published); ``out = ctx W_o``.
- sparse FFN: :func:`apex_tpu.parallel.expert.routed_experts` with sigmoid
  scores over ALL ``num_experts``, the top ``num_experts_per_tok`` of scores
  plus the correction bias (a buffer no gradient moves), weights
  renormalised and times ``routed_scaling_factor``, gated-SiLU experts; plus
  an ungated gated-SiLU shared expert on every token.
- MTP: ``m = [rms(Emb(t_{i+1})) ‖ rms(h_i)] W_eh``, the same block, ``rms``,
  the shared head; the loss is ``CE(next) + λ CE(two ahead)``.

A chip's share is part of the configuration — ``experts_held`` ``(first,
count)`` and ``vocab_size`` rows of embedding and head: it computes ITS
experts' part of each layer's sum and nothing stands in for the absent
chips; mixers, norms, router, the shared expert and the MTP join are whole
on every chip.  The defaults hold everything; :func:`glm4_moe_lite_share`
cuts a share out of the whole model's parameters.

Layers are a python loop (they differ in shape), each under
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
from .lfm2 import _normal, _rms_norm, _rope, causal_attention, causal_lm_loss


@dataclasses.dataclass(frozen=True)
class Glm4MoeLiteConfig:
    vocab_size: int = 154880            # the rows of embedding and head held
    hidden_size: int = 2048
    num_hidden_layers: int = 47
    first_k_dense_replace: int = 1      # the leading dense layers
    intermediate_size: int = 10240      # their FFN
    num_attention_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 1e6
    num_experts: int = 64               # n_routed_experts: what the router scores
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1536   # one routed expert's
    n_shared_experts: int = 1           # the shared expert is this many wide
    routed_scaling_factor: float = 1.8
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    num_nextn_predict_layers: int = 1   # MTP modules: 0 or 1
    mtp_loss_weight: float = 0.3        # λ (the source states none)
    experts_held: Tuple[int, int] = (0, 64)   # (first id, count) held here
    dtype: Any = jnp.float32
    remat: bool = False
    attn_impl: str = "default"    # as TransformerConfig's
    xent_impl: str = "auto"       # as TransformerConfig's

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


def glm47_flash_config(**overrides) -> Glm4MoeLiteConfig:
    """GLM-4.7-Flash as published (the defaults above).  A cut keeps the
    leading dense layer and whole layers after it, ``experts_held`` and a
    slice of the vocabulary: see ``examples/bert/pretrain.py
    --glm4-moe-lite``."""
    return Glm4MoeLiteConfig(**overrides)


def _block_params(key, cfg: Glm4MoeLiteConfig, dense: bool) -> dict:
    """One layer's leaves: the MLA mixer's, then a dense or a sparse FFN's."""
    d, heads = cfg.hidden_size, cfg.num_attention_heads
    ql, kl, rope = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    k = jax.random.split(key, 9)
    layer = {"input_norm": jnp.ones((d,), jnp.float32),
             "ffn_norm": jnp.ones((d,), jnp.float32),
             "q_a": _normal(k[0], (d, ql), d),
             "q_a_norm": jnp.ones((ql,), jnp.float32),
             "q_b": _normal(k[1], (ql, heads * cfg.qk_head_dim), ql),
             "kv_a": _normal(k[2], (d, kl + rope), d),
             "kv_a_norm": jnp.ones((kl,), jnp.float32),
             "kv_b": _normal(k[3], (kl, heads * (cfg.qk_nope_head_dim
                                                 + cfg.v_head_dim)), kl),
             "o": _normal(k[4], (heads * cfg.v_head_dim, d),
                          heads * cfg.v_head_dim)}
    if dense:
        f = cfg.intermediate_size
        layer.update(w13=_normal(k[5], (d, 2 * f), d),
                     w2=_normal(k[6], (f, d), f))
        return layer
    m, held = cfg.moe_intermediate_size, cfg.experts_held[1]
    ms = m * cfg.n_shared_experts
    k5, k6 = jax.random.split(k[5])
    layer.update(
        router=_normal(k[6], (d, cfg.num_experts), d),
        # chooses, never weighs, and no gradient reaches it: zero, and it
        # stays zero (the source's update rule is a training recipe's)
        expert_bias=jnp.zeros((cfg.num_experts,), jnp.float32),
        w13=_normal(k[7], (held, d, 2 * m), d),
        w2=_normal(k[8], (held, m, d), m),
        shared_w13=_normal(k5, (d, 2 * ms), d),
        shared_w2=_normal(k6, (ms, d), ms))
    return layer


def glm4_moe_lite_init(key, cfg: Glm4MoeLiteConfig):
    """Parameter tree of the held share: ``embed/tok``, a list of layers,
    ``head/norm``, ``head/out`` and ``mtp``, a list of
    ``num_nextn_predict_layers`` modules (``enorm``, ``hnorm``, ``eh_proj``
    (2d, d) — embedding beside hidden state —, a sparse layer's leaves and
    ``head_norm``).  Matrices are N(0, 1/fan_in) — 0.022 at the published
    width, the family's ``initializer_range`` 0.02; norm gains 1."""
    if cfg.num_nextn_predict_layers not in (0, 1):
        raise ValueError("one multi-token-prediction module at most, got "
                         f"{cfg.num_nextn_predict_layers}")
    d = cfg.hidden_size
    key, k_tok, k_out = jax.random.split(key, 3)
    layers = []
    for i in range(cfg.num_hidden_layers):
        key, k = jax.random.split(key)
        layers.append(_block_params(k, cfg, i < cfg.first_k_dense_replace))
    mtp = []
    for _ in range(cfg.num_nextn_predict_layers):
        key, k_eh, k = jax.random.split(key, 3)
        mtp.append(dict(_block_params(k, cfg, False),
                        enorm=jnp.ones((d,), jnp.float32),
                        hnorm=jnp.ones((d,), jnp.float32),
                        eh_proj=_normal(k_eh, (2 * d, d), 2 * d),
                        head_norm=jnp.ones((d,), jnp.float32)))
    return {"embed": {"tok": _normal(k_tok, (cfg.vocab_size, d), d)},
            "layers": layers,
            "head": {"norm": jnp.ones((d,), jnp.float32),
                     "out": _normal(k_out, (d, cfg.vocab_size), d)},
            "mtp": mtp}


def glm4_moe_lite_share(params, whole: Glm4MoeLiteConfig,
                        cfg: Glm4MoeLiteConfig):
    """The parameters ``cfg``'s share holds, cut out of ``whole``'s (which
    holds everything): its experts of every sparse layer, the MTP module's
    among them, and the first ``vocab_size`` ids.  What every chip holds
    alike (mixers, norms, router, its bias, the shared expert, the MTP join)
    is copied."""
    del whole                           # every other size is the weights'
    e0, e = cfg.experts_held

    def cut(lp):
        if "router" not in lp:
            return lp
        return dict(lp, w13=lp["w13"][e0:e0 + e], w2=lp["w2"][e0:e0 + e])
    return {"embed": {"tok": params["embed"]["tok"][:cfg.vocab_size]},
            "layers": [cut(lp) for lp in params["layers"]],
            "head": {"norm": params["head"]["norm"],
                     "out": params["head"]["out"][:, :cfg.vocab_size]},
            "mtp": [cut(mp) for mp in params["mtp"]]}


def _mla_mixer(u, lp, cfg: Glm4MoeLiteConfig):
    """Multi-head latent attention of ``u`` (B, S, d), already normed."""
    dt = u.dtype
    bsz, seq, _ = u.shape
    heads, nope = cfg.num_attention_heads, cfg.qk_nope_head_dim
    if cfg.qk_head_dim != cfg.v_head_dim:
        raise ValueError(f"QK width {cfg.qk_head_dim} != V width "
                         f"{cfg.v_head_dim}: one attention core takes one")
    eps = cfg.rms_norm_eps
    c_q = _rms_norm(u @ lp["q_a"].astype(dt), lp["q_a_norm"], eps)
    q = (c_q @ lp["q_b"].astype(dt)).reshape(bsz, seq, heads,
                                              cfg.qk_head_dim)
    c_kv, k_pe = jnp.split(u @ lp["kv_a"].astype(dt), [cfg.kv_lora_rank],
                           axis=-1)
    kv = (_rms_norm(c_kv, lp["kv_a_norm"], eps) @ lp["kv_b"].astype(dt)
          ).reshape(bsz, seq, heads, nope + cfg.v_head_dim)
    k_nope, v = jnp.split(kv, [nope], axis=-1)
    # ONE rotated key part, the same for every head
    k_pe = _rope(k_pe[:, :, None, :], cfg.rope_theta)
    q = jnp.concatenate(
        [q[..., :nope], _rope(q[..., nope:], cfg.rope_theta)], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, k_nope.shape[:3] + k_pe.shape[3:])],
        axis=-1)
    # (B, H, S, ·)
    q = (q * cfg.qk_head_dim ** -0.5).astype(dt).transpose(0, 2, 1, 3)
    ctx = causal_attention(q, k.transpose(0, 2, 1, 3),
                           v.transpose(0, 2, 1, 3), cfg.attn_impl)
    return ctx @ lp["o"].astype(dt)


def _shared_expert(flat, lp):
    """``W2ˢ(silu(W1ˢ x) ⊙ W3ˢ x)``, ungated: on every token, on every chip
    alike."""
    dt = flat.dtype
    with annotate("apex.shared_expert"):
        gate, up = jnp.split(flat @ lp["shared_w13"].astype(dt), 2, axis=-1)
        return (jax.nn.silu(gate) * up) @ lp["shared_w2"].astype(dt)


def _sparse_ffn(h, lp, cfg: Glm4MoeLiteConfig):
    """``(out, routing)``: the held experts' part of the routed sum, plus the
    shared expert."""
    flat = h.reshape(-1, h.shape[-1])
    routed, routing = routed_experts(
        flat, lp["router"], lp["expert_bias"], lp["w13"], lp["w2"],
        top_k=cfg.num_experts_per_tok, first=cfg.experts_held[0],
        norm_topk_prob=cfg.norm_topk_prob,
        routed_scaling_factor=cfg.routed_scaling_factor)
    return (routed + _shared_expert(flat, lp)).reshape(h.shape), routing


def _block(x, lp, *, cfg: Glm4MoeLiteConfig, dense: bool):
    """One layer: MLA, then a dense or a sparse FFN.  Returns ``(y,
    routing)``: ``routed_experts``' record, None from a dense layer."""
    dt, eps = x.dtype, cfg.rms_norm_eps
    with annotate("apex.mla"):
        x = x + _mla_mixer(_rms_norm(x, lp["input_norm"], eps), lp, cfg)
    if dense:
        with annotate("apex.mlp"):
            h = _rms_norm(x, lp["ffn_norm"], eps)
            gate, up = jnp.split(h @ lp["w13"].astype(dt), 2, axis=-1)
            return x + (jax.nn.silu(gate) * up) @ lp["w2"].astype(dt), None
    with annotate("apex.moe"):
        out, routing = _sparse_ffn(_rms_norm(x, lp["ffn_norm"], eps), lp, cfg)
        return x + out, routing


def _checkpointed(cfg: Glm4MoeLiteConfig, dense: bool):
    block = functools.partial(_block, cfg=cfg, dense=dense)
    return jax.checkpoint(block) if cfg.remat else block


def _head(x, norm, params, cfg: Glm4MoeLiteConfig):
    with annotate("apex.head"):
        return _rms_norm(x, norm, cfg.rms_norm_eps) \
            @ params["head"]["out"].astype(cfg.dtype)


def _forward(params, tokens, next_tokens, cfg: Glm4MoeLiteConfig):
    """``(logits, mtp_logits, routing)``: the MTP module runs where
    ``next_tokens`` (``t_{i+1}`` at position i: the batch's ``targets``) are
    given and the tree has it, else ``mtp_logits`` is None; ``routing``
    stacks every sparse layer's record, the MTP module's last (``ids`` (L,
    T, k), ``rows`` (L, held), ``dropped`` (L,), ``walks`` (L,), ``slots``
    (L,))."""
    if cfg.attn_impl not in ("default", "fast"):
        raise ValueError(
            f"attn_impl must be 'default' or 'fast', got {cfg.attn_impl!r}")
    if len(params["layers"]) != cfg.num_hidden_layers:
        raise ValueError(f"{len(params['layers'])} layers of parameters for "
                         f"{cfg.num_hidden_layers} hidden layers")
    dt, eps = cfg.dtype, cfg.rms_norm_eps
    embed = params["embed"]["tok"].astype(dt)
    with annotate("apex.embed"):
        x = embed[tokens]
    records = []
    for i, lp in enumerate(params["layers"]):
        x, record = _checkpointed(cfg, i < cfg.first_k_dense_replace)(x, lp)
        if record is not None:
            records.append(record)
    logits = _head(x, params["head"]["norm"], params, cfg)
    mtp_logits = None
    if next_tokens is not None and params["mtp"]:
        mp, = params["mtp"]
        with annotate("apex.mtp"):
            # [rms(Emb(t_{i+1})) ‖ rms(h_i)] W_eh: h before the final norm
            m = jnp.concatenate([_rms_norm(embed[next_tokens], mp["enorm"], eps),
                                 _rms_norm(x, mp["hnorm"], eps)], axis=-1)
            m, record = _checkpointed(cfg, False)(
                m @ mp["eh_proj"].astype(dt), mp)
            records.append(record)
            mtp_logits = _head(m, mp["head_norm"], params, cfg)
    routing = jax.tree_util.tree_map(lambda *r: jnp.stack(r), *records) \
        if records else None
    if records and _tel_events.active():
        # the routing meter: once a forward pass, outside the checkpoints so
        # remat's second forward does not count twice
        jax.debug.callback(_tel_events.record_expert_rows, routing["rows"],
                           jnp.sum(routing["dropped"]), routing["walks"],
                           slots=routing["slots"])
    return logits, mtp_logits, routing


def glm4_moe_lite_apply(params, tokens, cfg: Glm4MoeLiteConfig):
    """tokens (B, S) int32 -> the next-token logits (B, S, V) over the held
    vocabulary (the MTP module does not run)."""
    return _forward(params, tokens, None, cfg)[0]


def glm4_moe_lite_routing(params, batch, cfg: Glm4MoeLiteConfig):
    """What the forward pass over ``batch`` (``tokens``, ``targets``) routed,
    every sparse layer stacked, the MTP module's last:
    ``models.lfm2.lfm2_routing``'s record."""
    return _forward(params, batch["tokens"], batch["targets"], cfg)[2]


def mtp_batch(batch):
    """The MTP term's targets and weights: position i predicts ``t_{i+2}``
    = ``targets[i + 1]``, and weighs where both ``t_{i+1}`` and ``t_{i+2}``
    exist — 0 at the last position, and wherever ``weights`` is 0 at i or
    i + 1."""
    w = batch["weights"]
    return {"targets": jnp.pad(batch["targets"][:, 1:], ((0, 0), (0, 1))),
            "weights": jnp.pad(w[:, :-1] * w[:, 1:], ((0, 0), (0, 1)))}


def glm4_moe_lite_loss(params, batch, cfg: Glm4MoeLiteConfig):
    """``CE(logits, t_{i+1}) + λ CE(mtp_logits, t_{i+2})`` over the held
    vocabulary, each a weighted mean (:func:`causal_lm_loss`, :func:`mtp_batch`);
    the next-token term alone where the tree has no MTP module."""
    logits, mtp_logits, _ = _forward(params, batch["tokens"],
                                     batch["targets"], cfg)
    loss = causal_lm_loss(logits, batch, cfg.xent_impl)
    if mtp_logits is None:
        return loss
    with annotate("apex.mtp"):
        return loss + cfg.mtp_loss_weight * causal_lm_loss(
            mtp_logits, mtp_batch(batch), cfg.xent_impl)
