"""Qwen3-Next decoder (Qwen ``qwen3_next``; docs/qwen3_next.md): every layer
is ``x <- x + mixer(rms0(x)); x <- x + ffn(rms0(x))`` with the zero-centred
RMSNorm ``rms0(x; w) = (1 + w) ⊙ x / rms(x)``; three layers in four mix with
a **Gated DeltaNet** (linear attention with a delta rule), the fourth with
gated softmax attention; every FFN is sparse; then a final ``rms0`` and an
untied head.

- ``linear_attention``, Gated DeltaNet: ``(q, k, v, z) = u W_qkvz``, ``(b, a)
  = u W_ba``; ``(q, k, v) <- silu(conv(q ‖ k ‖ v))``, a causal depthwise
  convolution over ``linear_conv_kernel_dim`` steps; ``β = sigmoid(b)``, ``g =
  -exp(A_log) softplus(a + dt_bias)``; ``q``, ``k`` L2-normalised a head
  (``q`` also times ``d_k^-½``), a key head serving ``H_v / H_k`` value heads;
  ``S_t = e^{g_t} S_{t-1} + k_t ⊗ β_t (v_t - (e^{g_t} S_{t-1})ᵀ k_t)``, ``o_t
  = S_tᵀ q_t`` as a **chunked gated delta rule**
  (:func:`gated_delta_rule`); ``out = (rms(o; w) ⊙ silu(z)) W_out``.
- ``full_attention``: ``(q, gate) = u W_q`` a head, ``k``, ``v``;
  ``rms0`` over each head of q and k, rotate-half RoPE on the first
  ``partial_rotary_factor`` of a head, causal softmax (under
  ``attn_impl="fast"`` the flash kernel, every key/value head repeated for
  its query heads), ``out = (ctx ⊙ sigmoid(gate)) W_o``.
- sparse FFN: :func:`apex_tpu.parallel.expert.routed_experts` with
  ``softmax`` scores over ALL ``num_experts``, top ``num_experts_per_tok``,
  renormalised, gated-SiLU experts; plus ``sigmoid(x w_s) · FFN_shared(x)``.

A chip's share is part of the configuration — ``experts_held`` ``(first,
count)`` and ``vocab_size`` rows of embedding and head: it computes ITS
experts' part of each layer's sum and nothing stands in for the absent
chips; mixers, norms, router, the shared expert and its gate are whole on
every chip.  The defaults hold everything, so the uncut model is the same
code; :func:`qwen3_next_share` cuts a share's parameters out of the whole
model's.

Layers are a python loop (they differ in shape), each under
``jax.checkpoint`` where ``remat`` — which keeps, by name, the delta rule's
output where the kernel pair made it and recomputes the rest.  Plain
``jax.numpy`` around the kernels — flash attention, the gated delta rule's
pair (``ops.gated_delta_rule``, at the published head width 128 and chunk 64;
its ``jax.numpy`` twin :func:`_chunked_rule` otherwise), the loss — and the
grouped products; XLA fuses the rest.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops import gated_delta_rule as _rule_kernel
from ..parallel.expert import routed_experts
from ..pyprof import annotate, annotate_function
from ..telemetry import events as _tel_events
from .lfm2 import _normal, _rms_norm, _rope, causal_attention, causal_lm_loss
from .nemotron_h import _causal_conv


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936            # the rows of embedding and head held
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4    # layer i is full where (i+1) % 4 == 0
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    linear_num_key_heads: int = 16
    linear_key_head_dim: int = 128
    linear_num_value_heads: int = 32
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    chunk_size: int = 64                # of the chunked delta rule
    num_experts: int = 512              # what the router scores
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512    # one routed expert's
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    experts_held: Tuple[int, int] = (0, 512)   # (first id, count) held here
    dtype: Any = jnp.float32
    remat: bool = False
    attn_impl: str = "default"    # as TransformerConfig's
    xent_impl: str = "auto"       # as TransformerConfig's

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return tuple(
            "linear_attention" if (i + 1) % self.full_attention_interval
            else "full_attention" for i in range(self.num_hidden_layers))

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)


def qwen3_next_80b_a3b_config(**overrides) -> Qwen3NextConfig:
    """Qwen3-Next-80B-A3B-Instruct as published (the defaults above), without
    its multi-token-prediction module (docs/qwen3_next.md).  A cut keeps
    whole periods of (linear, linear, linear, full), ``experts_held`` and a
    slice of the vocabulary: see ``examples/bert/pretrain.py --qwen3-next``."""
    return Qwen3NextConfig(**overrides)


def _gdn_widths(cfg: Qwen3NextConfig):
    """(key width, value width) of a Gated DeltaNet layer: q and k are each
    the first, v and z each the second."""
    return (cfg.linear_num_key_heads * cfg.linear_key_head_dim,
            cfg.linear_num_value_heads * cfg.linear_value_head_dim)


def qwen3_next_init(key, cfg: Qwen3NextConfig):
    """Parameter tree of the held share: ``embed/tok``, a list of layers and
    ``head/norm``, ``head/out``.  Matrices are N(0, 1/fan_in) — 0.022 at the
    published width, the family's ``initializer_range`` 0.02 — the four
    convolution taps among them; the zero-centred norms' ``w`` 0, the gated
    norm's gain 1; ``A_log`` = log U(0, 16) (drawn from 1e-4 up: no head
    starts at -inf) and ``dt_bias`` = 1, as the public implementation has
    them.  ``in_proj_qkvz`` holds q beside k beside v
    beside z, ``in_proj_ba`` b beside a, ``wq`` each head's query beside its
    gate."""
    d, hd = cfg.hidden_size, cfg.head_dim
    key_w, value_w = _gdn_widths(cfg)
    heads_v = cfg.linear_num_value_heads
    q, kv = cfg.num_attention_heads * hd, cfg.num_key_value_heads * hd
    m, ms = cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size
    held = cfg.experts_held[1]
    key, k_tok, k_out = jax.random.split(key, 3)
    layers = []
    for kind in cfg.layer_types:
        key, k1, k2, k3, k4, k5 = jax.random.split(key, 6)
        layer = {"input_norm": jnp.zeros((d,), jnp.float32),
                 "ffn_norm": jnp.zeros((d,), jnp.float32)}
        if kind == "linear_attention":
            taps = cfg.linear_conv_kernel_dim
            layer.update(
                in_proj_qkvz=_normal(k1, (d, 2 * key_w + 2 * value_w), d),
                in_proj_ba=_normal(k2, (d, 2 * heads_v), d),
                conv_w=_normal(k3, (taps, 2 * key_w + value_w), taps),
                dt_bias=jnp.ones((heads_v,), jnp.float32),
                A_log=jnp.log(jax.random.uniform(
                    k4, (heads_v,), jnp.float32, 1e-4, 16.0)),
                gate_norm=jnp.ones((cfg.linear_value_head_dim,), jnp.float32),
                out_proj=_normal(k5, (value_w, d), value_w))
        else:
            layer.update(
                wq=_normal(k1, (d, 2 * q), d), wk=_normal(k2, (d, kv), d),
                wv=_normal(k3, (d, kv), d), wo=_normal(k4, (q, d), q),
                q_norm=jnp.zeros((hd,), jnp.float32),
                k_norm=jnp.zeros((hd,), jnp.float32))
        key, k1, k2, k3, k4, k5, k6 = jax.random.split(key, 7)
        layer.update(
            router=_normal(k1, (d, cfg.num_experts), d),
            w13=_normal(k2, (held, d, 2 * m), d),
            w2=_normal(k3, (held, m, d), m),
            shared_w13=_normal(k4, (d, 2 * ms), d),
            shared_w2=_normal(k5, (ms, d), ms),
            shared_gate=_normal(k6, (d, 1), d))
        layers.append(layer)
    return {"embed": {"tok": _normal(k_tok, (cfg.vocab_size, d), d)},
            "layers": layers,
            "head": {"norm": jnp.zeros((d,), jnp.float32),
                     "out": _normal(k_out, (d, cfg.vocab_size), d)}}


def qwen3_next_share(params, whole: Qwen3NextConfig, cfg: Qwen3NextConfig):
    """The parameters ``cfg``'s share holds, cut out of ``whole``'s (which
    holds everything): its experts of every layer and the first
    ``vocab_size`` ids.  What every chip holds alike (mixers, norms, router,
    the shared expert and its gate) is copied."""
    del whole                           # every other size is the weights'
    e0, e = cfg.experts_held
    return {"embed": {"tok": params["embed"]["tok"][:cfg.vocab_size]},
            "layers": [dict(lp, w13=lp["w13"][e0:e0 + e],
                            w2=lp["w2"][e0:e0 + e])
                       for lp in params["layers"]],
            "head": {"norm": params["head"]["norm"],
                     "out": params["head"]["out"][:, :cfg.vocab_size]}}


def _rms0(x, w, eps):
    """The zero-centred RMSNorm ``(1 + w) ⊙ x / rms(x)``: ``w`` starts 0."""
    return _rms_norm(x, 1.0 + w, eps)


#: float32 products of the triangular system: true float32 on a TPU too
_exact = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)


@jax.custom_vjp
def _unit_lower_inverse(a):
    """``(I - A)⁻¹`` of strictly lower triangular ``a`` (..., C, C) float32.
    ``a`` is nilpotent, so the inverse is ``Σ_{n<C} Aⁿ = Π_m (I + A^(2^m))``:
    ``2 log₂C - 2`` matrix products where forward substitution would take C
    dependent steps.  The reverse rule is the inverse's own, ``Ā = Tᵀ T̄ Tᵀ``,
    so no power is kept for it."""
    n = a.shape[-1]
    total, power, exponent = a + jnp.eye(n, dtype=a.dtype), a, 1
    while 2 * exponent < n:
        power, exponent = _exact(power, power), 2 * exponent
        total = total + _exact(total, power)
    return total


def _unit_lower_inverse_fwd(a):
    t = _unit_lower_inverse(a)
    return t, t


def _unit_lower_inverse_bwd(t, g):
    t_t = jnp.swapaxes(t, -1, -2)
    return (_exact(_exact(t_t, g), t_t),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def gated_delta_rule(q, k, v, g, beta, chunk: int):
    """:func:`_chunked_rule`'s ``o``, by the Pallas kernel pair
    (``ops.gated_delta_rule``) where the shape is the one it takes — heads
    128 wide, a chunk of 64: the published shape — and by the ``jax.numpy``
    form otherwise: the same arithmetic, the chunk-local matrices in VMEM and
    not in HBM.  Which of the two a traced call took is recorded
    (``telemetry.events.record_gdn_rule``).  The pair's output carries the
    name ``"gdn_rule_out"`` for :data:`_KEEP_RULE_OUT`; the ``jax.numpy``
    form's carries none, so a checkpoint around it keeps nothing and
    recomputes it whole, as before the kernels."""
    kernel = _rule_kernel.takes(q.shape[-1], v.shape[-1], chunk)
    _tel_events.record_gdn_rule("kernel" if kernel else "jnp")
    if not kernel:
        return _chunked_rule(q, k, v, g, beta, chunk)
    return checkpoint_name(
        _rule_kernel.gated_delta_rule(q, k, v, g, beta, chunk),
        "gdn_rule_out")


def _chunked_rule(q, k, v, g, beta, chunk: int):
    """The ``jax.numpy`` form — the kernel pair's twin, and the path of the
    shapes it does not take: ``o_t = S_tᵀ q_t`` of ``S_t = e^{g_t} S_{t-1} +
    k_t ⊗ β_t (v_t - (e^{g_t} S_{t-1})ᵀ k_t)``, ``S_0 = 0``, chunked: with ``γ``
    the cumulative sum of ``g`` within a chunk of ``chunk`` steps and ``u_t
    = β_t (v_t - (e^{g_t} S_{t-1})ᵀ k_t)`` the value a step writes,

        A = -strict_tril((β k) kᵀ ⊙ e^{γ_i - γ_j}),   T = (I - A)⁻¹
        U = T (β v) - T (β k ⊙ e^γ) S          (S the state the chunk enters)
        O = (q ⊙ e^γ) S + tril(q kᵀ ⊙ e^{γ_i - γ_j}) U
        S <- e^{γ_C} S + (k ⊙ e^{γ_C - γ})ᵀ U

    — ``T`` a unit lower-triangular system a chunk and head
    (:func:`_unit_lower_inverse`), then a recurrence over the S / chunk
    states.

    ``q``, ``k`` (B, S, H_k, d_k), already normalised and scaled; ``v`` (B, S,
    H_v, d_v), value head ``h`` reading key head ``h // (H_v // H_k)``; ``g``
    (≤ 0) and ``beta`` (B, S, H_v) float32.  Returns (B, S, H_v, d_v) of
    ``v``'s dtype.  ``γ``, every decay, ``A``, ``T`` and the carried state
    are float32; the other products take operands of ``v``'s dtype and
    accumulate in float32."""
    bsz, seq, groups, dk = q.shape
    heads, dv = v.shape[2:]
    per = heads // groups
    pad = -seq % chunk        # β = 0, g = 0: a step that writes nothing and
    if pad:                   # passes the state unchanged
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta))
    nc = (seq + pad) // chunk
    out = jax.vmap(_rule_of_a_sequence)((
        q.reshape(bsz, nc, chunk, groups, dk),
        k.reshape(bsz, nc, chunk, groups, dk),
        v.reshape(bsz, nc, chunk, groups, per, dv),
        g.reshape(bsz, nc, chunk, groups, per),
        beta.reshape(bsz, nc, chunk, groups, per)))
    # (B, c, i, G, r, dv) -> (B, S, H_v, dv)
    return out.reshape(bsz, nc * chunk, heads, dv)[:, :seq]


def _rule_of_a_sequence(inputs):
    """:func:`gated_delta_rule` over one sequence cut into chunks: ``q``,
    ``k`` (c, C, G, d_k), ``v`` (c, C, G, r, d_v), ``g``, ``beta`` (c, C, G,
    r) -> (c, C, G, r, d_v) of ``v``'s dtype."""
    q, k, v, g, beta = inputs
    chunk, dt = q.shape[1], v.dtype
    product = functools.partial(jnp.einsum,
                                preferred_element_type=jnp.float32)

    cum = jnp.cumsum(g, axis=1)                             # γ (c,i,G,r)
    cum_t = cum.transpose(0, 2, 3, 1)                       # (c,G,r,i)
    ones = jnp.ones((chunk, chunk), bool)
    decay = jnp.exp(jnp.where(
        jnp.tril(ones), cum_t[..., :, None] - cum_t[..., None, :],
        -jnp.inf))                                          # (c,G,r,i,j)
    # -- the triangular system a chunk: T = (I - A)^-1
    kk = product("cigd,cjgd->cgij", k, k)
    a = -jnp.where(jnp.tril(ones, -1),
                   kk[:, :, None] * decay
                   * beta.transpose(0, 2, 3, 1)[..., None], 0.0)
    t = _unit_lower_inverse(a).astype(dt)
    v_tilde = product("cgrij,cjgrp->cgrip", t,
                      (v * beta[..., None]).astype(dt))
    w = product("cgrij,cjgrd->cgrid", t,
                (k[..., None, :] * (beta * jnp.exp(cum))[..., None]
                 ).astype(dt)).astype(dt)
    # -- what the recurrence over chunks reads
    scores = (product("cigd,cjgd->cgij", q, k)[:, :, None]
              * decay).astype(dt)                           # tril by decay
    q_in = (q[..., None, :] * jnp.exp(cum)[..., None]).astype(dt)
    k_out = (k[..., None, :]
             * jnp.exp(cum[:, -1:] - cum)[..., None]).astype(dt)
    whole = jnp.exp(cum[:, -1])                             # (c,G,r)

    def carry_on(state, chunk_in):                          # (G,r,dk,dv)
        v_c, w_c, scores_c, q_c, k_c, whole_c = chunk_in
        entering = state.astype(dt)
        written = (v_c - product("grid,grdp->grip", w_c, entering)
                   ).astype(dt)                             # U
        out = product("igrd,grdp->grip", q_c, entering) \
            + product("grij,grjp->grip", scores_c, written)
        state = state * whole_c[..., None, None] \
            + product("jgrd,grjp->grdp", k_c, written)
        return state, out.astype(dt).transpose(2, 0, 1, 3)  # (i,G,r,dv)

    return jax.lax.scan(
        carry_on, jnp.zeros(v.shape[2:4] + q.shape[3:] + v.shape[4:],
                            jnp.float32),
        (v_tilde, w, scores, q_in, k_out, whole))[1]


#: what a recompute does not run again: the kernel pair's output, by name
_KEEP_RULE_OUT = jax.checkpoint_policies.save_only_these_names("gdn_rule_out")


def _l2_norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _gdn_mixer(u, lp, cfg: Qwen3NextConfig):
    """Projections and convolution over the batch; decays, normalised q and
    k, the rule and the gated norm ONE sequence at a time (``lax.map``), each
    sequence recomputed in the reverse pass — but for the kernel pair's
    output, kept by name (32 MiB a sequence in bfloat16), so the recompute
    runs no kernel: what is alive at once is one sequence's float32
    statistics and, where the kernel pair runs, its backward's sweep (the
    states the chunks entered, ``T``, ``w``, ``U``: 224 MiB a sequence at the
    published sizes); where the ``jax.numpy`` form runs — nothing of it is
    kept —, its chunk-local matrices: C·H_v floats a token each, 20.7 GiB of
    step over 8 x 4096 tokens at once (PERF.md §6, PR 34)."""
    dt = u.dtype
    bsz, seq, _ = u.shape
    groups, dk = cfg.linear_num_key_heads, cfg.linear_key_head_dim
    heads, dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
    key_w, value_w = _gdn_widths(cfg)
    qkv, z = jnp.split(u @ lp["in_proj_qkvz"].astype(dt),
                       [2 * key_w + value_w], axis=-1)
    ba = u @ lp["in_proj_ba"].astype(dt)
    qkv = jax.nn.silu(_causal_conv(qkv, lp["conv_w"].astype(dt), 0))

    def a_sequence(inputs):                     # (1, S, ·) each
        qkv, z, ba = inputs
        q, k, v = jnp.split(qkv, [key_w, 2 * key_w], axis=-1)
        b, a = jnp.split(ba.astype(jnp.float32), 2, axis=-1)
        with annotate("apex.gdn_rule"):
            beta = jax.nn.sigmoid(b)
            g = -jnp.exp(lp["A_log"]) * jax.nn.softplus(a + lp["dt_bias"])
            q = _l2_norm(q.astype(jnp.float32).reshape(1, seq, groups, dk)) \
                * dk ** -0.5
            k = _l2_norm(k.astype(jnp.float32).reshape(1, seq, groups, dk))
            o = gated_delta_rule(
                q.astype(dt), k.astype(dt), v.reshape(1, seq, heads, dv), g,
                beta, cfg.chunk_size)
        # y <- rms(o; w) ⊙ silu(z) over each head, in float32
        o = o.astype(jnp.float32)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + cfg.rms_norm_eps)
        y = o * lp["gate_norm"] * jax.nn.silu(
            z.astype(jnp.float32).reshape(1, seq, heads, dv))
        return y.astype(dt).reshape(1, seq, value_w)

    # a checkpointed loop body starts a name stack of its own: the mixer's
    # scope is entered again, or a trace would find these instructions under
    # no block
    a_sequence = jax.checkpoint(
        annotate_function(a_sequence, name="apex.gdn"),
        policy=_KEEP_RULE_OUT)
    y = jax.lax.map(a_sequence, tuple(t[:, None] for t in (qkv, z, ba)))
    return y.reshape(bsz, seq, value_w) @ lp["out_proj"].astype(dt)


def _partial_rope(x, cfg: Qwen3NextConfig):
    """Rotate-half RoPE on the first ``rotary_dim`` of every head of ``x``
    (B, S, H, hd); the rest pass."""
    rot = cfg.rotary_dim
    return jnp.concatenate(
        [_rope(x[..., :rot], cfg.rope_theta), x[..., rot:]], axis=-1)


def _attention_mixer(u, lp, cfg: Qwen3NextConfig):
    dt = u.dtype
    bsz, seq, _ = u.shape
    heads, kv_heads, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                           cfg.head_dim)
    q, gate = jnp.split(
        (u @ lp["wq"].astype(dt)).reshape(bsz, seq, heads, 2 * hd), 2,
        axis=-1)
    k = (u @ lp["wk"].astype(dt)).reshape(bsz, seq, kv_heads, hd)
    v = (u @ lp["wv"].astype(dt)).reshape(bsz, seq, kv_heads, hd)
    q = _partial_rope(_rms0(q, lp["q_norm"], cfg.rms_norm_eps), cfg)
    k = _partial_rope(_rms0(k, lp["k_norm"], cfg.rms_norm_eps), cfg)
    # (B, H, S, hd); a key/value head serves heads // kv_heads query heads
    q = (q * hd ** -0.5).astype(dt).transpose(0, 2, 1, 3)
    k = jnp.repeat(k.transpose(0, 2, 1, 3), heads // kv_heads, axis=1)
    v = jnp.repeat(v.transpose(0, 2, 1, 3), heads // kv_heads, axis=1)
    ctx = causal_attention(q, k, v, cfg.attn_impl)
    gate = jax.nn.sigmoid(gate.reshape(bsz, seq, heads * hd))
    return (ctx * gate) @ lp["wo"].astype(dt)


def _shared_expert(flat, lp):
    """``sigmoid(x w_s) · W2ˢ(silu(W1ˢ x) ⊙ W3ˢ x)``: on every token, on
    every chip alike."""
    dt = flat.dtype
    with annotate("apex.shared_expert"):
        gate, up = jnp.split(flat @ lp["shared_w13"].astype(dt), 2, axis=-1)
        out = (jax.nn.silu(gate) * up) @ lp["shared_w2"].astype(dt)
        return jax.nn.sigmoid(flat @ lp["shared_gate"].astype(dt)) * out


def _sparse_ffn(h, lp, cfg: Qwen3NextConfig):
    """``(out, routing)``: the held experts' part of the routed sum, plus the
    gated shared expert."""
    flat = h.reshape(-1, h.shape[-1])
    routed, routing = routed_experts(
        flat, lp["router"], None, lp["w13"], lp["w2"],
        top_k=cfg.num_experts_per_tok, first=cfg.experts_held[0],
        norm_topk_prob=cfg.norm_topk_prob, score="softmax")
    return (routed + _shared_expert(flat, lp)).reshape(h.shape), routing


def _block(x, lp, *, cfg: Qwen3NextConfig, kind: str):
    """One layer: ``kind`` picks the mixer.  Returns ``(y, routing)``,
    ``routed_experts``' record of the layer's sparse FFN."""
    linear = kind == "linear_attention"
    with annotate("apex.gdn" if linear else "apex.attn"):
        u = _rms0(x, lp["input_norm"], cfg.rms_norm_eps)
        x = x + (_gdn_mixer if linear else _attention_mixer)(u, lp, cfg)
    with annotate("apex.moe"):
        out, routing = _sparse_ffn(
            _rms0(x, lp["ffn_norm"], cfg.rms_norm_eps), lp, cfg)
        return x + out, routing


def _forward(params, tokens, cfg: Qwen3NextConfig):
    """``(logits, routing)``: ``routing`` stacks every layer's record
    (``ids`` (L, T, k), ``rows`` (L, held), ``dropped`` (L,), ``walks``
    (L,), ``slots`` (L,))."""
    if cfg.attn_impl not in ("default", "fast"):
        raise ValueError(
            f"attn_impl must be 'default' or 'fast', got {cfg.attn_impl!r}")
    if len(params["layers"]) != cfg.num_hidden_layers:
        raise ValueError(f"{len(params['layers'])} layers of parameters for "
                         f"{cfg.num_hidden_layers} hidden layers")
    dt = cfg.dtype
    with annotate("apex.embed"):
        x = params["embed"]["tok"].astype(dt)[tokens]
    records = []
    for kind, lp in zip(cfg.layer_types, params["layers"]):
        block = functools.partial(_block, cfg=cfg, kind=kind)
        if cfg.remat:
            block = jax.checkpoint(block, policy=_KEEP_RULE_OUT)
        x, record = block(x, lp)
        records.append(record)
    routing = jax.tree_util.tree_map(lambda *r: jnp.stack(r), *records)
    if _tel_events.active():
        # the routing meter, as models.lfm2 has it: once a forward pass
        jax.debug.callback(_tel_events.record_expert_rows, routing["rows"],
                           jnp.sum(routing["dropped"]), routing["walks"],
                           slots=routing["slots"])
    with annotate("apex.head"):
        x = _rms0(x, params["head"]["norm"], cfg.rms_norm_eps)
        return x @ params["head"]["out"].astype(dt), routing


def qwen3_next_apply(params, tokens, cfg: Qwen3NextConfig):
    """tokens (B, S) int32 -> logits (B, S, V) over the held vocabulary."""
    return _forward(params, tokens, cfg)[0]


def qwen3_next_routing(params, tokens, cfg: Qwen3NextConfig):
    """What the forward pass over ``tokens`` routed, every layer stacked:
    ``models.lfm2.lfm2_routing``'s record."""
    return _forward(params, tokens, cfg)[1]


def qwen3_next_loss(params, batch, cfg: Qwen3NextConfig):
    """Next-token cross entropy over the held vocabulary, as ``lfm2_loss``."""
    return causal_lm_loss(qwen3_next_apply(params, batch["tokens"], cfg),
                          batch, cfg.xent_impl)
