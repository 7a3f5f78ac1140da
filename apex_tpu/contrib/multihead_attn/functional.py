"""Pure-jnp attention functions — the ``impl='default'`` correctness path
(reference: ``apex/contrib/multihead_attn/self_multihead_attn_func.py`` and
``encdec_multihead_attn_func.py``).  Mask semantics parity:

  - ``key_padding_mask`` (B, Sk) bool/int: nonzero = PAD (masked out), as in
    ``self_multihead_attn_func.py:60-66``;
  - ``attn_mask`` (Sq, Sk) bool: True = masked (time mask),
    ``self_multihead_attn_func.py:54-58``;
  - ``mask_additive``: the mask is float and *added* to the scores
    (``mask_softmax_dropout_func.py`` additive path);
  - softmax, then dropout on probabilities (``:68-76``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def build_bias(mask, mask_additive, *, batch, sq, sk, use_time_mask):
    """Normalize every reference mask flavour into an additive f32 bias of
    shape (1|B, 1|Sq, Sk)."""
    if mask is None:
        return jnp.zeros((1, 1, sk), jnp.float32)
    if mask_additive:
        m = mask.astype(jnp.float32)
        if m.ndim == 1:
            m = m[None, :]
        return m.reshape(m.shape[0], 1, sk)
    if use_time_mask:           # (Sq, Sk) bool, True = masked
        return jnp.where(mask.astype(bool), -jnp.inf, 0.0
                         ).astype(jnp.float32)[None]
    # key padding (B, Sk), nonzero = pad
    return jnp.where(mask.astype(bool), -jnp.inf, 0.0
                     ).astype(jnp.float32).reshape(batch, 1, sk)


def attention_core(q, k, v, bias, *, causal=False, dropout_rate=0.0,
                   dropout_rng=None, heads=1, window=None):
    """q (B, H, Sq, D) pre-scaled, k/v (B, H, Sk, D), bias (1|B, 1|Sq, Sk).
    Returns (B, H, Sq, D).  Reference math path (softmax → dropout → PV).
    ``window`` (causal only): query i sees keys j with i - window < j <= i,
    the flash kernel's band."""
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32))
    s = s + bias[:, None, :, :]
    if window is not None and not causal:
        raise ValueError("a window is a causal band: pass causal=True")
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 1)
        seen = cols <= rows
        if window is not None:
            seen = seen & (cols > rows - window)
        s = jnp.where(seen[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if dropout_rate > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, p.shape)
        p = p * keep.astype(p.dtype) / (1.0 - dropout_rate)
    out = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)
    return out


def _split_heads(x, heads):
    """(S, B, E) -> (B, H, S, D) — the reference's seqs*heads batching
    (self_multihead_attn_func.py:33-39) in mesh-friendly layout."""
    S, B, E = x.shape
    D = E // heads
    return x.reshape(S, B, heads, D).transpose(1, 2, 0, 3)


def _merge_heads(x):
    """(B, H, S, D) -> (S, B, E)."""
    B, H, S, D = x.shape
    return x.transpose(2, 0, 1, 3).reshape(S, B, H * D)


def self_attn_func(use_time_mask, is_training, heads, scale, inputs,
                   input_weights, output_weights, input_biases,
                   output_biases, mask, mask_additive, dropout_prob,
                   dropout_rng=None):
    """Signature mirror of ``SelfAttnFunc.forward``
    (self_multihead_attn_func.py:6-14).  inputs (Sq, B, E); weights in the
    reference's torch layout: input_weights (3E, E), output_weights (E, E).
    """
    S, B, E = inputs.shape
    x = inputs.reshape(S * B, E)
    lin = x @ input_weights.T.astype(x.dtype)
    if input_biases is not None:
        lin = lin + input_biases.astype(lin.dtype)
    lin = lin.reshape(S, B, 3, E)
    q, k, v = (_split_heads(lin[:, :, i, :], heads) for i in range(3))

    bias = build_bias(mask, mask_additive, batch=B, sq=S, sk=S,
                      use_time_mask=use_time_mask)

    drop = dropout_prob if is_training else 0.0
    ctx = attention_core(q * scale, k, v, bias, dropout_rate=drop,
                         dropout_rng=dropout_rng, heads=heads)
    ctx = _merge_heads(ctx)                                   # (S, B, E)
    out = ctx.reshape(S * B, E) @ output_weights.T.astype(ctx.dtype)
    if output_biases is not None:
        out = out + output_biases.astype(out.dtype)
    return out.reshape(S, B, E)


def encdec_attn_func(use_time_mask, is_training, heads, scale, inputs_q,
                     inputs_kv, input_weights_q, input_weights_kv,
                     output_weights, mask, dropout_prob, dropout_rng=None):
    """Mirror of ``EncdecAttnFunc.forward`` (encdec_multihead_attn_func.py):
    separate Q projection (E, E) and fused KV projection (2E, E)."""
    Sq, B, E = inputs_q.shape
    Sk = inputs_kv.shape[0]
    q = (inputs_q.reshape(Sq * B, E)
         @ input_weights_q.T.astype(inputs_q.dtype)).reshape(Sq, B, E)
    kv = (inputs_kv.reshape(Sk * B, E)
          @ input_weights_kv.T.astype(inputs_kv.dtype)).reshape(Sk, B, 2, E)
    qh = _split_heads(q, heads)
    kh = _split_heads(kv[:, :, 0, :], heads)
    vh = _split_heads(kv[:, :, 1, :], heads)

    bias = build_bias(mask, False, batch=B, sq=Sq, sk=Sk,
                      use_time_mask=use_time_mask)

    drop = dropout_prob if is_training else 0.0
    ctx = attention_core(qh * scale, kh, vh, bias, dropout_rate=drop,
                         dropout_rng=dropout_rng, heads=heads)
    ctx = _merge_heads(ctx)
    out = ctx.reshape(Sq * B, E) @ output_weights.T.astype(ctx.dtype)
    return out.reshape(Sq, B, E)
