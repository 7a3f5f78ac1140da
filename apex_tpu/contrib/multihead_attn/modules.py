"""``SelfMultiheadAttn`` / ``EncdecMultiheadAttn`` — functional-JAX mirrors of
``apex/contrib/multihead_attn/self_multihead_attn.py:27-180`` and
``encdec_multihead_attn.py``.

The reference modules own ``nn.Parameter``s and pick a CUDA autograd function
by ``impl``; here the module is a *config object*: ``init_params(rng)``
builds the param pytree (same tensor names/layout as the reference —
``in_proj_weight (3E, E)`` etc.), ``__call__(params, query, ...)`` applies.
``impl='fast'`` routes through the Pallas flash kernel, ``impl='default'``
through the jnp reference path; both share mask/bias normalization, so
fast-vs-default parity tests (``apex/contrib/test/multihead_attn``) carry
over directly.
"""
from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ...normalization.fused_layer_norm import fused_layer_norm_affine
from .functional import (attention_core, build_bias, _split_heads,
                         _merge_heads)
from .flash import flash_attention


def _xavier_uniform(key, shape, gain=1.0):
    fan_in, fan_out = shape[1], shape[0]
    a = gain * math.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(key, shape, jnp.float32, -a, a)


def _is_causal_mask(mask) -> bool:
    """True when a *concrete* (Sq, Sq) time mask is exactly the strict upper
    triangle — the kernel then runs its causal fast path (block skipping)
    instead of streaming an O(S^2) bias."""
    if mask is None or isinstance(mask, jax.core.Tracer):
        return False
    import numpy as np
    m = np.asarray(mask)
    if m.ndim != 2 or m.shape[0] != m.shape[1]:
        return False
    return bool((m.astype(bool) == ~np.tril(np.ones(m.shape, bool))).all())


def _rng_seed_from(rng) -> jnp.ndarray:
    """Derive an int32 kernel seed from a JAX PRNG key."""
    if rng is None:
        return jnp.zeros((), jnp.int32)
    data = jax.random.key_data(rng)
    return data.reshape(-1)[-1].astype(jnp.int32)


class SelfMultiheadAttn:
    """Self-attention over (T, B, C) inputs, reference layout and options
    (``self_multihead_attn.py:32-44``): ``bias``, ``include_norm_add``,
    ``separate_qkv_params``, ``mask_additive``.

    ``impl``:
      - ``"fast"``    — Pallas flash kernel (the ``fast_*`` CUDA exts analog)
      - ``"default"`` — jnp reference math path
      - ``"ring"``    — sequence-parallel ring attention: call inside
        ``shard_map`` with ``seq_parallel_axis`` bound; the (T, B, C) input
        is this device's contiguous sequence block.  Causality is the
        STATIC ``causal`` constructor flag (global, from block offsets);
        per-call masks and attention dropout are out of contract and raise.
      - ``"ulysses"`` — sequence-parallel via all_to_all seq<->heads
        re-sharding (num_heads must divide the axis size); same contract
        as "ring" (constructor ``causal``, no masks/dropout).

    ``backward`` (flash paths only — ``impl="fast"`` and the ulysses
    ``seq_inner_impl="fast"`` core): gradient route for the Pallas
    forward — ``"pallas"`` recompute kernels, ``"xla"`` autodiff of the
    equivalent XLA math (identical dropout mask), or ``"auto"``
    (default: ``APEX_TPU_FLASH_BWD_IMPL`` > amp's ``flash_attn_backward``
    > the Pallas kernels).
    """

    def __init__(self, embed_dim, num_heads, dropout=0.0, bias=False,
                 include_norm_add=False, impl="fast",
                 separate_qkv_params=False, mask_additive=False,
                 seq_parallel_axis="seq", causal=False,
                 seq_inner_impl="default", backward="auto"):
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.dropout = dropout
        self.head_dim = embed_dim // num_heads
        assert self.head_dim * num_heads == embed_dim, \
            "embed_dim must be divisible by num_heads"
        self.bias = bias
        self.include_norm_add = include_norm_add
        self.impl = impl
        self.scaling = self.head_dim ** -0.5
        self.separate_qkv_params = separate_qkv_params
        self.mask_additive = mask_additive
        self.seq_parallel_axis = seq_parallel_axis
        self.causal = causal        # ring/ulysses only (global causality)
        # impl="ulysses" inner core: "fast" runs the flash kernel on the
        # gathered-sequence leg (ulysses_flash_attention) — the
        # long-context composition; ring's cross-device online-softmax
        # has no separate inner core to swap
        self.seq_inner_impl = seq_inner_impl
        self.backward = backward
        if mask_additive:
            assert not include_norm_add, \
                "additive mask not supported with layer norm"
        if impl not in ("fast", "default", "ring", "ulysses"):
            raise AssertionError(f"Unsupported impl: {impl} !")
        from .flash import BACKWARD_IMPLS
        if backward not in BACKWARD_IMPLS:
            raise AssertionError(
                f"Unsupported backward: {backward!r} (one of "
                f"{BACKWARD_IMPLS})")
        if seq_inner_impl not in ("default", "fast"):
            raise AssertionError(
                f"Unsupported seq_inner_impl: {seq_inner_impl} !")
        if seq_inner_impl == "fast" and impl != "ulysses":
            raise AssertionError(
                "seq_inner_impl='fast' applies to impl='ulysses' only")

    def init_params(self, key):
        E = self.embed_dim
        ks = jax.random.split(key, 4)
        p: dict = {}
        if self.separate_qkv_params:
            p["q_weight"] = _xavier_uniform(ks[0], (E, E))
            kk = jax.random.split(ks[1])
            p["k_weight"] = _xavier_uniform(kk[0], (E, E))
            p["v_weight"] = _xavier_uniform(kk[1], (E, E))
        else:
            # gain sqrt(2): (3E, E) initialized like (E, E)
            # (self_multihead_attn.py:105-111)
            p["in_proj_weight"] = _xavier_uniform(ks[0], (3 * E, E),
                                                  gain=math.sqrt(2))
        p["out_proj_weight"] = _xavier_uniform(ks[2], (E, E))
        if self.bias:
            if self.separate_qkv_params:
                p["q_bias"] = jnp.zeros((E,), jnp.float32)
                p["k_bias"] = jnp.zeros((E,), jnp.float32)
                p["v_bias"] = jnp.zeros((E,), jnp.float32)
            else:
                p["in_proj_bias"] = jnp.zeros((3 * E,), jnp.float32)
            p["out_proj_bias"] = jnp.zeros((E,), jnp.float32)
        if self.include_norm_add:
            p["lyr_nrm_gamma_weights"] = jnp.ones((E,), jnp.float32)
            p["lyr_nrm_beta_weights"] = jnp.zeros((E,), jnp.float32)
        return p

    # -- weight assembly (separate qkv -> interleaved (3E, E),
    #    self_multihead_attn.py:133-141) ------------------------------------
    def _input_weights(self, params):
        E, H, D = self.embed_dim, self.num_heads, self.head_dim
        if not self.separate_qkv_params:
            return params["in_proj_weight"], params.get("in_proj_bias")
        w = jnp.concatenate([
            params["q_weight"].reshape(H, 1, D, E),
            params["k_weight"].reshape(H, 1, D, E),
            params["v_weight"].reshape(H, 1, D, E)], axis=1
        ).reshape(3 * E, E)
        b = None
        if self.bias:
            b = jnp.concatenate([
                params["q_bias"].reshape(H, 1, D),
                params["k_bias"].reshape(H, 1, D),
                params["v_bias"].reshape(H, 1, D)], axis=1).reshape(3 * E)
        return w, b

    def __call__(self, params, query, key=None, value=None, *,
                 key_padding_mask=None, need_weights=False, attn_mask=None,
                 is_training=True, dropout_rng=None):
        """query (T, B, C).  Returns (output, None) like the reference
        (self_multihead_attn.py:124,179)."""
        del key, value  # self-attention: q == k == v (reference ignores them)
        if key_padding_mask is not None:
            assert attn_mask is None, \
                "attn_mask and key_padding_mask should not be both defined!"
            mask, use_time_mask = key_padding_mask, False
        elif attn_mask is not None:
            assert not self.mask_additive, \
                "additive mask not supported for time mask"
            mask, use_time_mask = attn_mask, True
        else:
            mask, use_time_mask = None, False

        in_w, in_b = self._input_weights(params)
        S, B, E = query.shape
        x = query
        residual = query
        if self.include_norm_add:
            x = fused_layer_norm_affine(
                x, params["lyr_nrm_gamma_weights"].astype(x.dtype),
                params["lyr_nrm_beta_weights"].astype(x.dtype), (E,))

        lin = x.reshape(S * B, E) @ in_w.T.astype(x.dtype)
        if in_b is not None:
            lin = lin + in_b.astype(lin.dtype)
        lin = lin.reshape(S, B, 3, E)
        q = _split_heads(lin[:, :, 0, :], self.num_heads) * self.scaling
        k = _split_heads(lin[:, :, 1, :], self.num_heads)
        v = _split_heads(lin[:, :, 2, :], self.num_heads)

        # No rng -> no dropout on EVERY impl (the fast path must not
        # fall back to a fixed seed: a constant mask every step is
        # silently-degraded training, and attention_core already
        # applies none in this situation).
        drop = (self.dropout
                if is_training and dropout_rng is not None else 0.0)

        if self.impl in ("ring", "ulysses"):
            # sequence-parallel paths (dispatched before build_bias: they
            # take no bias).  Causality is the STATIC constructor flag — a
            # per-call local mask cannot express global structure under
            # sequence sharding; masks/dropout are out of contract.
            if drop > 0.0:
                raise NotImplementedError(
                    f"impl={self.impl!r} does not support attention dropout")
            if mask is not None:
                raise NotImplementedError(
                    f"impl={self.impl!r} takes causality from the "
                    "constructor causal= flag; per-call masks are "
                    "unsupported")
            from ...parallel.sequence import (ring_attention,
                                              ulysses_attention,
                                              ulysses_flash_attention)
            if self.impl == "ring":
                seq_fn = ring_attention
            elif self.seq_inner_impl == "fast":
                import functools
                seq_fn = functools.partial(ulysses_flash_attention,
                                           backward=self.backward)
            else:
                seq_fn = ulysses_attention
            ctx = seq_fn(q, k, v, axis_name=self.seq_parallel_axis,
                         causal=self.causal, scale=1.0)
            bias = None
        elif self.impl == "fast":
            bias = build_bias(mask, self.mask_additive, batch=B, sq=S, sk=S,
                              use_time_mask=use_time_mask)
            H, D = self.num_heads, self.head_dim
            causal = use_time_mask and _is_causal_mask(mask)
            if causal:
                bias = jnp.zeros((1, 1, S), jnp.float32)
            ctx = flash_attention(
                q.reshape(B * H, S, D), k.reshape(B * H, S, D),
                v.reshape(B * H, S, D),
                jax.lax.stop_gradient(jnp.nan_to_num(bias, neginf=-1e30)),
                _rng_seed_from(dropout_rng), causal, drop, H,
                self.backward)
            ctx = ctx.reshape(B, H, S, D)
        else:
            bias = build_bias(mask, self.mask_additive, batch=B, sq=S, sk=S,
                              use_time_mask=use_time_mask)
            ctx = attention_core(q, k, v, bias, dropout_rate=drop,
                                 dropout_rng=dropout_rng,
                                 heads=self.num_heads)

        out = _merge_heads(ctx).reshape(S * B, E) \
            @ params["out_proj_weight"].T.astype(ctx.dtype)
        if self.bias:
            out = out + params["out_proj_bias"].astype(out.dtype)
        out = out.reshape(S, B, E)

        if self.include_norm_add:
            if is_training and self.dropout > 0.0 and dropout_rng is not None:
                rng = jax.random.fold_in(dropout_rng, 1)
                keep = jax.random.bernoulli(rng, 1.0 - self.dropout,
                                            out.shape)
                out = out * keep.astype(out.dtype) / (1.0 - self.dropout)
            out = residual + out
        return out, None


class EncdecMultiheadAttn:
    """Encoder-decoder attention (``encdec_multihead_attn.py``): Q from the
    decoder stream, fused KV projection (2E, E) from the encoder stream."""

    def __init__(self, embed_dim, num_heads, dropout=0.0, bias=False,
                 include_norm_add=False, impl="fast", backward="auto"):
        assert not bias, \
            "additive bias not supported by the reference encdec module"
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.dropout = dropout
        self.head_dim = embed_dim // num_heads
        assert self.head_dim * num_heads == embed_dim
        self.include_norm_add = include_norm_add
        self.impl = impl
        self.scaling = self.head_dim ** -0.5
        self.backward = backward
        if impl not in ("fast", "default"):
            raise AssertionError(f"Unsupported impl: {impl} !")
        from .flash import BACKWARD_IMPLS
        if backward not in BACKWARD_IMPLS:
            raise AssertionError(
                f"Unsupported backward: {backward!r} (one of "
                f"{BACKWARD_IMPLS})")

    def init_params(self, key):
        E = self.embed_dim
        ks = jax.random.split(key, 3)
        p = {
            "in_proj_weight_q": _xavier_uniform(ks[0], (E, E)),
            "in_proj_weight_kv": _xavier_uniform(ks[1], (2 * E, E),
                                                 gain=math.sqrt(2)),
            "out_proj_weight": _xavier_uniform(ks[2], (E, E)),
        }
        if self.include_norm_add:
            p["lyr_nrm_gamma_weights"] = jnp.ones((E,), jnp.float32)
            p["lyr_nrm_beta_weights"] = jnp.zeros((E,), jnp.float32)
        return p

    def __call__(self, params, query, key, value=None, *,
                 key_padding_mask=None, need_weights=False, attn_mask=None,
                 is_training=True, dropout_rng=None):
        del value  # kv both come from ``key`` (the encoder output)
        if key_padding_mask is not None:
            mask, use_time_mask = key_padding_mask, False
        elif attn_mask is not None:
            mask, use_time_mask = attn_mask, True
        else:
            mask, use_time_mask = None, False

        Sq, B, E = query.shape
        Sk = key.shape[0]
        x = query
        residual = query
        if self.include_norm_add:
            x = fused_layer_norm_affine(
                x, params["lyr_nrm_gamma_weights"].astype(x.dtype),
                params["lyr_nrm_beta_weights"].astype(x.dtype), (E,))

        q = (x.reshape(Sq * B, E)
             @ params["in_proj_weight_q"].T.astype(x.dtype)).reshape(Sq, B, E)
        kv = (key.reshape(Sk * B, E)
              @ params["in_proj_weight_kv"].T.astype(key.dtype)
              ).reshape(Sk, B, 2, E)
        H, D = self.num_heads, self.head_dim
        qh = _split_heads(q, H) * self.scaling
        kh = _split_heads(kv[:, :, 0, :], H)
        vh = _split_heads(kv[:, :, 1, :], H)

        bias = build_bias(mask, False, batch=B, sq=Sq, sk=Sk,
                          use_time_mask=use_time_mask)
        # No rng -> no dropout on EVERY impl (the fast path must not
        # fall back to a fixed seed: a constant mask every step is
        # silently-degraded training, and attention_core already
        # applies none in this situation).
        drop = (self.dropout
                if is_training and dropout_rng is not None else 0.0)

        if self.impl == "fast":
            causal = use_time_mask and _is_causal_mask(mask)
            if causal:
                bias = jnp.zeros((1, 1, Sk), jnp.float32)
            ctx = flash_attention(
                qh.reshape(B * H, Sq, D), kh.reshape(B * H, Sk, D),
                vh.reshape(B * H, Sk, D),
                jax.lax.stop_gradient(jnp.nan_to_num(bias, neginf=-1e30)),
                _rng_seed_from(dropout_rng), causal, drop, H,
                self.backward)
            ctx = ctx.reshape(B, H, Sq, D)
        else:
            ctx = attention_core(qh, kh, vh, bias, dropout_rate=drop,
                                 dropout_rng=dropout_rng, heads=H)

        out = _merge_heads(ctx).reshape(Sq * B, E) \
            @ params["out_proj_weight"].T.astype(ctx.dtype)
        out = out.reshape(Sq, B, E)

        if self.include_norm_add:
            if is_training and self.dropout > 0.0 and dropout_rng is not None:
                rng = jax.random.fold_in(dropout_rng, 1)
                keep = jax.random.bernoulli(rng, 1.0 - self.dropout,
                                            out.shape)
                out = out * keep.astype(out.dtype) / (1.0 - self.dropout)
            out = residual + out
        return out, None
