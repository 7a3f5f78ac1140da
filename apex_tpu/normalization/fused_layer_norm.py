"""FusedLayerNorm — layer norm with an explicit fused implementation.

Re-design of ``apex/normalization/fused_layer_norm.py:12-167`` (CUDA
``csrc/layer_norm_cuda_kernel.cu``).  The functional core keeps the
reference's contract: forward computes and saves (mean, invvar) residuals for
backward (``cuda_layer_norm:101``).  Two paths:

- XLA path (default): jnp math under ``jax.custom_vjp`` with the same
  residuals; XLA fuses it into ~two passes.
- Pallas path (``apex_tpu.ops.layer_norm``): blockwise kernel computing each
  row's stats in one HBM read — ``use_pallas=True`` on the module or the
  ``fused_layer_norm[_affine](..., use_pallas=True)`` functions.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp


def _norm_axes(x, normalized_shape):
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    n = len(normalized_shape)
    if tuple(x.shape[-n:]) != tuple(normalized_shape):
        raise ValueError(f"normalized_shape {normalized_shape} does not match "
                         f"trailing dims of {x.shape}")
    return tuple(range(x.ndim - n, x.ndim))


def fused_layer_norm_affine(x, weight, bias, normalized_shape, eps=1e-5,
                            *, use_pallas=False):
    """``use_pallas`` selects the Pallas kernel (ops/layer_norm.py);
    the default is the XLA custom-vjp path."""
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    normalized_shape = tuple(normalized_shape)   # hashable nondiff argnum
    if use_pallas:
        from ..ops.layer_norm import layer_norm_pallas
        return layer_norm_pallas(x, weight, bias, normalized_shape, eps)
    return _fused_layer_norm_affine_xla(x, weight, bias, normalized_shape,
                                        eps)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused_layer_norm_affine_xla(x, weight, bias, normalized_shape, eps=1e-5):
    out, _, _ = _ln_fwd(x, weight, bias, normalized_shape, eps)
    return out


def _ln_fwd(x, weight, bias, normalized_shape, eps):
    axes = _norm_axes(x, normalized_shape)
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=axes, keepdims=True)
    invvar = jax.lax.rsqrt(var + eps)
    xhat = (x32 - mean) * invvar
    out = xhat
    if weight is not None:
        out = out * weight.astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(x.dtype), mean, invvar


def _ln_fwd_vjp(x, weight, bias, normalized_shape, eps):
    out, mean, invvar = _ln_fwd(x, weight, bias, normalized_shape, eps)
    return out, (x, weight, bias, mean, invvar)


def _ln_bwd_vjp(normalized_shape, eps, res, g):
    x, weight, bias, mean, invvar = res
    axes = _norm_axes(x, normalized_shape)
    red_axes = tuple(range(x.ndim - len(axes)))  # batch axes for dw/db
    x32 = x.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    xhat = (x32 - mean) * invvar
    w32 = weight.astype(jnp.float32) if weight is not None else 1.0
    gxhat = g32 * w32
    n = np.prod([x.shape[a] for a in axes])
    # standard LN backward using saved (mean, invvar), matching
    # cuda_layer_norm_gradient (layer_norm_cuda.cpp:164)
    dx = (gxhat - jnp.mean(gxhat, axis=axes, keepdims=True)
          - xhat * jnp.mean(gxhat * xhat, axis=axes, keepdims=True)) * invvar
    dw = jnp.sum(g32 * xhat, axis=red_axes).astype(weight.dtype) \
        if weight is not None else None
    db = jnp.sum(g32, axis=red_axes).astype(bias.dtype) if bias is not None else None
    return dx.astype(x.dtype), dw, db


_fused_layer_norm_affine_xla.defvjp(_ln_fwd_vjp, _ln_bwd_vjp)


def fused_layer_norm(x, normalized_shape, eps=1e-5, *, use_pallas=False):
    """Non-affine variant (``FusedLayerNormFunction``, fused_layer_norm.py:39)."""
    return fused_layer_norm_affine(x, None, None, normalized_shape, eps,
                                   use_pallas=use_pallas)


class FusedLayerNorm:
    """Module-style wrapper mirroring ``apex.normalization.FusedLayerNorm``
    (fused_layer_norm.py:70-167).  Params are created by ``init`` and passed
    to ``apply`` — flax-style, so it nests in any pytree-based model.
    ``use_pallas=True`` selects the Pallas kernel (ops/layer_norm.py)."""

    def __init__(self, normalized_shape, eps=1e-5, elementwise_affine=True,
                 use_pallas=False):
        if isinstance(normalized_shape, int):
            normalized_shape = (normalized_shape,)
        self.normalized_shape = tuple(normalized_shape)
        self.eps = eps
        self.elementwise_affine = elementwise_affine
        self.use_pallas = use_pallas

    def init(self, rng=None):
        if not self.elementwise_affine:
            return {}
        return {"weight": jnp.ones(self.normalized_shape, jnp.float32),
                "bias": jnp.zeros(self.normalized_shape, jnp.float32)}

    def apply(self, params, x):
        if self.elementwise_affine:
            return fused_layer_norm_affine(
                x, params["weight"], params["bias"], self.normalized_shape,
                self.eps, use_pallas=self.use_pallas)
        return fused_layer_norm(x, self.normalized_shape, self.eps,
                                use_pallas=self.use_pallas)

    __call__ = apply
