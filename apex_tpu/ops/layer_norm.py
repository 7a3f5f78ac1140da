"""Pallas layer-norm kernel — the ``fused_layer_norm_cuda`` analog.

Re-design of ``csrc/layer_norm_cuda_kernel.cu`` (``cuda_layer_norm:101``
forward saving (mean, invvar), ``cuda_layer_norm_gradient:164`` backward)
for the TPU memory hierarchy:

- rows live in VMEM blocks of (block_rows, H); mean/var are computed in one
  HBM read per row (the CUDA kernel's Welford pass collapses into a VPU
  reduce over the resident block);
- forward emits (out, mean, invvar) — identical residual contract to the
  reference, so the backward never re-reduces x;
- backward kernel computes dx in one fused pass using the saved residuals;
  the (dw, db) batch reductions run as an XLA fusion over (g, xhat) — a
  column reduction XLA already does at bandwidth.

Off-TPU the kernels run in Pallas interpret mode (CPU tests); the module
entry point ``FusedLayerNorm(use_pallas=True)`` routes here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.pallas import (interpret_mode as _interpret,
                            compiler_params as _compiler_params,
                            out_vma as _out_vma, sds as _sds)

# per-block VMEM budget for the x block (fp32); leaves headroom for out +
# double buffering within ~16 MB VMEM
_BLOCK_BYTES = 2 * 1024 * 1024


def pallas_available(x=None) -> bool:
    """The kernel path works on TPU (compiled) and everywhere else via
    interpret mode; kept as a hook for callers that want to gate."""
    return True


def _block_rows(n_rows: int, h: int) -> int:
    br = max(8, _BLOCK_BYTES // max(4 * h, 1))
    br = min(br, 1024)
    br -= br % 8                       # sublane quantum
    br = max(br, 8)
    while br > 8 and n_rows % br:
        br -= 8
    return br if n_rows % br == 0 else 8


def _fwd_kernel(eps, affine, x_ref, *refs):
    if affine:
        w_ref, b_ref, o_ref, mean_ref, invvar_ref = refs
    else:
        o_ref, mean_ref, invvar_ref = refs
    x = x_ref[:].astype(jnp.float32)
    mean = jnp.mean(x, axis=1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    invvar = jax.lax.rsqrt(var + eps)
    xhat = xc * invvar
    if affine:
        out = xhat * w_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)
    else:
        out = xhat
    o_ref[:] = out.astype(o_ref.dtype)
    mean_ref[:] = mean
    invvar_ref[:] = invvar


def _bwd_kernel(affine, g_ref, x_ref, mean_ref, invvar_ref, *refs):
    if affine:
        w_ref, dx_ref = refs
    else:
        (dx_ref,) = refs
    g = g_ref[:].astype(jnp.float32)
    x = x_ref[:].astype(jnp.float32)
    invvar = invvar_ref[:]
    xhat = (x - mean_ref[:]) * invvar
    gxhat = g * w_ref[:].astype(jnp.float32) if affine else g
    m1 = jnp.mean(gxhat, axis=1, keepdims=True)
    m2 = jnp.mean(gxhat * xhat, axis=1, keepdims=True)
    dx_ref[:] = ((gxhat - m1 - xhat * m2) * invvar).astype(dx_ref.dtype)


def _row_spec(br):
    # memory_space pinned: an unpinned BlockSpec may default to HBM and
    # stream per-element (pallas guide, pitfall 1)
    return pl.BlockSpec((br, 1), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)


def _full_spec(br, h):
    return pl.BlockSpec((br, h), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)


def _param_spec(h):
    return pl.BlockSpec((1, h), lambda i: (0, 0),
                        memory_space=pltpu.VMEM)


def _pad_rows(x2d, br):
    n = x2d.shape[0]
    pad = (-n) % br
    if pad:
        x2d = jnp.pad(x2d, ((0, pad), (0, 0)))
    return x2d, n, pad


def ln_fwd_pallas(x2d, weight, bias, eps):
    """x2d (N, H) -> (out (N, H), mean (N, 1) f32, invvar (N, 1) f32)."""
    affine = weight is not None
    h = x2d.shape[1]
    x2d_p, n, _ = _pad_rows(x2d, _block_rows(max(x2d.shape[0], 8), h))
    br = _block_rows(x2d_p.shape[0], h)
    grid = x2d_p.shape[0] // br
    rows = x2d_p.shape[0]

    ins = [x2d_p]
    in_specs = [_full_spec(br, h)]
    if affine:
        ins += [weight.reshape(1, h), bias.reshape(1, h)]
        in_specs += [_param_spec(h), _param_spec(h)]

    vma = _out_vma(*ins)

    out, mean, invvar = pl.pallas_call(
        functools.partial(_fwd_kernel, eps, affine),
        grid=(grid,),
        in_specs=in_specs,
        out_specs=[_full_spec(br, h), _row_spec(br), _row_spec(br)],
        # typed with the inputs' varying mesh axes: under
        # shard_map(check_vma=True) an untyped out_shape is an error
        out_shape=[_sds((rows, h), x2d.dtype, vma),
                   _sds((rows, 1), jnp.float32, vma),
                   _sds((rows, 1), jnp.float32, vma)],
        compiler_params=_compiler_params(
            ("parallel",)),
        interpret=_interpret(),
        name="apex_layer_norm_fwd",
    )(*ins)
    return out[:n], mean[:n], invvar[:n]


def ln_bwd_pallas(g2d, x2d, mean, invvar, weight, eps):
    """dx for layer norm from saved residuals; (dw, db) are computed by the
    caller as XLA column reductions."""
    affine = weight is not None
    h = x2d.shape[1]
    br = _block_rows(max(x2d.shape[0], 8), h)
    x2d_p, n, pad = _pad_rows(x2d, br)
    g2d_p, _, _ = _pad_rows(g2d, br)
    mean_p, _, _ = _pad_rows(mean, br)
    # pad invvar with ones so padding rows can't divide by zero
    if pad:
        invvar_p = jnp.concatenate(
            [invvar, jnp.ones((pad, 1), jnp.float32)], axis=0)
    else:
        invvar_p = invvar
    br = _block_rows(x2d_p.shape[0], h)
    grid = x2d_p.shape[0] // br
    rows = x2d_p.shape[0]

    ins = [g2d_p, x2d_p, mean_p, invvar_p]
    in_specs = [_full_spec(br, h), _full_spec(br, h), _row_spec(br),
                _row_spec(br)]
    if affine:
        ins.append(weight.reshape(1, h))
        in_specs.append(_param_spec(h))

    dx = pl.pallas_call(
        functools.partial(_bwd_kernel, affine),
        grid=(grid,),
        in_specs=in_specs,
        out_specs=_full_spec(br, h),
        out_shape=_sds((rows, h), x2d.dtype, _out_vma(*ins)),
        compiler_params=_compiler_params(
            ("parallel",)),
        interpret=_interpret(),
        name="apex_layer_norm_bwd",
    )(*ins)
    return dx[:n]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def layer_norm_pallas(x, weight, bias, normalized_shape, eps=1e-5):
    """Layer norm over trailing ``normalized_shape`` dims via the Pallas
    kernel (weight/bias may be None).  Same numerics contract as
    ``fused_layer_norm_affine``."""
    out, _, _ = _ln_pallas_fwd_res(x, weight, bias, normalized_shape, eps)
    return out


def _flatten_norm(x, normalized_shape):
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    k = len(normalized_shape)
    if tuple(x.shape[-k:]) != tuple(normalized_shape):
        raise ValueError(f"normalized_shape {normalized_shape} does not match "
                         f"trailing dims of {x.shape}")
    lead = x.shape[:-k]
    h = 1
    for s in x.shape[-k:]:
        h *= s
    return x.reshape(-1, h), lead, h


def _ln_pallas_fwd_res(x, weight, bias, normalized_shape, eps):
    x2d, lead, h = _flatten_norm(x, normalized_shape)
    w = weight.reshape(-1) if weight is not None else None
    b = bias.reshape(-1) if bias is not None else None
    out, mean, invvar = ln_fwd_pallas(x2d, w, b, eps)
    return out.reshape(x.shape), mean, invvar


def _ln_pallas_vjp_fwd(x, weight, bias, normalized_shape, eps):
    out, mean, invvar = _ln_pallas_fwd_res(x, weight, bias, normalized_shape,
                                           eps)
    return out, (x, weight, bias, mean, invvar)


def _ln_pallas_vjp_bwd(normalized_shape, eps, res, g):
    x, weight, bias, mean, invvar = res
    x2d, lead, h = _flatten_norm(x, normalized_shape)
    g2d = g.reshape(-1, h)
    w = weight.reshape(-1) if weight is not None else None
    dx = ln_bwd_pallas(g2d, x2d, mean, invvar, w, eps).reshape(x.shape)
    dw = db = None
    if weight is not None or bias is not None:
        g32 = g2d.astype(jnp.float32)
        if weight is not None:
            xhat = (x2d.astype(jnp.float32) - mean) * invvar
            dw = jnp.sum(g32 * xhat, axis=0).reshape(
                weight.shape).astype(weight.dtype)
        if bias is not None:
            db = jnp.sum(g32, axis=0).reshape(bias.shape).astype(bias.dtype)
    return dx, dw, db


layer_norm_pallas.defvjp(_ln_pallas_vjp_fwd, _ln_pallas_vjp_bwd)
