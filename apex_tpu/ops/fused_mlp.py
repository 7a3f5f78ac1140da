"""Pallas fused GEMM+epilogue — the ``mlp_cuda`` perf-ceiling analog.

The reference's ``csrc/mlp_cuda.cu`` (~1.5k LoC) runs the whole MLP as
chained cuBLAS GEMMs with hand-fused bias/ReLU/sigmoid epilogue kernels in
one workspace (``mlp_fp:1056``, ``mlp_bp:1156``).  On TPU the epilogue
fusion is the kernel's job too, but the GEMM must live on the MXU: this
kernel tiles C = act(A @ B + bias) over (block_m, block_n) output tiles
with a k-loop in VMEM, applying bias + activation while the tile is still
resident — one HBM write of the activated output, no separate elementwise
pass.

Layer chaining and the backward pass stay in XLA: the bwd of a fused
epilogue GEMM is two plain GEMMs (dx, dw) plus a cheap mask — shapes XLA
already schedules at peak; recomputing the mask from the saved OUTPUT
(relu: out > 0; sigmoid: out*(1-out)) avoids saving pre-activation.

Off-TPU the kernel runs in Pallas interpret mode (CPU tests).
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


def _kernel(activation, has_bias, x_ref, w_ref, *refs):
    if has_bias:
        b_ref, o_ref, acc_ref = refs
    else:
        o_ref, acc_ref = refs
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jnp.dot(x_ref[:], w_ref[:],
                          preferred_element_type=jnp.float32)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _():
        h = acc_ref[:]
        if has_bias:
            h = h + b_ref[:].astype(jnp.float32)
        if activation == "relu":
            h = jnp.maximum(h, 0.0)
        elif activation == "sigmoid":
            h = jax.nn.sigmoid(h)
        o_ref[:] = h.astype(o_ref.dtype)


def _pad_to(x, m, axis):
    pad = (-x.shape[axis]) % m
    if pad:
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        x = jnp.pad(x, widths)
    return x


def fused_dense_act(x, w, b=None, activation="relu", *, block_m=256,
                    block_n=256, block_k=512):
    """act(x @ w + b) as one Pallas kernel.  x (M, K), w (K, N), b (N,)."""
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    xp = _pad_to(_pad_to(x, block_m, 0), block_k, 1)
    wp = _pad_to(_pad_to(w, block_k, 0), block_n, 1)
    Mp, Kp = xp.shape
    Np = wp.shape[1]
    grid = (Mp // block_m, Np // block_n, Kp // block_k)

    # memory_space pinned on every spec: an unpinned BlockSpec may default
    # to HBM (pallas guide, pitfall 1)
    in_specs = [
        pl.BlockSpec((block_m, block_k), lambda mi, ni, ki: (mi, ki),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((block_k, block_n), lambda mi, ni, ki: (ki, ni),
                     memory_space=pltpu.VMEM),
    ]
    ins = [xp, wp]
    has_bias = b is not None
    if has_bias:
        in_specs.append(pl.BlockSpec((1, block_n),
                                     lambda mi, ni, ki: (0, ni),
                                     memory_space=pltpu.VMEM))
        ins.append(_pad_to(b.reshape(1, N), block_n, 1))

    out = pl.pallas_call(
        functools.partial(_kernel, activation, has_bias),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda mi, ni, ki: (mi, ni),
                               memory_space=pltpu.VMEM),
        # typed with the inputs' varying mesh axes: under
        # shard_map(check_vma=True) an untyped out_shape is an error
        out_shape=_sds((Mp, Np), x.dtype, _out_vma(*ins)),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="apex_dense_act",
    )(*ins)
    return out[:M, :N]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def dense_act(x, w, b, activation="relu"):
    """Differentiable fused GEMM+bias+activation (Pallas fwd, XLA bwd)."""
    return fused_dense_act(x, w, b, activation)


def _dense_fwd(x, w, b, activation):
    out = fused_dense_act(x, w, b, activation)
    return out, (x, w, b, out)


def _dense_bwd(activation, res, g):
    x, w, b, out = res
    g32 = g.astype(jnp.float32)
    if activation == "relu":
        g32 = g32 * (out > 0)
    elif activation == "sigmoid":
        o32 = out.astype(jnp.float32)
        g32 = g32 * o32 * (1.0 - o32)
    gx = (g32 @ w.astype(jnp.float32).T).astype(x.dtype)
    gw = (x.astype(jnp.float32).T @ g32).astype(w.dtype)
    gb = None if b is None else jnp.sum(g32, axis=0).astype(b.dtype)
    return gx, gw, gb


dense_act.defvjp(_dense_fwd, _dense_bwd)


def mlp_pallas(x, weights, biases, activation="relu"):
    """Whole-MLP forward with fused per-layer kernels (the ``mlp_fp``
    chain); differentiable."""
    h = x
    for w, b in zip(weights, biases):
        h = dense_act(h, w, b, activation)
    return h
