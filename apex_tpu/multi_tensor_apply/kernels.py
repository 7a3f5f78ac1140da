"""Pallas TPU kernels for the multi-tensor engine.

These are the TPU equivalents of the ``amp_C`` kernel family
(``csrc/amp_C_frontend.cpp:1-136`` + ``multi_tensor_*.cu``): fused elementwise
updates over *flat packed buffers* (see ``flattener.py``) instead of pointer
tables.  Each kernel views the flat (total,) buffer as (rows, 128) and walks a
1-D grid of chunks; per-chunk blocks live in VMEM and hyperparameter scalars
ride in SMEM.

Tuned to the on-chip measurements in PERF_NOTES.md §2 (round 3, v5e):

- grid steps are declared ``parallel`` — the round-2 sequential-grid
  SMEM overflow-flag accumulation (init at step 0 + read-modify-write
  each step, mirroring ``multi_tensor_apply.cuh``'s ``noop_flag``)
  forced ``arbitrary`` semantics and serialized the pipeline (~10x
  slower).  The overflow flag is now ONE XLA ``isfinite`` reduce over
  the kernel's output — non-finite inputs propagate to the output (and
  a low-precision cast overflow shows up there too), so checking the
  output preserves the reference's input-or-output flag semantics.
- no ``input_output_aliases``: in-kernel donation measured ~1.6x SLOWER
  on TPU — the opposite of the CUDA in-place intuition.  The kernels
  therefore write fresh output buffers; memory-bound callers (the ZeRO
  optimizers with shard sizes near HBM capacity) recover the in-place
  footprint by donating the optimizer state at THEIR jit boundary
  (``jax.jit(step, donate_argnums=...)``) — buffer reuse then happens in
  XLA's allocator, outside the kernel's pipeline, without the aliasing
  penalty.  Our own jit sites (``__graft_entry__._dryrun_zero_leg``,
  the 2-process ZeRO worker) do this.
- ``multi_tensor_l2norm`` keeps its sequential single-cell accumulation:
  it measured FASTER than the XLA reduce (1.17 ms vs 1.65 ms on 1.34 GB) —
  on a buffer that is ALREADY flat.  Getting a tree of tiled leaves into
  that layout is what costs (``pad_bitcast_fusion`` 4.1 ms + the kernel's
  1.9 ms a BERT-large step, PERF.md section 5): the per-leaf step reduces
  each gradient leaf where it lies instead.

On non-TPU backends (CPU tests) kernels run in Pallas interpret mode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flattener import LANE, DEFAULT_CHUNK

_BR = DEFAULT_CHUNK // LANE  # block rows per grid step


from ..utils.pallas import interpret_mode as _interpret, \
    compiler_params as _compiler_params, out_vma as _out_vma, \
    sds as _sds, align_vma as _align_vma


def _block_rows(total: int) -> int:
    """Largest block (<= DEFAULT_CHUNK) that evenly divides the buffer, so
    kernels work for any TreeFlattener chunk size, not just the default."""
    rows = total // LANE
    br = min(_BR, rows)
    while br > 1 and rows % br:
        br -= 1
    return max(br, 1)


def _grid_call(name, kernel, flats, out_dtypes, *, scalars=None,
               block_rows=None):
    """Run ``kernel`` (named ``name`` in lowered text and traces) over
    1-D flat buffers chunked as (block_rows, LANE) with ``parallel`` grid
    semantics (PERF_NOTES §2).

    flats: list of (total,) arrays (equal length).  scalars: optional (1, S)
    f32 array placed in SMEM.
    """
    total = flats[0].shape[0]
    if block_rows is None:
        block_rows = _block_rows(total)
    assert total % (block_rows * LANE) == 0, (total, block_rows)
    rows = total // LANE
    grid = rows // block_rows

    views = [f.reshape(rows, LANE) for f in flats]
    in_specs = []
    ins = []
    if scalars is not None:
        in_specs.append(pl.BlockSpec(
            scalars.shape, lambda i: (0, 0), memory_space=pltpu.SMEM))
        ins.append(scalars)
    for v in views:
        in_specs.append(pl.BlockSpec(
            (block_rows, LANE), lambda i: (i, 0), memory_space=pltpu.VMEM))
        ins.append(v)

    ins, vma = _align_vma(ins)
    out_shape = [_sds((rows, LANE), d, vma) for d in out_dtypes]
    out_specs = [pl.BlockSpec((block_rows, LANE), lambda i: (i, 0),
                              memory_space=pltpu.VMEM)
                 for _ in out_dtypes]

    outs = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=_compiler_params(
            ("parallel",)),
        interpret=_interpret(),
        name=name,
    )(*ins)
    if not isinstance(outs, (list, tuple)):
        outs = (outs,)
    return [o.reshape(total) for o in outs]


def _overflow_flag(flat_out) -> jax.Array:
    """i32 0/1 overflow flag — ONE XLA reduce over the kernel output,
    replacing the serializing in-kernel SMEM flag (PERF_NOTES §2)."""
    return jnp.logical_not(jnp.all(jnp.isfinite(
        flat_out.astype(jnp.float32)))).astype(jnp.int32)


# --------------------------------------------------------------------------
# multi_tensor_scale (multi_tensor_scale_kernel.cu): out = in * scale,
# overflow flag on non-finite input/output.
# --------------------------------------------------------------------------

def multi_tensor_scale(flat_in, scale, out_dtype=None):
    out_dtype = jnp.dtype(out_dtype or flat_in.dtype)
    scalars = jnp.reshape(jnp.asarray(scale, jnp.float32), (1, 1))

    def kernel(s_ref, x_ref, o_ref):
        y = x_ref[:].astype(jnp.float32) * s_ref[0, 0]
        o_ref[:] = y.astype(o_ref.dtype)

    (out,) = _grid_call("apex_mt_scale", kernel, [flat_in], [out_dtype],
                        scalars=scalars)
    return out, _overflow_flag(out)


# --------------------------------------------------------------------------
# multi_tensor_axpby (multi_tensor_axpby_kernel.cu): out = a*x + b*y
# --------------------------------------------------------------------------

def multi_tensor_axpby(flat_x, flat_y, a, b, out_dtype=None):
    out_dtype = jnp.dtype(out_dtype or flat_x.dtype)
    scalars = jnp.stack([jnp.asarray(a, jnp.float32),
                         jnp.asarray(b, jnp.float32)]).reshape(1, 2)

    def kernel(s_ref, x_ref, y_ref, o_ref):
        r = (x_ref[:].astype(jnp.float32) * s_ref[0, 0]
             + y_ref[:].astype(jnp.float32) * s_ref[0, 1])
        o_ref[:] = r.astype(o_ref.dtype)

    (out,) = _grid_call("apex_mt_axpby", kernel, [flat_x, flat_y],
                        [out_dtype], scalars=scalars)
    return out, _overflow_flag(out)


# --------------------------------------------------------------------------
# multi_tensor_l2norm (multi_tensor_l2norm_kernel.cu): the CUDA two-stage
# reduction collapses into sequential accumulation over the TPU grid.
# Kept sequential on purpose: measured FASTER than the XLA reduce
# (PERF_NOTES §2: 1.17 ms vs 1.65 ms over 1.34 GB).
# --------------------------------------------------------------------------

def multi_tensor_l2norm(flat_in):
    total = flat_in.shape[0]
    if total == 0:
        return jnp.zeros((), jnp.float32)
    rows = total // LANE
    br = _block_rows(total)
    grid = rows // br

    # TPU grid steps run sequentially under `arbitrary` semantics, so the
    # sum accumulates into one (1, 1) SMEM cell (the two-stage partials of
    # multi_tensor_l2norm_kernel.cu:197 collapse into sequential
    # accumulation).
    def kernel(x_ref, acc_ref):
        @pl.when(pl.program_id(0) == 0)
        def _():
            acc_ref[0, 0] = 0.0

        x = x_ref[:].astype(jnp.float32)
        acc_ref[0, 0] += jnp.sum(x * x)

    sumsq = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((br, LANE), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((1, 1), lambda i: (0, 0),
                               memory_space=pltpu.SMEM),
        out_shape=_sds((1, 1), jnp.float32, _out_vma(flat_in)),
        compiler_params=_compiler_params(
            ("arbitrary",)),
        interpret=_interpret(),
        name="apex_l2norm",
    )(flat_in.reshape(rows, LANE))
    return jnp.sqrt(sumsq[0, 0])


# --------------------------------------------------------------------------
# multi_tensor_adam (multi_tensor_adam.cu AdamFunctor): Adam / AdamW on flat
# master buffers, optional low-precision model-copy output (the reference's
# fp16 output-params mode, fused_adam_cuda.cpp:79-85).
# scalars layout: [lr, beta1, beta2, eps, wd, rc1, rc2, inv_scale]
#   rc1 = 1/(1-beta1^t), rc2 = 1/(1-beta2^t)
# --------------------------------------------------------------------------

def fused_adam_flat(flat_g, flat_p, flat_m, flat_v, scalars, *,
                    adam_w_mode=True, model_dtype=None):
    out_dtypes = [jnp.float32, jnp.float32, jnp.float32]
    if model_dtype is not None:
        out_dtypes.append(jnp.dtype(model_dtype))

    def kernel(s_ref, g_ref, p_ref, m_ref, v_ref, po_ref, mo_ref, vo_ref,
               *maybe_model):
        lr, b1, b2, eps = s_ref[0, 0], s_ref[0, 1], s_ref[0, 2], s_ref[0, 3]
        wd, rc1, rc2, inv_scale = (s_ref[0, 4], s_ref[0, 5], s_ref[0, 6],
                                   s_ref[0, 7])
        g = g_ref[:].astype(jnp.float32) * inv_scale
        p = p_ref[:]
        if not adam_w_mode:
            g = g + wd * p          # classic L2 (ADAM_MODE_0)
        m = b1 * m_ref[:] + (1.0 - b1) * g
        v = b2 * v_ref[:] + (1.0 - b2) * g * g
        update = (m * rc1) / (jnp.sqrt(v * rc2) + eps)
        if adam_w_mode:
            update = update + wd * p  # decoupled decay (ADAM_MODE_1)
        p_new = p - lr * update
        po_ref[:] = p_new
        mo_ref[:] = m
        vo_ref[:] = v
        if maybe_model:
            maybe_model[0][:] = p_new.astype(maybe_model[0].dtype)

    return _grid_call("apex_adam_flat", kernel,
                      [flat_g, flat_p, flat_m, flat_v], out_dtypes,
                      scalars=scalars)  # [p, m, v] (+ model copy)


# --------------------------------------------------------------------------
# multi_tensor_lamb stage 1 (multi_tensor_lamb.cu LAMBStage1Functor): m/v
# update + unscaled LAMB step direction, with global-grad-norm clipping.
# Stage 2 (per-tensor trust ratio) runs as XLA segment ops in the optimizer —
# the per-tensor norms come from TreeFlattener.per_tensor_sumsq.
# scalars: [beta1, beta2, eps, wd, rc1, rc2, clip, inv_scale, beta3]
#   clip = 1.0 / max(1, global_norm/max_grad_norm)
#   beta3 = 1-beta1 when grad_averaging else 1.0 (multi_tensor_lamb.cu:41
#   takes beta3 as an explicit kernel argument; so do we)
# --------------------------------------------------------------------------

def fused_lamb_stage1_flat(flat_g, flat_p, flat_m, flat_v, scalars, *,
                           adam_w_mode=True):
    def kernel(s_ref, g_ref, p_ref, m_ref, v_ref, u_ref, mo_ref, vo_ref):
        b1, b2, eps, wd = s_ref[0, 0], s_ref[0, 1], s_ref[0, 2], s_ref[0, 3]
        rc1, rc2, clip, inv_scale = (s_ref[0, 4], s_ref[0, 5], s_ref[0, 6],
                                     s_ref[0, 7])
        beta3 = s_ref[0, 8]
        g = g_ref[:].astype(jnp.float32) * inv_scale * clip
        p = p_ref[:]
        if not adam_w_mode:
            g = g + wd * p
        m = b1 * m_ref[:] + beta3 * g
        v = b2 * v_ref[:] + (1.0 - b2) * g * g
        u = (m * rc1) / (jnp.sqrt(v * rc2) + eps)
        if adam_w_mode:
            u = u + wd * p
        u_ref[:] = u
        mo_ref[:] = m
        vo_ref[:] = v

    return _grid_call("apex_lamb_stage1_flat", kernel,
                      [flat_g, flat_p, flat_m, flat_v],
                      [jnp.float32, jnp.float32, jnp.float32],
                      scalars=scalars)  # [update, m, v]


# NOTE: the SGD/Adagrad Pallas kernels were retired in round 3 — the fused
# optimizers now do their elementwise math as XLA fusions over the
# permanently-flat state, which measured faster than any Pallas elementwise
# variant on TPU (PERF_NOTES.md §2).  The reason, found by PR 37 on the chip
# (PERF.md section 6), is layout, not arithmetic: an XLA fusion reads each
# operand in the tiling it already has, while a kernel over a flat (rows, 128)
# view first needs every tiled 2-D leaf relaid into it — at BERT-large that
# packing and unpacking was half of the flat update's 100 B a parameter, and
# a replicated update now runs leaf by leaf and keeps no flat buffer.  The
# Adam/LAMB-stage1 kernels above remain in use by the sharded ZeRO optimizers
# (contrib/optimizers), where a replica's shard is a slice of one buffer.
