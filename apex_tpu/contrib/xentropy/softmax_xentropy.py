"""Fused label-smoothing softmax cross-entropy.

TPU re-design of ``apex/contrib/csrc/xentropy/xentropy_kernel.cu`` (~730 LoC)
behind the ``SoftmaxCrossEntropyLoss`` API of
``apex/contrib/xentropy/softmax_xentropy.py:6-32``:

    loss_i = (1 - smoothing) * (lse_i - x_i[label_i])
             + smoothing * (lse_i - mean_j x_i[j])        (0 where padding)

The forward saves only ``max_log_sum_exp`` (here: the log-sum-exp, carrying
the same information) for the backward — the defining trick of the CUDA
kernel — so the bwd needs no re-reduction:

    dx_i = g_i * (softmax(x_i) - (1-s) * onehot(label_i) - s / H)

Two interchangeable implementations:
  - ``impl="xla"``: jnp expression; XLA fuses it into ~two passes.
  - ``impl="pallas"``: single-pass blockwise kernel with online max/sum
    rescaling (flash-softmax style) — one read of the logits for loss *and*
    lse, the perf-ceiling version on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


from ...utils.pallas import (interpret_mode as _interpret,
                             compiler_params as _compiler_params,
                             out_vma as _out_vma, sds as _sds)


# --------------------------------------------------------------------------
# reference (XLA) path
# --------------------------------------------------------------------------

def _xent_fwd_xla(logits, labels, smoothing):
    x = logits.astype(jnp.float32)
    m = jnp.max(x, axis=-1)
    lse = m + jnp.log(jnp.sum(jnp.exp(x - m[:, None]), axis=-1))
    gold = jnp.take_along_axis(x, labels[:, None], axis=-1)[:, 0]
    nll = lse - gold
    smooth = lse - jnp.mean(x, axis=-1)
    return (1.0 - smoothing) * nll + smoothing * smooth, lse


# --------------------------------------------------------------------------
# Pallas single-pass path
# --------------------------------------------------------------------------

def _fwd_kernel(labels_ref, x_ref, loss_ref, lse_ref,
                m_ref, s_ref, xsum_ref, gold_ref, *, bh, h_total, smoothing):
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        s_ref[:] = jnp.zeros_like(s_ref)
        xsum_ref[:] = jnp.zeros_like(xsum_ref)
        gold_ref[:] = jnp.zeros_like(gold_ref)

    x = x_ref[:].astype(jnp.float32)                     # (bn, bh)
    col = j * bh + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    valid = col < h_total
    x = jnp.where(valid, x, NEG_INF)

    # online max/sum rescale (the xentropy kernel's single-pass reduction)
    m_old = m_ref[:, 0]
    m_new = jnp.maximum(m_old, jnp.max(x, axis=1))
    scale = jnp.exp(m_old - m_new)
    s_ref[:, 0] = s_ref[:, 0] * scale + jnp.sum(
        jnp.exp(x - m_new[:, None]), axis=1)
    m_ref[:, 0] = m_new

    xsum_ref[:, 0] += jnp.sum(jnp.where(valid, x, 0.0), axis=1)
    hit = col == labels_ref[:]                           # (bn, bh) vs (bn, 1)
    gold_ref[:, 0] += jnp.sum(jnp.where(hit, x, 0.0), axis=1)

    @pl.when(j == nj - 1)
    def _():
        lse = m_ref[:, 0] + jnp.log(s_ref[:, 0])
        nll = lse - gold_ref[:, 0]
        smooth = lse - xsum_ref[:, 0] / h_total
        loss_ref[:, 0] = (1.0 - smoothing) * nll + smoothing * smooth
        lse_ref[:, 0] = lse


def _xent_fwd_pallas(logits, labels, smoothing, bn=256, bh=512):
    # No host-side padding copy: ragged boundary blocks are legal (Pallas
    # clips them); garbage in out-of-range columns is masked by the
    # ``col < h_total`` test in the kernel, garbage rows fall outside [:n].
    n, h = logits.shape
    bn = min(bn, max(8, (n + 7) // 8 * 8))
    lab = labels.astype(jnp.int32)[:, None]

    loss, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, bh=bh, h_total=h,
                          smoothing=float(smoothing)),
        grid=((n + bn - 1) // bn, (h + bh - 1) // bh),
        in_specs=[
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, bh), lambda i, j: (i, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        # typed with the inputs' varying mesh axes: under
        # shard_map(check_vma=True) an untyped out_shape is an error
        out_shape=[_sds((n, 1), jnp.float32, _out_vma(lab, logits))] * 2,
        scratch_shapes=[pltpu.VMEM((bn, 1), jnp.float32)] * 4,
        # rows (i) are independent; the vocab walk (j) accumulates into
        # scratch sequentially.  Same declaration the measured-fast
        # elementwise kernels carry (PERF_NOTES §2)
        compiler_params=_compiler_params(
            ("parallel", "arbitrary")),
        interpret=_interpret(),
        name="apex_xentropy_fwd",
    )(lab, logits)
    return loss[:, 0], lse[:, 0]


# --------------------------------------------------------------------------
# public API
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def softmax_xentropy_loss(logits, labels, smoothing=0.0, padding_idx=0,
                          half_to_float=False, impl="auto"):
    """Per-row label-smoothing cross entropy; rows whose label equals
    ``padding_idx`` contribute 0 (softmax_xentropy.py:9 ``masked_fill_``).

    logits (N, H) float; labels (N,) int.  Returns (N,) float32 losses
    (``half_to_float`` is implicit: reductions are always fp32, matching the
    reference's ``half_to_float=True`` recommended mode).
    """
    loss, _ = _fwd(logits, labels, smoothing, impl)
    return jnp.where(labels == padding_idx, 0.0, loss)


def _fwd(logits, labels, smoothing, impl):
    if impl == "auto":
        # APEX_TPU_XENT_IMPL overrides the auto choice (the hatch for a
        # first-contact Mosaic failure); else pallas on TPU
        import os
        impl = (os.environ.get("APEX_TPU_XENT_IMPL", "")
                or ("pallas" if jax.default_backend() == "tpu" else "xla"))
    if impl == "pallas":
        return _xent_fwd_pallas(logits, labels, smoothing)
    return _xent_fwd_xla(logits, labels, smoothing)


def _vjp_fwd(logits, labels, smoothing, padding_idx, half_to_float, impl):
    loss, lse = _fwd(logits, labels, smoothing, impl)
    loss = jnp.where(labels == padding_idx, 0.0, loss)
    return loss, (logits, labels, lse)


def _vjp_bwd(smoothing, padding_idx, half_to_float, impl, res, g):
    logits, labels, lse = res
    x = logits.astype(jnp.float32)
    h = x.shape[-1]
    g = jnp.where(labels == padding_idx, 0.0, g.astype(jnp.float32))
    probs = jnp.exp(x - lse[:, None])
    onehot = (jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
              == labels[:, None].astype(jnp.int32))
    target = (1.0 - smoothing) * onehot.astype(jnp.float32) + smoothing / h
    grad = g[:, None] * (probs - target)
    out_dtype = jnp.float32 if half_to_float else logits.dtype
    return grad.astype(out_dtype), None


softmax_xentropy_loss.defvjp(_vjp_fwd, _vjp_bwd)


class SoftmaxCrossEntropyLoss:
    """API mirror of the reference autograd Function
    (``softmax_xentropy.py:4-28``): ``SoftmaxCrossEntropyLoss.apply(...)``."""

    @staticmethod
    def apply(logits, labels, smoothing=0.0, padding_idx=0,
              half_to_float=False, impl="auto"):
        return softmax_xentropy_loss(logits, labels, smoothing, padding_idx,
                                     half_to_float, impl)
