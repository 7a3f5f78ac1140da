"""Flash-attention-style fused attention kernels (Pallas TPU).

TPU re-design of the reference's monolithic MHA CUDA extensions
(``apex/contrib/csrc/multihead_attn/*`` — QKV GEMM → strided-batched QK^T →
fused (masked) softmax+dropout → PV, ~6.5k LoC CUDA).  The CUDA code
materializes the (Sq, Sk) score matrix in HBM; on TPU we go blockwise with
online-softmax rescaling so scores never leave VMEM (O(S) memory), which is
both the perf win and what makes a later ring/sequence-parallel variant an
extension rather than a rewrite (SURVEY §5.7).

Semantics parity with the CUDA kernels:
  - softmax over keys, THEN dropout on the probabilities (the denominator
    sees no dropout) — ``self_multihead_attn_func.py:72-76``;
  - dropout mask regeneration in backward from the same counter-based seeds
    (the CUDA side saves the mask; the TPU side re-derives it — cheaper than
    an (Sq, Sk) HBM roundtrip);
  - additive bias supports key-padding masks (B, 1, Sk), additive masks, and
    full (1|B, Sq, Sk) score masks; ``causal`` covers the time-mask path.

forward  : out, lse   (lse = log-sum-exp per query row, the saved residual)
backward : recompute-based (flash bwd).  One algorithm whose tile and
    residency are functions of the shape the call has (``_flash_bwd`` asks
    in this order): P and the dropout mask are recomputed ONCE per tile
    and feed dq, dk and dv.
      - whole_key: where the keys of a head fit ONE tile
        (``_whole_key_blocks``; nk = 1: S 512 at D 64 runs ONE 512x512
        tile a head) the step's ``ds @ k`` IS that q block's dq and the
        kernel writes it itself, in ``q.dtype``;
      - resident: where they do not, but a head's K, V and its f32 dk / dv
        accumulators fit VMEM (``_resident_blocks``; S 4096 at D 64), one
        kernel keeps them there for the head's q sweep and walks the keys
        in pieces: dq accumulates over the pieces and leaves once a q
        tile, dk / dv once a head — nothing partial goes to HBM, and a
        causal walk stops at the diagonal;
      - partials: beyond that the grid is (BH, nk, nq) over 128x128 tiles
        and dq leaves the kernel as per-k-block f32 partials
        (BH, nk, Sq, D) summed by XLA (the splash-attention fused-backward
        layout) — O(Sk/bk * Sq) per batch-head, so only under a byte cap
        (``_resolve_fuse``);
      - split: above the cap (or forced), one kernel for dq (grid over q
        blocks) and one for dk/dv (grid over k blocks), each with its OWN
        tunable block sizes (their VMEM footprints differ; see
        ``vmem_estimate``).
    The whole Pallas backward can also be swapped for the XLA math path via
    ``backward="pallas"|"xla"|"auto"`` on :func:`flash_attention` — ``auto``
    is the Pallas kernels unless ``APEX_TPU_FLASH_BWD_IMPL`` or amp's
    ``flash_attn_backward`` says otherwise (:func:`_resolve_backward`).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...pyprof import annotate
from ...telemetry import events as _tel_events

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024
# The recompute-backward tile where one step cannot hold a head's keys
# (long sequences), for the split dq / dkv kernels, and for callers that
# give ``_clamp_blocks`` no shape: the 128-block regime of jax's own pallas
# flash kernel.  Where the keys fit, the fused backward's built-in is
# ``_whole_key_blocks`` instead: a 128x128 grid at S 512 ran 22.1 ms a call
# at b112 (6.9 % of its roofline; ledger, PR 24) — per-step overhead, not
# work — where one 512x512 tile a head runs 4.4 ms (PERF.md section 6, PR 26).
DEFAULT_BWD_BLOCK_Q = 128
DEFAULT_BWD_BLOCK_K = 128
# q rows of a whole-key tile: as many as fit the budget, between these.
# Under 128 rows a step's five products no longer fill the MXU's rows and
# today's 128x128 paths take over.
_WHOLE_KEY_MAX_BQ = 512
_WHOLE_KEY_MIN_BQ = 128
# Where a head's keys do not fit ONE tile they may still fit VMEM whole
# (S 4096 x D 64 in bf16: K and V 512 KiB each): the resident kernel keeps
# them there for the head and walks them in pieces of at most this many
# keys, so a tile is again 512 x 512 and not a grid step each of 128 x 128.
_RESIDENT_MAX_BK = 512
# That kernel tells Mosaic what it needs (``vmem_limit_bytes``) instead of
# living inside half the 16 MiB scoped default as the others do, so its
# budget is this multiple of ``_vmem_budget()``: 32 MiB, a quarter of a v5e
# core's VMEM.  The limit it states is the budget and a quarter more, for
# what the model does not count (the dropout hash's tiles).
_RESIDENT_VMEM_FACTOR = 4.0
NEG_INF = -1e30

# Fused-backward dq-partials buffer cap (HBM bytes): the fused kernel emits
# dq as (BH, ceil(Sk/bk), Sq, D) f32 partials — quadratic in sequence — so
# past this budget the split kernels run instead.  APEX_TPU_FLASH_BWD_FUSE
# (0/1) forces the strategy; APEX_TPU_FLASH_BWD_FUSE_MB moves the cap.
_FUSE_BUFFER_CAP_MB = 1024.0

# Process-level default for flash_attention(backward="auto"), set by
# apex_tpu.amp.initialize (Properties.flash_attn_backward) — sits between
# the env override and the built-in in _resolve_backward's chain.
_DEFAULT_BACKWARD = "auto"

BACKWARD_IMPLS = ("auto", "pallas", "xla")


def set_default_backward(value: str) -> None:
    """Set the process-level default consulted by ``backward="auto"``
    (``"auto"`` defers on to the built-in)."""
    global _DEFAULT_BACKWARD
    if value not in BACKWARD_IMPLS:
        raise ValueError(f"backward must be one of {BACKWARD_IMPLS}, "
                         f"got {value!r}")
    _DEFAULT_BACKWARD = value


def _resolve_backward(backward: str) -> str:
    """Collapse ``backward`` to a concrete impl at trace time.

    Precedence: explicit "pallas"/"xla" argument > APEX_TPU_FLASH_BWD_IMPL
    env > amp-config default (:func:`set_default_backward`) > "pallas"
    built-in."""
    import os
    if backward not in BACKWARD_IMPLS:
        raise ValueError(f"backward must be one of {BACKWARD_IMPLS}, "
                         f"got {backward!r}")
    if backward != "auto":
        return backward
    env = os.environ.get("APEX_TPU_FLASH_BWD_IMPL")
    if env in ("pallas", "xla"):
        return env
    if _DEFAULT_BACKWARD != "auto":
        return _DEFAULT_BACKWARD
    return "pallas"

# Mosaic fails at compile time (or spills) when a step's blocks exceed VMEM
# (~16 MiB/core on v4/v5e-class chips); budget half of it so the pipeline
# can double-buffer.  Overridable for tuning on real hardware without code
# edits: APEX_TPU_FLASH_BLOCK_Q / _K pin the default block sizes (explicit
# caller-passed sizes always win), APEX_TPU_FLASH_VMEM_MB moves the budget.
_VMEM_BUDGET_MB = 8.0


def _vmem_budget() -> float:
    import os
    return float(os.environ.get("APEX_TPU_FLASH_VMEM_MB",
                                _VMEM_BUDGET_MB)) * 2 ** 20


def _whole_key_blocks(sq, sk, D, esz, bias_per_q, causal):
    """The fused backward's built-in tile, from the shape the call has:
    ``(bq, bk)`` with ``bk`` covering ALL keys of a head (nk = 1, so dq is
    finished inside the kernel) and as many q rows as the VMEM budget
    holds, or None where the keys do not fit beside ``_WHOLE_KEY_MIN_BQ``
    rows (long sequences: the 128x128 paths run there).

    ``causal`` is an input of the rule although it changes no answer today:
    at nk = 1 nothing of a causal tile is skipped, and on the v5e at
    S 512 / BH 512 the whole-key tile still took 1.24 ms a call against
    5.90 ms + 0.71 ms of partials' sum for the 128x128 grid with its
    skipping (256x512: 1.42 ms; PERF.md section 6, PR 26)."""
    del causal
    bk = max(128, -(-sk // 128) * 128)
    bq = min(_WHOLE_KEY_MAX_BQ, max(8, -(-sq // 8) * 8))
    floor = min(bq, _WHOLE_KEY_MIN_BQ)
    budget = _vmem_budget()
    while vmem_estimate(bq, bk, D, esz, bias_per_q, "fused") > budget:
        if bq // 2 < floor:
            return None
        bq = max(8, (bq // 2 // 8) * 8)
    return bq, bk


def _resident_budget() -> float:
    return _RESIDENT_VMEM_FACTOR * _vmem_budget()


def _resident_blocks(sq, sk, D, esz, bias_per_q):
    """The resident backward's tile, from the shape the call has: ``(bq,
    bk)`` where a head's K, V and their f32 dk / dv accumulators fit VMEM
    beside a tile of at least ``_WHOLE_KEY_MIN_BQ`` q rows by ``bk`` keys,
    or None where they do not (very long sequences, or a per-query bias
    whose (bq, Sk) block is too large: the 128x128 paths run there).  The
    q rows shrink first, as in :func:`_whole_key_blocks`, then the piece."""
    budget = _resident_budget()
    top = min(_WHOLE_KEY_MAX_BQ, max(8, -(-sq // 8) * 8))
    floor = min(top, _WHOLE_KEY_MIN_BQ)
    bk = min(_RESIDENT_MAX_BK, max(128, -(-sk // 128) * 128))
    while bk >= 128:
        bq = top
        while bq >= floor:
            if vmem_estimate(bq, bk, D, esz, bias_per_q, "resident",
                             sk=sk) <= budget:
                return bq, bk
            bq = (bq // 2 // 8) * 8
        bk = (bk // 2 // 128) * 128
    return None


def _chosen_blocks(bq, bk, bwd):
    """``(bq, bk)`` as somebody CHOSE them for the kernel ``bwd`` names —
    argument > env pin — with None where nobody did and the built-in end
    of the chain decides.

    The backward kernels have their own optimum (fwd blocks that stream
    k/v differ from bwd blocks that also stream do and accumulate dk/dv),
    so bwd reads ONLY the bwd env pins: per-kernel (``bwd="dq"|"dkv"|
    "fused"``; fused rides the dkv names, it runs on the dkv grid), then
    the shared ``APEX_TPU_FLASH_BWD_BLOCK_Q`` / ``_K``."""
    import os
    if not bwd:
        pins_q, pins_k = ["APEX_TPU_FLASH_BLOCK_Q"], ["APEX_TPU_FLASH_BLOCK_K"]
    else:
        pins_q = ["APEX_TPU_FLASH_BWD_BLOCK_Q"]
        pins_k = ["APEX_TPU_FLASH_BWD_BLOCK_K"]
        if bwd in ("dq", "dkv", "fused"):
            kern = "DQ" if bwd == "dq" else "DKV"
            pins_q.insert(0, f"APEX_TPU_FLASH_BWD_{kern}_BLOCK_Q")
            pins_k.insert(0, f"APEX_TPU_FLASH_BWD_{kern}_BLOCK_K")

    def _pick(pins):
        for env in pins:
            if env in os.environ:
                return int(os.environ[env])
        return None

    return (_pick(pins_q) if bq is None else bq,
            _pick(pins_k) if bk is None else bk)


def _clamp_blocks(bq, bk, D, esz, bias_per_q, bwd=False, sq=None, sk=None,
                  causal=False):
    """Shrink (bq, bk) until the kernel's per-step VMEM estimate fits the
    budget.  Only the built-in defaults are budget-clamped; a value
    somebody chose (argument or env pin, :func:`_chosen_blocks`) is taken
    as-is so what runs is what was asked for — a config that genuinely
    exceeds VMEM then fails loudly at compile.
    ``sq``/``sk`` (the actual sequence lengths) cap the blocks BEFORE
    estimating, so short sequences aren't shrunk below what fits anyway;
    for ``bwd="fused"`` they (with ``causal``) also decide the built-in
    end of the chain — :func:`_whole_key_blocks` where the keys fit, the
    128x128 constants where they do not or where no shape is given.
    ``bwd`` selects the footprint model AND the env names:
    ``False`` (forward), ``"dq"`` / ``"dkv"`` / ``"fused"`` (the three
    backward kernels — per-kernel pins, falling back to the shared bwd
    pins), or ``True`` (legacy combined backward model, shared pins only).
    Alignment floors: bk multiple of 128 (lane dim of the bias block), bq
    multiple of 8 (sublane)."""
    bq, bk = _chosen_blocks(bq, bk, bwd)
    bq_pinned, bk_pinned = bq is not None, bk is not None
    if (bq is None and bk is None and bwd == "fused"
            and sq is not None and sk is not None):
        whole = _whole_key_blocks(sq, sk, D, esz, bias_per_q, causal)
        if whole is not None:
            return whole
    if bq is None:
        bq = DEFAULT_BWD_BLOCK_Q if bwd else DEFAULT_BLOCK_Q
    if bk is None:
        bk = DEFAULT_BWD_BLOCK_K if bwd else DEFAULT_BLOCK_K
    if sq is not None:
        bq = min(bq, max(8, -(-sq // 8) * 8))
    if sk is not None:
        bk = min(bk, max(128, -(-sk // 128) * 128))
    budget = _vmem_budget()

    while (vmem_estimate(bq, bk, D, esz, bias_per_q, bwd) > budget
           and not bk_pinned and bk > 128):
        bk //= 2
    while (vmem_estimate(bq, bk, D, esz, bias_per_q, bwd) > budget
           and not bq_pinned and bq > 8):
        bq //= 2
    return max(8, (bq // 8) * 8), max(128, (bk // 128) * 128)


def vmem_estimate(bq, bk, D, esz, bias_per_q, bwd=False, sk=None) -> int:
    """Per-grid-step VMEM footprint model (bytes) behind ``_clamp_blocks``.

    ``bwd``: ``False`` forward; ``"dq"`` / ``"dkv"`` / ``"fused"`` model the
    individual backward kernels (the dq kernel streams one (bq, D) output +
    one f32 accumulator; the dkv kernel streams dk+dv outputs + two (bk, D)
    f32 accumulators; fused adds the dq output block on top of dkv, counted
    as the f32 partial — the whole-key kernel's ``q.dtype`` block is
    smaller) — their footprints genuinely differ, which is why their block
    sizes tune independently.  ``True`` keeps the legacy combined model (a
    superset of dq+dkv, used by the shared-chain callers).  ``"resident"``
    models the kernel that holds a head's ``sk`` keys (rounded up to whole
    pieces of ``bk``) for all of its q tiles: K, V, the dk / dv output
    blocks (each double-buffered like any block) and the f32 dk / dv
    accumulators are counted at ``sk`` rows of whole 128-lane vregs (what
    Mosaic allocates: at S 4096 x D 64 the three accumulators are 4.25 MiB
    by its own report, and the kernel's whole need came to 16.2 MiB at
    512 x 512 with dropout, 25.0 at S 8192 x 256 x 512, where this model
    says 18.25 and 27.5; PERF.md section 6, PR 28), the bias block at
    ``sk`` columns, the tile at (bq, bk).  ``"packed"`` models the
    projection layout's backward (:func:`_bwd_packed_kernel`), whose tile
    holds a pair of heads' every query and key (``bq = bk = S``, ``D`` the
    pair's 128 lanes): q, k, v, dO and O blocks, the bias block and the two
    ``lse`` rows (sublane-padded to 8), each double-buffered; the three
    (S, D) blocks the gradients leave from by DMA; one head's (S, S) tile
    (Mosaic frees it before the next head's); both heads' f32 dq, dk and
    dv; the ``lse`` and ``delta`` columns.  It leaves out the lane-masked
    operands and the dropout hash, and is still above what Mosaic needs
    for a described v5e at every shape it admits, with dropout and causal
    masking: 7.38 MiB at S 512 in bf16 with dropout where it says 7.69
    (PERF.md section 6, the projection layout;
    ``tests/L0/test_gated_delta_rule.py`` compiles each admitted shape
    within it).

    Every backward model counts what dominates a large tile: the (bq, bk)
    f32 intermediates of the recompute (``s``/``p``, ``dp``, ``ds``) and
    the stream-dtype copies of ``p`` and ``ds`` the MXU takes — 4 MiB at
    512 x 512 in bf16 beside ~1.5 MiB of streams — and the (bq, 1) f32
    ``lse`` / ``delta`` columns at the 128 lanes a VMEM block pads them to."""
    qkv_io = (bq * D + 2 * bk * D + bq * D) * esz   # q, k, v, out|dq
    bias = (bq if bias_per_q else 1) * bk * 4
    scratch = bq * (2 + D) * 4 + bq * 4
    tile = bq * bk * (3 * 4 + 2 * esz)              # s|p, dp, ds + MXU copies
    columns = 2 * bq * 128 * 4                      # lse, delta (lane-padded)
    if bwd == "resident":
        keys = -(-sk // bk) * bk
        lanes = -(-D // 128) * 128      # a VMEM row of D 64 fills 128 lanes
        # q, do, dq tiles; K, V and the dk, dv blocks of the whole head
        io = (3 * bq + 4 * keys) * lanes * esz + columns
        bias = max(8, bq if bias_per_q else 1) * keys * 4   # sublane-padded
        scratch = (2 * keys + bq) * lanes * 4           # dk, dv, dq accumulators
        return 2 * (io + bias) + scratch + tile
    if bwd == "packed":
        io = 5 * bq * D * esz + 8 * bq * 4          # q, k, v, do, o; lse rows
        bias = max(8, bq if bias_per_q else 1) * bk * 4
        staged = 3 * bq * D * esz                   # dq, dk, dv out by DMA
        grads = 6 * bq * D * 4                      # both heads' dq, dk, dv
        return 2 * (io + bias) + staged + tile + grads + columns
    if bwd in ("dq", "dkv", "fused"):
        # streams common to every backward kernel: q, k, v, do, lse, delta
        io = (2 * bq * D + 2 * bk * D) * esz + columns
        if bwd == "dq":
            io += bq * D * esz                      # dq output
            scratch = bq * D * 4                    # dq accumulator
        else:
            io += 2 * bk * D * esz                  # dk + dv outputs
            scratch = 2 * bk * D * 4                # dk/dv accumulators
            if bwd == "fused":
                io += bq * D * 4                    # dq (partial) output
        return 2 * (io + bias) + scratch + tile
    total = 2 * (qkv_io + bias) + scratch           # x2: double buffer
    if bwd:
        extra_io = bq * D * esz + columns           # do, lse, delta
        extra_io += 2 * bk * D * esz                # dk + dv outputs
        total += 2 * extra_io + 2 * bk * D * 4      # + dkv accumulators
        total += tile
    return total


from ...utils.pallas import (interpret_mode as _interpret,
                             compiler_params as _compiler_params,
                             out_vma as _out_vma, sds as _sds)


def _dropout_keep(seed, bh, row0, col0, shape, rate):
    """Counter-based dropout keep-mask over *global* (head, row, col)
    coordinates — squirrel3-style integer hash in plain jnp, so forward and
    both backward kernels regenerate bit-identical masks regardless of their
    grid shapes, on every backend (the CUDA side instead saves the mask to
    HBM; a hash is cheaper than the round-trip).  Uniformity is ample for
    dropout."""
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    x = (rows.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
         + cols.astype(jnp.uint32) * jnp.uint32(0x85EBCA77)
         + seed.astype(jnp.uint32) * jnp.uint32(0xC2B2AE3D))
    x = x * jnp.uint32(0xB5297A4D)
    # mix the head index in its own round: adding a small prime multiple to
    # the seed (round 1) made (seed, head) pairs collide trivially — two
    # seeds 7919 apart reused another head's exact mask
    x = x ^ (bh.astype(jnp.uint32) * jnp.uint32(0x27D4EB2F))
    x = x ^ (x >> jnp.uint32(8))
    x = x + jnp.uint32(0x68E31DA4)
    x = x ^ (x << jnp.uint32(8))
    x = x * jnp.uint32(0x1B56C4E9)
    x = x ^ (x >> jnp.uint32(8))
    threshold = jnp.uint32(int(rate * (2 ** 32)))
    return (x >= threshold).astype(jnp.float32)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _head_lanes(j, hd, shape):
    """The lanes of head ``j`` in a block that holds several heads of
    ``hd`` side by side (the projection layout: two heads of 64 a 128-lane
    block)."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    return (lanes >= j * hd) & (lanes < (j + 1) * hd)


def _only(mine, x):
    """``x`` with every lane outside ``mine`` zeroed: a product over the
    block's 128 lanes is then one head's product, and costs the MXU the
    pass a 64-wide operand costs anyway."""
    return jnp.where(mine, x, jnp.zeros_like(x))


def _head_of(block, pack, j):
    """The b·H + h index of head ``j`` of grid block ``block`` (= b·H/pack +
    h // pack), which the dropout mask hashes: a mask is the same whichever
    layout the head was read from."""
    return block if pack == 1 else pack * block + j


def _scaled(q, scale):
    """q times the softmax scale, rounded to q's dtype: what the transposing
    path hands the kernel pre-scaled (1/8 at hd 64 is exact)."""
    if scale is None:
        return q
    return (q.astype(jnp.float32) * scale).astype(q.dtype)


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref,
                m_ref, l_ref, acc_ref, *, bq, bk, causal, dropout_rate,
                heads, pack=1, scale=None, window=None, keys=None):
    """One (q block, k block) step of the online softmax for ``pack`` heads
    side by side in the block's lanes.  ``pack`` 1: the (B·H, S, D) layout,
    ``lse`` a (bq, 1) column.  ``pack`` 2: the projection layout — two heads
    of 64 a 128-lane block; each head's product takes the block with the
    other head's lanes of q and v zeroed, ``m`` / ``l`` are a column a head
    and ``lse`` leaves as ``pack`` rows with the queries on the lanes.
    ``window``: the band's grid (:func:`_band_fwd`) — step ``ki`` is key
    block ``first + ki`` of the ``keys`` blocks, and none past the band's
    last computes."""
    bh, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    hd = q_ref.shape[-1] // pack

    @pl.when(ki == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    run, kb = True, ki
    if window is not None:
        first, last = _band_keys(qi, bq, bk, window, keys)
        kb = first + ki
        run = kb <= last
    elif causal:
        # whole block above the diagonal: nothing to do
        run = (ki * bk) <= (qi * bq + bq - 1)

    @pl.when(run)
    def _():
        q = _scaled(q_ref[0], scale)
        for j in range(pack):
            mine = _head_lanes(j, hd, q.shape) if pack > 1 else None
            # matmuls take the native dtype (bf16 rides the MXU at full
            # rate) and accumulate in f32 via preferred_element_type
            s = jax.lax.dot_general(
                q if mine is None else _only(mine, q), k_ref[0],
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            s = s + bias_ref[0].astype(jnp.float32)           # (bq|1, bk)
            if causal:
                s = _causal_mask(s, qi, bq, kb, bk, window)

            m_old = m_ref[:, j]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=1))
            scale_j = jnp.exp(m_old - m_new)
            p = jnp.exp(s - m_new[:, None])                   # (bq, bk)
            l_ref[:, j] = l_ref[:, j] * scale_j + jnp.sum(p, axis=1)
            m_ref[:, j] = m_new

            if dropout_rate > 0.0:
                keep = _dropout_keep(seed_ref[0], _head_of(bh, pack, j),
                                     qi * bq, kb * bk, p.shape, dropout_rate)
                p = p * keep / (1.0 - dropout_rate)

            v = v_ref[0]                                      # (bk, d)
            if mine is None:
                acc_ref[:] = acc_ref[:] * scale_j[:, None] + \
                    jax.lax.dot_general(p.astype(v.dtype), v,
                                        (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)
            else:       # the product is zero outside the head's lanes
                acc_ref[:] = acc_ref[:] * jnp.where(
                    mine[:1], scale_j[:, None], 1.0) + jax.lax.dot_general(
                        p.astype(v.dtype),
                        _only(_head_lanes(j, hd, v.shape), v),
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _():
        out = stats = None
        for j in range(pack):
            l = l_ref[:, j]
            safe_l = jnp.where(l == 0.0, 1.0, l)
            # a row whose max never rose above the mask floor saw only
            # masked keys: emit zeros (constant NEG_INF bias cancels in the
            # online softmax, so without this test pad content would leak
            # through)
            dead = m_ref[:, j] <= NEG_INF / 2
            o = acc_ref[:] / safe_l[:, None]
            o = jnp.where(dead[:, None], 0.0, o)
            if pack == 1:
                o_ref[0] = o.astype(o_ref.dtype)
            # dead rows store +NEG_INF-magnitude lse so the backward's
            # exp(s - lse) underflows to 0 (zero grads for dead rows)
            lse = jnp.where(dead, -NEG_INF, m_ref[:, j] + jnp.log(safe_l))
            if pack == 1:
                lse_ref[0, :, 0] = lse
            elif out is None:
                out, stats = o, jnp.broadcast_to(lse[:, None], o.shape)
            else:
                out = jnp.where(_head_lanes(j, hd, o.shape), o, out)
                stats = jnp.where(_head_lanes(j, 1, o.shape), lse[:, None],
                                  stats)
        if pack > 1:
            o_ref[0] = out.astype(o_ref.dtype)
            # head j's lse sits in lane j: one transpose makes them rows
            lse_ref[0, 0] = jnp.transpose(stats)[:pack]


def _bias_spec(bias, heads, bq, bk):
    """BlockSpec for an additive bias of shape (1|B, 1|Sq, Sk)."""
    b_bcast = bias.shape[0] == 1
    q_bcast = bias.shape[1] == 1

    def index_map(bh, qi, ki):
        return (0 if b_bcast else bh // heads, 0 if q_bcast else qi, ki)

    return pl.BlockSpec((1, 1 if q_bcast else bq, bk), index_map,
                        memory_space=pltpu.VMEM)


def _pad_inputs(q, k, v, bias, do=None, bq=DEFAULT_BLOCK_Q,
                bk=DEFAULT_BLOCK_K):
    """Pad ragged Sq/Sk up to block multiples.  Padded key columns carry
    NEG_INF bias (zero attention weight); padded query rows are sliced off
    by the caller.  Returns (q, k, v, bias, do, orig_sq, orig_sk)."""
    Sq, Sk = q.shape[1], k.shape[1]
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    sq_pad = -Sq % bq
    sk_pad = -Sk % bk
    if sq_pad:
        q = jnp.pad(q, ((0, 0), (0, sq_pad), (0, 0)))
        if do is not None:
            do = jnp.pad(do, ((0, 0), (0, sq_pad), (0, 0)))
        if bias.shape[1] != 1:
            bias = jnp.pad(bias, ((0, 0), (0, sq_pad), (0, 0)))
    if sk_pad:
        k = jnp.pad(k, ((0, 0), (0, sk_pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, sk_pad), (0, 0)))
        bias = jnp.pad(bias, ((0, 0), (0, 0), (0, sk_pad)),
                       constant_values=NEG_INF)
    return q, k, v, bias, do, Sq, Sk


def _check_bias_layout(q, bias, heads):
    """Trace-time shape validation.  Lives here (not in the custom_vjp
    wrapper, whose primal body jax replaces with _vjp_fwd under grad) so it
    fires on BOTH the inference and training paths."""
    bh = q.shape[0]
    if bh % heads:
        raise ValueError(f"leading dim {bh} is not a multiple of heads="
                         f"{heads} — pass heads explicitly")
    if bias.shape[0] not in (1, bh // heads):
        # bias rows are indexed by bh//heads (batch): a per-batch mask with
        # the default heads=1 would silently read the wrong batch's rows
        raise ValueError(
            f"bias batch dim {bias.shape[0]} matches neither 1 nor "
            f"batch={bh // heads} (= leading dim {bh} / heads={heads}); "
            f"pass the heads= the q layout uses")


def _flash_fwd(q, k, v, bias, causal, dropout_rate, seed, heads,
               bq=None, bk=None):
    """q (BH, Sq, D), k/v (BH, Sk, D), bias (1|B, 1|Sq, Sk) f32.
    Returns out (BH, Sq, D), lse (BH, Sq, 1) f32."""
    _check_bias_layout(q, bias, heads)
    bq, bk = _clamp_blocks(bq, bk, q.shape[-1], q.dtype.itemsize,
                           bias_per_q=bias.shape[1] != 1,
                           sq=q.shape[1], sk=k.shape[1])
    q, k, v, bias, _, orig_sq, _ = _pad_inputs(q, k, v, bias, bq=bq, bk=bk)
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    grid = (BH, (Sq + bq - 1) // bq, (Sk + bk - 1) // bk)
    seed_arr = jnp.reshape(jnp.asarray(seed, jnp.int32), (1,))
    # outputs vary over every mesh axis an input varies over: under
    # shard_map(check_vma=True) an untyped out_shape is an error
    vma = _out_vma(q, k, v, bias)

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, bq=bq, bk=bk, causal=causal,
                          dropout_rate=dropout_rate, heads=heads),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),           # seed
            pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, D), lambda bh, qi, ki: (bh, ki, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, D), lambda bh, qi, ki: (bh, ki, 0),
                         memory_space=pltpu.VMEM),
            _bias_spec(bias, heads, bq, bk),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, 1), lambda bh, qi, ki: (bh, qi, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[_sds((BH, Sq, D), q.dtype, vma),
                   _sds((BH, Sq, 1), jnp.float32, vma)],
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, D), jnp.float32)],
        # bh/qi produce independent outputs (parallel); ki accumulates
        # into scratch sequentially (arbitrary).  Declaring this matters:
        # the round-3 on-chip measurements (PERF_NOTES §2) put ~10x on
        # all-arbitrary defaults for grids whose steps Mosaic could
        # otherwise overlap
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="apex_flash_fwd",
    )(seed_arr, q, k, v, bias)
    return out[:, :orig_sq], lse[:, :orig_sq]


# ---------------------------------------------------------------------------
# backward (recompute): dq kernel (grid over q), dkv kernel (grid over k)
# ---------------------------------------------------------------------------

def _causal_mask(s, qi, bq, ki, bk, window=None):
    """The (bq, bk) tile ``s`` of q block ``qi`` and key block ``ki`` with
    NEG_INF wherever the key lies after the query or, given a ``window``,
    ``window`` or more keys before it: query i sees keys j with i - window <
    j <= i."""
    rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    seen = cols <= rows
    if window is not None:
        seen = seen & (cols > rows - window)
    return jnp.where(seen, s, NEG_INF)


def _recompute_p(q_ref, k_ref, bias_ref, lse_ref, qi, ki, bq, bk, causal,
                 window=None):
    s = _recompute_s(q_ref[0], k_ref[0], bias_ref, qi, ki, bq, bk, causal,
                     window)
    return jnp.exp(s - lse_ref[0, :, 0][:, None])             # (bq, bk)


def _recompute_s(q, k, bias_ref, qi, ki, bq, bk, causal, window=None):
    """The scores of one (q tile, key block) of one head, bias and causal
    (or band) mask applied."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s + bias_ref[0].astype(jnp.float32)
    if causal:
        s = _causal_mask(s, qi, bq, ki, bk, window)
    return s


def _tile_grads(p, keep, q, k, v, do, delta):
    """dv, dk and dq (float32) of one recomputed tile of one head: ``p`` the
    softmax (bq, bk), ``keep`` the dropout mask over 1 - rate (or None),
    ``q`` / ``do`` the tile's query rows, ``k`` / ``v`` its keys, ``delta``
    the (bq, 1) rowsum(dO·O).  One recompute feeds all three."""
    pd = p if keep is None else p * keep
    dv = jax.lax.dot_general(pd.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    if keep is not None:
        dp = dp * keep
    ds = p * (dp - delta)                                     # (bq, bk)
    dk = jax.lax.dot_general(ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    dq = jax.lax.dot_general(ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return dv, dk, dq


def _bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, dq_acc, *, bq, bk, causal, dropout_rate,
                   heads, window=None, keys=None):
    """dq of one q block over the key blocks the grid walks: all of them,
    or with a ``window`` the band's (as in :func:`_fwd_kernel`)."""
    bh, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run, kb = True, ki
    if window is not None:
        first, last = _band_keys(qi, bq, bk, window, keys)
        kb = first + ki
        run = kb <= last
    elif causal:
        run = (ki * bk) <= (qi * bq + bq - 1)

    @pl.when(run)
    def _():
        p = _recompute_p(q_ref, k_ref, bias_ref, lse_ref, qi, kb, bq, bk,
                         causal, window)
        dp = jax.lax.dot_general(do_ref[0], v_ref[0],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            keep = _dropout_keep(seed_ref[0], bh, qi * bq, kb * bk, p.shape,
                                 dropout_rate)
            dp = dp * keep / (1.0 - dropout_rate)
        ds = p * (dp - delta_ref[0, :, 0][:, None])           # (bq, bk)
        k = k_ref[0]
        dq_acc[:] += jax.lax.dot_general(ds.astype(k.dtype), k,
                                         (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, bq, bk,
                    causal, dropout_rate, heads, window=None, queries=None):
    """dk and dv of one key block over the q blocks the grid walks: all of
    them, or with a ``window`` the band's — step ``qi`` is q block ``first
    + qi`` of the ``queries`` blocks (:func:`_band_queries`)."""
    bh, ki, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run, qb = True, qi
    if window is not None:
        first, last = _band_queries(ki, bq, bk, window, queries)
        qb = first + qi
        run = qb <= last
    elif causal:
        run = (ki * bk) <= (qi * bq + bq - 1)

    @pl.when(run)
    def _():
        p = _recompute_p(q_ref, k_ref, bias_ref, lse_ref, qb, ki, bq, bk,
                         causal, window)                      # (bq, bk)
        do = do_ref[0]                                        # (bq, d)
        if dropout_rate > 0.0:
            keep = _dropout_keep(seed_ref[0], bh, qb * bq, ki * bk, p.shape,
                                 dropout_rate) / (1.0 - dropout_rate)
            pd = p * keep
        else:
            pd = p
        # dv += pd^T @ do
        dv_acc[:] += jax.lax.dot_general(pd.astype(do.dtype), do,
                                         (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v_ref[0], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            dp = dp * keep
        ds = p * (dp - delta_ref[0, :, 0][:, None])           # (bq, bk)
        q = q_ref[0]
        dk_acc[:] += jax.lax.dot_general(ds.astype(q.dtype), q,
                                         (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_fused_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref,
                      lse_ref, delta_ref, dq_ref, dk_ref, dv_ref, dk_acc,
                      dv_acc, *, bq, bk, causal, dropout_rate, heads):
    """One recompute feeds all three gradients: P (and the dropout mask) is
    rebuilt ONCE per (k-block, q-block) step; dk/dv accumulate in scratch
    over the q sweep; the step's ``ds @ k`` leaves through ``dq_ref``, whose
    block the caller shapes by nk.  With one k block a head (whole_key) it
    is the (bq, D) block of dq itself, written in ``q.dtype``.  With more
    it is this step's f32 partial (``dq_ref`` is then (BH, nk, Sq, D) under
    ``_resolve_fuse``'s byte cap), summed over k blocks outside the kernel
    (the splash-attention fused-backward layout).  Either way each block is
    visited exactly once, so there is no output-revisit hazard.  Versus the
    split kernels this halves the P recompute and the do@v^T matmul and
    regenerates the dropout mask once instead of twice."""
    bh, ki, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)
    dq_blk = (0,) * (len(dq_ref.shape) - 2)     # (bh,) or (bh, ki) of the block

    @pl.when(qi == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = True
    if causal:
        run = (ki * bk) <= (qi * bq + bq - 1)

    @pl.when(run)
    def _():
        p = _recompute_p(q_ref, k_ref, bias_ref, lse_ref, qi, ki, bq, bk,
                         causal)                              # (bq, bk)
        keep = None
        if dropout_rate > 0.0:
            keep = _dropout_keep(seed_ref[0], bh, qi * bq, ki * bk, p.shape,
                                 dropout_rate) / (1.0 - dropout_rate)
        dv, dk, dq = _tile_grads(p, keep, q_ref[0], k_ref[0], v_ref[0],
                                 do_ref[0], delta_ref[0, :, 0][:, None])
        dv_acc[:] += dv
        dk_acc[:] += dk
        dq_ref[dq_blk] = dq.astype(dq_ref.dtype)

    if causal:
        @pl.when(jnp.logical_not(run))
        def _():
            # a causal-skipped step still owns its dq block (each is
            # visited exactly once): it must be defined
            dq_ref[dq_blk] = jnp.zeros(dq_ref.shape[-2:], dq_ref.dtype)

    @pl.when(qi == nq - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_resident_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref,
                         lse_ref, delta_ref, dq_ref, dk_ref, dv_ref, dq_acc,
                         dk_acc, dv_acc, *, bq, bk, causal, dropout_rate,
                         heads):
    """All three gradients of a head whose keys do not fit one tile but do
    fit VMEM.  Grid (BH, nq): K, V, the dk / dv output blocks and their f32
    accumulators stay for the head's whole q sweep; a step takes one q tile
    and walks the keys in pieces of ``bk``.  P (and the dropout mask) is
    rebuilt ONCE a (q tile, piece) and feeds dq, dk and dv, as in
    :func:`_bwd_fused_kernel`; dq accumulates in f32 over the pieces and
    leaves once a step in ``q.dtype``, dk / dv once a head.  Causal: the
    walk stops at the diagonal (nothing above it is computed, and with the
    keys resident there is nothing to fetch), and only the pieces the
    diagonal crosses pay for the mask."""
    bh, qi = pl.program_id(0), pl.program_id(1)
    nq = pl.num_programs(1)
    pieces = k_ref.shape[1] // bk
    row0 = qi * bq

    @pl.when(qi == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    dq_acc[:] = jnp.zeros_like(dq_acc)

    def piece(kc, masked):
        col0 = pl.multiple_of(kc * bk, bk)
        at = pl.ds(col0, bk)
        q, do = q_ref[0], do_ref[0]                           # (bq, d)
        k, v = k_ref[0, at, :], v_ref[0, at, :]               # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s + bias_ref[0, :, at].astype(jnp.float32)
        if masked:
            rows = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(cols <= rows, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0])                           # (bq, bk)
        if dropout_rate > 0.0:
            keep = _dropout_keep(seed_ref[0], bh, row0, col0, p.shape,
                                 dropout_rate) / (1.0 - dropout_rate)
            pd = p * keep
        else:
            pd = p
        dv_acc[at, :] += jax.lax.dot_general(
            pd.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            dp = dp * keep
        ds = (p * (dp - delta_ref[0])).astype(q.dtype)        # (bq, bk)
        dk_acc[at, :] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dq_acc[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    def walk(lo, hi, masked):
        jax.lax.fori_loop(lo, hi, lambda kc, _: piece(kc, masked), None)

    if causal:
        # pieces wholly under the diagonal, then those it crosses; those
        # wholly above it (first key past the tile's last row) never run
        under = jnp.minimum(pieces, (row0 + 1) // bk)
        walk(0, under, False)
        walk(under, jnp.minimum(pieces, (row0 + bq - 1) // bk + 1), True)
    else:
        walk(0, pieces, False)
    dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)

    @pl.when(qi == nq - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _pad_lse_delta(lse, delta, Sq):
    if Sq != delta.shape[1]:
        delta = jnp.pad(delta, ((0, 0), (0, Sq - delta.shape[1]), (0, 0)))
        lse = jnp.pad(lse, ((0, 0), (0, Sq - lse.shape[1]), (0, 0)))
    return lse, delta


def _flash_bwd_dq(q, k, v, bias, causal, dropout_rate, seed, heads, lse,
                  delta, do, bq=None, bk=None):
    """dq via the standalone dq kernel (grid over q blocks); blocks resolve
    through the ``dq`` chain of :func:`_clamp_blocks`."""
    bq, bk = _clamp_blocks(bq, bk, q.shape[-1], q.dtype.itemsize,
                           bias_per_q=bias.shape[1] != 1, bwd="dq",
                           sq=q.shape[1], sk=k.shape[1])
    q, k, v, bias, do, orig_sq, _ = _pad_inputs(q, k, v, bias, do,
                                                bq=bq, bk=bk)
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    lse, delta = _pad_lse_delta(lse, delta, Sq)
    seed_arr = jnp.reshape(jnp.asarray(seed, jnp.int32), (1,))

    dq_in = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, bk, D), lambda bh, qi, ki: (bh, ki, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, bk, D), lambda bh, qi, ki: (bh, ki, 0),
                     memory_space=pltpu.VMEM),
        _bias_spec(bias, heads, bq, bk),
        pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, bq, 1), lambda bh, qi, ki: (bh, qi, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, bq, 1), lambda bh, qi, ki: (bh, qi, 0),
                     memory_space=pltpu.VMEM),
    ]

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, bq=bq, bk=bk, causal=causal,
                          dropout_rate=dropout_rate, heads=heads),
        grid=(BH, (Sq + bq - 1) // bq, (Sk + bk - 1) // bk),
        in_specs=dq_in,
        out_specs=pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0),
                               memory_space=pltpu.VMEM),
        out_shape=_sds((BH, Sq, D), q.dtype,
                       _out_vma(q, k, v, bias, do, lse, delta)),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="apex_flash_bwd_dq",
    )(seed_arr, q, k, v, bias, do, lse, delta)
    return dq[:, :orig_sq]


def _dkv_in_specs(bias, heads, bq, bk, D):
    """in_specs shared by the dkv and fused kernels — grid (BH, nk, nq);
    index maps swap qi/ki roles versus the dq kernel."""
    return [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((1, bq, D), lambda bh, ki, qi: (bh, qi, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, bk, D), lambda bh, ki, qi: (bh, ki, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, bk, D), lambda bh, ki, qi: (bh, ki, 0),
                     memory_space=pltpu.VMEM),
        _bias_spec_swapped(bias, heads, bq, bk),
        pl.BlockSpec((1, bq, D), lambda bh, ki, qi: (bh, qi, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, bq, 1), lambda bh, ki, qi: (bh, qi, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, bq, 1), lambda bh, ki, qi: (bh, qi, 0),
                     memory_space=pltpu.VMEM),
    ]


def _flash_bwd_dkv(q, k, v, bias, causal, dropout_rate, seed, heads, lse,
                   delta, do, bq=None, bk=None):
    """dk/dv via the standalone dkv kernel (grid over k blocks); blocks
    resolve through the ``dkv`` chain of :func:`_clamp_blocks`."""
    bq, bk = _clamp_blocks(bq, bk, q.shape[-1], q.dtype.itemsize,
                           bias_per_q=bias.shape[1] != 1, bwd="dkv",
                           sq=q.shape[1], sk=k.shape[1])
    q, k, v, bias, do, _, orig_sk = _pad_inputs(q, k, v, bias, do,
                                                bq=bq, bk=bk)
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    lse, delta = _pad_lse_delta(lse, delta, Sq)
    seed_arr = jnp.reshape(jnp.asarray(seed, jnp.int32), (1,))

    vma = _out_vma(q, k, v, bias, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, bq=bq, bk=bk, causal=causal,
                          dropout_rate=dropout_rate, heads=heads),
        grid=(BH, (Sk + bk - 1) // bk, (Sq + bq - 1) // bq),
        in_specs=_dkv_in_specs(bias, heads, bq, bk, D),
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda bh, ki, qi: (bh, ki, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, D), lambda bh, ki, qi: (bh, ki, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[_sds((BH, Sk, D), k.dtype, vma),
                   _sds((BH, Sk, D), v.dtype, vma)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="apex_flash_bwd_dkv",
    )(seed_arr, q, k, v, bias, do, lse, delta)
    return dk[:, :orig_sk], dv[:, :orig_sk]


def _flash_bwd_fused(q, k, v, bias, causal, dropout_rate, seed, heads, lse,
                     delta, do, bq, bk):
    """All three gradients from one kernel on the dkv grid (blocks arrive
    pre-clamped through the ``fused`` chain).  With nk = 1 the kernel's dq
    output IS dq, (BH, Sq, D) in ``q.dtype``; with nk > 1 dq comes back as
    per-k-block f32 partials summed here by XLA."""
    q, k, v, bias, do, orig_sq, orig_sk = _pad_inputs(q, k, v, bias, do,
                                                      bq=bq, bk=bk)
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    lse, delta = _pad_lse_delta(lse, delta, Sq)
    seed_arr = jnp.reshape(jnp.asarray(seed, jnp.int32), (1,))
    nk = (Sk + bk - 1) // bk
    vma = _out_vma(q, k, v, bias, do, lse, delta)
    if nk == 1:
        dq_spec = pl.BlockSpec((1, bq, D), lambda bh, ki, qi: (bh, qi, 0),
                               memory_space=pltpu.VMEM)
        dq_shape = _sds((BH, Sq, D), q.dtype, vma)
    else:
        dq_spec = pl.BlockSpec((1, 1, bq, D),
                               lambda bh, ki, qi: (bh, ki, qi, 0),
                               memory_space=pltpu.VMEM)
        dq_shape = _sds((BH, nk, Sq, D), jnp.float32, vma)

    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_fused_kernel, bq=bq, bk=bk, causal=causal,
                          dropout_rate=dropout_rate, heads=heads),
        grid=(BH, nk, (Sq + bq - 1) // bq),
        in_specs=_dkv_in_specs(bias, heads, bq, bk, D),
        out_specs=[
            dq_spec,
            pl.BlockSpec((1, bk, D), lambda bh, ki, qi: (bh, ki, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, D), lambda bh, ki, qi: (bh, ki, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[dq_shape,
                   _sds((BH, Sk, D), k.dtype, vma),
                   _sds((BH, Sk, D), v.dtype, vma)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="apex_flash_bwd_fused",
    )(seed_arr, q, k, v, bias, do, lse, delta)
    if nk > 1:
        dq = jnp.sum(dq, axis=1).astype(q.dtype)
    return dq[:, :orig_sq], dk[:, :orig_sk], dv[:, :orig_sk]


def _flash_bwd_resident(q, k, v, bias, causal, dropout_rate, seed, heads,
                        lse, delta, do, bq, bk):
    """All three gradients from ONE kernel where nk > 1
    (:func:`_bwd_resident_kernel`; the tile comes from
    :func:`_resident_blocks`): grid (BH, nq), the head's padded K / V and
    the whole-head dk / dv blocks indexed by the head alone, so they are
    fetched and written once a head.  Nothing partial goes to HBM."""
    q, k, v, bias, do, orig_sq, orig_sk = _pad_inputs(q, k, v, bias, do,
                                                      bq=bq, bk=bk)
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    lse, delta = _pad_lse_delta(lse, delta, Sq)
    seed_arr = jnp.reshape(jnp.asarray(seed, jnp.int32), (1,))
    vma = _out_vma(q, k, v, bias, do, lse, delta)
    b_bcast, q_bcast = bias.shape[0] == 1, bias.shape[1] == 1

    def tile(width):
        return pl.BlockSpec((1, bq, width), lambda bh, qi: (bh, qi, 0),
                            memory_space=pltpu.VMEM)

    head = pl.BlockSpec((1, Sk, D), lambda bh, qi: (bh, 0, 0),
                        memory_space=pltpu.VMEM)
    limit = int(1.25 * _resident_budget())

    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_resident_kernel, bq=bq, bk=bk, causal=causal,
                          dropout_rate=dropout_rate, heads=heads),
        grid=(BH, Sq // bq),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),           # seed
            tile(D), head, head,                             # q, k, v
            pl.BlockSpec((1, 1 if q_bcast else bq, Sk),
                         lambda bh, qi: (0 if b_bcast else bh // heads,
                                         0 if q_bcast else qi, 0),
                         memory_space=pltpu.VMEM),
            tile(D), tile(1), tile(1),                       # do, lse, delta
        ],
        out_specs=[tile(D), head, head],
        out_shape=[_sds((BH, Sq, D), q.dtype, vma),
                   _sds((BH, Sk, D), k.dtype, vma),
                   _sds((BH, Sk, D), v.dtype, vma)],
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32),
                        pltpu.VMEM((Sk, D), jnp.float32),
                        pltpu.VMEM((Sk, D), jnp.float32)],
        # heads are independent; a head's q tiles accumulate dk / dv
        compiler_params=_compiler_params(("parallel", "arbitrary"),
                                         vmem_limit_bytes=limit),
        interpret=_interpret(),
        name="apex_flash_bwd_fused",
    )(seed_arr, q, k, v, bias, do, lse, delta)
    return dq[:, :orig_sq], dk[:, :orig_sk], dv[:, :orig_sk]


def _forced_fuse(fuse):
    """A fused-vs-split choice somebody MADE, else None: explicit argument
    > APEX_TPU_FLASH_BWD_FUSE env (0/1)."""
    import os
    if fuse is not None:
        return bool(fuse)
    env = os.environ.get("APEX_TPU_FLASH_BWD_FUSE")
    if env is not None:
        # same disable vocabulary as telemetry's _env_enabled: 'off' and
        # 'no' disable (they used to read as truthy — ROADMAP deferral b)
        return env.lower() not in ("0", "off", "false", "no", "")
    return None


def _resolve_fuse(fuse, BH, Sq, Sk, D, bk):
    """Fused-vs-split strategy where dq leaves the kernel as partials
    (nk > 1).  :func:`_forced_fuse` (argument > env) > built-in
    heuristic: fuse while the dq-partials buffer stays under the byte cap
    (it grows as Sq*Sk/bk — "where the grid allows")."""
    import os
    forced = _forced_fuse(fuse)
    if forced is not None:
        return forced
    cap = float(os.environ.get("APEX_TPU_FLASH_BWD_FUSE_MB",
                               _FUSE_BUFFER_CAP_MB)) * 2 ** 20
    nk = -(-Sk // bk)
    return BH * nk * Sq * D * 4 <= cap


def _flash_bwd(q, k, v, bias, causal, dropout_rate, seed, heads, out, lse,
               do, bq=None, bk=None, dq_blocks=None, dkv_blocks=None,
               fuse=None):
    """Recompute-backward dispatcher: (dq, dk, dv).

    Three questions of the shape choose among the four paths, in this
    order and with no knob: does ONE tile hold a head's keys (nk = 1 ->
    ``whole_key``: the fused kernel writes dq itself); where it does not,
    do a head's K, V and dk / dv accumulators fit VMEM beside a tile
    (:func:`_resident_blocks` -> ``resident``: one kernel walks the keys in
    pieces, nothing partial leaves it); and only then does the f32
    partials buffer of the 128x128 grid stay under :func:`_resolve_fuse`'s
    cap (``partials``, else ``split``).  A tile or a strategy somebody
    CHOSE (argument, env pin) keeps its meaning: it names the
    128x128 grid's kernels, so ``resident`` is not asked.

    ``bq``/``bk`` pin BOTH kernels (the legacy shared knob);
    ``dq_blocks``/``dkv_blocks`` (each an optional (bq, bk) tuple) pin the
    kernels separately — their VMEM footprints differ, so their optima do
    too.  ``fuse`` forces the fused/split strategy
    (None = auto)."""
    # delta_i = rowsum(dO * O): tiny elementwise+reduce, XLA fuses it —
    # computed ONCE here and streamed to whichever backward kernels run
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)                   # (BH, Sq, 1)
    D, esz = q.shape[-1], q.dtype.itemsize
    BH, Sq, Sk = q.shape[0], q.shape[1], k.shape[1]
    per_q = bias.shape[1] != 1
    dq_bq, dq_bk = dq_blocks if dq_blocks is not None else (bq, bk)
    kv_bq, kv_bk = dkv_blocks if dkv_blocks is not None else (bq, bk)
    f_bq, f_bk = _clamp_blocks(kv_bq, kv_bk, D, esz, per_q, bwd="fused",
                               sq=Sq, sk=Sk, causal=causal)
    nk = -(-Sk // f_bk)
    forced = _forced_fuse(fuse)
    if nk == 1:
        fuse = forced is not False
    else:
        c_bq, c_bk = _chosen_blocks(kv_bq, kv_bk, "fused")
        resident = None
        if forced is None and c_bq is None and c_bk is None:
            resident = _resident_blocks(Sq, Sk, D, esz, per_q)
        if resident is not None:
            r_bq, r_bk = resident
            _tel_events.record_flash_bwd("resident", r_bq, r_bk,
                                         -(-Sk // r_bk))
            return _flash_bwd_resident(q, k, v, bias, causal, dropout_rate,
                                       seed, heads, lse, delta, do, r_bq,
                                       r_bk)
        fuse = _resolve_fuse(fuse, BH, Sq, Sk, D, f_bk)
    if fuse:
        _tel_events.record_flash_bwd(
            "whole_key" if nk == 1 else "partials", f_bq, f_bk, nk)
        return _flash_bwd_fused(q, k, v, bias, causal, dropout_rate, seed,
                                heads, lse, delta, do, f_bq, f_bk)
    _tel_events.record_flash_bwd("split", f_bq, f_bk, nk)
    dq = _flash_bwd_dq(q, k, v, bias, causal, dropout_rate, seed, heads,
                       lse, delta, do, bq=dq_bq, bk=dq_bk)
    dk, dv = _flash_bwd_dkv(q, k, v, bias, causal, dropout_rate, seed,
                            heads, lse, delta, do, bq=kv_bq, bk=kv_bk)
    return dq, dk, dv


def _bias_spec_swapped(bias, heads, bq, bk):
    b_bcast = bias.shape[0] == 1
    q_bcast = bias.shape[1] == 1

    def index_map(bh, ki, qi):
        return (0 if b_bcast else bh // heads, 0 if q_bcast else qi, ki)

    return pl.BlockSpec((1, 1 if q_bcast else bq, bk), index_map,
                        memory_space=pltpu.VMEM)


# ---------------------------------------------------------------------------
# the band: causal attention within a sliding window
# ---------------------------------------------------------------------------
#
# With ``window`` W query i sees keys j with i - W < j <= i: W keys, counting
# itself.  A causal grid would visit every key block up to the diagonal; the
# band's grids visit only the blocks that meet the band — the key blocks of
# a q block (forward, dq), the q blocks of a key block (dk, dv) — by an index
# map that offsets the block by the row of blocks the step belongs to, so a
# tile outside the band is neither fetched nor computed.  A step past the
# band's last block (the rows of blocks near the start meet fewer) repeats
# its index, which copies nothing, and computes nothing.

_BAND_BLOCK = 512
#: (window, head width, dtype) the band kernels compiled and ran at on a
#: v5e, at ``_BAND_BLOCK`` square tiles: every other window takes the XLA
#: band path (:func:`_band_admitted`)
_BAND_SHAPES = ((512, 128, "bfloat16"),)


def _band_keys(qi, bq, bk, window, keys, lib=jnp):
    """(first, last) key block that q block ``qi``'s band meets, of
    ``keys``; ``lib`` numpy for python ints."""
    first = lib.maximum(qi * bq - window + 1, 0) // bk
    return first, lib.minimum((qi * bq + bq - 1) // bk, keys - 1)


def _band_queries(ki, bq, bk, window, queries, lib=jnp):
    """(first, last) q block whose band meets key block ``ki``, of
    ``queries``."""
    last = (ki * bk + bk - 1 + window - 1) // bq
    return (ki * bk) // bq, lib.minimum(last, queries - 1)


def _band_axis(span, rows):
    """``(steps, block)`` of a band grid's walking axis over ``rows`` rows
    of blocks, ``span(row, lib)`` = (first, last) block of a row: the most
    blocks a row covers, and the block step j of row i reads — clamped to
    the row's last, so a step past it fetches nothing new."""
    steps = max(last - first + 1 for first, last in (
        span(i, np) for i in range(rows)))

    def block(i, j):
        first, last = span(i, jnp)
        return jnp.minimum(first + j, last)
    return int(steps), block


def _band_admitted(q, k, bias, window, dropout_rate):
    """Whether the band kernels take this call: a shape of
    ``_BAND_SHAPES``, self-attention over whole ``_BAND_BLOCK`` blocks, a
    bias over keys alone and no dropout."""
    S = q.shape[1]
    return ((window, q.shape[-1], q.dtype.name) in _BAND_SHAPES
            and k.shape[1] == S and S % _BAND_BLOCK == 0
            and bias.shape[1] == 1 and dropout_rate == 0.0)


def _band_specs(bias, heads, bq, bk, D, rows_of, cols_of):
    """in_specs (seed, q, k, v, bias, do, lse, delta) on a band grid whose
    step ``(bh, i, j)`` reads q block ``rows_of(i, j)`` and key block
    ``cols_of(i, j)``; the bias is over keys, (1|B, 1, Sk)."""
    b_bcast = bias.shape[0] == 1

    def at_rows(width):
        return pl.BlockSpec((1, bq, width),
                            lambda bh, i, j: (bh, rows_of(i, j), 0),
                            memory_space=pltpu.VMEM)

    def at_cols(width):
        return pl.BlockSpec((1, bk, width),
                            lambda bh, i, j: (bh, cols_of(i, j), 0),
                            memory_space=pltpu.VMEM)

    def bias_map(bh, i, j):
        return (0 if b_bcast else bh // heads, 0, cols_of(i, j))

    return [pl.BlockSpec(memory_space=pltpu.SMEM),
            at_rows(D), at_cols(D), at_cols(D),
            pl.BlockSpec((1, 1, bk), bias_map, memory_space=pltpu.VMEM),
            at_rows(D), at_rows(1), at_rows(1)]


def _band_fwd(q, k, v, bias, window, heads, bq=_BAND_BLOCK, bk=_BAND_BLOCK):
    """The band's forward: q (BH, S, D) pre-scaled, k / v (BH, S, D), bias
    (1|B, 1, S).  Grid (BH, nq, steps): step j of q block i reads key block
    ``first(i) + j``, clamped to the band's last.  Returns out (BH, S, D)
    and lse (BH, S, 1) f32."""
    _check_bias_layout(q, bias, heads)
    q, k, v, bias, _, orig, _ = _pad_inputs(q, k, v, bias, bq=bq, bk=bk)
    BH, Sq, D = q.shape
    bq, bk = min(bq, Sq), min(bk, k.shape[1])
    nq, nk = Sq // bq, k.shape[1] // bk
    steps, key_block = _band_axis(
        lambda i, lib: _band_keys(i, bq, bk, window, nk, lib), nq)
    seed_arr = jnp.zeros((1,), jnp.int32)
    vma = _out_vma(q, k, v, bias)
    specs = _band_specs(bias, heads, bq, bk, D, lambda i, j: i, key_block)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, bq=bq, bk=bk, causal=True,
                          dropout_rate=0.0, heads=heads, window=window,
                          keys=nk),
        grid=(BH, nq, steps),
        in_specs=specs[:5],
        out_specs=[specs[1], specs[6]],
        out_shape=[_sds((BH, Sq, D), q.dtype, vma),
                   _sds((BH, Sq, 1), jnp.float32, vma)],
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="apex_flash_window_fwd",
    )(seed_arr, q, k, v, bias)
    return out[:, :orig], lse[:, :orig]


def _band_bwd(q, k, v, bias, window, heads, out, lse, do, bq=_BAND_BLOCK,
              bk=_BAND_BLOCK):
    """(dq, dk, dv) of the band: the split pair of the recompute backward
    on band grids — dq over the key blocks of each q block's band
    (``apex_flash_window_bwd_dq``), dk / dv over the q blocks of each key
    block's (``apex_flash_window_bwd_dkv``)."""
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)                   # (BH, Sq, 1)
    q, k, v, bias, do, orig, _ = _pad_inputs(q, k, v, bias, do, bq=bq, bk=bk)
    BH, Sq, D = q.shape
    bq, bk = min(bq, Sq), min(bk, k.shape[1])
    nq, nk = Sq // bq, k.shape[1] // bk
    lse, delta = _pad_lse_delta(lse, delta, Sq)
    seed_arr = jnp.zeros((1,), jnp.int32)
    vma = _out_vma(q, k, v, bias, do, lse, delta)
    _tel_events.record_flash_bwd("window", bq, bk, nk)
    key_steps, key_block = _band_axis(
        lambda i, lib: _band_keys(i, bq, bk, window, nk, lib), nq)
    q_steps, q_block = _band_axis(
        lambda i, lib: _band_queries(i, bq, bk, window, nq, lib), nk)

    specs = _band_specs(bias, heads, bq, bk, D, lambda i, j: i, key_block)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, bq=bq, bk=bk, causal=True,
                          dropout_rate=0.0, heads=heads, window=window,
                          keys=nk),
        grid=(BH, nq, key_steps),
        in_specs=specs,
        out_specs=specs[1],
        out_shape=_sds((BH, Sq, D), q.dtype, vma),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="apex_flash_window_bwd_dq",
    )(seed_arr, q, k, v, bias, do, lse, delta)

    specs = _band_specs(bias, heads, bq, bk, D, q_block, lambda i, j: i)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, bq=bq, bk=bk, causal=True,
                          dropout_rate=0.0, heads=heads, window=window,
                          queries=nq),
        grid=(BH, nk, q_steps),
        in_specs=specs,
        out_specs=[specs[2], specs[3]],
        out_shape=[_sds((BH, k.shape[1], D), k.dtype, vma),
                   _sds((BH, k.shape[1], D), v.dtype, vma)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="apex_flash_window_bwd_dkv",
    )(seed_arr, q, k, v, bias, do, lse, delta)
    return dq[:, :orig], dk[:, :orig], dv[:, :orig]


# ---------------------------------------------------------------------------
# XLA backward: the 11 ms fwd+bwd pair as a drop-in gradient path
# ---------------------------------------------------------------------------

def _xla_reference(q, k, v, bias, causal, dropout_rate, seed, heads,
                   window=None):
    """Plain-XLA mirror of the kernel semantics on (BH, S, D) layouts:
    softmax over keys THEN dropout on the probabilities (denominator sees
    no dropout), the SAME counter-based keep mask (``_dropout_keep`` is
    plain jnp, so the mask is bit-identical to the kernels'), NEG_INF dead
    rows emitting zeros.  Exists so ``backward="xla"`` can take
    ``jax.vjp`` of it — gradients consistent with the Pallas forward — and
    is the XLA band path: with a ``window`` the causal mask is the band's."""
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32))
    b = bias.astype(jnp.float32)
    if b.shape[0] != 1:
        b = jnp.repeat(b, heads, axis=0)          # (B, ., Sk) -> (BH, ., Sk)
    s = s + b
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 1)
        seen = cols <= rows
        if window is not None:
            seen = seen & (cols > rows - window)
        s = jnp.where(seen[None], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    dead = m <= NEG_INF / 2
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    p = p / jnp.where(l == 0.0, 1.0, l)[..., None]
    if dropout_rate > 0.0:
        seed32 = jnp.asarray(seed, jnp.int32)
        keep = jax.vmap(lambda bh: _dropout_keep(
            seed32, bh, 0, 0, (Sq, Sk), dropout_rate))(
                jnp.arange(BH, dtype=jnp.int32))
        p = p * keep / (1.0 - dropout_rate)
    o = jnp.einsum("bqk,bkd->bqd", p.astype(v.dtype), v)
    return jnp.where(dead[..., None], 0.0, o).astype(q.dtype)


def _xla_bwd(q, k, v, bias, causal, dropout_rate, seed, heads, out, lse, do,
             window=None):
    """(dq, dk, dv) via autodiff of :func:`_xla_reference`
    (``backward="xla"``, and the XLA band path).  The saved out/lse
    residuals are unused; XLA refuses nothing at these shapes and fuses its
    own recompute."""
    del out, lse
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _xla_reference(q_, k_, v_, bias, causal,
                                          dropout_rate, seed, heads, window),
        q, k, v)
    return vjp(do)


# ---------------------------------------------------------------------------
# public entry: custom_vjp over (q, k, v, bias)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def flash_attention(q, k, v, bias, seed=0, causal=False, dropout_rate=0.0,
                    heads=1, backward="auto", window=None):
    """Fused attention.  q (BH, Sq, D) pre-scaled; k/v (BH, Sk, D);
    bias (1|B, 1|Sq, Sk) additive f32 (use 0s for none); seed may be a traced
    int32 (fold your step rng into it).  Returns (BH, Sq, D).

    ``backward`` selects the gradient path while the Pallas forward stays:
    ``"pallas"`` (recompute kernels), ``"xla"`` (autodiff of the XLA math
    with the identical dropout mask), or ``"auto"``
    (:func:`_resolve_backward`: env > amp-config > pallas).

    ``window`` (with ``causal``; None: every earlier key) is a sliding
    window: query i sees keys j with i - window < j <= i, ``window`` keys
    counting itself.  The band kernels (``apex_flash_window_*``) visit only
    the tiles that meet the band, at the shapes they were measured at
    (:func:`_band_admitted`); every other shape takes the XLA band path.

    ``bias`` is NOT differentiated on this path (cotangent is zero): it
    models masks — data, not parameters — exactly like the reference's CUDA
    kernels, whose masks have no gradient.  Use ``impl='default'`` /
    ``attention_core`` for a *learned* additive bias.
    """
    _check_backward(backward)
    with annotate("apex.flash"):
        out, _ = _attention_fwd(q, k, v, bias, causal, dropout_rate, seed,
                                heads, window)
    return out


def _attention_fwd(q, k, v, bias, causal, dropout_rate, seed, heads, window):
    """``(out, lse)`` of :func:`flash_attention`'s forward; ``lse`` is None
    where the XLA band path ran."""
    if window is None:
        return _flash_fwd(q, k, v, bias, causal, dropout_rate, seed, heads)
    if not causal or window < 1:
        raise ValueError(f"a window of {window} keys wants causal=True and "
                         "a window of at least one key")
    if _band_admitted(q, k, bias, window, dropout_rate):
        return _band_fwd(q, k, v, bias, window, heads)
    return _xla_reference(q, k, v, bias, True, dropout_rate, seed, heads,
                          window), None


def _check_backward(backward):
    """Trace-time validation.  Called from the primal body AND _vjp_fwd
    (jax replaces the primal with _vjp_fwd under grad — same reason
    _check_bias_layout lives inside _flash_fwd) so a bogus value raises at
    the call site on both the inference and training paths, not at the
    first backward trace."""
    if backward not in BACKWARD_IMPLS:
        raise ValueError(f"backward must be one of {BACKWARD_IMPLS}, "
                         f"got {backward!r}")


def _vjp_fwd(q, k, v, bias, seed, causal, dropout_rate, heads, backward,
             window):
    _check_backward(backward)
    with annotate("apex.flash"):
        out, lse = _attention_fwd(q, k, v, bias, causal, dropout_rate, seed,
                                  heads, window)
    return out, (q, k, v, bias, seed, out, lse)


def _vjp_bwd(causal, dropout_rate, heads, backward, window, res, do):
    q, k, v, bias, seed, out, lse = res
    impl = _resolve_backward(backward)
    with annotate("apex.flash"):
        if impl == "xla" or (window is not None and lse is None):
            _tel_events.record_flash_bwd("xla")
            dq, dk, dv = _xla_bwd(q, k, v, bias, causal, dropout_rate, seed,
                                  heads, out, lse, do, window)
        elif window is not None:
            dq, dk, dv = _band_bwd(q, k, v, bias, window, heads, out, lse,
                                   do)
        else:
            dq, dk, dv = _flash_bwd(q, k, v, bias, causal, dropout_rate,
                                    seed, heads, out, lse, do)
    return dq, dk, dv, None, None


flash_attention.defvjp(_vjp_fwd, _vjp_bwd)


# ---------------------------------------------------------------------------
# the projection's layout: self-attention read from and written to (B, S, ·)
# ---------------------------------------------------------------------------
#
# A model computes q, k and v as ONE projection (B, S, 3·H·hd) and feeds its
# output projection from (B, S, H·hd).  The (B·H, S, hd) entry above needs
# both transposed around it: at hd 64 each such copy moves twice the bytes
# the data holds (a 64-wide row pads to 128 lanes in HBM), and so does every
# (B·H, S, 1) float32 ``lse`` / ``delta`` column.  Where the shape allows,
# the kernels below index the heads along the projection's last axis
# instead — one 128-lane block holds two heads of 64 (``pack`` 2 of
# ``_fwd_kernel``) — write the context in place, keep ``lse`` as rows
# (B, H/2, 2, S) with the queries on the lanes, and write the three
# gradients straight into the (B, S, 3·H·hd) cotangent of the projection.

_PACK = 2           # heads a 128-lane block


def _packed_tile(S, heads, hd, esz, bias_per_q):
    """The backward's tile ``(S, S)`` where the projection layout engages,
    else None: heads of 64 in pairs, S whole 128-lane blocks up to the
    whole-key tile's ``_WHOLE_KEY_MAX_BQ`` rows, the Pallas backward (the
    ``"auto"`` chain of :func:`_resolve_backward`), nobody's block or
    strategy pins (they name the (B·H, S, D) kernels), and a pair's whole
    tile within the VMEM budget by the ``"packed"`` model of
    :func:`vmem_estimate` (S 128 to 384 in either precision with either
    bias, S 512 in bf16 with a bias over keys alone: the shapes Mosaic is
    shown to take in ``tests/L0/test_gated_delta_rule.py``).  Every other
    shape takes the transposing path around :func:`flash_attention`."""
    if (hd * _PACK != 128 or heads % _PACK or S % 128
            or S > _WHOLE_KEY_MAX_BQ
            or _resolve_backward("auto") != "pallas"
            or _chosen_blocks(None, None, "fused") != (None, None)
            or _chosen_blocks(None, None, False) != (None, None)
            or _forced_fuse(None) is False
            or vmem_estimate(S, S, _PACK * hd, esz, bias_per_q, "packed")
            > _vmem_budget()):
        return None
    fwd = _clamp_blocks(None, None, _PACK * hd, esz, bias_per_q, sq=S, sk=S)
    return (S, S) if S % fwd[0] == 0 and S % fwd[1] == 0 else None


def _flash_fwd_packed(qkv, bias, causal, dropout_rate, seed, heads):
    """qkv (B, S, 3·H·64) as projected (q unscaled), bias (1|B, 1|S, S).
    Returns the context (B, S, H·64) and lse (B, H/2, 2, S) f32 — head
    2·g + j's row of queries at [b, g, j]."""
    B, S, width = qkv.shape
    W = width // 3
    pairs, lanes = heads // _PACK, W // (heads // _PACK)
    _check_bias_layout(qkv, bias, 1)
    bq, bk = _clamp_blocks(None, None, lanes, qkv.dtype.itemsize,
                           bias_per_q=bias.shape[1] != 1, sq=S, sk=S)
    seed_arr = jnp.reshape(jnp.asarray(seed, jnp.int32), (1,))
    vma = _out_vma(qkv, bias)
    b_bcast, q_bcast = bias.shape[0] == 1, bias.shape[1] == 1

    def part(t, rows):      # q (t 0), k (1) or v (2) of pair i % pairs
        return pl.BlockSpec(
            (1, rows, lanes),
            lambda i, qi, ki: (i // pairs, qi if t == 0 else ki,
                               t * pairs + i % pairs),
            memory_space=pltpu.VMEM)

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, bq=bq, bk=bk, causal=causal,
                          dropout_rate=dropout_rate, heads=heads,
                          pack=_PACK, scale=1.0 / math.sqrt(W // heads)),
        grid=(B * pairs, S // bq, S // bk),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),     # seed
                  part(0, bq), part(1, bk), part(2, bk),
                  pl.BlockSpec((1, 1 if q_bcast else bq, bk),
                               lambda i, qi, ki: (
                                   0 if b_bcast else i // pairs,
                                   0 if q_bcast else qi, ki),
                               memory_space=pltpu.VMEM)],
        out_specs=[
            pl.BlockSpec((1, bq, lanes),
                         lambda i, qi, ki: (i // pairs, qi, i % pairs),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, _PACK, bq),
                         lambda i, qi, ki: (i // pairs, i % pairs, 0, qi),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[_sds((B, S, W), qkv.dtype, vma),
                   _sds((B, pairs, _PACK, S), jnp.float32, vma)],
        scratch_shapes=[pltpu.VMEM((bq, _PACK), jnp.float32),
                        pltpu.VMEM((bq, _PACK), jnp.float32),
                        pltpu.VMEM((bq, lanes), jnp.float32)],
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="apex_flash_fwd",
    )(seed_arr, qkv, qkv, qkv, bias)
    return out, lse


def _bwd_packed_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref,
                       o_ref, lse_ref, dqkv_ref, dq_buf, dk_buf, dv_buf,
                       sems, *, pairs, causal, dropout_rate, scale):
    """The three gradients of one pair of heads of one sequence, whole (the
    tile holds every query and every key: ``_packed_tile``).  Each head's
    tile is :func:`_bwd_fused_kernel`'s (:func:`_tile_grads`) on the block
    with the other head's lanes of q, k and dO zeroed, so its dq, dk and dv
    land in the head's own lanes and the pair's blocks are sums.  ``delta``
    is rowsum(dO·O) over the head's lanes, a column here; ``lse`` arrives as
    rows and one transpose makes them columns.  The blocks go by DMA to the
    pair's three column blocks of the projection's cotangent (a BlockSpec
    output is one block of one array); the step after waits for them before
    it refills the buffers, so the copies overlap its work."""
    i, n = pl.program_id(0), pl.num_programs(0)
    b, g = i // pairs, i % pairs
    q = _scaled(q_ref[0], scale)
    k, v, do = k_ref[0], v_ref[0], do_ref[0]
    seq, hd = q.shape[0], q.shape[1] // _PACK
    lse = jnp.transpose(lse_ref[0, 0])                        # (S, pack)
    grads = None                        # dv, dk, dq: each head in its lanes
    for j in range(_PACK):
        mine = _head_lanes(j, hd, q.shape)
        q_j, k_j, do_j = _only(mine, q), _only(mine, k), _only(mine, do)
        p = jnp.exp(_recompute_s(q_j, k, bias_ref, 0, 0, seq, seq, causal)
                    - lse[:, j:j + 1])                        # (S, S)
        delta = jnp.sum(do_j.astype(jnp.float32)
                        * o_ref[0].astype(jnp.float32), axis=1, keepdims=True)
        keep = None
        if dropout_rate > 0.0:
            keep = _dropout_keep(seed_ref[0], _head_of(i, _PACK, j), 0, 0,
                                 p.shape, dropout_rate) / (1.0 - dropout_rate)
        head = _tile_grads(p, keep, q_j, k_j, v, do_j, delta)
        grads = head if grads is None else [a + h for a, h in zip(grads, head)]
    dv, dk, dq = grads

    def copies():
        return [pltpu.make_async_copy(
            buf, dqkv_ref.at[b, :, pl.ds(
                pl.multiple_of((t * pairs + g) * buf.shape[-1], 128),
                buf.shape[-1])], sems.at[t])
            for t, buf in enumerate((dq_buf, dk_buf, dv_buf))]

    @pl.when(i > 0)
    def _():
        for c in copies():
            c.wait()

    dq_buf[...] = (dq * scale).astype(dq_buf.dtype)
    dk_buf[...] = dk.astype(dk_buf.dtype)
    dv_buf[...] = dv.astype(dv_buf.dtype)
    for c in copies():
        c.start()

    @pl.when(i == n - 1)
    def _():
        for c in copies():
            c.wait()


def _flash_bwd_packed(qkv, bias, causal, dropout_rate, seed, heads, out,
                      lse, do):
    """d(qkv) (B, S, 3·H·64) from the projection, the context ``out`` and its
    cotangent ``do`` (B, S, H·64) and the packed forward's ``lse`` rows."""
    B, S, width = qkv.shape
    W = width // 3
    pairs, lanes = heads // _PACK, W // (heads // _PACK)
    _tel_events.record_flash_bwd("projection", S, S, 1)
    seed_arr = jnp.reshape(jnp.asarray(seed, jnp.int32), (1,))
    b_bcast, q_bcast = bias.shape[0] == 1, bias.shape[1] == 1

    def block(t):           # column block t·pairs + pair of a (B, S, ·) array
        return pl.BlockSpec((1, S, lanes),
                            lambda i: (i // pairs, 0, t * pairs + i % pairs),
                            memory_space=pltpu.VMEM)

    return pl.pallas_call(
        functools.partial(_bwd_packed_kernel, pairs=pairs, causal=causal,
                          dropout_rate=dropout_rate,
                          scale=1.0 / math.sqrt(W // heads)),
        grid=(B * pairs,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),           # seed
            block(0), block(1), block(2),                    # q, k, v
            pl.BlockSpec((1, 1 if q_bcast else S, S),
                         lambda i: (0 if b_bcast else i // pairs, 0, 0),
                         memory_space=pltpu.VMEM),
            block(0), block(0),                              # do, out
            pl.BlockSpec((1, 1, _PACK, S),
                         lambda i: (i // pairs, i % pairs, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=_sds((B, S, width), qkv.dtype,
                       _out_vma(qkv, bias, out, lse, do)),
        scratch_shapes=[pltpu.VMEM((S, lanes), qkv.dtype)] * 3
        + [pltpu.SemaphoreType.DMA((3,))],
        # a step waits for the copies of the step before it: in order
        compiler_params=_compiler_params(("arbitrary",)),
        interpret=_interpret(),
        name="apex_flash_bwd_fused",
    )(seed_arr, qkv, qkv, qkv, bias, do, out, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _packed_attention(qkv, bias, seed, causal, dropout_rate, heads):
    with annotate("apex.flash"):
        out, _ = _flash_fwd_packed(qkv, bias, causal, dropout_rate, seed,
                                   heads)
    return out


def _packed_vjp_fwd(qkv, bias, seed, causal, dropout_rate, heads):
    with annotate("apex.flash"):
        out, lse = _flash_fwd_packed(qkv, bias, causal, dropout_rate, seed,
                                     heads)
    return out, (qkv, bias, seed, out, lse)


def _packed_vjp_bwd(causal, dropout_rate, heads, res, do):
    qkv, bias, seed, out, lse = res
    with annotate("apex.flash"):
        dqkv = _flash_bwd_packed(qkv, bias, causal, dropout_rate, seed,
                                 heads, out, lse, do)
    return dqkv, None, None


_packed_attention.defvjp(_packed_vjp_fwd, _packed_vjp_bwd)


def flash_attention_qkv(qkv, bias, seed=0, causal=False, dropout_rate=0.0,
                        heads=1):
    """Self-attention over a fused QKV projection: qkv (B, S, 3·H·hd) as the
    projection wrote it, q NOT pre-scaled (1/sqrt(hd) is applied here); bias
    (1|B, 1|S, S) additive f32; returns the context (B, S, H·hd), what the
    output projection reads.  Semantics, dropout mask included, are
    :func:`flash_attention`'s on the transposed heads.

    The layout follows from the shape (:func:`_packed_tile`): heads of 64 in
    pairs at a length whose whole tile fits VMEM read the projection in
    place, two heads a 128-lane block; every other shape transposes to
    (B·H, S, hd) around :func:`flash_attention`, whose backward is the
    ``"auto"`` chain's."""
    B, S, width = qkv.shape
    hd = width // 3 // heads
    if _packed_tile(S, heads, hd, qkv.dtype.itemsize,
                    bias.shape[1] != 1) is not None:
        return _packed_attention(qkv, bias, seed, causal, dropout_rate, heads)
    q, k, v = (t.reshape(B, S, heads, hd).transpose(0, 2, 1, 3)
               for t in jnp.split(qkv, 3, axis=-1))
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    qf = (q.astype(jnp.float32) * scale).astype(qkv.dtype) \
        .reshape(B * heads, S, hd)
    ctx = flash_attention(qf, k.reshape(B * heads, S, hd),
                          v.reshape(B * heads, S, hd), bias, seed, causal,
                          dropout_rate, heads)
    return ctx.reshape(B, heads, S, hd).transpose(0, 2, 1, 3) \
        .reshape(B, S, heads * hd)
