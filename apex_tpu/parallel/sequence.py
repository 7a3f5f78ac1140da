"""Sequence/context parallelism: ring attention + Ulysses (all-to-all).

The reference has NO sequence parallelism (SURVEY §5.7: it scales batch,
never sequence) — but long-context is first-class for the TPU rebuild, and
the attention stack was written blockwise precisely so sequence sharding is
an extension, not a rewrite.  Two standard schemes, both as collective ops
to call inside ``shard_map`` with the ``seq`` mesh axis bound:

- ``ring_attention(q, k, v)``: q/k/v sharded along sequence; k/v blocks
  rotate around the ring via ``lax.ppermute`` while each device folds every
  block into a running online-softmax (flash-attention across devices, so
  per-device memory is O(S_local²-free): no (S, S) matrix ever
  materializes).  Communication rides ICI neighbor links — the canonical
  long-context layout.
- ``ulysses_attention(q, k, v)``: ``lax.all_to_all`` re-shards sequence ->
  heads, runs ordinary full-sequence attention on each device's head slice,
  and re-shards back.  Cheaper compute (one pass), all-to-all traffic; needs
  num_heads % axis_size == 0.

Both differentiate through the collectives (autodiff of ppermute/all_to_all
emits the reverse rotation), so the same function serves training.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .mesh import SEQ_AXIS
from ..utils.pallas import to_varying

_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)


class SequenceShardingError(ValueError):
    """A sequence-parallel structural constraint is violated (heads vs
    the Ulysses all-to-all, sequence length vs the ring chunking).
    Raised eagerly with the offending numbers in the message — the
    alternative is a cryptic reshape/all_to_all shape error several
    stack frames downstream."""


def validate_sp(seq: int, heads: int, sp: int, strategy: str) -> None:
    """Pre-trace validation for a sequence-parallel plan: ``seq`` must
    chunk evenly over ``sp`` devices (both ring and Ulysses shard the
    sequence), and Ulysses additionally re-shards heads, so ``heads``
    must divide over ``sp``.  Raises :class:`SequenceShardingError`
    naming the numbers."""
    if sp <= 1:
        return
    if seq % sp:
        raise SequenceShardingError(
            f"sequence length {seq} does not chunk over sp={sp} devices "
            f"({seq} % {sp} != 0) — ring/Ulysses sequence parallelism "
            "needs equal per-device sequence blocks")
    if strategy == "ulysses" and heads % sp:
        raise SequenceShardingError(
            f"num_heads {heads} does not divide over sp={sp} devices "
            f"({heads} % {sp} != 0) — the Ulysses all-to-all re-shards "
            "sequence -> heads; use ring attention or an sp that divides "
            "the head count")


def _block_attn(q, k, v, *, causal, q_off, k_off, m, l, acc):
    """Fold one k/v block into the running online softmax.
    q (B, H, Sq, D); k/v (B, H, Sk, D); m/l (B, H, Sq); acc like q@v."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32))
    if causal:
        Sq, Sk = q.shape[2], k.shape[2]
        qpos = q_off + jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 0)
        kpos = k_off + jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 1)
        s = jnp.where((kpos <= qpos)[None, None], s, _NEG)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # guard: rows with every key masked keep m at its (finite) init
    p = jnp.exp(s - m_new[..., None])
    if causal:
        p = jnp.where((s <= _NEG * 0.5), 0.0, p)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    acc_new = acc * corr[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return m_new, l_new, acc_new


def ring_attention(q, k, v, *, axis_name: str = SEQ_AXIS, causal: bool = False,
                   scale: Optional[float] = None):
    """Ring self/cross attention over a sequence-sharded axis.

    Call inside ``shard_map`` with q/k/v (B, H, S_local, D) — each device's
    contiguous sequence block (device i holds positions
    [i*S_local, (i+1)*S_local)).  Returns (B, H, S_local, D).
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    q = q * jnp.asarray(scale, q.dtype)

    # the running stats are per-device values: fresh constants are
    # replicated under the vma type system, while the loop body makes them
    # vary over everything q/k/v vary over (the ring axis, plus e.g. the
    # data axis of a dp x sp mesh) — lift them so the fori_loop carry is
    # type-stable
    carry_axes = (jax.typeof(q).vma | jax.typeof(k).vma | jax.typeof(v).vma
                  | {axis_name})
    m0 = to_varying(jnp.full((B, H, Sq), _NEG * 0.5, jnp.float32),
                    carry_axes)
    l0 = to_varying(jnp.zeros((B, H, Sq), jnp.float32), carry_axes)
    a0 = to_varying(jnp.zeros((B, H, Sq, D), jnp.float32), carry_axes)
    perm = [(j, (j + 1) % n) for j in range(n)]
    q_off = idx * Sq

    def step(i, carry):
        m, l, acc, kk, vv = carry
        src = (idx - i) % n                   # origin of the block we hold
        m, l, acc = _block_attn(q, kk, vv, causal=causal, q_off=q_off,
                                k_off=src * Sk, m=m, l=l, acc=acc)
        # rotate after folding (the final rotation returns the blocks to
        # their origin — a wasted hop kept for a type-stable loop carry)
        kk = jax.lax.ppermute(kk, axis_name, perm)
        vv = jax.lax.ppermute(vv, axis_name, perm)
        return m, l, acc, kk, vv

    m, l, acc, _, _ = jax.lax.fori_loop(0, n, step, (m0, l0, a0, k, v))
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    return out.astype(q.dtype)


def ulysses_attention(q, k, v, *, axis_name: str = SEQ_AXIS,
                      causal: bool = False, scale: Optional[float] = None,
                      attn_fn=None):
    """Ulysses all-to-all context parallelism.

    Inside ``shard_map``: q/k/v (B, H, S_local, D) sequence-sharded.
    ``all_to_all`` converts to (B, H/n, S_full, D) head-sharding, runs full
    attention per local head group (``attn_fn`` override hooks in e.g. the
    Pallas flash kernel), and converts back.  Requires H % axis_size == 0.
    """
    n = jax.lax.axis_size(axis_name)
    B, H, S_local, D = q.shape
    if H % n:
        raise SequenceShardingError(
            f"num_heads {H} does not divide over seq axis size {n} "
            f"({H} % {n} != 0) — the Ulysses all-to-all re-shards "
            "sequence -> heads; use ring attention or a head count the "
            "axis divides")
    if scale is None:
        scale = 1.0 / (D ** 0.5)

    def to_heads(x):
        # (B, H, S_local, D) -> (B, H/n, S_full, D)
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    def to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
    if attn_fn is not None:
        out = attn_fn(qh * scale, kh, vh, causal=causal)
        # (attn_fn contract: q arrives pre-scaled, returns (B, H/n, S, D))
    else:
        s = jnp.einsum("bhqd,bhkd->bhqk", qh.astype(jnp.float32) * scale,
                       kh.astype(jnp.float32))
        if causal:
            S = s.shape[-1]
            rows = jax.lax.broadcasted_iota(jnp.int32, (S, S), 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, (S, S), 1)
            s = jnp.where((cols <= rows)[None, None], s, _NEG)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhqk,bhkd->bhqd", p, vh.astype(jnp.float32))
    return to_seq(out.astype(q.dtype))


def ulysses_flash_attention(q, k, v, *, axis_name: str = SEQ_AXIS,
                            causal: bool = False,
                            scale: Optional[float] = None,
                            backward: str = "auto"):
    """Ulysses with the Pallas flash kernel on the gathered-sequence leg.

    After the all_to_all each device holds its head group at FULL sequence
    length — exactly the aligned layout the flash kernel wants (causal
    block-skipping included, online softmax, O(S) attention memory).  This
    is the long-context composition: all_to_all re-shard + flash core,
    with gradients flowing through the kernel's custom VJP and the linear
    all_to_alls.  Contrast ``ring_attention``, whose cross-device
    online-softmax already never materializes the score matrix.

    ``backward`` routes the flash core's gradient path
    (``"pallas"|"xla"|"auto"`` — see :func:`flash_attention`); the
    all_to_alls differentiate the same either way."""
    from ..contrib.multihead_attn.flash import flash_attention

    def attn_fn(qh, kh, vh, causal):
        B, Hl, S, D = qh.shape
        Sk = kh.shape[2]           # cross-attention: kv length may differ
        bias = jnp.zeros((1, 1, Sk), jnp.float32)
        out = flash_attention(qh.reshape(B * Hl, S, D),
                              kh.reshape(B * Hl, Sk, D),
                              vh.reshape(B * Hl, Sk, D),
                              bias, causal=causal, heads=Hl,
                              backward=backward)
        return out.reshape(B, Hl, S, D)

    return ulysses_attention(q, k, v, axis_name=axis_name, causal=causal,
                             scale=scale, attn_fn=attn_fn)
