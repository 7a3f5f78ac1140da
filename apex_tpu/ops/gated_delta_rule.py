"""The chunked gated delta rule as a Pallas kernel pair (docs/qwen3_next.md,
"The chunked gated delta rule"): ``o_t = S_tᵀ q_t`` of ``S_t = e^{g_t}
S_{t-1} + k_t ⊗ β_t (v_t - (e^{g_t} S_{t-1})ᵀ k_t)`` with a chunk's
triangular system, its products and the carried state in VMEM.

The arithmetic is that of ``models.qwen3_next._chunked_rule`` (its twin in
``jax.numpy``, the path of every shape this kernel does not take): ``γ``,
every decay, ``A``, ``T = (I - A)⁻¹`` and the carried state in float32 — the
products of the triangular system at ``Precision.HIGHEST`` —, the other
products on operands of ``v``'s dtype accumulating in float32.

- **Grid** (sequence, key head, block of chunks): the first two
  ``parallel``, the last ``arbitrary``.  A grid step holds up to
  ``_CHUNKS_A_STEP`` chunks of one key head and ALL its value heads (the
  published two): ``q kᵀ`` and ``k kᵀ`` are taken once for them.  What no
  state enters (decays, ``A``, ``T``, ``T (β v ‖ β e^γ k)``, the scores) is
  taken for every chunk of the step first, independent chains the scheduler
  can interleave; then the chunks in order: ``w`` over ``q e^γ`` in one
  2C-row product against the state, ``U``, ``O``, the state's update.  The
  state (H_v / H_k x d_k x d_v float32) is a VMEM scratch, zeroed at the
  first block.
- **T** in 16 x 16 blocks, several matrices side by side in the 128 lanes
  (:func:`_packed_inverse`).
- **Operands**: ``q``, ``k`` as (B, S, H_k·d_k) and ``v``, ``o`` as (B, S,
  H_v·d_v) — the layouts the mixer has them in, a head a 128-lane column
  block —, ``γ`` (XLA's cumulative sum within a chunk) and ``β`` as (B, S,
  H_v) float32: a head's column is read with a lane mask and turned into a
  row with the identity's.
- **Backward**: a forward sweep of its own (the same kernel body writing, a
  chunk and head, the state it entered, ``T``, ``w`` and ``U`` in place of
  ``o``) and the reverse kernel: chunks in reverse, the state's cotangent a
  VMEM scratch; ``dq`` and ``dk`` summed over a key head's value heads in
  the step that holds both; ``dγ`` and ``dβ`` leave as lanes of one
  128-lane block.  Residuals of the ``custom_vjp`` are ``q k v g β``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..pyprof import annotate
from ..utils.pallas import (compiler_params as _compiler_params,
                            interpret_mode as _interpret)

#: chunks, and bytes of a row of one head of ``v``'s dtype times rows, a grid
#: step holds at most: the chunks' state-free parts are independent work, but
#: the kernels are unrolled over them — 4 chunks of 64 bfloat16 rows measure
#: as fast as 8 and trace and lower in a third of the time (PERF.md §6 PR 35);
#: float32 operands take half the rows, for the VMEM
_CHUNKS_A_STEP, _ROW_BYTES_A_STEP = 4, 512
_LANES, _CHUNK = 128, 64

_HIGHEST = jax.lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))      # a b
_NT = (((1,), (1,)), ((), ()))      # a bᵀ
_TN = (((0,), (0,)), ((), ()))      # aᵀ b


def takes(key_dim: int, value_dim: int, chunk: int) -> bool:
    """The shape rule: what the kernels were held to on the chip and in the
    tests — heads one 128-lane tile wide and a chunk of 64 steps, the
    published 128, 128, 64.  Every other shape is the ``jax.numpy`` form's."""
    return key_dim == value_dim == _LANES and chunk == _CHUNK


def _exact(a, b, dims=_NN):
    """A float32 product of the triangular system: true float32."""
    return jax.lax.dot_general(a, b, dims, precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _product(a, b, dims=_NN):
    """A product on operands of the model's dtype, accumulated in float32
    (float32 operands: a true float32 product; narrower ones have one
    precision, whatever ``jax.default_matmul_precision`` asks around the
    trace — Mosaic refuses another)."""
    return jax.lax.dot_general(
        a, b, dims, preferred_element_type=jnp.float32,
        precision=_HIGHEST if a.dtype == jnp.float32
        else jax.lax.Precision.DEFAULT)


def _unit_lower_inverses(matrices):
    """``(I - A)⁻¹`` of every strictly lower triangular ``A`` (C, C) float32
    of the list, two side by side in the 128 lanes (:func:`_packed_inverse`);
    an odd one out beside a zero matrix, whose inverse nobody reads."""
    chunk = matrices[0].shape[-1]
    width = _LANES // chunk
    inverses = []
    for at in range(0, len(matrices), width):
        pack = matrices[at:at + width]
        packed = _packed_inverse(jnp.concatenate(
            pack + [jnp.zeros_like(pack[0])] * (width - len(pack)), axis=1))
        inverses += [packed[:, j * chunk:(j + 1) * chunk]
                     for j in range(len(pack))]
    return inverses


def _packed_inverse(a):
    """``[(I - A₀)⁻¹ ‖ (I - A₁)⁻¹]`` of ``a = [A₀ ‖ A₁]`` (C, L) float32 — L
    the 128 lanes —, every ``A`` strictly lower triangular, in blocks: the
    16 x 16 diagonal blocks of all of them at once as ``Π_m (I + D^(2^m))``,
    then ``[[T₁, 0], [T₂ A₂₁ T₁, T₂]]`` from 16 to 32 to C.  A level's blocks
    lie side by side in the L lanes and multiply a block-diagonal L x L:
    ``[X₀ ‖ X₁ ‖ …] · diag(Y₀, Y₁, …) = [X₀ Y₀ ‖ X₁ Y₁ ‖ …]``, so a product
    streams 16 or 32 rows through the MXU where the doubling form over whole
    matrices streams C — the same true-float32 products, a third of the
    passes."""
    chunk, lanes = a.shape

    def group(size):
        return jax.lax.broadcasted_iota(jnp.int32, (size, lanes), 1) // size

    def spread(x, size, source=lambda j: j):
        """(L, L): the rows of lane group ``j`` hold ``x``'s lane group
        ``source(j)`` and zeros elsewhere."""
        at = group(size)
        return jnp.concatenate(
            [jnp.zeros_like(x) if source(j) is None
             else jnp.where(at == source(j), x, 0.0)
             for j in range(lanes // size)], axis=0)

    def placed(size, blocks, shift):
        """(size, L): every matrix's row block ``blk`` of ``blocks``, kept
        where its lanes are block ``blk - shift``'s: the block on the
        diagonal (``shift`` 0) or the one left of it."""
        within = group(size) % (chunk // size)
        return sum(jnp.where(within == blk - shift,
                             a[blk * size:(blk + 1) * size], 0.0)
                   for blk in blocks)

    size = 16
    power, exponent = placed(size, range(chunk // size), 0), 1
    rows = jax.lax.broadcasted_iota(jnp.int32, power.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, power.shape, 1)
    inverse = power + (cols % size == rows).astype(a.dtype)
    while 2 * exponent < size:
        power, exponent = _exact(power, spread(power, size)), 2 * exponent
        inverse = inverse + _exact(inverse, spread(power, size))
    while size < chunk:
        odd = group(size) % 2 == 1
        upper, lower = jnp.where(odd, 0.0, inverse), jnp.where(odd, inverse,
                                                               0.0)
        joint = _exact(placed(size, range(1, chunk // size, 2), 1),
                       spread(inverse, size))                       # A₂₁ T₁
        joint = _exact(lower, spread(
            joint, size, lambda j: j - 1 if j % 2 else None))       # T₂ ·
        inverse, size = jnp.concatenate([upper, joint + lower],
                                        axis=0), 2 * size
    return inverse


def _column(block, lane):
    """Lane ``lane`` (traced) of ``block`` (R, H) as (R, 1)."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
    return jnp.sum(jnp.where(lanes == lane, block, 0.0), axis=1,
                   keepdims=True)


def _as_row(column, eye):
    """(C, 1) -> (1, C)."""
    return jnp.sum(jnp.where(eye, column, 0.0), axis=0, keepdims=True)


def _head_columns(ref, per):
    """The (R, 1) columns of the grid step's ``per`` value heads in ``ref``
    (R, H_v)."""
    first = pl.program_id(1) * per
    return [_column(ref[...], first + r) for r in range(per)]


def _scores_of(q, k):
    """``(q kᵀ, k kᵀ)`` (C, C) float32 in one product: a key head's, shared
    by its value heads."""
    both = _product(jnp.concatenate([q, k], axis=0), k, _NT)
    return both[:q.shape[0]], both[q.shape[0]:]


def _chunk_locals(q, k, v, gamma, beta, qk, kk, triangle):
    """What a chunk and value head needs of its own tokens.  ``q``, ``k`` (C,
    d_k), ``v`` (C, d_v) of the model's dtype; ``gamma``, ``beta`` (C, 1)
    float32; ``qk``, ``kk`` (C, C) float32."""
    dt = v.dtype
    eye, lower, strict = triangle
    decay = jnp.where(lower, jnp.exp(gamma - _as_row(gamma, eye)), 0.0)
    last = gamma[-1:]                                       # γ_C (1, 1)
    grown, shrunk = jnp.exp(gamma), jnp.exp(last - gamma)
    return {
        "decay": decay, "grown": grown, "shrunk": shrunk,   # e^γ, e^{γ_C - γ}
        "a": -jnp.where(strict, kk * decay * beta, 0.0),
        "scores": (qk * decay).astype(dt),
        "v_in": (v.astype(jnp.float32) * beta).astype(dt),          # β v
        "k_in": (k.astype(jnp.float32) * (beta * grown)).astype(dt),
        "q_in": (q.astype(jnp.float32) * grown).astype(dt),
        "k_out": (k.astype(jnp.float32) * shrunk).astype(dt),
        "whole": jnp.exp(last)}


def _triangle(chunk):
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    return rows == cols, rows >= cols, rows > cols


def _forward_kernel(chunk, per, sweep, q_ref, k_ref, v_ref, gamma_ref,
                    beta_ref, *refs):
    """One block of chunks of one key head: ``q_ref``, ``k_ref`` (R, d_k),
    ``v_ref`` (R, per·d_v), ``gamma_ref``, ``beta_ref`` (R, H_v).  Writes
    ``o`` (R, per·d_v) or, as the backward's sweep, the entering state, ``T``,
    ``w`` and ``U`` of every chunk and head."""
    if sweep:
        state_out, t_out, w_out, u_out, state_ref = refs
    else:
        o_ref, state_ref = refs
    dv, dt = v_ref.shape[-1] // per, v_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    triangle = _triangle(chunk)
    gammas, betas = _head_columns(gamma_ref, per), _head_columns(beta_ref, per)
    local = {}
    for n in range(q_ref.shape[0] // chunk):
        rows = slice(n * chunk, (n + 1) * chunk)
        q, k = q_ref[rows, :], k_ref[rows, :]
        qk, kk = _scores_of(q, k)
        for r in range(per):
            local[n, r] = _chunk_locals(
                q, k, v_ref[rows, r * dv:(r + 1) * dv], gammas[r][rows],
                betas[r][rows], qk, kk, triangle)
    for c, t in zip(local.values(), _unit_lower_inverses(
            [c.pop("a") for c in local.values()])):
        solved = _product(t.astype(dt), jnp.concatenate(
            [c.pop("v_in"), c.pop("k_in")], axis=1))
        c["t"], c["v_tilde"] = t, solved[:, :dv]
        c["w"] = solved[:, dv:].astype(dt)
    states = [state_ref[r] for r in range(per)]
    for (n, r), c in local.items():
        rows, state = slice(n * chunk, (n + 1) * chunk), states[r]
        entering = state.astype(dt)
        read = _product(jnp.concatenate([c["w"], c["q_in"]], axis=0),
                        entering)                           # w S over q_in S
        written = (c["v_tilde"] - read[:chunk]).astype(dt)          # U
        if sweep:
            state_out[n, r] = state
            t_out[n, r] = c["t"]
            w_out[n, r] = c["w"]
            u_out[n, r] = written
        else:
            o_ref[rows, r * dv:(r + 1) * dv] = (
                read[chunk:] + _product(c["scores"], written)).astype(dt)
        states[r] = state * c["whole"] + _product(c["k_out"], written, _TN)
    for r in range(per):
        state_ref[r] = states[r]


def _reverse_kernel(chunk, per, q_ref, k_ref, v_ref, gamma_ref, beta_ref,
                    do_ref, state_in, t_in, w_in, u_in, dq_ref, dk_ref,
                    dv_ref, aux_ref, dstate_ref):
    """The reverse pass over one block of chunks of one key head, the blocks
    and the chunks inside one in reverse: the sweep's ``state_in``, ``t_in``,
    ``w_in``, ``u_in`` (n, per, ·, ·) beside the forward's operands and
    ``do_ref``; ``dstate_ref`` carries the state's cotangent.  ``aux_ref``
    (R, 128) takes ``dγ`` in lanes [0, per) and ``dβ`` in [per, 2 per)."""
    dk, dv = q_ref.shape[-1], v_ref.shape[-1] // per
    dt, f32 = v_ref.dtype, jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)

    triangle = _triangle(chunk)
    eye, _, strict = triangle
    row_id = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (chunk, _LANES), 1)
    gammas, betas = _head_columns(gamma_ref, per), _head_columns(beta_ref, per)

    def rowsum(x):
        return jnp.sum(x, axis=1, keepdims=True)

    def total(x):                                           # (1, 1)
        return jnp.sum(rowsum(x), axis=0, keepdims=True)

    for n in reversed(range(q_ref.shape[0] // chunk)):
        rows = slice(n * chunk, (n + 1) * chunk)
        q, k = q_ref[rows, :], k_ref[rows, :]
        q32, k32 = q.astype(f32), k.astype(f32)
        qk, kk = _scores_of(q, k)
        dq, dkey = jnp.zeros((chunk, dk), f32), jnp.zeros((chunk, dk), f32)
        aux = jnp.zeros((chunk, _LANES), f32)
        for r in range(per):
            gamma, beta = gammas[r][rows], betas[r][rows]
            v = v_ref[rows, r * dv:(r + 1) * dv]
            c = _chunk_locals(q, k, v, gamma, beta, qk, kk, triangle)
            decay, grown, shrunk = c["decay"], c["grown"], c["shrunk"]
            t, w, written = t_in[n, r], w_in[n, r], u_in[n, r]
            state, dstate = state_in[n, r], dstate_ref[r]
            entering, dleaving = state.astype(dt), dstate.astype(dt)
            do = do_ref[rows, r * dv:(r + 1) * dv]
            # U: read by the scores and by the state's update
            dwritten = _product(c["scores"], do, _TN) \
                + _product(c["k_out"], dleaving)
            dwritten_dt = dwritten.astype(dt)
            dscores = _product(do, written, _NT)                    # (C, C)
            # against the entering state: d(q e^γ) over -dw
            against = _product(jnp.concatenate([do, dwritten_dt], axis=0),
                               entering, _NT)
            dq_in, dw = against[:chunk], -against[chunk:]
            dk_out = _product(written, dleaving, _NT)
            dwhole = total(state * dstate)
            dstate_ref[r] = dstate * c["whole"] + _product(
                jnp.concatenate([c["q_in"], -w], axis=0),
                jnp.concatenate([do, dwritten_dt], axis=0), _TN)
            # through T (β v ‖ β e^γ k)
            cotangent = jnp.concatenate([dwritten_dt, dw.astype(dt)], axis=1)
            operands = jnp.concatenate([c["v_in"], c["k_in"]], axis=1)
            dt_matrix = _product(cotangent, operands, _NT)          # (C, C)
            doperands = _product(t.astype(dt), cotangent, _TN)
            dv_in, dk_in = doperands[:, :dv], doperands[:, dv:]
            # Ā = Tᵀ T̄ Tᵀ, A = -strict(β_i (k kᵀ)_ij decay_ij)
            da = _exact(_exact(t, dt_matrix, _TN), t, _NT)
            minus = jnp.where(strict, -da, 0.0)
            through_a = minus * kk * decay                  # a row: dβ
            dkk = (minus * decay * beta).astype(dt)
            dqk = (dscores * decay).astype(dt)
            ddecay = through_a * beta + dscores * qk * decay        # · decay
            dboth = jnp.concatenate([dkk, dqk], axis=0)
            onto = _product(dboth, k)
            dkey = dkey + onto[:chunk] + _product(
                dboth, jnp.concatenate([k, q], axis=0), _TN)
            dq = dq + onto[chunk:] + dq_in * grown
            dkey = dkey + dk_in * (beta * grown) + dk_out * shrunk
            dv_ref[rows, r * dv:(r + 1) * dv] = (dv_in * beta).astype(dt)
            of_k_in, of_q_in = rowsum(dk_in * k32), rowsum(dq_in * q32)
            of_k_out = rowsum(dk_out * k32) * shrunk
            dbeta = rowsum(through_a) + rowsum(dv_in * v.astype(f32)) \
                + grown * of_k_in
            dgamma = rowsum(ddecay) \
                - jnp.sum(jnp.where(eye, jnp.sum(ddecay, axis=0,
                                                 keepdims=True), 0.0),
                          axis=1, keepdims=True) \
                + grown * (beta * of_k_in + of_q_in) - of_k_out \
                + jnp.where(row_id == chunk - 1,
                            total(of_k_out) + c["whole"] * dwhole, 0.0)
            aux = jnp.where(lanes == r, dgamma, aux)
            aux = jnp.where(lanes == per + r, dbeta, aux)
        dq_ref[rows, :] = dq.astype(dq_ref.dtype)
        dk_ref[rows, :] = dkey.astype(dk_ref.dtype)
        aux_ref[rows, :] = aux


class _Shape(NamedTuple):
    """A call's sizes: ``chunks`` whole chunks cover ``seq``, a grid step
    holds ``a_step`` of them."""
    bsz: int
    seq: int
    groups: int
    dk: int
    heads: int
    dv: int
    chunk: int
    chunks: int
    a_step: int

    @classmethod
    def of(cls, q, v, chunk):
        bsz, seq, groups, dk = q.shape
        heads, dv = v.shape[2:]
        chunks = -(-seq // chunk)
        rows = max(chunk, _ROW_BYTES_A_STEP // v.dtype.itemsize)
        a_step = max(n for n in range(1, _CHUNKS_A_STEP + 1)
                     if chunks % n == 0 and n * chunk <= rows)
        return cls(bsz, seq, groups, dk, heads, dv, chunk, chunks, a_step)

    @property
    def per(self):                      # value heads a key head
        return self.heads // self.groups

    @property
    def padded(self):
        return self.chunks * self.chunk

    @property
    def grid(self):
        return self.bsz, self.groups, self.chunks // self.a_step

    def specs(self, reverse=False):
        """Block specs of ``q``/``k`` (and ``dγ ‖ dβ``: 128 lanes a key head
        too), of ``v``/``o``, of ``γ``/``β``, and of the sweep's (B, H_k,
        chunks, per, ·, ·) arrays; ``reverse`` walks the blocks from the
        last."""
        rows, last = self.a_step * self.chunk, self.grid[2] - 1
        at = (lambda c: last - c) if reverse else (lambda c: c)
        narrow = pl.BlockSpec((None, rows, self.dk),
                              lambda b, g, c: (b, at(c), g))
        wide = pl.BlockSpec((None, rows, self.per * self.dv),
                            lambda b, g, c: (b, at(c), g))
        scalars = pl.BlockSpec((None, rows, self.columns),
                               lambda b, g, c: (b, at(c), 0))
        kept = [pl.BlockSpec((None, None, self.a_step, self.per) + tail,
                             lambda b, g, c: (b, g, at(c), 0, 0, 0))
                for tail in self.kept_tails]
        return narrow, wide, scalars, kept

    @property
    def columns(self):
        """Of ``γ`` and ``β`` as the kernels read them: a column a value head,
        and two where there is one head — Mosaic cannot spread ``γ_C`` over a
        state from a one-lane block, whose lane mask folds away."""
        return max(2, self.heads)

    @property
    def kept_tails(self):               # state, T, w, U of a chunk and head
        return ((self.dk, self.dv), (self.chunk, self.chunk),
                (self.chunk, self.dk), (self.chunk, self.dv))

    def operands(self, q, k, v, g, beta):
        """The kernels' operands: padded to whole chunks (β = 0, g = 0: a
        step that writes nothing and passes the state unchanged), heads
        folded into lanes, ``γ`` the cumulative sum of ``g`` within a
        chunk."""
        pad = self.padded - self.seq
        if pad:
            q, k, v, g, beta = (
                jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                for t in (q, k, v, g, beta))
        gamma = jnp.cumsum(
            g.reshape(self.bsz, self.chunks, self.chunk, self.heads),
            axis=2).reshape(g.shape)
        gamma, beta = (
            jnp.pad(t, ((0, 0), (0, 0), (0, self.columns - self.heads)))
            for t in (gamma, beta))
        return (q.reshape(self.bsz, self.padded, self.groups * self.dk),
                k.reshape(self.bsz, self.padded, self.groups * self.dk),
                v.reshape(self.bsz, self.padded, self.heads * self.dv),
                gamma, beta)


def _call(kernel, name, shape, **more):
    return pl.pallas_call(
        kernel, grid=shape.grid, name=name, interpret=_interpret(),
        scratch_shapes=[pltpu.VMEM((shape.per, shape.dk, shape.dv),
                                   jnp.float32)],
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary")), **more)


# jitted: traced once a signature, and lowered once a program however many
# layers call it — unrolled over chunks and heads, a kernel's trace is seconds
@functools.partial(jax.jit, static_argnames=("chunk", "sweep"))
def _forward(q, k, v, g, beta, chunk, sweep=False):
    """``o`` (B, S, H_v, d_v) or, with ``sweep``, what the reverse kernel
    reads of the forward: (states, T, w, U), float32 the first two."""
    shape = _Shape.of(q, v, chunk)
    narrow, wide, scalars, kept = shape.specs()
    operands = shape.operands(q, k, v, g, beta)
    lead = (shape.bsz, shape.groups, shape.chunks, shape.per)
    out = _call(
        functools.partial(_forward_kernel, chunk, shape.per, sweep),
        "apex_gdn_rule_sweep" if sweep else "apex_gdn_rule_fwd", shape,
        in_specs=[narrow, narrow, wide, scalars, scalars],
        out_specs=kept if sweep else wide,
        out_shape=[jax.ShapeDtypeStruct(lead + tail, dtype)
                   for tail, dtype in zip(
                       shape.kept_tails,
                       (jnp.float32, jnp.float32, v.dtype, v.dtype))]
        if sweep else jax.ShapeDtypeStruct(operands[2].shape, v.dtype),
    )(*operands)
    if sweep:
        return out
    return out.reshape(shape.bsz, -1, shape.heads, shape.dv)[:, :shape.seq]


@functools.partial(jax.jit, static_argnames=("chunk",))
def _backward(q, k, v, g, beta, chunk, do):
    """(dq, dk, dv, dg, dβ) of :func:`_forward`'s ``o`` under ``do``."""
    shape = _Shape.of(q, v, chunk)
    bsz, seq, groups, heads, per = (shape.bsz, shape.seq, shape.groups,
                                    shape.heads, shape.per)
    kept_arrays = _forward(q, k, v, g, beta, chunk=chunk, sweep=True)
    narrow, wide, scalars, kept = shape.specs(reverse=True)
    aux_spec = pl.BlockSpec((None, narrow.block_shape[1], _LANES),
                            narrow.index_map)
    operands = shape.operands(q, k, v, g, beta)
    do = jnp.pad(do, ((0, 0), (0, shape.padded - seq), (0, 0), (0, 0))
                 ).reshape(operands[2].shape)
    dq, dkey, dvalue, aux = _call(
        functools.partial(_reverse_kernel, chunk, per),
        "apex_gdn_rule_bwd", shape,
        in_specs=[narrow, narrow, wide, scalars, scalars, wide, *kept],
        out_specs=(narrow, narrow, wide, aux_spec),
        out_shape=(jax.ShapeDtypeStruct(operands[0].shape, q.dtype),
                   jax.ShapeDtypeStruct(operands[1].shape, k.dtype),
                   jax.ShapeDtypeStruct(operands[2].shape, v.dtype),
                   jax.ShapeDtypeStruct((bsz, shape.padded, groups * _LANES),
                                        jnp.float32)),
    )(*operands, do, *kept_arrays)
    aux = aux.reshape(bsz, shape.chunks, chunk, groups, _LANES)
    # γ is a cumulative sum within a chunk: its reverse is one from the end
    dgamma = aux[..., :per].reshape(bsz, shape.chunks, chunk, heads)
    dg = jnp.flip(jnp.cumsum(jnp.flip(dgamma, 2), axis=2), 2)
    dbeta = aux[..., per:2 * per]
    return (dq.reshape(bsz, -1, groups, shape.dk)[:, :seq],
            dkey.reshape(bsz, -1, groups, shape.dk)[:, :seq],
            dvalue.reshape(bsz, -1, heads, shape.dv)[:, :seq],
            dg.reshape(bsz, -1, heads)[:, :seq].astype(g.dtype),
            dbeta.reshape(bsz, -1, heads)[:, :seq].astype(beta.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def gated_delta_rule(q, k, v, g, beta, chunk: int):
    """``models.qwen3_next.gated_delta_rule`` at the shapes :func:`takes`
    holds: ``q``, ``k`` (B, S, H_k, d_k), ``v`` (B, S, H_v, d_v), ``g``,
    ``beta`` (B, S, H_v) float32 -> (B, S, H_v, d_v) of ``v``'s dtype."""
    with annotate("apex.gdn_rule"):
        return _forward(q, k, v, g, beta, chunk=chunk)


def _vjp_fwd(q, k, v, g, beta, chunk):
    with annotate("apex.gdn_rule"):
        return _forward(q, k, v, g, beta, chunk=chunk), (q, k, v, g, beta)


def _vjp_bwd(chunk, residuals, do):
    with annotate("apex.gdn_rule"):
        return _backward(*residuals, chunk=chunk, do=do)


gated_delta_rule.defvjp(_vjp_fwd, _vjp_bwd)
