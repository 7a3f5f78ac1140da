"""Expert parallelism: MoE expert sharding + all-to-all token routing.

Not in the reference (SURVEY §2.3 lists its parallelism as DP + sharded-DP
only), but first-class for the TPU rebuild alongside sequence parallelism:
the mesh/axis machinery is already here, and expert parallelism is the
remaining standard sharding family (dp/tp/sp/ep).

Design (switch-style top-1 routing, capacity-factored, fully static shapes
for XLA):

- experts are sharded over the ``expert`` mesh axis: each device owns
  ``E / n`` experts' FFN weights;
- tokens are routed by a (learned) router; each device keeps a fixed
  per-expert capacity buffer (static shape — required under jit), dispatch
  is a one-hot matmul (MXU-friendly, no scatter);
- ``lax.all_to_all`` exchanges the per-expert token buffers so each device
  receives exactly the tokens bound for ITS experts, runs its local expert
  FFNs batched, and the reverse all-to-all returns outputs;
- overflowed tokens (beyond capacity) pass through with zero expert output
  (standard switch behavior), router gets the usual softmax-prob scaling
  so gradients train it.

``moe_ffn`` is the collective op (call inside shard_map with the axis
bound; degrades to single-device MoE when unbound); ``MoELayer`` carries
init/apply around it.

:func:`routed_experts` is the other family (docs/lfm2.md,
docs/nemotron_h.md, docs/qwen3_next.md): the model's scores (sigmoid with a
selection bias that chooses and does not weigh, or a softmax over all),
top-k of ALL experts, and a share of them held here.  The
expert's form (gated SiLU or squared ReLU) and the rows it reads are the
model's; its matrix products are grouped ones
(``jax.lax.ragged_dot``) over the rows the held experts were sent, sorted by
expert, in a buffer of twice their even share of the T·k assignments
(:func:`buffer_rows`); a load that outgrows the buffer is walked again by
the same body, so it drops no assignment at any imbalance.  A walk's rows
are summed back to the tokens in the form that moves fewer rows
(:func:`sums_in_row_space`): sorted by token, neighbours added and one row a
token gathered where few of a token's experts are held, a gather a slot
where the buffer is as long as the tokens.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..pyprof import annotate
from ..telemetry import events as _tel_events
from .mesh import axis_is_bound

EXPERT_AXIS = "expert"


def _one_hot_dispatch(logits, n_experts, capacity):
    """Token -> (expert, slot) assignment as dense one-hot tensors.

    logits (T, E).  Returns (dispatch (T, E, C) bool-ish f32, combine
    (T, E, C) f32 with router prob, aux load-balancing loss scalar)."""
    T, E = logits.shape
    if E != n_experts:
        raise ValueError(
            f"router width {E} != expert count {n_experts} "
            "(w_in leading dim x expert-axis size)")
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert = jnp.argmax(probs, axis=-1)                 # (T,) top-1
    onehot = jax.nn.one_hot(expert, E, dtype=jnp.float32)   # (T, E)

    # position of each token within its expert's queue (prefix count)
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0     # (T, E), -1 elsewhere
    in_cap = (pos >= 0) & (pos < capacity)
    slot = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                          dtype=jnp.float32)            # (T, E, C)
    dispatch = slot * in_cap[..., None]
    gate = jnp.sum(probs * onehot, axis=-1)             # (T,) chosen prob
    combine = dispatch * gate[:, None, None]

    # switch-transformer load-balancing aux loss: E * sum_e f_e * p_e
    frac_tokens = jnp.mean(onehot, axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac_tokens * frac_probs)
    return dispatch, combine, aux


def moe_ffn(x, router_w, w_in, w_out, *, axis_name: Optional[str] = EXPERT_AXIS,
            capacity_factor: float = 1.25):
    """Top-1 MoE FFN over (T, D) tokens.

    ``router_w`` (D, E_total); ``w_in`` (E_local, D, F), ``w_out``
    (E_local, F, D) — the LOCAL expert shard when ``axis_name`` is bound
    (E_total = E_local * axis_size), the full set otherwise.
    Returns (out (T, D), aux_loss)."""
    T, D = x.shape
    e_local = w_in.shape[0]
    bound = axis_name is not None and axis_is_bound(axis_name)
    n = jax.lax.axis_size(axis_name) if bound else 1
    e_total = e_local * n
    capacity = max(int(capacity_factor * T / e_total), 1)

    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    dispatch, combine, aux = _one_hot_dispatch(logits, e_total, capacity)

    # (T, E, C) x (T, D) -> (E, C, D): expert queues, dense (MXU dispatch)
    expert_in = jnp.einsum("tec,td->ecd", dispatch, x.astype(jnp.float32))

    if bound:
        # (E_total, C, D) is owner-major; the tiled all_to_all swaps
        # owner-major for source-major: afterwards this device holds, for
        # every SOURCE device, the (e_local, C, D) queues destined for its
        # own experts
        exchanged = jax.lax.all_to_all(
            expert_in.reshape(e_total * capacity, D), axis_name,
            split_axis=0, concat_axis=0, tiled=True)
        # (n_src, e_local, C, D) -> (e_local, n_src*C, D): one batched FFN
        # over each local expert's merged queue
        expert_in = jnp.moveaxis(
            exchanged.reshape(n, e_local, capacity, D), 0, 1
        ).reshape(e_local, n * capacity, D)

    # local expert FFN, batched over experts: relu(x @ w_in) @ w_out
    h = jnp.maximum(jnp.einsum("ecd,edf->ecf", expert_in,
                               w_in.astype(jnp.float32)), 0.0)
    expert_out = jnp.einsum("ecf,efd->ecd", h, w_out.astype(jnp.float32))

    if bound:
        # undo: (e_local, n_src*C, D) -> (n_src, e_local, C, D) -> flat,
        # reverse exchange returns outputs to the token owners, owner-major
        expert_out = jnp.moveaxis(
            expert_out.reshape(e_local, n, capacity, D), 1, 0)
        expert_out = jax.lax.all_to_all(
            expert_out.reshape(e_total * capacity, D), axis_name,
            split_axis=0, concat_axis=0, tiled=True
        ).reshape(e_total, capacity, D)

    out = jnp.einsum("tec,ecd->td", combine, expert_out)
    return out.astype(x.dtype), aux


@dataclasses.dataclass
class MoELayer:
    """Module wrapper: ``init(key) -> params``, ``apply(params, x)``.

    ``num_experts`` is the GLOBAL expert count; under an ``expert`` mesh
    axis of size n each device initializes/holds ``num_experts / n``
    experts (pass ``n_shards``)."""
    d_model: int
    d_ff: int
    num_experts: int
    n_shards: int = 1
    capacity_factor: float = 1.25
    axis_name: Optional[str] = EXPERT_AXIS

    def init(self, key):
        if self.num_experts % self.n_shards:
            raise ValueError(f"{self.num_experts} experts must divide over "
                             f"{self.n_shards} shards")
        e_local = self.num_experts // self.n_shards
        k1, k2, k3 = jax.random.split(key, 3)
        s_in = (2.0 / self.d_model) ** 0.5
        s_out = (1.0 / self.d_ff) ** 0.5
        return {
            "router": 0.02 * jax.random.normal(
                k1, (self.d_model, self.num_experts), jnp.float32),
            "w_in": s_in * jax.random.normal(
                k2, (e_local, self.d_model, self.d_ff), jnp.float32),
            "w_out": s_out * jax.random.normal(
                k3, (e_local, self.d_ff, self.d_model), jnp.float32),
        }

    def apply(self, params, x):
        """x (..., D) -> (out (..., D), aux_loss)."""
        lead = x.shape[:-1]
        out, aux = moe_ffn(x.reshape(-1, self.d_model), params["router"],
                           params["w_in"], params["w_out"],
                           axis_name=self.axis_name,
                           capacity_factor=self.capacity_factor)
        return out.reshape(*lead, self.d_model), aux

    __call__ = apply


# ---------------------------------------------------------------------------
# top-k routing over all experts, a share of them held here, nothing dropped
# ---------------------------------------------------------------------------

ROUTER_SCORES = ("sigmoid", "softmax")


def route_top_k(x, router_w, expert_bias, top_k: int, *,
                norm_topk_prob: bool = True,
                routed_scaling_factor: float = 1.0, score: str = "sigmoid"):
    """``(ids (T, k) int32, weights (T, k) float32)`` of every token's
    experts.  ``s = score(x W_g)`` — each expert's own ``sigmoid`` or a
    ``softmax`` over all of them, the model's (:data:`ROUTER_SCORES`); the
    top-k of ``s + b`` choose — the bias ``b`` is a buffer, it carries no
    gradient and weighs nothing; None where the model has none — and the
    weights are the chosen ``s``, normalised over the k where
    ``norm_topk_prob``.  All of it in float32 at the highest matmul
    precision, whatever ``x`` is: which experts a token takes must not turn
    on bfloat16 rounding of a score."""
    if score not in ROUTER_SCORES:
        raise ValueError(f"score must be one of {ROUTER_SCORES}, "
                         f"got {score!r}")
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = (jax.nn.sigmoid(logits) if score == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    choose = scores if expert_bias is None else scores \
        + jax.lax.stop_gradient(expert_bias.astype(jnp.float32))
    _, ids = jax.lax.top_k(choose, top_k)
    weights = jnp.take_along_axis(scores, ids, axis=-1)
    if norm_topk_prob:
        # k sigmoids can all be 0 in float32; the largest of a softmax over
        # E is at least 1/E, and the published rule divides by the bare sum
        floor = 1e-6 if score == "sigmoid" else 0.0
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + floor)
    return ids, weights * routed_scaling_factor


def buffer_rows(tokens: int, top_k: int, experts: int, held: int) -> int:
    """Rows of the dispatch buffer: twice the held experts' even share of
    the T·k assignments, in whole 512s, and never more than all of them —
    which is what it is where every expert is held.  A rule of the shapes,
    not a knob (docs/performance.md)."""
    full = tokens * top_k
    return min(full, -(-2 * full * held // (experts * 512)) * 512)


def _walk(i, order, ends, top_k, c):
    """Walk ``i`` covers rows ``[i·c, (i + 1)·c)`` of the sorted order:
    ``(lo, token (c,), assignment (c,), group sizes (held,), valid (c,))`` —
    ``valid`` where the row is a held assignment's."""
    lo = i * c
    seg = jax.lax.dynamic_slice(order, (lo,), (c,))
    hi = jnp.clip(ends - lo, 0, c)
    sizes = jnp.diff(hi, prepend=0)
    return lo, seg // top_k, seg, sizes, jnp.arange(c) < hi[-1]


EXPERT_FORMS = ("gated_silu", "relu2")


def _expert_ffn(form, xs, w13, w2, sizes):
    """An expert's FFN over the rows of each held group: ``gated_silu``
    ``W2ᵉ(silu(W1ᵉ x) ⊙ W3ᵉ x)``, ``w13`` holding W1 beside W3; ``relu2``
    ``W2ᵉ relu(W1ᵉ x)²``, ``w13`` holding W1 alone."""
    with annotate("apex.experts"):
        h = jax.lax.ragged_dot(xs, w13.astype(xs.dtype), sizes)
        if form == "gated_silu":
            half = h.shape[1] // 2
            h = jax.nn.silu(h[:, :half]) * h[:, half:]
        else:
            h = jnp.square(jax.nn.relu(h))
        return jax.lax.ragged_dot(h, w2.astype(xs.dtype), sizes)


def _slots(place, here, lo, c):
    """Where in a walk's buffer each of a token's k slots lies, ``(at, mine)``
    (T, k): ``mine`` where this walk holds the row.  A row past the held
    groups is not numbers anybody wrote — the transposed grouped product
    leaves it unwritten on a TPU — and is never read as one."""
    at = place - lo
    return jnp.clip(at, 0, c - 1), here & (at >= 0) & (at < c)


def sums_in_row_space(tokens: int, top_k: int, held: int, c: int) -> bool:
    """Which form a walk's sum back to the tokens takes: the one that moves
    fewer rows.  In row space (:func:`_row_sum`) the buffer's c rows are
    gathered once, gone over again for each further row a token can have
    there — ``min(top_k, held)`` at most — and T rows are gathered; a slot at
    a time (:func:`_slot_sum`) it is k gathers of T rows, whatever is held.
    A rule of the shapes, not a knob (docs/performance.md): 8 of 512 experts
    held at k 22 sums in row space, 8 of 64 at k 4 — where the buffer is as
    long as the tokens — a slot at a time, as does every expert held."""
    return min(top_k, held, c) * c + tokens < top_k * tokens


def _slot_sum(src, at, mine, scale=None):
    """``Σ_j src[at[t, j]]`` over the slots this walk holds (times
    ``scale[t, j]``, in its dtype): the transpose of the walk's row gather,
    as k gathers of T rows."""
    total = 0
    for j in range(at.shape[1]):
        term = jnp.where(mine[:, j, None], src[at[:, j]], 0)
        total = total + (term if scale is None
                         else term.astype(scale.dtype) * scale[:, j, None])
    return total


def _row_sum(src, token, valid, mine, slots, weight=None):
    """The same sum, ``out[t] = Σ_r src[r]`` over the walk's valid rows ``r``
    of token ``t`` (each times ``weight[r]``, in its dtype), in row space.
    The rows are sorted by token — ONE gather of c rows, the rows past the
    held groups masked before anything is added to them —, a token's rows
    are then neighbours and at most ``slots`` of them, so ``slots - 1``
    shifted slices add them onto the first, and ONE gather of T rows reads
    each token's first (``mine`` (T, k) counts the rows this walk holds of
    each token).  The gathers write c + T rows, not T·k."""
    c, tokens = token.shape[0], mine.shape[0]
    key, perm = jax.lax.sort(
        (jnp.where(valid, token, tokens), jnp.arange(c, dtype=jnp.int32)),
        num_keys=1, is_stable=True)
    rows = jnp.where((key < tokens)[:, None], src[perm], 0)
    scale = None if weight is None else weight[perm]
    total = 0
    for j in range(min(slots, c)):      # row r + j, where it is r's token's
        term = jnp.where((key[j:] == key[:c - j])[:, None], rows[j:], 0)
        if scale is not None:
            term = term.astype(scale.dtype) * scale[j:, None]
        total = total + jnp.pad(term, ((0, j), (0, 0)))
    count = jnp.sum(mine, axis=1, dtype=jnp.int32)
    first = jnp.minimum(jnp.cumsum(count) - count, c - 1)
    return jnp.where((count > 0)[:, None], total[first], 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _held_experts(c, form, x, w13, w2, weights, order, place, here, ends):
    """``Σ_j weights[t, j] · FFN_{e(t, j)}(x[t])`` over the held assignments,
    (T, D) float32, through a buffer of ``c`` rows: the held rows are the
    first ``ends[-1]`` of the sorted ``order`` (padded to whole walks), and
    the same body walks them ``c`` at a time until none is left — once where
    the load fits.  The trip count is the device's, so the reverse pass is
    stated here and not derived: its residuals are the arguments, and it
    recomputes a walk's rows as remat would."""
    tokens, top_k = place.shape
    slots = min(top_k, w13.shape[0])
    in_rows = sums_in_row_space(tokens, top_k, w13.shape[0], c)

    def body(carry):
        i, out = carry
        lo, token, assignment, sizes, valid = _walk(i, order, ends, top_k, c)
        ys = _expert_ffn(form, x[token], w13, w2, sizes)
        at, mine = _slots(place, here, lo, c)
        if in_rows:
            part = _row_sum(ys, token, valid, mine, slots,
                            weights.reshape(-1)[assignment])
        else:
            part = _slot_sum(ys, at, mine, weights)
        return i + 1, out + part

    walks = -(-ends[-1] // c)
    return jax.lax.while_loop(
        lambda carry: carry[0] < walks, body,
        (jnp.int32(0), jnp.zeros((tokens, x.shape[1]), jnp.float32)))[1]


def _held_experts_fwd(c, form, *args):
    return _held_experts(c, form, *args), args


def _held_experts_bwd(c, form, res, g):
    x, w13, w2, weights, order, place, here, ends = res
    tokens, top_k = place.shape
    slots = min(top_k, w13.shape[0])
    in_rows = sums_in_row_space(tokens, top_k, w13.shape[0], c)
    g = g.astype(x.dtype)

    def body(carry):
        i, dx, dw13, dw2, d_weights = carry
        lo, token, assignment, sizes, valid = _walk(i, order, ends, top_k, c)
        ys, transpose = jax.vjp(
            lambda xs, w13, w2: _expert_ffn(form, xs, w13, w2, sizes),
            x[token], w13, w2)
        # row r's cotangent is its assignment's weight times its token's,
        # and its weight's the product of the two rows: both in row space
        g_rows = g[token]
        weight = jnp.where(valid, weights.reshape(-1)[assignment], 0.0)
        d_xs, d13, d2 = transpose((weight[:, None] * g_rows).astype(x.dtype))
        d_weight = jnp.sum(ys.astype(jnp.float32) * g_rows, axis=-1)
        # an expert with no row in this walk adds nothing, whatever the
        # grouped product left in its slice
        sent = (sizes > 0)[:, None, None]
        at, mine = _slots(place, here, lo, c)
        part = (_row_sum(d_xs, token, valid, mine, slots) if in_rows
                else _slot_sum(d_xs, at, mine))
        # a row's weight gradient goes to its assignment's place in (T, k):
        # a scatter of the c rows, not a gather over the T·k places
        d_weights = d_weights.reshape(-1).at[
            jnp.where(valid, assignment, d_weights.size)].add(
            jnp.where(valid, d_weight, 0.0), mode="drop"
        ).reshape(d_weights.shape)
        return (i + 1, dx + part, dw13 + jnp.where(sent, d13, 0),
                dw2 + jnp.where(sent, d2, 0), d_weights)

    walks = -(-ends[-1] // c)
    _, dx, dw13, dw2, d_weights = jax.lax.while_loop(
        lambda carry: carry[0] < walks, body,
        (jnp.int32(0), jnp.zeros_like(x), jnp.zeros(w13.shape, jnp.float32),
         jnp.zeros(w2.shape, jnp.float32), jnp.zeros_like(weights)))
    return (dx, dw13.astype(w13.dtype), dw2.astype(w2.dtype), d_weights,
            None, None, None, None)


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def routed_experts(x, router_w, expert_bias, w13, w2, *, top_k: int,
                   first: int = 0, norm_topk_prob: bool = True,
                   routed_scaling_factor: float = 1.0,
                   axis_name: Optional[str] = EXPERT_AXIS,
                   form: str = "gated_silu", rows=None,
                   score: str = "sigmoid"):
    """The held experts' part of a routed FFN over ``x`` (T, D):
    ``Σ_{e ∈ S(t), e held} w_e · FFNᵉ(rows_t)``.

    ``router_w`` (D, E) and ``expert_bias`` (E,; None: no selection bias)
    cover ALL E experts and the router reads ``x`` through the model's
    ``score`` function (:func:`route_top_k`); the experts compute on ``rows`` (T, R) — ``x`` itself
    where None, a narrower projection of it in a latent expert layer — and
    the result is (T, R).  ``form`` names the expert (:data:`EXPERT_FORMS`):
    ``gated_silu`` ``W2ᵉ(silu(W1ᵉ h) ⊙ W3ᵉ h)`` with ``w13`` (held, R, 2·F) —
    W1 beside W3 —, ``relu2`` ``W2ᵉ relu(W1ᵉ h)²`` with ``w13`` (held, R, F);
    ``w2`` (held, F, R).  They are the experts ``first .. first + held``
    that live here; every other size is the weights'.  An assignment to an
    absent expert adds nothing: nothing stands in for the chips that hold
    the others.  With ``axis_name`` bound (inside ``shard_map``, tokens
    replicated over the axis) device i holds experts ``i·held ..`` and the
    parts are summed over the axis; unbound there is no exchange.

    The assignments are sorted by expert with the absent ones last, and the
    held rows at the front of that order go through a buffer of
    :func:`buffer_rows` rows — gather, grouped products, weighted sum back to
    the tokens in the form that moves fewer rows
    (:func:`sums_in_row_space`) — as many times as it takes: once where the
    load fits, T·k / rows at total imbalance.  No assignment is dropped.
    Returns ``(out (T, D), routing)``; ``routing`` holds ``ids`` (T, k)
    int32, the experts every token took, ``rows`` (held,) int32, the
    assignments each held expert was sent, ``dropped`` () int32, the held
    assignments no walk reached, which is 0, ``walks`` () int32, the times
    the buffer was gone over (0 where no token took a held expert), and
    ``slots`` () int32, the most held assignments any token has (at most
    ``min(top_k, held)``: the neighbours a sum in row space adds)."""
    return _routed_experts(x, router_w, expert_bias, w13, w2, top_k=top_k,
                           first=first, norm_topk_prob=norm_topk_prob,
                           routed_scaling_factor=routed_scaling_factor,
                           axis_name=axis_name, form=form, rows=rows,
                           score=score)


def _routed_experts(x, router_w, expert_bias, w13, w2, *, top_k, first=0,
                    norm_topk_prob=True, routed_scaling_factor=1.0,
                    axis_name=EXPERT_AXIS, form="gated_silu", rows=None,
                    score="sigmoid", rows_a_walk=None):
    """:func:`routed_experts`; ``rows_a_walk`` pins the buffer under the
    rule's (:func:`buffer_rows`) so that a test can make it walk."""
    if form not in EXPERT_FORMS:
        raise ValueError(f"form must be one of {EXPERT_FORMS}, got {form!r}")
    rows = x if rows is None else rows
    tokens, _ = x.shape
    held = w13.shape[0]
    bound = axis_name is not None and axis_is_bound(axis_name)
    if bound:
        first = jax.lax.axis_index(axis_name) * held
    c = rows_a_walk or buffer_rows(tokens, top_k, router_w.shape[1], held)
    _tel_events.record_moe_layout(
        experts=router_w.shape[1], held=held, top_k=top_k, buffer_rows=c,
        sum_rows=(c + tokens if sums_in_row_space(tokens, top_k, held, c)
                  else top_k * tokens))

    with annotate("apex.router"):
        ids, weights = route_top_k(
            x, router_w, expert_bias, top_k, norm_topk_prob=norm_topk_prob,
            routed_scaling_factor=routed_scaling_factor, score=score)

    # -- dispatch: sort the T·k assignments by held expert, absent last ------
    local = (ids - first).reshape(-1)
    here = (local >= 0) & (local < held)
    group = jnp.where(here, local, held)
    order = jnp.argsort(group, stable=True).astype(jnp.int32)
    place = jnp.argsort(order).astype(jnp.int32).reshape(tokens, top_k)
    here = here.reshape(tokens, top_k)
    sent = jnp.sum(group[:, None] == jnp.arange(held)[None, :], axis=0,
                   dtype=jnp.int32)
    ends = jnp.cumsum(sent)
    walks = -(-ends[-1] // c)
    # whole walks cover every assignment, so the buffer has a row for each
    covered = -(-tokens * top_k // c) * c
    dropped = jnp.maximum(ends[-1] - covered, 0)

    out = _held_experts(
        c, form, rows, w13, w2, jnp.where(here, weights, 0.0),
        jnp.pad(order, (0, covered - tokens * top_k)), place, here, ends
    ).astype(rows.dtype)
    if bound:
        out = jax.lax.psum(out, axis_name)
    return out, {"ids": ids, "rows": sent, "dropped": dropped,
                 "walks": walks,
                 "slots": jnp.max(jnp.sum(here, axis=1, dtype=jnp.int32))}
