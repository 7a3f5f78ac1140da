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

:func:`routed_experts` is the other family (docs/lfm2.md): sigmoid scores,
a selection bias that chooses and does not weigh, top-k of ALL experts, and
a share of them held here.  It drops no assignment — its buffer has a row
for every one of the T·k — and its three matrix products are grouped ones
(``jax.lax.ragged_dot``) over the rows the held experts were sent, sorted by
expert, so their time follows the load and not the buffer.
"""
from __future__ import annotations

import dataclasses
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

def route_top_k(x, router_w, expert_bias, top_k: int, *,
                norm_topk_prob: bool = True,
                routed_scaling_factor: float = 1.0):
    """``(ids (T, k) int32, weights (T, k) float32)`` of every token's
    experts.  ``s = sigmoid(x W_g)``; the top-k of ``s + b`` choose — the
    bias ``b`` is a buffer, it carries no gradient and weighs nothing —
    and the weights are the chosen ``s``, normalised over the k where
    ``norm_topk_prob``.  All of it in float32 at the highest matmul
    precision, whatever ``x`` is: which experts a token takes must not turn
    on bfloat16 rounding of a score."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    choose = scores + jax.lax.stop_gradient(expert_bias.astype(jnp.float32))
    _, ids = jax.lax.top_k(choose, top_k)
    weights = jnp.take_along_axis(scores, ids, axis=-1)
    if norm_topk_prob:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-6)
    return ids, weights * routed_scaling_factor


@jax.custom_vjp
def _dispatch(x, order, place, here):
    """Buffer row r holds the token of assignment ``order[r]`` (a token's k
    assignments are consecutive: assignment a belongs to token a // k)."""
    return x[order // place.shape[1]]


def _dispatch_fwd(x, order, place, here):
    return _dispatch(x, order, place, here), (place, here)


def _dispatch_bwd(res, g):
    # the transpose of a gather is a scatter-add; over a permutation it is
    # the gather by the inverse permutation, summed over a token's k rows —
    # those of its held assignments: rows past the held groups are not
    # numbers anybody wrote
    place, here = res
    dx = sum(jnp.where(here[:, j, None], g[place[:, j]], 0)
             for j in range(place.shape[1]))
    return dx, None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(ys, weights, order, place):
    """``Σ_j weights[t, j] · ys[place[t, j]]`` in float32; a zero weight (an
    assignment to an absent expert) reads no row."""
    return sum(jnp.where(weights[:, j, None] != 0,
                         ys[place[:, j]].astype(jnp.float32), 0.0)
               * weights[:, j, None] for j in range(place.shape[1]))


def _combine_fwd(ys, weights, order, place):
    return _combine(ys, weights, order, place), (ys, weights, order, place)


def _combine_bwd(res, g):
    ys, weights, order, place = res
    top_k = place.shape[1]
    # row r's cotangent is its assignment's weight times its token's: again
    # a gather, and zero for every row past the held groups
    d_ys = (weights.reshape(-1)[order][:, None]
            * g.astype(ys.dtype)[order // top_k]).astype(ys.dtype)
    d_weights = jnp.stack(
        [jnp.sum(jnp.where(weights[:, j, None] != 0,
                           ys[place[:, j]].astype(jnp.float32), 0.0) * g,
                 axis=-1) for j in range(top_k)], axis=1)
    return d_ys, d_weights, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def routed_experts(x, router_w, expert_bias, w13, w2, *, top_k: int,
                   first: int = 0, norm_topk_prob: bool = True,
                   routed_scaling_factor: float = 1.0,
                   axis_name: Optional[str] = EXPERT_AXIS):
    """The held experts' part of a routed gated FFN over ``x`` (T, D):
    ``Σ_{e ∈ S(t), e held} w_e · W2ᵉ(silu(W1ᵉ h) ⊙ W3ᵉ h)``.

    ``router_w`` (D, E) and ``expert_bias`` (E,) cover ALL E experts;
    ``w13`` (held, D, 2·F) — W1 beside W3 — and ``w2`` (held, F, D) are the
    experts ``first .. first + held`` that live here.  An assignment to an
    absent expert adds nothing: nothing stands in for the chips that hold
    the others.  With ``axis_name`` bound (inside ``shard_map``, tokens
    replicated over the axis) device i holds experts ``i·held ..`` and the
    parts are summed over the axis; unbound there is no exchange.

    No assignment is dropped at any imbalance: the buffer has T·k rows, one
    for each, sorted by expert with the absent ones last, and the grouped
    products run over the rows of the held groups only.  Returns
    ``(out (T, D), routing)``; ``routing`` holds ``ids`` (T, k) int32, the
    experts every token took, ``rows`` (held,) int32, the assignments each
    held expert was sent, and ``dropped`` () int32, the held assignments
    the buffer had no row for, which is 0."""
    tokens, _ = x.shape
    held, _, two_f = w13.shape
    bound = axis_name is not None and axis_is_bound(axis_name)
    if bound:
        first = jax.lax.axis_index(axis_name) * held
    _tel_events.record_moe_layout(
        experts=router_w.shape[1], held=held, top_k=top_k,
        buffer_rows=tokens * top_k)

    with annotate("apex.router"):
        ids, weights = route_top_k(
            x, router_w, expert_bias, top_k, norm_topk_prob=norm_topk_prob,
            routed_scaling_factor=routed_scaling_factor)

    # -- dispatch: sort the T·k assignments by held expert, absent last ------
    local = (ids - first).reshape(-1)
    here = (local >= 0) & (local < held)
    group = jnp.where(here, local, held)
    order = jnp.argsort(group, stable=True).astype(jnp.int32)
    place = jnp.argsort(order).astype(jnp.int32).reshape(tokens, top_k)
    here = here.reshape(tokens, top_k)
    rows = jnp.sum(group[:, None] == jnp.arange(held)[None, :], axis=0,
                   dtype=jnp.int32)
    dropped = jnp.maximum(jnp.sum(rows) - tokens * top_k, 0)
    xs = _dispatch(x, order, place, here)

    with annotate("apex.experts"):
        h = jax.lax.ragged_dot(xs, w13.astype(x.dtype), rows)
        h = jax.nn.silu(h[:, : two_f // 2]) * h[:, two_f // 2:]
        ys = jax.lax.ragged_dot(h, w2.astype(x.dtype), rows)

    # -- combine: a token's k rows, weighed; rows past the held groups hold
    # whatever the grouped product left there and are never read as numbers
    out = _combine(ys, jnp.where(here, weights, 0.0), order, place).astype(
        x.dtype)
    if bound:
        out = jax.lax.psum(out, axis_name)
    return out, {"ids": ids, "rows": rows, "dropped": dropped}
