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
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

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
