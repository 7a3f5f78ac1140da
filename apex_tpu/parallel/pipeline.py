"""Pipeline parallelism: GPipe-style microbatched stage execution.

Not in the reference (SURVEY §2.3: no pipeline parallelism anywhere) — but
part of the standard TPU sharding vocabulary (dp/tp/sp/ep/pp), so the mesh
toolkit carries a first-class implementation: layers are partitioned into
S stages sharded over a ``pipe`` mesh axis; M microbatches stream through a
fill–drain schedule; activations hop stage-to-stage over
``lax.ppermute`` (neighbor ICI links).  Differentiable end to end —
reverse-mode re-runs the schedule backwards with reversed permutes, giving
textbook GPipe backward without hand-written plumbing.

    # inside shard_map, params_stacked sharded P("pipe"), x replicated
    out = pipeline_apply(stage_fn, local_stage_params, x_microbatches)

Schedule: T = M + S - 1 ticks; stage s processes microbatch m at tick
m + s.  Per-device state is one activation buffer (the simplest GPipe; no
1F1B interleaving — on TPU the win of 1F1B is memory, which
``jax.checkpoint`` over ``stage_fn`` recovers more simply).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from ..utils.pallas import to_varying

PIPE_AXIS = "pipe"


def pipeline_apply(stage_fn: Callable, stage_params, x, *,
                   axis_name: str = PIPE_AXIS):
    """Run ``x`` (M, B, ...) microbatches through the S-stage pipeline.

    Call inside ``shard_map`` with ``axis_name`` bound; ``stage_params`` is
    THIS device's stage parameters (pass the (S, ...) stack through
    in_specs=P(axis_name) and squeeze the leading 1).  ``stage_fn(params,
    h) -> h`` must preserve the activation shape (classic pipeline
    contract).  Returns (M, B, ...) outputs, REPLICATED on every device.
    """
    S = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    M = x.shape[0]
    ticks = M + S - 1
    perm_fwd = [(i, i + 1) for i in range(S - 1)]   # non-cyclic: stage chain

    # per-device buffers (varying over the pipe axis) — fresh zeros are
    # replicated under the vma type system, so lift for a stable loop carry
    h0 = to_varying(jnp.zeros_like(x[0]), (axis_name,))
    outs0 = to_varying(jnp.zeros_like(x), (axis_name,))

    def tick(t, carry):
        recv, outs = carry
        # stage 0 injects microbatch t (clamped; masked later), others take
        # the activation received from the previous stage
        m_in = jnp.clip(t, 0, M - 1)
        inject = jax.lax.dynamic_index_in_dim(x, m_in, keepdims=False)
        h_in = jnp.where(idx == 0, inject, recv)
        h_out = stage_fn(stage_params, h_in)
        # last stage: write finished microbatch t-(S-1) when in range
        m_out = t - (S - 1)
        valid = (idx == S - 1) & (m_out >= 0)
        outs = jax.lax.dynamic_update_index_in_dim(
            outs,
            jnp.where(valid, h_out, jax.lax.dynamic_index_in_dim(
                outs, jnp.clip(m_out, 0, M - 1), keepdims=False)),
            jnp.clip(m_out, 0, M - 1), axis=0)
        # hop to the next stage (stage S-1's send is dropped: non-cyclic
        # perm delivers zeros to stage 0, which ignores them)
        recv = jax.lax.ppermute(h_out, axis_name, perm_fwd)
        return recv, outs

    _, outs = jax.lax.fori_loop(0, ticks, tick, (h0, outs0))
    # only the last stage holds real outputs; psum replicates them (every
    # other device contributes zeros)
    outs = jnp.where(idx == S - 1, outs, jnp.zeros_like(outs))
    return jax.lax.psum(outs, axis_name)


def stack_stage_params(per_stage_params):
    """[stage0_tree, stage1_tree, ...] -> stacked tree with leading S axis
    (shard it over the pipe axis with ``P('pipe')``)."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *per_stage_params)


def unstack_local(stacked_local):
    """Inside shard_map: strip the local leading 1-axis of a P(pipe)-sharded
    stage-param stack.  Requires one stage per device (leading local dim
    == 1): multi-stage-per-device schedules are a different pipeline shape
    and must not be silently truncated."""
    def pick(l):
        if l.shape[0] != 1:
            raise ValueError(
                f"expected 1 local stage per device, got {l.shape[0]} — "
                "the number of stages must equal the pipe-axis size")
        return l[0]
    return jax.tree_util.tree_map(pick, stacked_local)
