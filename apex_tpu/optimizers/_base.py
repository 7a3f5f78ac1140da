"""Shared machinery for the fused optimizers (reference: ``apex/optimizers``).

Design: each optimizer is a stateless *algorithm object* (hyperparams only)
with pure ``init(params) -> state`` and ``step(state, grads, params, ...) ->
(new_params, new_state)`` methods, so the whole update nests under ``jit`` /
``pjit`` and threads through scan-based training loops.  Two interchangeable
implementations:

- ``impl="xla"``: per-leaf ``tree_map`` updates.  Under jit, XLA emits one
  fused elementwise loop per leaf inside a single executable — the kernel
  -launch-overhead problem the CUDA multi-tensor engine solves does not exist
  inside one XLA program — and every leaf is read and written in the tiled
  layout it already has.  What a REPLICATED update runs (the bert example's
  ``run_standard``, every cell of the benchmark): at BERT-large 49 B a
  parameter and no scratch where the flat engine moves 100 B and keeps 8.7
  (PERF.md section 6, PR 37).
- ``impl="fused"``: the flat-buffer engine (``multi_tensor_apply``) —
  optimizer state AND master params live permanently in one contiguous fp32
  buffer per field; the update is expressed as XLA elementwise math over the
  flat buffers (plus the flattener's static per-tensor reductions).  This is
  the architectural mirror of ``amp_C``'s multi-tensor engine, and what a
  SHARDED update needs — a replica's shard is a slice of one buffer
  (``parallel.weight_update``, the plan engines, ZeRO).  Its math streams at
  the chip's rate; its cost on a TPU is the packing: a flat 1-D buffer does
  not have the memory order of a tiled 2-D leaf, so the gradient tree in and
  the model copy out are a relayout each way.  See PERF_NOTES.md for the
  measurements that chose XLA-on-flat over Pallas elementwise kernels.

The fused impl's native API is flat: ``step_flat(state, flat_grads)`` updates
the state (master included) with zero per-step packing; the tree-level
``step(state, grads, params)`` compat wrapper flattens grads and unflattens
the master every call (convenient, but pays ~2 extra buffer copies — use
``step_flat`` + ``model_params`` in performance-critical loops).  In fused
mode the flat master weights in the state are authoritative; the ``params``
argument of ``step`` supplies structure/dtypes only (matching the
reference's master-weight contract, ``apex/contrib/optimizers/fp16_optimizer.py:4``).

Both impls produce identical numerics (tested against torch.optim oracles
like ``tests/L0/run_optimizers/test_adam.py:8-60``).
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..multi_tensor_apply.flattener import TreeFlattener


def _f32(x):
    return x.astype(jnp.float32)


def tree_zeros_f32(params):
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)


def global_l2norm(tree):
    """Global grad norm across a pytree (``multi_tensor_l2norm`` +
    final-reduce, fused_lamb.py:123-135)."""
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return jnp.zeros((), jnp.float32)
    return jnp.sqrt(sum(jnp.sum(_f32(l) ** 2) for l in leaves))


def resolve(value, count):
    """Hyperparams may be schedules: callables of the int step count."""
    if callable(value):
        return value(count)
    return value


def resolve_state_dtype(state_dtype):
    """Validate + default the moment-storage dtype (shared by the flat
    engine and the ZeRO optimizers — one guard, no drift)."""
    if state_dtype is None:
        return jnp.float32
    dt = jnp.dtype(state_dtype)
    if not jnp.issubdtype(dt, jnp.floating):
        # an int dtype would silently truncate every stored moment
        # toward zero and stall training with no error
        raise ValueError(f"state_dtype must be a float dtype, got {dt}")
    return dt


class FusedOptimizer:
    """Base: handles impl selection and the flattener for the fused path.

    ``state_dtype`` (fused impl; FusedLAMB's per-leaf step too): storage
    dtype for the m/v moment buffers.  The flat optimizer step is HBM-
    bandwidth-bound (r5 on-chip: 23.0 ms at 334M params ~= 16 GB of
    buffer traffic); storing moments in bf16 cuts ~2.7 GB/step (~17%) at
    334M.  All arithmetic stays fp32 (moments are upcast at read, cast
    back at store) — only the STORAGE narrows, the reference trade-off of
    low-precision optimizer states.  Master params always stay fp32."""

    #: The flat update is strictly per-element: a contiguous slice of the
    #: flat buffers updates exactly like the full buffer, so weight-update
    #: sharding (``parallel.weight_update``) can run ``step_flat`` on each
    #: replica's 1/N slice unchanged.  Optimizers with cross-element
    #: reductions in their flat math (LAMB's per-tensor trust ratios,
    #: NovoGrad's per-tensor second moment) set this False and override
    #: :meth:`step_flat_shard` with the cross-shard form.
    elementwise_flat_update = True

    #: whether the per-leaf step (impl='xla') stores its moments in
    #: ``state_dtype`` too; where it does not, asking for one is an error
    leafwise_state_dtype = False

    def __init__(self, lr, weight_decay=0.0, impl="xla", state_dtype=None):
        if impl not in ("xla", "fused"):
            raise ValueError(f"impl must be 'xla' or 'fused', got {impl!r}")
        if (state_dtype is not None and impl != "fused"
                and not self.leafwise_state_dtype):
            raise ValueError("state_dtype is a flat-engine (impl='fused') "
                             "knob; the xla impl keeps fp32 moments")
        self.lr = lr
        self.weight_decay = weight_decay
        self.impl = impl
        self.state_dtype = resolve_state_dtype(state_dtype)
        self._flattener: Optional[TreeFlattener] = None
        self._flattener_key = None

    def _store_moment(self, x):
        """Cast an fp32-computed moment to its storage dtype (no-op fp32)."""
        return x.astype(self.state_dtype)

    def flattener_for(self, params, chunk=None) -> TreeFlattener:
        """Packing plan for ``params``.  ``chunk`` pins the flat buffer's
        padding quantum — ``parallel.weight_update`` passes ``LANE *
        n_shards`` so the total divides evenly into whole-lane shards;
        ``None`` keeps whatever plan is cached for this structure (or the
        default chunk when building fresh), so ``init``/``step`` calls
        that follow a chunk-pinned build reuse the pinned plan."""
        leaves, treedef = jax.tree_util.tree_flatten(params)
        key = (treedef, tuple(l.shape for l in leaves),
               tuple(jnp.dtype(l.dtype) for l in leaves))
        rebuild = self._flattener is None or self._flattener_key != key
        if not rebuild and chunk is not None \
                and self._flattener.chunk != int(chunk):
            rebuild = True
        if rebuild:
            # rebuilt when the param set/shapes change (add_param_group analog,
            # _process_optimizer.py:469-489) — a retrace, not a runtime error
            self._flattener = (TreeFlattener(params) if chunk is None
                               else TreeFlattener(params, chunk=int(chunk)))
            self._flattener_key = key
        return self._flattener

    @property
    def flattener(self) -> TreeFlattener:
        """The packing plan from the last ``init``/``flattener_for`` call —
        what ``step_flat`` callers use to pack grads / unpack params."""
        if self._flattener is None:
            raise RuntimeError("no flattener yet: call init(params) first")
        return self._flattener

    def step_flat(self, state, flat_grads, *, scale=1.0, lr=None):
        """Flat-native update (impl='fused' only): new state whose ``master``
        field holds the updated flat fp32 params.  Zero per-step packing —
        the fast path for flat-native training loops."""
        raise NotImplementedError(
            f"{type(self).__name__} has no fused impl" if self.impl != "fused"
            else f"{type(self).__name__}.step_flat not implemented")

    def step_flat_shard(self, state, g_shard, *, shard, scale=1.0, lr=None):
        """Sharded flat update (``parallel.weight_update``): ``state``'s
        flat fields and ``g_shard`` hold this replica's contiguous 1/N
        slice of the flat buffers; ``shard`` is a
        :class:`~apex_tpu.parallel.weight_update.ShardContext` (axis name
        + packing plan + psum'd per-tensor reductions) for optimizers
        whose update spans shards.  The default covers every strictly
        elementwise flat update — the slice IS the full math."""
        if not self.elementwise_flat_update:
            raise NotImplementedError(
                f"{type(self).__name__} has cross-tensor reductions in its "
                "flat update and no sharded override — weight-update "
                "sharding needs a step_flat_shard implementation")
        return self.step_flat(state, g_shard, scale=scale, lr=lr)

    def model_params(self, state, dtype=None):
        """Unpack the fused state's flat master into a param tree (the
        master->model copy; pass dtype=bfloat16 for the amp model copy)."""
        return self.flattener.unflatten(state.master, dtype=dtype)

    # optax-style aliases so apex_tpu optimizers drop into optax training loops
    def update(self, grads, state, params):
        new_params, new_state = self.step(state, grads, params)
        updates = jax.tree_util.tree_map(lambda n, p: n - p, new_params, params)
        return updates, new_state
