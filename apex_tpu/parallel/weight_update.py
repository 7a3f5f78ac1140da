"""Weight-update sharding for plain DDP — the ZeRO-1 memory win without
leaving the DDP programming model.

Plain-DDP replicas each run the full optimizer update over the entire
flat master/moment buffers and hold N redundant copies of optimizer
state — with the bf16+fp32-master O5 discipline, optimizer state is the
dominant HBM class (``telemetry.memory`` attributes it).  "Automatic
Cross-Replica Sharding of Weight Update in Data-Parallel Training"
(arXiv:2004.13336, PAPERS.md) eliminates exactly this waste: replace
the allreduce-then-replicated-update with

  1. **reduce-scatter** of the flat gradient buffer — each replica
     receives its contiguous 1/N slice of the summed gradients
     (compressed schemes from ``parallel.collectives`` ride the same
     wire as the DDP allreduce: ``APEX_TPU_COLLECTIVES``, with
     optional int8 error-feedback residuals);
  2. a **``step_flat``-style update over the 1/N slice** of the
     permanently-flat master/moment buffers (PERF_NOTES §1 — the flat
     engine makes slicing trivial; elementwise optimizers run their
     ``step_flat`` unchanged, LAMB/NovoGrad override
     ``step_flat_shard`` with psum'd per-tensor reductions);
  3. an **allgather of the updated params** back to every replica,
     optionally bf16/int8_blockscale (explicit ``allgather_scheme``
     only — the ambient ``APEX_TPU_COLLECTIVES`` env never quantizes
     params, same posture as the ZeRO allgather).

Per-replica optimizer-state HBM and update FLOPs drop by 1/N while the
training loop stays DDP-shaped: replicated params in, local grads in,
replicated updated params out.  **When to prefer this over full ZeRO**
(``contrib.optimizers.DistributedFused*``): you keep the plain
replicated-params programming model and any fused flat optimizer
(Adam/LAMB/SGD/NovoGrad/Adagrad with ``impl="fused"``) — full ZeRO is
its own optimizer class with permanently sharded state and a two-level
(ICI/DCN) topology.  See docs/parallel.md "Weight-update sharding".

amp semantics: ``step(..., scale=)`` divides grads inside the shard
update, and the overflow flag is computed over the full local flat
grads **pre-scatter** and ``pmin``'d across the axis — every replica
skips identically even when a compressed scatter would mangle the
non-finite values, matching ``amp``'s skip-step contract.

Knob precedence (``resolve_mode``): explicit ``update_sharding``
argument > ``APEX_TPU_UPDATE_SHARDING`` env > ``"off"``.

Telemetry: the two collectives meter as ``ddp.reduce_scatter`` /
``ddp.param_allgather`` through ``record_collective`` (logical vs wire
bytes, scheme, dtype), and ``ddp.opt_state_bytes_per_replica`` /
``ddp.update_shard_world`` gauges carry the sharded-state footprint —
the numbers the acceptance tests assert.  The sharded state is a plain
pytree (the optimizer's own state class with shard-length flat fields),
so it snapshots/restores bitwise through ``resilience.TrainGuard`` like
any other step carry.
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Optional

import jax
import jax.numpy as jnp

from .mesh import DATA_AXIS
from ..utils.pallas import presummed
from ..multi_tensor_apply.flattener import TreeFlattener, LANE

__all__ = ["MODES", "ENV_KNOB", "resolve_mode", "ShardContext",
           "ShardedUpdate"]

MODES = ("off", "zero1")
ENV_KNOB = "APEX_TPU_UPDATE_SHARDING"


def resolve_mode(mode: Optional[str] = None) -> str:
    """Resolve the update-sharding mode: explicit ``mode`` >
    ``APEX_TPU_UPDATE_SHARDING`` env > ``"off"``."""
    if mode is None:
        mode = os.environ.get(ENV_KNOB, "").strip().lower() or "off"
    if mode not in MODES:
        raise ValueError(
            f"update_sharding must be one of {MODES}, got {mode!r}")
    return mode


class ShardContext:
    """Static facts of one sharded update, handed to
    ``FusedOptimizer.step_flat_shard``: the mesh axis, the packing plan
    (whole-lane shards — ``chunk = LANE * n_shards``), and the psum'd
    per-tensor reductions optimizers with cross-tensor math need
    (LAMB trust ratios, NovoGrad per-layer norms).  Built per trace by
    :class:`ShardedUpdate`; everything here is trace-time static except
    the ``axis_index``-dependent segment slice."""

    def __init__(self, axis_name: str, flattener: TreeFlattener,
                 n_shards: int):
        self.axis_name = axis_name
        self.flattener = flattener
        self.n_shards = int(n_shards)

    @property
    def shard_rows(self) -> int:
        return self.flattener.total // LANE // self.n_shards

    def segments(self):
        """This shard's row->leaf segment ids (dynamic on the shard
        index: shard_map traces one program for all devices — same
        scheme as ``DistributedFusedLAMB._shard_segments``)."""
        idx = jax.lax.axis_index(self.axis_name)
        return jax.lax.dynamic_slice(
            jnp.asarray(self.flattener._row_segments),
            (idx * self.shard_rows,), (self.shard_rows,))

    def global_sumsq(self, x_shard):
        """Global sum of squares across all shards (the grad-norm
        side-reduce)."""
        return jax.lax.psum(jnp.sum(x_shard.astype(jnp.float32) ** 2),
                            self.axis_name)

    def per_tensor_sumsq(self, x_shard):
        """(num_leaves,) per-tensor sum of squares spanning shards:
        per-shard segment partials + psum."""
        fl = self.flattener
        rows = x_shard.reshape(-1, LANE).astype(jnp.float32)
        part = jax.ops.segment_sum(jnp.sum(rows * rows, axis=1),
                                   self.segments(),
                                   num_segments=fl.num_leaves + 1)
        return jax.lax.psum(part, self.axis_name)[: fl.num_leaves]

    def per_tensor_maxabs(self, x_shard):
        """(num_leaves,) per-tensor max |x| spanning shards (NovoGrad's
        inf-norm mode).  A leaf with no rows in this shard contributes
        -inf from ``segment_max``'s empty-segment fill — masked to 0
        before the pmax (0 never exceeds a true max-abs).  ONLY the
        -inf fill is masked: a genuine +inf/NaN partial must propagate
        exactly as the unsharded ``TreeFlattener.per_tensor_maxabs``
        propagates it (|x| is never -inf, so the mask cannot hide a
        real value)."""
        fl = self.flattener
        rows = jnp.abs(x_shard.reshape(-1, LANE).astype(jnp.float32))
        part = jax.ops.segment_max(jnp.max(rows, axis=1), self.segments(),
                                   num_segments=fl.num_leaves + 1)
        part = jnp.where(part == -jnp.inf, 0.0, part)
        return jax.lax.pmax(part, self.axis_name)[: fl.num_leaves]

    def broadcast_rows(self, values):
        """(num_leaves,) per-tensor values -> (shard_rows,) per-row
        values for this shard (padding rows read the appended 0)."""
        vals = jnp.concatenate([values.astype(jnp.float32),
                                jnp.zeros((1,), jnp.float32)])
        return vals[self.segments()]


class ShardedUpdate:
    """The zero1 weight-update engine for plain DDP.

    Wraps a fused-flat optimizer; ``init``/``step`` are *collectives* —
    call them inside ``shard_map``/``pmap`` with ``axis_name`` bound,
    exactly like the ZeRO optimizers.  Construct directly, or via
    ``DistributedDataParallel(update_sharding="zero1").weight_update(opt)``
    (which returns None when the resolved mode is ``"off"``, so the
    caller falls back to the classic allreduce path)::

        ddp = DistributedDataParallel(axis_name="data",
                                      update_sharding="zero1")
        opt = FusedAdam(lr=1e-3, impl="fused")
        wu = ddp.weight_update(opt)
        # inside shard_map:
        state = wu.init(params)                     # 1/N state per replica
        params, state = wu.step(state, grads, params, scale=loss_scale)

    ``collective_scheme``/``collective_min_bytes`` ride the gradient
    reduce-scatter (default: ``APEX_TPU_COLLECTIVES`` env — the same
    wire as the DDP allreduce); ``allgather_scheme`` rides the param
    gather (explicit arg > fp32).  ``residual`` support mirrors the
    DDP/ZeRO error-feedback contract (:meth:`init_residual`)."""

    def __init__(self, optimizer, *, axis_name: str = DATA_AXIS,
                 gradient_average: bool = True,
                 gradient_predivide_factor: Optional[float] = None,
                 check_overflow: bool = True,
                 collective_scheme=None,
                 collective_min_bytes: Optional[int] = None,
                 allgather_scheme=None,
                 overlap: Optional[str] = None,
                 message_size: Optional[int] = None):
        if getattr(optimizer, "impl", None) != "fused":
            raise ValueError(
                "weight-update sharding needs the flat engine: construct "
                "the optimizer with impl='fused' (PERF_NOTES §1 — the "
                "permanently-flat master/moment buffers are what make the "
                "1/N slice trivial)")
        self.optimizer = optimizer
        self.axis_name = axis_name
        self.gradient_average = gradient_average
        self.gradient_predivide_factor = gradient_predivide_factor
        self.check_overflow = check_overflow
        self.collective_scheme = collective_scheme
        self.collective_min_bytes = collective_min_bytes
        self.allgather_scheme = allgather_scheme
        # async overlap execution (parallel.overlap): "bucketed" issues
        # the grad reduce-scatter per column-chunk and the param
        # allgather per shard segment (~``message_size`` elements each),
        # so XLA can overlap each chunk's wire time with the backward
        # compute behind the next one / the forward compute consuming
        # the previous one.  Resolution is TRACE-TIME (explicit arg >
        # APEX_TPU_OVERLAP > off); fp32 chunking is
        # bitwise vs the whole-buffer path, block-aligned int8 too.
        if overlap is not None:
            from . import overlap as _ov
            _ov.resolve_mode(overlap)
        self.overlap = overlap
        self.message_size = message_size

    # -- packing -------------------------------------------------------------

    def _fl(self, params, n_shards: int) -> TreeFlattener:
        # chunk = LANE*n ⇒ total % n == 0 and every shard is a whole
        # number of 128-lanes (the ZeRO alignment, distributed_fused.py)
        return self.optimizer.flattener_for(params, chunk=LANE * n_shards)

    def layout_meta(self, params, n_shards: int) -> dict:
        """The flat-shard layout facts a checkpoint manifest records so
        an elastic resume (``apex_tpu.elastic``) can re-slice the
        N-way state into M-way shards deterministically: the chunk pin
        (``LANE * n_shards``), the padded canonical total, the ``used``
        prefix that carries real leaf data (``flattener.offsets[-1]`` —
        everything past it is zero padding, the fact
        ``collectives.rechunk_flat`` relies on), and each shard's
        offset into the canonical buffer.  Checkpointed flat fields
        (master/moments, EF residuals) are *canonical-flat exports
        already*: ``jax.device_get`` of the P("data")-sharded global
        array gathers the shards back into this exact layout."""
        fl = self._fl(params, n_shards)
        per = fl.total // n_shards
        return {
            "kind": "zero1_flat",
            "lane": LANE,
            "chunk": fl.chunk,
            "flat_total": fl.total,
            "used": int(fl.offsets[-1]),
            "shard_offsets": [i * per for i in range(n_shards)],
        }

    # -- scheme resolution (trace time) --------------------------------------

    def _resolve_rs(self):
        """Gradient reduce-scatter scheme: explicit arg >
        ``APEX_TPU_COLLECTIVES`` env — this IS the DDP gradient wire,
        just scattered instead of allreduced."""
        from . import collectives as _coll
        return _coll.resolve(self.collective_scheme,
                             min_bytes=self.collective_min_bytes)

    def _resolve_ag(self):
        """Param allgather scheme: explicit arg > fp32.  The ambient
        ``APEX_TPU_COLLECTIVES`` env is deliberately NOT consulted —
        quantizing params is an accuracy trade an A/B knob must not
        flip implicitly (the ZeRO posture)."""
        from . import collectives as _coll
        if self.allgather_scheme is not None:
            return _coll.resolve(self.allgather_scheme)
        return None

    # -- metering ------------------------------------------------------------

    def _meter(self, op, logical, wire, seconds, scheme, dtype):
        from ..telemetry import events as _tel_events
        if _tel_events.metering():
            _tel_events.record_collective(
                self.axis_name, int(logical), 1, seconds,
                wire_bytes=int(wire), dtype=dtype, scheme=scheme,
                op=op, family="ddp")

    def _state_bytes(self, state) -> int:
        return int(sum(l.size * jnp.dtype(l.dtype).itemsize
                       for l in jax.tree_util.tree_leaves(state)))

    def _gauge_state(self, state, n_shards: int):
        from ..telemetry import events as _tel_events
        _tel_events.record_update_sharding(self._state_bytes(state),
                                           n_shards)

    # -- state bring-up ------------------------------------------------------

    def init(self, params):
        """Build the sharded optimizer state.  MUST run inside
        shard_map/pmap with ``axis_name`` bound: the full flat init is
        built once per device and each device keeps only its contiguous
        1/N slice of every flat-length field (scalars and per-tensor
        vectors — NovoGrad's ``v`` — stay replicated)."""
        n = jax.lax.axis_size(self.axis_name)
        fl = self._fl(params, n)
        state = self._slice_state(self.optimizer.init(params), fl, n)
        self._gauge_state(state, n)
        return state

    def _slice_state(self, state, fl: TreeFlattener, n_shards: int):
        per = fl.total // n_shards
        idx = jax.lax.axis_index(self.axis_name)

        def slice_leaf(l):
            if getattr(l, "ndim", None) == 1 and l.shape[0] == fl.total:
                return jax.lax.dynamic_slice(l, (idx * per,), (per,))
            return l
        return jax.tree_util.tree_map(slice_leaf, state)

    def state_pspecs(self, params, n_shards: int):
        """PartitionSpecs for the sharded state (shard_map in/out_specs
        or NamedSharding building): flat-length fields shard over
        ``axis_name``, everything else replicated.  ``n_shards`` is the
        mesh axis size (this runs OUTSIDE any bound axis)."""
        from jax.sharding import PartitionSpec as P
        fl = self._fl(params, n_shards)
        shape_state = jax.eval_shape(self.optimizer.init, params)
        return jax.tree_util.tree_map(
            lambda l: (P(self.axis_name)
                       if l.ndim == 1 and l.shape[0] == fl.total else P()),
            shape_state)

    def init_residual(self, params):
        """Zero int8 error-feedback residual for the gradient
        reduce-scatter — full flat, fp32, per-device.  MUST run inside
        shard_map/pmap with ``axis_name`` bound; carry it through
        ``step(..., residual=...)`` so TrainGuard snapshots it."""
        n = jax.lax.axis_size(self.axis_name)
        return jnp.zeros((self._fl(params, n).total,), jnp.float32)

    # -- the step ------------------------------------------------------------

    def step(self, state, grads, params, *, scale=1.0, lr=None,
             residual=None):
        """One collective step: this device's local UNREDUCED grads
        (full model tree) in; ``(new_params_full_tree, new_state)`` out
        — or a 3-tuple ending in ``new_residual`` when ``residual``
        threads the error-feedback state.  ``params`` supplies
        structure/dtypes (the fused master contract); ``scale`` divides
        grads (amp loss-scale interop)."""
        from . import collectives as _coll
        from . import overlap as _ov
        mode = _ov.resolve_mode(self.overlap)
        msize = (self.message_size if self.message_size is not None
                 else _ov.DEFAULT_MESSAGE_SIZE)
        n = jax.lax.axis_size(self.axis_name)
        fl = self._fl(params, n)
        flat_g = fl.flatten(grads)

        # amp overflow-skip: the finite flag is computed over the FULL
        # local flat grads BEFORE the scatter and pmin'd, so every
        # replica skips identically — a compressed scatter would mangle
        # the non-finite values a post-scatter check relies on
        if self.check_overflow:
            ok = jax.lax.pmin(
                jnp.all(jnp.isfinite(flat_g)).astype(jnp.float32),
                self.axis_name)
        else:
            ok = jnp.ones((), jnp.float32)

        # pre/post scaling follows allreduce_tree's reference semantics
        # (allreduce_bucket, distributed.py:446-455): with a predivide
        # factor f, grads are divided by f BEFORE the reduce (fp16/bf16
        # dynamic-range safety) and multiplied back by f/world after
        # (sum/f stays when gradient_average=False); without it, plain
        # post-multiply by 1/world when averaging
        pre = 1.0
        post = 1.0
        if self.gradient_predivide_factor is not None:
            pre = 1.0 / self.gradient_predivide_factor
            post = (self.gradient_predivide_factor / n
                    if self.gradient_average else 1.0)
        elif self.gradient_average:
            post = 1.0 / n

        # -- reduce-scatter of the flat grad buffer (ddp.reduce_scatter).
        # vma-typed shard_map note (same contract as allreduce_tree):
        # gradients taken wrt REPLICATED params arrive already
        # psum-summed by the cotangent rule — scattering them again
        # would double-sum, so a pre-summed flat buffer just slices
        # (no collective runs, and none is metered).
        already_summed = presummed(flat_g, self.axis_name)
        per = fl.total // n
        if already_summed:
            idx = jax.lax.axis_index(self.axis_name)
            g_shard = jax.lax.dynamic_slice(flat_g, (idx * per,), (per,))
            new_residual = residual
            # the cotangent psum ran; only the (pre*post) scaling remains
            if pre * post != 1.0:
                g_shard = g_shard * (pre * post)
        else:
            spec = self._resolve_rs()
            if spec is not None:
                # per-bucket threshold: the flat buffer is one bucket
                name = _coll.leaf_scheme(spec, flat_g.size * 4)
                if name != spec.scheme:
                    spec = dataclasses.replace(spec, scheme=name)
            info = _coll.get_scheme(spec.scheme) if spec is not None else None
            if pre != 1.0:
                flat_g = flat_g * pre
            # async overlap: issue the scatter per column-chunk so each
            # chunk's collective depends only on its own grad bytes —
            # XLA overlaps chunk k's wire with the compute behind chunk
            # k+1.  Adasum's merge couples the whole buffer and cannot
            # stream (one-time warning, deferred fallback).
            stream = mode == "bucketed"
            if stream and info is not None and info.self_scaling:
                _ov.warn_once(
                    ("no_stream_rs", spec.scheme),
                    "overlap='bucketed' requested with a collective scheme "
                    "that cannot stream per-chunk (adasum's pairwise merge "
                    "needs the full grad buffer) — falling back to the "
                    "whole-buffer reduce-scatter")
                stream = False
            _sname = spec.scheme if spec is not None else None
            _sdtype = info.wire_dtype if info is not None else "float32"
            if stream:
                g_shard, new_residual, _ = _ov.chunked_reduce_scatter(
                    flat_g, self.axis_name, spec, residual=residual,
                    message_size=msize, label="ddp.reduce_scatter",
                    on_chunk=lambda logical, wire, dt: self._meter(
                        "reduce_scatter", logical, wire, dt,
                        _sname, _sdtype))
            else:
                t0 = time.perf_counter()
                g_shard, new_residual = _coll.reduce_scatter_flat(
                    flat_g, self.axis_name, spec, residual=residual,
                    label="ddp.reduce_scatter")
                logical = flat_g.size * 4
                self._meter("reduce_scatter", logical,
                            (info.wire_bytes(flat_g.size, spec.block)
                             if info is not None else logical),
                            time.perf_counter() - t0, _sname, _sdtype)
            # adasum sets its own magnitude (only the predivide
            # pre-scale is undone; ``gradient_average`` is a no-op) —
            # everything else applies ``post``, matching allreduce_tree
            # (post-multiply in fp32 — the disabled path stays bitwise)
            if info is not None and info.self_scaling:
                p_scale = self.gradient_predivide_factor or 1.0
            else:
                p_scale = post
            if p_scale != 1.0:
                g_shard = g_shard * p_scale

        # -- the 1/N-slice update over the flat master/moment buffers (a
        # shard is a slice of ONE buffer: the flat path, whatever the leaves)
        from ..telemetry import events as _tel_events
        _tel_events.record_update_path("flat")
        ctx = ShardContext(self.axis_name, fl, n)
        new_state = self.optimizer.step_flat_shard(
            state, g_shard, shard=ctx, scale=scale, lr=lr)
        new_state = jax.tree_util.tree_map(
            lambda nw, old: jnp.where(ok > 0, nw, old), new_state, state)
        if residual is not None:
            # a skipped step's quantization error was never applied
            new_residual = jnp.where(ok > 0, new_residual, residual)
        self._gauge_state(new_state, n)

        # -- allgather of the updated params (ddp.param_allgather).
        # Bucketed overlap issues it per shard segment — the segment
        # gathers are mutually independent, so XLA overlaps segment
        # k+1's wire with the unflatten/forward compute consuming
        # segment k (the layer-by-layer prefetch, riding the same
        # message_size schedule as the grad buckets in reverse).
        ag_spec = self._resolve_ag()
        _agname = ag_spec.scheme if ag_spec is not None else None
        _agdtype = {"int8_blockscale": "int8",
                    "bf16": "bfloat16"}.get(_agname, "float32")
        if mode == "bucketed":
            full, ag_wire, ag_dtype, _ = _ov.segmented_allgather(
                new_state.master, self.axis_name, ag_spec,
                message_size=msize, label="ddp.param_allgather",
                on_segment=lambda logical, wire, dt: self._meter(
                    "param_allgather", logical, wire, dt, _agname,
                    _agdtype))
        else:
            t0 = time.perf_counter()
            full, ag_wire, ag_dtype = _coll.allgather_flat(
                new_state.master, self.axis_name, ag_spec,
                label="ddp.param_allgather")
            self._meter("param_allgather", new_state.master.size * 4,
                        ag_wire, time.perf_counter() - t0, _agname,
                        ag_dtype)

        new_params = fl.unflatten(full, like=params)
        if residual is None:
            return new_params, new_state
        return new_params, new_state, new_residual
