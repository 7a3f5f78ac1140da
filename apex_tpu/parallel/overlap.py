"""Async overlap execution — backward-bucketed gradient reduction and
layer-granular zero1 collective chunking.

The stack can *measure* exposed communication precisely
(``telemetry.timeline`` decomposes device traces into exposed-collective
ms; the goodput ledger charges it as ``badput.exposed_comm_ms``) — this
module *lowers* it.  The reference Apex DDP hides gradient wire time
behind backward compute with ``delay_allreduce=False`` comm-ready
buckets on side CUDA streams (``apex/parallel/distributed.py:162-175``,
``comm_ready_buckets`` ``:478-557``): per-param backward hooks fill
``message_size``-element flat buckets in grad-production order and each
bucket allreduces as soon as it fills, while autograd keeps producing
the next one.  Under SPMD there are no hooks and no streams — but the
same capability exists one level down: XLA's latency-hiding scheduler
overlaps *independent* collectives with remaining compute.  The deferred
path hands it ONE reduction depending on EVERY grad leaf, so nothing can
start before backward ends; this module hands it one collective per
bucket, each depending only on its own leaves, restoring the freedom the
reference bought with streams:

``bucketed_allreduce``
    Partition the grad pytree into ``message_size``-element buckets in
    reverse flat (≈ reverse-layer, i.e. grad-production) order —
    deterministic from static pytree facts alone, the rank-0
    bucket-layout broadcast invariant the reference enforces after
    iteration 1 (``distributed.py:316-334``) holds by construction.
    Each bucket concatenates its leaves into one flat fp32 buffer and
    reduces under the ambient collective scheme
    (``parallel.collectives``), carrying int8 error-feedback residuals
    per-bucket while keeping the residual *pytree* layout identical to
    the deferred path (grad-shaped leaves — TrainGuard snapshots, guard
    preempt/resume and elastic re-ingest are unchanged).  fp32/legacy
    buckets are bitwise-identical to the deferred per-leaf psum (psum is
    elementwise; concatenation commutes with it); quantized buckets
    match to summation tolerance (bucket-granular blocks).

``chunked_reduce_scatter`` / ``segmented_allgather``
    The zero1 (``weight_update.ShardedUpdate``) analogue: the flat-grad
    reduce-scatter is issued per column-chunk
    (``reshape(world, per)[:, a:b]`` — every chunk carries exactly the
    rows each shard needs, so chunk k of the scatter depends only on
    bytes [a,b) of every device's buffer and XLA's
    slice-of-concatenate simplification severs the false dependency on
    the whole flat buffer), and the updated-param allgather is issued
    per shard segment so layer L+1's params can be on the wire while
    layer L's forward consumes already-arrived ones.  Both are
    bitwise-identical to the whole-buffer lowering for fp32 (pure
    re-association of the same elementwise sums / data movement) and
    bitwise for block-aligned int8 segments (chunk bounds are placed on
    quantization-block multiples, so the block set — hence every code
    and scale — is unchanged).

Mode resolution (``resolve_mode``): explicit ``overlap=`` argument >
``APEX_TPU_OVERLAP`` env > ``"off"``.
``DistributedDataParallel(delay_allreduce=True)`` is the explicit
deferred path and pins ``"off"`` (the reference's own escape hatch for
models whose backward graph varies per step).  Schemes that cannot
stream per-bucket — adasum's pairwise tree needs the full grad set
(its merge coefficients couple every element it reduces), and callable
per-leaf routing has no bucket meaning — fall back to the deferred
path with a one-time warning (``can_stream`` / ``warn_once``).

Success is self-measuring: the per-bucket collectives meter through the
same ``record_collective`` counters (logical bytes sum exactly to the
deferred path's), and a run that proves loss parity is the same one
in which the timeline's ``exposed_comm_fraction`` and the ledger's
``badput.exposed_comm_ms`` must drop.  See docs/parallel.md "Async
overlap execution".
"""
from __future__ import annotations

import dataclasses
import hashlib
import math
import os
import time
import warnings
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .mesh import DATA_AXIS, axis_is_bound
from ..utils.pallas import presummed
from ..multi_tensor_apply.flattener import LANE

__all__ = ["MODES", "ENV_KNOB", "DEFAULT_MESSAGE_SIZE",
           "resolve_mode", "can_stream", "warn_once",
           "Bucket", "BucketLayout", "partition_buckets",
           "bucketed_allreduce", "shard_chunk_bounds",
           "chunked_reduce_scatter", "segmented_allgather"]

MODES = ("off", "bucketed")
ENV_KNOB = "APEX_TPU_OVERLAP"
#: reference default bucket threshold, in ELEMENTS (``message_size``,
#: apex/parallel/distributed.py:162: 10M elements ≈ 40 MB fp32)
DEFAULT_MESSAGE_SIZE = 10_000_000


def resolve_mode(mode: Optional[str] = None) -> str:
    """Resolve the overlap mode: explicit ``mode`` >
    ``APEX_TPU_OVERLAP`` env > ``"off"``.  Trace-time, like every
    other knob in the family — a ``Plan.apply`` env pin flips it with
    no signature changes anywhere."""
    if mode is None:
        mode = os.environ.get(ENV_KNOB, "").strip().lower() or "off"
    if mode not in MODES:
        raise ValueError(f"overlap must be one of {MODES}, got {mode!r}")
    return mode


_WARNED: set = set()


def warn_once(key, message: str) -> None:
    """Emit ``message`` once per process per ``key`` — bucketed-overlap
    fallbacks fire at trace time, which can recur per recompile."""
    if key in _WARNED:
        return
    _WARNED.add(key)
    warnings.warn(message)


def can_stream(scheme) -> bool:
    """Whether a collective-scheme choice can ship per-bucket during
    backward.  Adasum cannot: its pairwise-tree merge coefficients are
    inner products over everything it reduces, so per-bucket merges
    compute a different (bucket-granular) interpolation than the
    deferred per-leaf path — the reference analogue is that adasum
    needs the full grad set.  Callable per-leaf routing has no
    bucket-level meaning either.  ``scheme=None`` resolves the ambient
    env choice, exactly as the reduction itself will."""
    if callable(scheme):
        return False
    from . import collectives as _coll
    spec = _coll.resolve(scheme)
    if spec is None:
        return True
    return not _coll.get_scheme(spec.scheme).self_scaling


# ---------------------------------------------------------------------------
# bucket partitioning — deterministic from static pytree facts
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Bucket:
    """One comm-ready bucket: which flat-order leaves it carries (ids
    index the FORWARD flatten order), their paths, and its size."""
    index: int
    leaf_ids: Tuple[int, ...]
    paths: Tuple[str, ...]
    elems: int
    nbytes: int


@dataclasses.dataclass(frozen=True)
class BucketLayout:
    """A full partition plus its identity: ``signature`` hashes the
    (path, shape, dtype) sequence and the threshold, so two processes
    (or two runs) agreeing on the signature provably hold the same
    bucket layout — the invariant the reference establishes with a
    rank-0 broadcast after iteration 1, established here statically."""
    buckets: Tuple[Bucket, ...]
    num_leaves: int
    message_size: int
    signature: str


def _leaf_facts(tree):
    """(paths, shapes, dtypes, sizes) in flat order — works on concrete
    arrays and ShapeDtypeStructs alike."""
    from .distributed import _leaf_paths
    leaves, paths, _ = _leaf_paths(tree, True)
    shapes = [tuple(jnp.shape(l)) for l in leaves]
    dtypes = [str(getattr(l, "dtype", None) or jnp.result_type(l))
              for l in leaves]
    sizes = [int(math.prod(s)) if s else 1 for s in shapes]
    return paths, shapes, dtypes, sizes


def _greedy(order: Sequence[int], paths, sizes, nbytes,
            message_size: int) -> List[Bucket]:
    """Reference semantics (``distributed.py:478-557``): fill the
    current bucket in grad-production order and close it once it holds
    ≥ ``message_size`` elements.  A giant leaf simply overflows its
    bucket (no splitting — leaves are atomic); the LAST bucket may be
    under the threshold (the non-divisible remainder)."""
    buckets: List[Bucket] = []
    cur: List[int] = []
    cur_elems = cur_bytes = 0
    for i in order:
        cur.append(i)
        cur_elems += sizes[i]
        cur_bytes += nbytes[i]
        if cur_elems >= message_size:
            buckets.append(Bucket(len(buckets), tuple(cur),
                                  tuple(paths[j] for j in cur),
                                  cur_elems, cur_bytes))
            cur, cur_elems, cur_bytes = [], 0, 0
    if cur:
        buckets.append(Bucket(len(buckets), tuple(cur),
                              tuple(paths[j] for j in cur),
                              cur_elems, cur_bytes))
    return buckets


def partition_buckets(tree, *, message_size: int = DEFAULT_MESSAGE_SIZE,
                      reverse: bool = True) -> BucketLayout:
    """Partition a pytree into size-thresholded buckets.

    ``reverse=True`` walks leaves in REVERSE flat order — for the
    flagship's alphabetical dict flatten (embed, head, layers) that
    approximates reverse-layer ≈ grad-production order, the order the
    reference's backward hooks fill buckets in.  The layout is a pure
    function of ((path, shape, dtype)...) and the threshold: no data,
    no device, no world size — same pytree + threshold ⇒ identical
    buckets on every process and every run (``signature`` certifies
    it)."""
    if int(message_size) <= 0:
        raise ValueError(f"message_size must be positive, got "
                         f"{message_size!r}")
    message_size = int(message_size)
    paths, shapes, dtypes, sizes = _leaf_facts(tree)
    nbytes = [sizes[i] * jnp.dtype(dtypes[i]).itemsize
              for i in range(len(sizes))]
    order = range(len(sizes) - 1, -1, -1) if reverse else range(len(sizes))
    buckets = _greedy(list(order), paths, sizes, nbytes, message_size)
    h = hashlib.sha256()
    h.update(repr((tuple(zip(paths, shapes, dtypes)), message_size,
                   bool(reverse))).encode())
    return BucketLayout(tuple(buckets), len(sizes), message_size,
                        h.hexdigest())


# ---------------------------------------------------------------------------
# backward-bucketed allreduce (the DDP tentpole)
# ---------------------------------------------------------------------------

def bucketed_allreduce(grads, *, axis_name: str = DATA_AXIS,
                       average: bool = True,
                       predivide_factor: Optional[float] = None,
                       always_fp32: bool = False,
                       scheme=None, residuals=None,
                       min_compress_bytes: Optional[int] = None,
                       message_size: int = DEFAULT_MESSAGE_SIZE):
    """Bucketed drop-in for
    :func:`~apex_tpu.parallel.distributed.allreduce_tree`: identical
    signature semantics (scaling, always_fp32, vma pre-summed leaves,
    error-feedback residuals, metering totals), but one collective per
    ``message_size``-element bucket in reverse flat order instead of
    one per leaf — each bucket's reduction depends only on its own
    leaves, so XLA schedules it against the backward compute that
    produces the NEXT bucket.

    Parity contract (tests/L0/test_overlap.py): with ``scheme`` None or
    fp32 the result is BITWISE equal to the deferred path (psum is
    elementwise — concatenating leaves first changes nothing);
    compressed schemes match to summation tolerance (quantization
    blocks span bucket buffers, not leaves).  The residual pytree keeps
    the deferred path's grad-shaped leaf layout (bucket slices are
    reassembled per leaf), so step carries, guard snapshots and elastic
    re-ingest are layout-unchanged.  Per-bucket
    ``record_collective`` calls sum to exactly the deferred path's
    logical bytes.  Adasum / callable schemes raise — callers gate on
    :func:`can_stream` and fall back to the deferred path.
    """
    from . import collectives as _coll
    from .distributed import _leaf_paths
    if callable(scheme):
        raise ValueError(
            "bucketed_allreduce cannot stream a callable per-leaf scheme; "
            "gate on can_stream() and use the deferred allreduce_tree")
    # a scheme=None default consults the controller's live override
    # (collectives.set_live_spec) ahead of the env — the comm-retune
    # actuator's surface; effective at the next traced build
    spec = _coll.resolve(scheme, min_bytes=min_compress_bytes)
    if spec is not None and _coll.get_scheme(spec.scheme).self_scaling:
        raise ValueError(
            f"collective scheme {spec.scheme!r} cannot stream per-bucket "
            "(its merge needs the full grad set); gate on can_stream() "
            "and use the deferred allreduce_tree")
    if not axis_is_bound(axis_name):
        return grads if residuals is None else (grads, residuals)
    world = jax.lax.axis_size(axis_name)

    from ..telemetry import events as _tel_events
    metering = _tel_events.metering()

    # reference allreduce_bucket scaling (distributed.py:446-455) —
    # identical to allreduce_tree
    pre = 1.0
    post = 1.0
    if predivide_factor is not None:
        pre = 1.0 / predivide_factor
        post = predivide_factor / world if average else 1.0
    elif average:
        post = 1.0 / world

    leaves, paths, treedef = _leaf_paths(grads, True)
    n = len(leaves)
    res_leaves = (jax.tree_util.tree_leaves(residuals)
                  if residuals is not None else [None] * n)
    out = [None] * n
    out_res = list(res_leaves)

    # pass 1: vma classification (trace-static, so the bucket layout
    # stays deterministic) — pre-summed leaves scale in place and never
    # bucket/meter, exactly as in allreduce_tree
    orig_dtypes = [g.dtype for g in leaves]
    work = [None] * n
    active: List[int] = []
    for i, g in enumerate(leaves):
        if always_fp32 and g.dtype != jnp.float32:
            g = g.astype(jnp.float32)
        if presummed(g, axis_name):
            scale = pre * post
            if scale != 1.0:
                g = g * scale
            out[i] = g.astype(orig_dtypes[i])
            continue
        work[i] = g
        active.append(i)

    sizes = [int(g.size) for g in leaves]
    nbytes = [sizes[i] * jnp.dtype(work[i].dtype).itemsize
              if work[i] is not None else 0 for i in range(n)]
    # reverse flat order over the ACTIVE leaves = grad-production order
    buckets = _greedy(list(reversed(active)), paths, sizes, nbytes,
                      int(message_size))

    def _record(logical, wire, n_leaves, dt, scheme_name, dtype):
        _tel_events.record_collective(
            axis_name, int(logical), n_leaves, dt,
            wire_bytes=int(wire), dtype=dtype, scheme=scheme_name)

    for b in buckets:
        ids = b.leaf_ids
        t0 = time.perf_counter() if metering else 0.0
        if spec is not None:
            # one fp32 flat buffer per bucket, reduced under the
            # bucket-level scheme choice (the per-bucket threshold the
            # reference's message_size expresses: a small trailing
            # bucket stays fp32)
            xs = [work[i].astype(jnp.float32).reshape(-1) for i in ids]
            buf = jnp.concatenate(xs) if len(xs) > 1 else xs[0]
            if pre != 1.0:
                buf = buf * pre
            info = _coll.get_scheme(_coll.leaf_scheme(spec, buf.size * 4))
            eff = dataclasses.replace(spec, scheme=info.name)
            rbuf = None
            if residuals is not None and info.stateful:
                rs = [res_leaves[i].astype(jnp.float32).reshape(-1)
                      for i in ids]
                rbuf = jnp.concatenate(rs) if len(rs) > 1 else rs[0]
            red, new_rbuf = _coll.reduce(eff, buf, axis_name,
                                         residual=rbuf)
            if post != 1.0:
                red = red * post
            off = 0
            for i in ids:
                sz = sizes[i]
                piece = jax.lax.slice_in_dim(red, off, off + sz)
                out[i] = piece.reshape(jnp.shape(leaves[i])).astype(
                    orig_dtypes[i])
                if new_rbuf is not None:
                    out_res[i] = jax.lax.slice_in_dim(
                        new_rbuf, off, off + sz).reshape(
                            jnp.shape(leaves[i]))
                off += sz
            if metering:
                _record(buf.size * 4, info.wire_bytes(buf.size, eff.block),
                        len(ids), time.perf_counter() - t0, eff.scheme,
                        info.wire_dtype)
        else:
            # legacy native-dtype psum: per-dtype flat buffers inside
            # the bucket (concatenation needs a single dtype; psum of
            # the concat is elementwise-identical to per-leaf psums, so
            # this path stays BITWISE equal to the deferred one)
            groups = {}
            for i in ids:
                groups.setdefault(jnp.dtype(work[i].dtype), []).append(i)
            logical = 0
            dts = set()
            for dt_key, gids in groups.items():
                xs = [work[i].reshape(-1) for i in gids]
                buf = jnp.concatenate(xs) if len(xs) > 1 else xs[0]
                if pre != 1.0:
                    buf = buf * pre
                logical += buf.size * jnp.dtype(buf.dtype).itemsize
                dts.add(str(buf.dtype))
                buf = jax.lax.psum(buf, axis_name)
                if post != 1.0:
                    buf = buf * post
                off = 0
                for i in gids:
                    sz = sizes[i]
                    out[i] = jax.lax.slice_in_dim(
                        buf, off, off + sz).reshape(
                            jnp.shape(leaves[i])).astype(orig_dtypes[i])
                    off += sz
            if metering:
                _record(logical, logical, len(ids),
                        time.perf_counter() - t0, None,
                        (next(iter(dts)) if len(dts) == 1 else "mixed"))

    reduced = jax.tree_util.tree_unflatten(treedef, out)
    if residuals is None:
        return reduced
    res_treedef = jax.tree_util.tree_structure(residuals)
    new_res = jax.tree_util.tree_unflatten(res_treedef, out_res)
    return reduced, new_res


# ---------------------------------------------------------------------------
# zero1 chunking — reduce-scatter per column-chunk, allgather per segment
# ---------------------------------------------------------------------------

def shard_chunk_bounds(per: int, message_size: int,
                       align: int) -> List[Tuple[int, int]]:
    """Chunk bounds ``[(a, b), ...)`` covering ``[0, per)`` where every
    bound is a multiple of ``align`` and chunks hold ≈ ``message_size``
    elements.  Deterministic from the three ints alone (the zero1
    analogue of the bucket-layout invariant).  Falls back to a single
    chunk when ``per`` is not align-divisible (quantization blocks
    could not be preserved) or the threshold spans the whole shard."""
    per, align = int(per), max(1, int(align))
    if per <= 0:
        return []
    if per % align:
        return [(0, per)]
    step = max(1, int(message_size) // align) * align
    if step >= per:
        return [(0, per)]
    return [(a, min(a + step, per)) for a in range(0, per, step)]


def chunked_reduce_scatter(flat_g, axis_name: str, spec=None, *,
                           residual=None,
                           message_size: int = DEFAULT_MESSAGE_SIZE,
                           label: str = "ddp.reduce_scatter",
                           on_chunk: Optional[Callable] = None):
    """Reduce-scatter a full flat grad buffer in column chunks.

    ``flat_g`` is ``(world * per,)``; viewing it as ``m = reshape(world,
    per)``, shard d of the whole-buffer scatter is ``Σ_dev
    m_dev[d, :]`` — so the columns ``[a, b)`` of every device form an
    independent sub-scatter whose result is exactly shard rows
    ``[a, b)``.  Chunk k's collective therefore depends only on bytes
    ``[a, b)`` of each device's row, and XLA's slice-of-concatenate
    simplification traces that dependency through the flattener's
    concat, freeing the scheduler to launch chunk k while the grads
    behind chunk k+1 are still being produced.  fp32 chunks are
    bitwise-identical to the whole-buffer ``psum_scatter`` (same
    elementwise sums); int8 chunks are bitwise too when ``per`` is
    divisible by the lcm(LANE, block) alignment (chunk bounds land on
    quantization-block multiples, so every block's codes and scales are
    unchanged) — otherwise a single whole-buffer chunk runs.

    ``residual`` is the CANONICAL full-flat fp32 error-feedback buffer;
    it is column-sliced per chunk and reassembled, so its layout (and
    every checkpoint/guard/elastic consumer of it) is unchanged.
    ``on_chunk(logical_bytes, wire_bytes, seconds)`` meters each chunk.
    Returns ``(g_shard, new_residual, n_chunks)``.
    """
    from . import collectives as _coll
    world = jax.lax.axis_size(axis_name)
    per = flat_g.shape[0] // world
    if spec is None or spec.scheme == "fp32":
        align = LANE
    else:
        align = math.lcm(LANE, spec.block)
    bounds = shard_chunk_bounds(per, message_size, align)
    info = _coll.get_scheme(spec.scheme) if spec is not None else None
    if len(bounds) <= 1:
        t0 = time.perf_counter()
        shard, new_res = _coll.reduce_scatter_flat(
            flat_g, axis_name, spec, residual=residual, label=label)
        if on_chunk is not None:
            on_chunk(flat_g.size * 4,
                     (info.wire_bytes(flat_g.size, spec.block)
                      if info is not None else flat_g.size * 4),
                     time.perf_counter() - t0)
        return shard, new_res, 1
    m = flat_g.reshape(world, per)
    rm = residual.reshape(world, per) if residual is not None else None
    shard_parts = []
    res_parts = []
    for a, b in bounds:
        t0 = time.perf_counter()
        cbuf = jax.lax.slice(m, (0, a), (world, b)).reshape(-1)
        cres = (jax.lax.slice(rm, (0, a), (world, b)).reshape(-1)
                if rm is not None else None)
        cshard, cnew = _coll.reduce_scatter_flat(
            cbuf, axis_name, spec, residual=cres, label=label)
        shard_parts.append(cshard)
        if rm is not None:
            res_parts.append((cres if cnew is None else cnew).reshape(
                world, b - a))
        if on_chunk is not None:
            on_chunk(cbuf.size * 4,
                     (info.wire_bytes(cbuf.size, spec.block)
                      if info is not None else cbuf.size * 4),
                     time.perf_counter() - t0)
    g_shard = jnp.concatenate(shard_parts)
    if rm is None:
        return g_shard, residual, len(bounds)
    new_res = jnp.concatenate(res_parts, axis=1).reshape(-1)
    return g_shard, new_res, len(bounds)


def segmented_allgather(shard, axis_name: str, spec=None, *,
                        message_size: int = DEFAULT_MESSAGE_SIZE,
                        label: str = "ddp.param_allgather",
                        on_segment: Optional[Callable] = None):
    """Allgather an updated-param shard in segments.

    The whole-shard gather makes every consumer of ANY param wait for
    ALL of them; per-segment gathers are mutually independent, so XLA
    can overlap segment k+1's wire time with compute already consuming
    segment k (the layer-by-layer prefetch — the segment schedule is
    the bucket schedule in reverse).  Reconstruction: segment k's
    tiled gather is ``concat_d shard_d[a:b]``; stacking each as
    ``(world, b-a)`` and concatenating on axis 1 rebuilds ``(world,
    per)`` = the canonical full flat buffer — pure data movement, so
    fp32/bf16 segments are bitwise vs the whole-shard gather, and int8
    segments are too when bounds land on quantization-block multiples
    (enforced via the alignment; otherwise one whole-shard segment
    runs).  ``on_segment(logical_bytes, wire_bytes, seconds)`` meters
    each segment.  Returns ``(full, wire_bytes_total, wire_dtype,
    n_segments)``.
    """
    from . import collectives as _coll
    world = jax.lax.axis_size(axis_name)
    s = int(shard.shape[0])
    if spec is not None and spec.scheme == "int8_blockscale":
        align = math.lcm(LANE, spec.block)
    else:
        align = LANE
    bounds = shard_chunk_bounds(s, message_size, align)
    if len(bounds) <= 1:
        t0 = time.perf_counter()
        full, wire, dt = _coll.allgather_flat(shard, axis_name, spec,
                                              label=label)
        if on_segment is not None:
            on_segment(s * 4, wire, time.perf_counter() - t0)
        return full, wire, dt, 1
    pieces = []
    total_wire = 0
    dt = "float32"
    for a, b in bounds:
        t0 = time.perf_counter()
        seg, wire, dt = _coll.allgather_flat(
            jax.lax.slice_in_dim(shard, a, b), axis_name, spec,
            label=label)
        pieces.append(seg.reshape(world, b - a))
        total_wire += wire
        if on_segment is not None:
            on_segment((b - a) * 4, wire, time.perf_counter() - t0)
    full = jnp.concatenate(pieces, axis=1).reshape(-1)
    return full, total_wire, dt, len(bounds)
