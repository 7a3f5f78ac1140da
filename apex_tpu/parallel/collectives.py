"""Collective-scheme registry: compressed + adaptive gradient reductions.

Gradient allreduce is the dominant multi-chip cost at scale (ROADMAP:
"the single biggest lever on multi-chip step time at production
scale").  The reference apex attacks the same wire with bf16 DDP
buckets (``apex/parallel/distributed.py:51-58,241-244``); this module
generalizes that into a pluggable registry of *collective schemes*,
selectable per-bucket (per-leaf) through the DDP
:func:`~apex_tpu.parallel.distributed.allreduce_tree` /
:class:`~apex_tpu.parallel.distributed.Reducer` paths, through
ZeRO's reduce-scatter / allgather
(``contrib/optimizers/distributed_fused.py``), and through the plain-
DDP weight-update sharding path (``parallel.weight_update`` — the
shared :func:`reduce_scatter_flat` / :func:`allgather_flat` flat-buffer
lowerings at the bottom of this module serve both).

Built-in schemes
----------------
``fp32``
    Upcast to fp32, ``psum``, cast back — the reference's
    ``allreduce_always_fp32`` semantics as a named scheme.  4 B/elem on
    the wire.
``bf16``
    Reduce at bf16 (the reference's bf16-bucket trade): halve the wire
    at bf16 summation precision.  2 B/elem.
``int8_blockscale``
    Block-scaled int8 quantization (EQuARX, arXiv:2506.17615): each
    ``block``-element block ships one int8 payload + one fp32 scale
    (max-abs / 127), is exchanged over the axis, and is dequantized and
    summed in fp32 on arrival.  ~1.03 B/elem at the default block of
    128 — ~3.9x fewer wire bytes than fp32.  Optionally carries a
    per-replica **error-feedback residual** (the quantization error is
    added back into the next step's gradient before quantizing), which
    removes the persistent bias of naive quantization; the residual is
    a plain pytree so step state that carries it snapshots/rolls back
    bitwise through :class:`~apex_tpu.resilience.TrainGuard`.
``adasum``
    Adaptive pairwise merge (Adaptive Summation, arXiv:2006.02924) as
    an alternative *reduction rule*: replicas are combined pairwise
    with ``a' = (1 - a.b/2|a|^2) a + (1 - a.b/2|b|^2) b`` over a
    log2(world) tree, interpolating between the sum (orthogonal
    gradients) and the mean (parallel gradients).  Full-precision wire
    (4 B/elem) — the win is convergence, not bytes.  Adasum defines its
    own magnitude, so the caller's ``gradient_average`` knob does not
    apply to adasum leaves.

Selection and the per-bucket threshold
--------------------------------------
Precedence everywhere: explicit argument > ``APEX_TPU_COLLECTIVES`` env
> off (the legacy native-dtype psum).  The env/arg spec grammar::

    APEX_TPU_COLLECTIVES="int8_blockscale"
    APEX_TPU_COLLECTIVES="int8_blockscale:block=128,min_bytes=4096"

Leaves smaller than ``min_bytes`` (fp32 bytes) stay on the ``fp32``
scheme — small/precision-critical leaves (layernorm scales, biases)
are not worth compressing and are the classic quantization-sensitivity
hot spots.  ``allreduce_tree`` also accepts a callable
``scheme(path, leaf)`` for fully custom per-bucket routing.

Implementation note: under SPMD the quantized exchange is expressed as
``all_gather`` of the (int8, scales) pair + local dequant-sum (DDP) or
``all_to_all`` + dequant-sum (ZeRO reduce-scatter) — the per-device
payload that crosses the wire is the compressed representation, which
is what the telemetry wire-byte meters count
(``ddp.allreduce_compressed_bytes``, docs/telemetry.md).  Everything is
shard_map/SPMD-composable and A/B-able on the CPU mesh
(tests/L0/test_collectives.py).

Chaos coverage: every scheme reduction passes a
``faults.collective_fail`` gate (the same one-shot schedule as
:func:`~apex_tpu.resilience.faults.wrap_collective`, counted per scheme
entry point at trace time), so the quantized and adasum paths are
exercised by the resilience chaos tests.
"""
from __future__ import annotations

import dataclasses
import os
import re
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

#: default quantization block: one fp32 scale per 128 elements.  Lane-
#: aligned, so it divides every ZeRO shard (TreeFlattener chunks are
#: whole 128-lanes per shard).
DEFAULT_BLOCK = 128
#: leaves smaller than this (fp32 bytes) stay on the fp32 scheme
DEFAULT_MIN_BYTES = 4096
_SCALE_BYTES = 4          # fp32 scale per block on the wire

ENV_KNOB = "APEX_TPU_COLLECTIVES"
_ENV_OFF = ("", "0", "off", "none")


class CollectiveError(ValueError):
    """Unknown scheme name or unparseable spec string."""


@dataclasses.dataclass(frozen=True)
class CollectiveSpec:
    """A resolved scheme choice: which scheme, its quantization block,
    and the byte threshold below which leaves stay fp32."""
    scheme: str = "fp32"
    block: int = DEFAULT_BLOCK
    min_bytes: int = DEFAULT_MIN_BYTES


@dataclasses.dataclass(frozen=True)
class SchemeInfo:
    """Registry entry.  ``reduce(x, axis_name, block, residual)`` takes
    a pre-scaled fp32 leaf and returns ``(sum_over_axis, new_residual)``
    (``new_residual`` is None unless ``stateful`` and a residual was
    passed).  ``self_scaling`` schemes (adasum) return their own
    magnitude — callers must not divide by world.  ``wire_bytes(n,
    block)`` is the per-device payload the scheme ships for an
    ``n``-element leaf."""
    name: str
    reduce: Callable
    wire_bytes: Callable[[int, int], int]
    wire_dtype: str = "float32"
    stateful: bool = False
    self_scaling: bool = False


_REGISTRY: Dict[str, SchemeInfo] = {}


def register_scheme(info: SchemeInfo) -> SchemeInfo:
    """Add (or replace) a scheme in the registry — the pluggability
    surface: custom schemes route through the same per-bucket selection,
    metering, and chaos gate as the built-ins."""
    _REGISTRY[info.name] = info
    return info


def get_scheme(name: str) -> SchemeInfo:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise CollectiveError(
            f"unknown collective scheme {name!r}; registered: "
            f"{available()}") from None


def available() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


# ---------------------------------------------------------------------------
# spec parsing / resolution
# ---------------------------------------------------------------------------

_OPT = re.compile(r"^(block|min_bytes)=(\d+)$")

# -- live override (apex_tpu.control comm retune) ---------------------------
# The run controller's actuation surface: a process-wide spec that
# :func:`resolve` consults for DEFAULT resolutions (scheme=None) ahead
# of the APEX_TPU_COLLECTIVES env.  Explicitly
# passed schemes still win — a caller that pinned a wire stays pinned.
# Takes effect at the next engine build (resolve time): overlap.Reducer
# / spmd.build_plan_step re-resolve when (re)constructed, which is
# exactly when an elastic resume or a fresh jit brings the run back up.

_LIVE_SPEC: Optional[CollectiveSpec] = None


def set_live_spec(spec) -> Optional[CollectiveSpec]:
    """Install ``spec`` (a :class:`CollectiveSpec`, a spec string, a
    bare scheme name, or None to clear) as the live override.  Returns
    the previous override so actuators can revert on failure."""
    global _LIVE_SPEC
    prev = _LIVE_SPEC
    if spec is None:
        _LIVE_SPEC = None
    elif isinstance(spec, CollectiveSpec):
        get_scheme(spec.scheme)   # validate before anything resolves it
        _LIVE_SPEC = spec
    else:
        _LIVE_SPEC = parse_spec(str(spec))
    return prev


def get_live_spec() -> Optional[CollectiveSpec]:
    return _LIVE_SPEC


def parse_spec(text: str) -> CollectiveSpec:
    """``"int8_blockscale:block=128,min_bytes=4096"`` ->
    :class:`CollectiveSpec` (options optional; order-free)."""
    head, _, opts = text.strip().partition(":")
    name = head.strip()
    if name not in _REGISTRY:
        raise CollectiveError(
            f"unknown collective scheme {name!r} in spec {text!r}; "
            f"registered: {available()}")
    kw = {}
    for raw in filter(None, (o.strip() for o in opts.split(","))):
        m = _OPT.match(raw)
        if not m:
            raise CollectiveError(
                f"bad option {raw!r} in collective spec {text!r}; "
                "expected block=N or min_bytes=N")
        kw[m.group(1)] = int(m.group(2))
    return CollectiveSpec(scheme=name, **kw)


def resolve(scheme=None, *, min_bytes: Optional[int] = None,
            block: Optional[int] = None) -> Optional[CollectiveSpec]:
    """Resolve a scheme choice to a spec (or None = legacy psum).

    Precedence: explicit ``scheme`` (name / spec string /
    :class:`CollectiveSpec`) > the controller's live override
    (:func:`set_live_spec`) > ``APEX_TPU_COLLECTIVES`` env > None.
    ``min_bytes``/``block`` override the spec's own values when given.
    """
    spec: Optional[CollectiveSpec] = None
    if scheme is None:
        if _LIVE_SPEC is not None:
            spec = _LIVE_SPEC
            if min_bytes is not None:
                spec = dataclasses.replace(spec, min_bytes=int(min_bytes))
            if block is not None:
                spec = dataclasses.replace(spec, block=int(block))
            return spec
        env = os.environ.get(ENV_KNOB)
        if env is not None and env.strip().lower() in _ENV_OFF:
            return None
        if env:
            spec = parse_spec(env)
    elif isinstance(scheme, CollectiveSpec):
        spec = scheme
    else:
        spec = parse_spec(str(scheme))
    if spec is None:
        return None
    if min_bytes is not None:
        spec = dataclasses.replace(spec, min_bytes=int(min_bytes))
    if block is not None:
        spec = dataclasses.replace(spec, block=int(block))
    get_scheme(spec.scheme)   # validate before anything traces with it
    return spec


def leaf_scheme(spec: CollectiveSpec, leaf_bytes: int) -> str:
    """Per-bucket routing: the spec's scheme, unless the leaf is under
    the byte threshold — then it stays fp32 (full precision)."""
    if spec.scheme != "fp32" and leaf_bytes < spec.min_bytes:
        return "fp32"
    return spec.scheme


def wire_bytes(scheme: str, nelems: int,
               block: int = DEFAULT_BLOCK) -> int:
    """Static per-device payload bytes for an ``nelems`` leaf under
    ``scheme`` — the number the telemetry compressed-bytes counter
    accounts with."""
    return get_scheme(scheme).wire_bytes(int(nelems), int(block))


def init_residuals(grads):
    """Zero error-feedback residual pytree for ``grads`` — carry it in
    step state and thread it through ``allreduce_tree(...,
    residuals=...)``; TrainGuard snapshots it like any other leaf."""
    return jax.tree_util.tree_map(
        lambda g: jnp.zeros(jnp.shape(g), jnp.float32), grads)


# ---------------------------------------------------------------------------
# chaos gate (resilience satellite): every scheme reduction consults the
# active fault plan's collective_fail schedule, same one-shot semantics
# as faults.wrap_collective (the index counts traced builds under jit)
# ---------------------------------------------------------------------------

def chaos_gate(label: str) -> None:
    """Raise :class:`~apex_tpu.resilience.faults.CollectiveFault` when a
    ``collective_fail`` fault is scheduled at this entry point's call
    index.  Public so the ZeRO collectives (which build their own
    all_to_all/all_gather exchange) share the gate.

    The per-label index lives ON the plan (cleared by
    ``FaultPlan.reset``), so it starts at 0 for every freshly installed
    plan — the same fresh-counter semantics as ``wrap_collective``;
    reductions traced before the plan existed never advance it."""
    from ..resilience import faults as _faults
    plan = _faults.active_plan()
    if plan is None:
        return
    counters = getattr(plan, "_scheme_calls", None)
    if counters is None:
        counters = {}
        plan._scheme_calls = counters
    i = counters.get(label, 0)
    counters[label] = i + 1
    if plan.fire("collective_fail", i) is not None:
        raise _faults.CollectiveFault(
            f"injected collective failure in {label} (call {i})")


# ---------------------------------------------------------------------------
# quantization primitives
# ---------------------------------------------------------------------------

def quantize_blockscale(x, block: int = DEFAULT_BLOCK):
    """1-D fp32 ``x`` -> ``(q, scales)``: int8 codes ``(nblocks,
    block)`` (zero-padded to a whole block) and one fp32 max-abs/127
    scale per block.  All-zero blocks get scale 0 (and dequantize to
    exact zeros)."""
    n = x.shape[0]
    nb = -(-n // block)
    pad = nb * block - n
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
    xb = x.reshape(nb, block)
    scale = jnp.max(jnp.abs(xb), axis=1) / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(xb / safe[:, None]), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_blockscale(q, scales, n: int):
    """Inverse of :func:`quantize_blockscale`: 1-D fp32 of length ``n``."""
    x = q.astype(jnp.float32) * scales[:, None]
    return x.reshape(-1)[:n]


def adasum_pair(a, b):
    """One Adasum merge (arXiv:2006.02924 eq. 2): scale each side down
    by its projection onto the other, so parallel gradients average and
    orthogonal gradients add.  Zero-norm sides fall back to plain
    addition (coefficient 1)."""
    dot = jnp.vdot(a, b)
    na = jnp.vdot(a, a)
    nb = jnp.vdot(b, b)
    ca = jnp.where(na > 0, 1.0 - dot / (2.0 * na), 1.0)
    cb = jnp.where(nb > 0, 1.0 - dot / (2.0 * nb), 1.0)
    return ca * a + cb * b


def adasum_merge(stacked):
    """Pairwise-tree Adasum over the leading axis of ``stacked``
    (``(world, ...)``): log2(world) rounds of :func:`adasum_pair`; an
    odd element carries to the next round.  The tree is the same on
    every device, so the merged result is replica-identical."""
    vals = [stacked[i] for i in range(stacked.shape[0])]
    while len(vals) > 1:
        nxt = [adasum_pair(vals[i], vals[i + 1])
               for i in range(0, len(vals) - 1, 2)]
        if len(vals) % 2:
            nxt.append(vals[-1])
        vals = nxt
    return vals[0]


def _gather(x, axis_name, *, tiled: bool = False):
    """all_gather with a leading world axis, typed *invariant* (every
    device provably holds the same stack — the replication fact
    check_vma needs, same pattern as the ZeRO param allgather).
    ``tiled=True`` concatenates along axis 0 instead of stacking (the
    flat-buffer allgather shape)."""
    from jax._src.lax.parallel import all_gather_invariant
    return all_gather_invariant(x, axis_name, axis=0, tiled=tiled)


# ---------------------------------------------------------------------------
# built-in scheme reductions (x arrives fp32, pre-scaled by the caller)
# ---------------------------------------------------------------------------

def _fp32_reduce(x, axis_name, block, residual):
    return jax.lax.psum(x, axis_name), None


def _bf16_reduce(x, axis_name, block, residual):
    return jax.lax.psum(x.astype(jnp.bfloat16), axis_name).astype(
        jnp.float32), None


def _int8_reduce(x, axis_name, block, residual):
    """Block-scaled int8 exchange: quantize (error feedback folded in
    when a residual rides along), all_gather the (codes, scales) pair,
    dequantize every replica's contribution and sum in fp32."""
    flat = x.reshape(-1)
    if residual is not None:
        flat = flat + residual.reshape(-1)
    q, scales = quantize_blockscale(flat, block)
    new_res = None
    if residual is not None:
        new_res = (flat - dequantize_blockscale(q, scales, flat.shape[0])
                   ).reshape(x.shape)
    qg = _gather(q, axis_name)               # (world, nb, block) int8
    sg = _gather(scales, axis_name)          # (world, nb)
    total = jnp.sum(qg.astype(jnp.float32) * sg[..., None], axis=0)
    return total.reshape(-1)[: x.size].reshape(x.shape), new_res


def _adasum_reduce(x, axis_name, block, residual):
    return adasum_merge(_gather(x, axis_name)), None


def _int8_wire(n, block):
    nb = -(-n // block)
    return nb * block + nb * _SCALE_BYTES


register_scheme(SchemeInfo(
    name="fp32", reduce=_fp32_reduce,
    wire_bytes=lambda n, b: 4 * n))
register_scheme(SchemeInfo(
    name="bf16", reduce=_bf16_reduce, wire_dtype="bfloat16",
    wire_bytes=lambda n, b: 2 * n))
register_scheme(SchemeInfo(
    name="int8_blockscale", reduce=_int8_reduce, wire_dtype="int8",
    stateful=True, wire_bytes=_int8_wire))
register_scheme(SchemeInfo(
    name="adasum", reduce=_adasum_reduce, self_scaling=True,
    wire_bytes=lambda n, b: 4 * n))


# ---------------------------------------------------------------------------
# flat-buffer collectives shared by the sharded optimizer paths: ZeRO
# (contrib.optimizers.distributed_fused) and plain-DDP weight-update
# sharding (parallel.weight_update) exchange the same wire formats —
# one lowering, two consumers.
# ---------------------------------------------------------------------------

def reduce_scatter_flat(x, axis_name, spec: Optional[CollectiveSpec] = None,
                        *, residual=None, label: str = "reduce_scatter"):
    """Sum-reduce-scatter a 1-D buffer over ``axis_name``: every device
    contributes its full local buffer and receives its own contiguous
    1/world slice of the element-wise axis sum.

    ``spec`` None or ``fp32`` lowers to ``lax.psum_scatter`` (the legacy
    path — no chaos gate, matching the uncompressed DDP psum);
    compressed schemes ship their wire representation via ``all_to_all``
    + a local dequant-sum, gated by :func:`chaos_gate` under
    ``"<label>.<scheme>"``.  ``residual`` threads the int8
    error-feedback state (full flat, fp32).  The caller owns all
    pre/post scaling (predivide, gradient averaging) and metering.
    Returns ``(shard, new_residual)``.
    """
    if spec is None or spec.scheme == "fp32":
        return jax.lax.psum_scatter(x, axis_name, scatter_dimension=0,
                                    tiled=True), residual
    info = get_scheme(spec.scheme)
    chaos_gate(f"{label}.{info.name}")
    world = jax.lax.psum(1, axis_name)
    per = x.shape[0] // world
    new_residual = residual
    if spec.scheme == "int8_blockscale":
        block = spec.block
        if per % block:
            raise ValueError(
                f"int8_blockscale reduce-scatter needs block ({block}) to "
                f"divide the shard length ({per}); use a block that "
                f"divides total/{world}")
        if residual is not None:
            x = x + residual
        q, scales = quantize_blockscale(x, block)
        if residual is not None:
            new_residual = x - dequantize_blockscale(q, scales, x.shape[0])
        nb_per = per // block
        qt = jax.lax.all_to_all(q.reshape(world, nb_per, block),
                                axis_name, 0, 0)
        st = jax.lax.all_to_all(scales.reshape(world, nb_per),
                                axis_name, 0, 0)
        shard = jnp.sum(qt.astype(jnp.float32) * st[..., None],
                        axis=0).reshape(per)
    elif spec.scheme == "bf16":
        xt = jax.lax.all_to_all(x.astype(jnp.bfloat16).reshape(world, per),
                                axis_name, 0, 0)
        shard = jnp.sum(xt.astype(jnp.float32), axis=0)
    elif spec.scheme == "adasum":
        xt = jax.lax.all_to_all(x.reshape(world, per), axis_name, 0, 0)
        shard = adasum_merge(xt)
    else:
        raise ValueError(
            f"collective scheme {spec.scheme!r} has no reduce-scatter "
            "lowering (custom schemes ride the DDP allreduce path)")
    return shard, new_residual


def allgather_flat(x, axis_name, spec: Optional[CollectiveSpec] = None,
                   *, label: str = "allgather"):
    """Gather a 1-D fp32 shard into the full concatenated fp32 buffer
    (invariant all_gather — every device provably holds the same
    result).  ``spec`` ``bf16`` ships bf16; ``int8_blockscale`` ships
    the block-quantized (codes, scales) pair and dequantizes on arrival
    (gated by :func:`chaos_gate` under ``"<label>.int8_blockscale"``);
    ``adasum`` has no allgather meaning and raises.  Returns ``(full,
    wire_bytes_per_device, wire_dtype)`` — the caller meters.
    """
    if spec is not None and spec.scheme == "adasum":
        raise ValueError("adasum is a reduction rule; it has no "
                         "allgather meaning")
    if spec is not None and spec.scheme == "int8_blockscale":
        chaos_gate(f"{label}.int8_blockscale")
        if x.shape[0] % spec.block:
            # a block that doesn't divide the shard would pad each shard
            # before the gather, silently interleaving zeros into the
            # flat buffer unflatten slices by fixed offsets
            raise ValueError(
                f"int8_blockscale allgather needs block ({spec.block}) "
                f"to divide the shard length ({x.shape[0]})")
        xf = x.astype(jnp.float32)
        q, scales = quantize_blockscale(xf, spec.block)
        qg = _gather(q, axis_name, tiled=True)       # (world*nb, block)
        sg = _gather(scales, axis_name, tiled=True)  # (world*nb,)
        full = (qg.astype(jnp.float32) * sg[:, None]).reshape(-1)
        return (full, wire_bytes("int8_blockscale", x.size, spec.block),
                "int8")
    if spec is not None and spec.scheme == "bf16":
        y = x.astype(jnp.bfloat16)
        return (_gather(y, axis_name, tiled=True).astype(jnp.float32),
                2 * x.size, "bfloat16")
    return (_gather(x, axis_name, tiled=True).astype(jnp.float32),
            x.size * jnp.dtype(x.dtype).itemsize, str(x.dtype))


def rechunk_flat(buf, *, used: int, total: int):
    """Deterministically re-slice a canonical flat buffer to a new
    chunk-padded length — the elastic-resume primitive
    (``apex_tpu.elastic``).

    The zero1/ZeRO flat layouts are *canonical*: the per-leaf content of
    the buffer depends only on the pytree (LANE-aligned leaf offsets,
    ``flattener.offsets``), never on the world size — only the trailing
    padding that rounds ``used`` up to a whole number of per-shard
    chunks does.  So moving a checkpointed flat field (master/moment
    buffers, int8 error-feedback residuals) from an N-way to an M-way
    layout is exactly: keep the first ``used`` elements, re-pad with
    zeros to the new ``total``.  Padding is provably zero in every flat
    field this serves: ``TreeFlattener.flatten`` zero-pads, the fused
    optimizers propagate zero grads/params to zero state there, and an
    all-zero block quantizes with scale 0 so the EF residual is zero
    too — which is also why the re-slice preserves the residual *sum*
    bitwise.  A nonzero tail is real data this re-slice would destroy,
    so it raises instead of truncating.

    Host-side (numpy) on checkpoint payloads — never traced.
    """
    import numpy as np
    a = np.asarray(buf).reshape(-1)
    used, total = int(used), int(total)
    if used > a.shape[0] or used > total:
        raise ValueError(
            f"rechunk_flat: used={used} exceeds the buffer ({a.shape[0]}) "
            f"or the target total ({total})")
    tail = a[used:]
    if tail.size and np.any(tail != 0):
        raise ValueError(
            f"rechunk_flat: buffer carries nonzero data beyond its used "
            f"length ({used} of {a.shape[0]}) — not a canonical flat "
            "buffer; refusing to truncate real data")
    out = np.zeros((total,), a.dtype)
    out[:used] = a[:used]
    return out


def reduce(spec: CollectiveSpec, x, axis_name, *, residual=None):
    """Reduce one fp32 leaf over ``axis_name`` under ``spec``'s scheme
    (no per-bucket thresholding here — callers route via
    :func:`leaf_scheme` first).  Returns ``(reduced, new_residual)``;
    ``new_residual`` is None unless the scheme is stateful AND a
    residual was passed."""
    info = get_scheme(spec.scheme)
    chaos_gate(f"collectives.{info.name}")
    return info.reduce(x, axis_name, spec.block,
                       residual if info.stateful else None)
