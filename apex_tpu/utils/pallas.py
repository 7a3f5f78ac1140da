"""Shared Pallas helpers."""
from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as pltpu


def interpret_mode() -> bool:
    """Pallas kernels run in interpret mode off-TPU (CPU tests)."""
    return jax.default_backend() != "tpu"


def compiler_params(dimension_semantics, vmem_limit_bytes=None):
    """Mosaic compiler params carrying the grid's dimension semantics and,
    for a kernel that needs more than the scoped default, the VMEM it may
    use."""
    return pltpu.CompilerParams(
        dimension_semantics=tuple(dimension_semantics),
        vmem_limit_bytes=vmem_limit_bytes)


def to_varying(a, axes):
    """Lift ``a`` to vary over the manual mesh ``axes`` (inside
    ``shard_map``).  ``pcast`` rejects axes the value already varies
    over, so only the missing ones are cast — callers name the axes they
    need, whatever the value's current type."""
    missing = tuple(ax for ax in axes if ax not in jax.typeof(a).vma)
    return jax.lax.pcast(a, missing, to="varying") if missing else a


def presummed(g, axis_name) -> bool:
    """True when the gradient ``g`` is already summed over ``axis_name``.

    Under ``shard_map``'s varying-axes typing, a cotangent of a
    replicated input arrives psum-SUMMED and typed unvarying; reducing it
    again would double-count.  ``shard_map(check_vma=False)`` types
    NOTHING as varying and sums nothing, so an empty type proves
    anything only when typing is on — which ``axis_index``, varying by
    construction, reveals."""
    tracked = axis_name in jax.typeof(jax.lax.axis_index(axis_name)).vma
    return tracked and axis_name not in jax.typeof(g).vma


def out_vma(*arrays):
    """Varying-mesh-axes set for pallas_call out_shapes: the union of the
    inputs' vma (under shard_map(check_vma=True) outputs inherit what the
    inputs vary over; elsewhere this is just frozenset())."""
    union = frozenset()
    for a in arrays:
        union = union | jax.typeof(a).vma
    return union


def align_vma(arrays):
    """Lift every array to the union vma (a no-op outside shard_map).
    Pallas interpret-mode evaluates the kernel body with the operands'
    types, and mixed vma (a varying grad next to a replicated scalar) is a
    type error there.  Returns (arrays, union_vma)."""
    union = out_vma(*arrays)
    return [to_varying(a, union) for a in arrays], union


def sds(shape, dtype, vma):
    """ShapeDtypeStruct typed with the given varying-mesh-axes set."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
