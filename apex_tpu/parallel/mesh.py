"""Device-mesh management: the TPU-native replacement for the reference's
process-group plumbing.

The reference builds on ``torch.distributed`` process groups (NCCL) — e.g.
``apex/parallel/__init__.py:58-95`` (``create_syncbn_process_group``),
``apex/parallel/distributed.py:613`` (per-stream ``dist.new_group``) and the
process-per-GPU launcher ``apex/parallel/multiproc.py:1-35``.  On TPU the
analogous objects are a ``jax.sharding.Mesh`` with named axes and mesh
*sub-axes* for grouped collectives; transport is XLA collectives over ICI/DCN,
launch is ``jax.distributed.initialize``.

Axis-name conventions used throughout apex_tpu:
  - ``data``:  data parallelism (DDP / grad psum)
  - ``group``: optional sub-grouping (SyncBN group_size, two-level sharded opt)
  - ``model``: tensor parallelism (available to users; see apex_tpu.parallel)
  - ``seq``:   sequence/context parallelism (ring attention)
"""
from __future__ import annotations

import contextlib
from typing import Optional, Sequence

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
GROUP_AXIS = "group"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"

_current_mesh: Optional[Mesh] = None


def initialize_distributed(coordinator_address=None, num_processes=None,
                           process_id=None, local_device_ids=None):
    """Multi-host bring-up — replaces ``apex.parallel.multiproc`` +
    ``torch.distributed.init_process_group`` (NCCL) with
    ``jax.distributed.initialize``.  No-op for single-process runs.

    Arguments default from the ``APEX_TPU_*`` env set by
    ``python -m apex_tpu.parallel.multiproc`` (jax itself does not read
    num-processes/process-id from env), so a launched script can simply call
    ``initialize_distributed()`` with no args.
    """
    import os
    if coordinator_address is None:
        coordinator_address = os.environ.get("APEX_TPU_COORDINATOR_ADDRESS")
    if num_processes is None and "APEX_TPU_NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["APEX_TPU_NUM_PROCESSES"])
    if process_id is None and "APEX_TPU_PROCESS_ID" in os.environ:
        process_id = int(os.environ["APEX_TPU_PROCESS_ID"])
    if num_processes is None or num_processes <= 1:
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        local_device_ids=local_device_ids,
    )


def create_mesh(axis_sizes: Optional[dict] = None,
                devices: Optional[Sequence] = None) -> Mesh:
    """Create a named mesh over all (or given) devices.

    ``axis_sizes`` maps axis name -> size; a size of -1 means "everything
    left".  Default: 1-D data-parallel mesh over all devices, the TPU analog
    of the reference's flat NCCL world (``distributed.py:235-237``).
    """
    if devices is None:
        devices = jax.devices()
    devices = np.asarray(devices)
    n = devices.size
    if not axis_sizes:
        axis_sizes = {DATA_AXIS: n}
    names, sizes = [], []
    wildcard = None
    for name, size in axis_sizes.items():
        names.append(name)
        if size == -1:
            wildcard = name
            sizes.append(-1)
        else:
            sizes.append(int(size))
    fixed = int(np.prod([s for s in sizes if s != -1])) if sizes else 1
    if wildcard is not None:
        rem, mod = divmod(n, fixed)
        if mod:
            raise ValueError(f"{n} devices not divisible by fixed axes {fixed}")
        sizes = [rem if s == -1 else s for s in sizes]
    if int(np.prod(sizes)) != n:
        raise ValueError(f"mesh {dict(zip(names, sizes))} != {n} devices")
    return Mesh(devices.reshape(sizes), axis_names=tuple(names))


def create_grouped_mesh(group_size: int, devices=None) -> Mesh:
    """2-D (group, data-within-group) mesh: the TPU analog of
    ``create_syncbn_process_group(group_size)`` (``parallel/__init__.py:58-95``)
    — world is split into contiguous groups of ``group_size``; collectives over
    the ``group`` axis stay inside a group (and on ICI when group_size divides
    the slice)."""
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if group_size <= 0 or n % group_size:
        raise ValueError(
            f"group_size {group_size} must divide world size {n}")
    devs = np.asarray(devices).reshape(n // group_size, group_size)
    return Mesh(devs, axis_names=(DATA_AXIS, GROUP_AXIS))


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """Set the ambient mesh (also enters ``jax.sharding.use_mesh`` context)."""
    global _current_mesh
    prev = _current_mesh
    _current_mesh = mesh
    try:
        with mesh:
            yield mesh
    finally:
        _current_mesh = prev


def set_mesh(mesh: Optional[Mesh]):
    global _current_mesh
    _current_mesh = mesh


def current_mesh() -> Optional[Mesh]:
    m = _current_mesh
    if m is not None:
        return m
    # fall back to jax's ambient physical mesh if inside `with mesh:`
    try:
        env_mesh = jax.sharding.get_abstract_mesh()
        if env_mesh is not None and env_mesh.shape_tuple:
            return env_mesh
    except Exception:
        pass
    return None


def axis_is_bound(axis_name) -> bool:
    """True when ``axis_name`` (or every name in a tuple) is bound by an
    enclosing shard_map/pmap trace.  Single source of truth for the
    "mapped context or single-device?" decision used by the collectives
    wrappers (distributed.allreduce_tree, sync_batchnorm).
    """
    names = (axis_name if isinstance(axis_name, (tuple, list))
             else (axis_name,))
    from jax._src.core import get_axis_env
    env = get_axis_env()
    return all(env.axis_exists(n) for n in names)


def bound_axes(*names) -> tuple:
    """The subset of ``names`` currently bound (ordered as given)."""
    return tuple(n for n in names if axis_is_bound(n))


def axis_size(axis_name: str, mesh: Optional[Mesh] = None) -> int:
    mesh = mesh or current_mesh()
    if mesh is None:
        return 1
    return dict(mesh.shape).get(axis_name, 1)


def num_slices(devices: Optional[Sequence] = None) -> int:
    """Distinct TPU slices among ``devices`` (default: all).  Multislice
    pods expose ``device.slice_index``; collectives crossing slices ride
    DCN, not ICI — the fact the planner's alpha-beta model
    (``plan.collective_time_s``) needs to charge DCN terms.  Single-slice
    and non-TPU backends report 1."""
    if devices is None:
        devices = jax.devices()
    return len({getattr(d, "slice_index", 0) for d in devices}) or 1


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def data_sharding(mesh: Mesh, axis: str = DATA_AXIS) -> NamedSharding:
    """Shard leading (batch) dim over the data axis."""
    return NamedSharding(mesh, P(axis))
