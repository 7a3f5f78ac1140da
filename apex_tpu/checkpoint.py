"""Unified checkpoint/resume: one file holding (model params, optimizer
state, amp/scaler state, anything else picklable).

The reference documents the save/restore workflow as a hand-rolled triple —
model/optimizer/amp state_dicts (README.md:63-110, tested by
``tests/L0/run_amp/test_checkpointing.py:73-240``) — and the examples save
torch checkpoints per epoch (``examples/imagenet/main_amp.py:252-261``).
Here that workflow is one pair of functions over arbitrary pytrees:

    from apex_tpu import checkpoint
    checkpoint.save("ckpt.pkl", step=step, amp=amp.state_dict(st),
                    model=st.model_params, masters=st.master_params,
                    opt=st.opt_state, bn=bn_state)
    ckpt = checkpoint.load("ckpt.pkl")          # dict of numpy pytrees

Arrays come back as numpy (host) arrays; feed them to ``jax.device_put`` /
``amp.load_state_dict`` / your train-state constructor.  ``save`` is atomic
(write to temp + rename) so a preempted save never corrupts the previous
checkpoint — the failure-handling posture of SURVEY §5.4.

Precision portability: pass ``amp.AmpState.params_for_eval()`` (fp32 view)
as the model entry to reproduce the reference's O2 state_dict hook
(``_initialize.py:133-142``), or save ``model_params`` as-is for an exact
resume.

Hardening (SURVEY §5.4 failure posture, built on by
``apex_tpu.resilience.ckpt``): every file :func:`save` writes is framed
with a magic tag, payload length and CRC32, so :func:`load` can tell a
truncated or bit-rotten checkpoint from a good one and raise a clear
:class:`CheckpointError` instead of a bare ``UnpicklingError`` mid-resume.
Legacy bare-pickle files (pre-framing) still load; any corruption in them
surfaces as :class:`CheckpointError` too.  :func:`verify` is the cheap
integrity probe (header + CRC, no unpickling) the resume protocol's
``latest()`` scan uses to skip bad files.
"""
from __future__ import annotations

import os
import pickle
import struct
import tempfile
import zlib
from typing import Any, Dict

import jax
import numpy as np


class CheckpointError(RuntimeError):
    """A checkpoint file is unreadable: truncated, checksum-mismatched,
    or not a checkpoint at all.  Resume code can catch this one type and
    fall back to an older file (``resilience.ckpt.CheckpointManager``)."""


_MAGIC = b"APEXCKPT1\x00"
_HEADER = struct.Struct("<QI")          # payload length, CRC32
_CHUNK = 1 << 20


class _CrcWriter:
    """File-object proxy that accumulates CRC32 + length while pickle
    STREAMS to disk — no state-sized ``dumps`` copy in host RAM (the
    states this frames are multi-GB at BERT-large scale)."""

    def __init__(self, fh):
        self._fh = fh
        self.crc = 0
        self.length = 0

    def write(self, b):
        self.crc = zlib.crc32(b, self.crc)
        # nbytes, not len(): at protocol 5 the pickler hands large array
        # payloads over as raw buffer-protocol objects (PickleBuffer),
        # which have no len() — any leaf past the ~64 KB framing
        # threshold used to crash the save
        self.length += memoryview(b).nbytes
        return self._fh.write(b)


def _to_host(tree):
    """Device arrays -> numpy (leaves that aren't arrays pass through)."""
    def conv(x):
        if hasattr(x, "dtype") and hasattr(x, "shape"):
            return np.asarray(jax.device_get(x))
        return x
    return jax.tree_util.tree_map(conv, tree)


def save(path: str, **entries: Any) -> None:
    """Atomically write ``entries`` (pytrees of arrays / picklable values).

    The on-disk record is CRC-framed (``magic | length | crc32 | pickle``)
    so :func:`load`/:func:`verify` detect truncation and corruption.
    The pickle streams to disk through a CRC accumulator and the header
    is patched in afterwards — peak host memory stays one payload, not
    two."""
    payload = {k: _to_host(v) for k, v in entries.items()}
    d = os.path.dirname(os.path.abspath(path)) or "."
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, prefix=".ckpt_tmp_")
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(_MAGIC + _HEADER.pack(0, 0))        # placeholder
            w = _CrcWriter(f)
            pickle.dump(payload, w, protocol=pickle.HIGHEST_PROTOCOL)
            f.flush()
            f.seek(len(_MAGIC))
            f.write(_HEADER.pack(w.length, w.crc & 0xffffffff))
        os.replace(tmp, path)       # atomic on POSIX
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def _crc_scan(f, path: str, length: int, crc: int) -> None:
    """Chunked CRC pass over the payload region (no whole-file read);
    raises on truncation / mismatch and seeks back to the payload
    start so the caller can stream-unpickle."""
    start = f.tell()
    actual, n = 0, 0
    while True:
        chunk = f.read(_CHUNK)
        if not chunk:
            break
        actual = zlib.crc32(chunk, actual)
        n += len(chunk)
    if n != length:
        raise CheckpointError(
            f"{path}: truncated checkpoint ({n} of {length} "
            f"payload bytes — an interrupted or partial write)")
    if actual & 0xffffffff != crc:
        raise CheckpointError(f"{path}: checkpoint checksum mismatch "
                              "(file corrupted on disk)")
    f.seek(start)


def _open_checked(f, path: str):
    """Position ``f`` at the pickle stream after integrity checks.
    Framed files get the CRC pass; legacy bare-pickle files rewind to
    0; empty files raise."""
    head = f.read(len(_MAGIC))
    if head == _MAGIC:
        hdr = f.read(_HEADER.size)
        if len(hdr) < _HEADER.size:
            raise CheckpointError(f"{path}: truncated checkpoint header")
        length, crc = _HEADER.unpack(hdr)
        _crc_scan(f, path, length, crc)
        return f
    if not head:
        raise CheckpointError(f"{path}: empty checkpoint file")
    f.seek(0)                        # legacy pre-framing bare pickle
    return f


def load(path: str) -> Dict[str, Any]:
    """Read a checkpoint written by :func:`save` (numpy pytrees).

    Raises :class:`CheckpointError` for a truncated file, a checksum
    mismatch, or garbage content (legacy files included) — never a bare
    ``UnpicklingError`` mid-resume."""
    with open(path, "rb") as f:
        src = _open_checked(f, path)
        try:
            return pickle.load(src)
        except Exception as e:
            raise CheckpointError(
                f"{path}: checkpoint payload does not unpickle "
                f"({type(e).__name__}: {e})") from e


def verify(path: str) -> None:
    """Cheap integrity check: header + CRC for framed files (no
    unpickling), a full :func:`load` for legacy ones.  Raises
    :class:`CheckpointError` (or ``OSError`` for an unreadable path) on
    any problem — the probe ``resilience.ckpt``'s ``latest()`` runs
    before trusting a manifest entry."""
    with open(path, "rb") as f:
        head = f.read(len(_MAGIC))
        if head == _MAGIC:
            hdr = f.read(_HEADER.size)
            if len(hdr) < _HEADER.size:
                raise CheckpointError(f"{path}: truncated checkpoint header")
            length, crc = _HEADER.unpack(hdr)
            _crc_scan(f, path, length, crc)
            return
    load(path)


#: the conversion between the two optimizer layouts
FLAT_STATE_HINT = (
    "one side is a flat optimizer state (impl='fused': 1-D master / m / v), "
    "the other a per-leaf one (impl='xla': trees); convert with "
    "apex_tpu.multi_tensor_apply.TreeFlattener(params).unflatten(buffer, "
    "dtype=jnp.float32) (flat to tree; .flatten(tree) goes back)")


def _holds_flat_state(leaves) -> bool:
    """Whether ``leaves`` look like a flat optimizer state: some 1-D buffer
    is at least as long as every matrix and stacked leaf put together (the
    flat master / m / v span the whole model; a per-leaf state's vectors are
    biases and norm scales)."""
    arrays = [x for x in leaves if hasattr(x, "shape")]
    longest = max((x.size for x in arrays if x.ndim == 1), default=0)
    return 0 < longest >= sum(x.size for x in arrays if x.ndim >= 2)


def flat_state_hint(template_leaves, saved_leaves) -> str:
    """``"; " + FLAT_STATE_HINT`` where exactly one side holds a flat
    optimizer state, else ``""``: a mismatch of any other kind (a changed
    model) is not sent towards this conversion."""
    if _holds_flat_state(template_leaves) != _holds_flat_state(saved_leaves):
        return "; " + FLAT_STATE_HINT
    return ""


def restore_like(template, host_tree):
    """Device-put ``host_tree`` with the dtypes/shardings of ``template``
    (leaf-wise).  Shapes and structure must match (where one side is a flat
    optimizer state and the other per-leaf, the refusal names the
    conversion: :data:`FLAT_STATE_HINT`); dtypes are cast to the
    template's."""
    from jax.sharding import NamedSharding

    def put(t, h):
        arr = np.asarray(h)
        if tuple(arr.shape) != tuple(t.shape):
            raise ValueError(
                f"checkpoint leaf shape {arr.shape} != template {t.shape}")
        sh = getattr(t, "sharding", None)
        # only commit to an explicit mesh sharding; a plain single-device
        # placement would pin the restored array and fight jit's automatic
        # replication against sharded batch inputs
        if not isinstance(sh, NamedSharding):
            sh = None
        return jax.device_put(arr.astype(t.dtype), sh)
    if (jax.tree_util.tree_structure(template)
            != jax.tree_util.tree_structure(host_tree)):
        raise ValueError(
            "checkpoint tree structure differs from the template's: "
            f"{jax.tree_util.tree_structure(host_tree)} against "
            f"{jax.tree_util.tree_structure(template)}"
            + flat_state_hint(jax.tree_util.tree_leaves(template),
                              jax.tree_util.tree_leaves(host_tree)))
    return jax.tree_util.tree_map(put, template, host_tree)


# ---------------------------------------------------------------------------
# orbax backend — sharded, multi-host-safe checkpoints (SURVEY §5.4's
# "orbax-style checkpoint of (params, opt state, scaler state)").
#
# The pickle path above round-trips through host memory on one process —
# right for unit tests and single-chip runs, wrong at sharded-model scale
# (it would gather every shard to every host).  The orbax path writes each
# shard from the process that owns it and restores onto the template's
# shardings without materializing the global array anywhere.
# ---------------------------------------------------------------------------

def save_sharded(path: str, tree) -> None:
    """Write ``tree`` (a pytree of possibly-sharded jax arrays) with orbax.

    Every process in a multi-host job must call this with its view of the
    same global arrays; each writes only the shards it owns.  ``path``
    becomes a checkpoint directory (not a single file).

    Overwrite is non-destructive: the new checkpoint is written to a
    sibling temp dir and swapped in; a preemption mid-save leaves either
    the old checkpoint at ``path`` or (between the two renames) at
    ``path + ".old"`` — never zero checkpoints, matching the pickle
    path's atomic posture.

    Multi-host protocol: orbax's save is *collective* — every process
    writes only the shards it owns — so the temp dir name must be the
    same on every process (a per-pid name would scatter shards across
    directories and no directory would ever hold a complete checkpoint).
    Filesystem mutations of the shared ``path`` (stale-tmp cleanup and
    the final swap) run on process 0 only, fenced by global barriers so
    no process races ahead of the swap.

    Because the temp dir name is shared, concurrent *independent* jobs
    saving to the same ``path`` are unsupported: each would treat the
    other's live temp dir as its own stale leftover.  The stale-tmp
    cleanup is age-gated (only dirs untouched for >60s are removed) as a
    guard against deleting a live peer's write, but that is a heuristic,
    not a coordination mechanism — give independent jobs distinct paths.

    Failure coverage: the ok-flag allgather below turns a rank that
    *raises* during the save phase into a clean collective failure (all
    ranks raise together).  It cannot cover a rank that dies without
    raising — SIGKILL, machine loss, or a failure inside orbax's own
    internal sync points — which leaves peers blocked in ``ckptr.save``
    / ``process_allgather`` until the distributed runtime's own timeout.
    Multi-host jobs should run under a job-level watchdog (the posture
    of the reference's launcher) to bound that residual hang window."""
    import shutil

    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    tmp = f"{path}.new"
    is_lead = jax.process_index() == 0
    multihost = jax.process_count() > 1

    def _barrier(tag: str) -> None:
        if multihost:
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices(f"apex_tpu.save_sharded.{tag}")

    if is_lead:
        if not os.path.exists(path) and os.path.exists(f"{path}.old"):
            # survivor of a save preempted between the two swap renames:
            # .old is the last committed checkpoint — put it back before
            # anything else so "never zero checkpoints" holds across the
            # crash window (load_sharded has the matching fallback)
            os.rename(f"{path}.old", path)
        if os.path.exists(tmp):
            # leftover from a previous preempted save; remove before the
            # collective write so force=True semantics stay orbax-internal.
            # Age-gated: a tmp written to in the last minute may be a live
            # collective write from a concurrent independent job (an
            # unsupported layout — see docstring) — leave a fresh one to
            # orbax's own force handling rather than rmtree a live write.
            # "Written to" means the newest mtime ANYWHERE under the tree:
            # orbax streams shards into subdirectories, so the top-level
            # dir's mtime goes quiet seconds into a long live save.
            import time as _time
            newest = 0.0
            try:
                newest = os.path.getmtime(tmp)
                for root, _dirs, files in os.walk(tmp):
                    for ent in files:
                        try:
                            newest = max(newest, os.path.getmtime(
                                os.path.join(root, ent)))
                        except OSError:
                            pass
            except OSError:
                pass
            if newest == 0.0 or _time.time() - newest > 60.0:
                shutil.rmtree(tmp, ignore_errors=True)
    _barrier("pre_save")
    # capture a save-phase failure instead of raising past the collective:
    # a process that raises before the sync point strands its peers in the
    # barrier — instead every process reaches the allgather, learns whether
    # any peer failed, and they all raise together (clean job-level failure)
    save_err: BaseException | None = None
    try:
        with ocp.StandardCheckpointer() as ckptr:
            ckptr.save(tmp, tree, force=True)
    except BaseException as e:
        save_err = e
    if multihost:
        import numpy as _np
        from jax.experimental import multihost_utils
        ok_all = multihost_utils.process_allgather(
            _np.array([save_err is None]))
        if not bool(ok_all.all()):
            if save_err is not None:
                raise save_err
            raise RuntimeError(
                "save_sharded: collective orbax save failed on a peer "
                f"process (this rank ok); checkpoint left incomplete at {tmp}")
    elif save_err is not None:
        raise save_err
    try:
        if is_lead:
            if os.path.exists(path):
                old = f"{path}.old"
                shutil.rmtree(old, ignore_errors=True)
                os.rename(path, old)
                os.rename(tmp, path)
                shutil.rmtree(old, ignore_errors=True)
            else:
                os.rename(tmp, path)
    finally:
        # barrier unconditionally: a lead-side OSError must not leave the
        # other processes hanging in sync_global_devices — they release,
        # the lead raises, and the job-level launcher sees the failure
        _barrier("post_swap")


def load_sharded(path: str, template):
    """Restore a :func:`save_sharded` checkpoint directly onto
    ``template``'s shapes/dtypes/shardings (pass e.g. the freshly-built
    train state, or ``jax.eval_shape`` + shardings of one) — shards land
    on the devices that own them, no host gather."""
    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    if not os.path.exists(path) and os.path.exists(f"{path}.old"):
        # a save preempted between its two swap renames leaves the last
        # committed checkpoint at .old; every process sees the same
        # shared filesystem so this fallback is rank-consistent
        path = f"{path}.old"
    with ocp.StandardCheckpointer() as ckptr:
        return ckptr.restore(path, template)
