"""apex_tpu.elastic — topology-adaptive resume across chip-count changes.

The reference Apex (and every fixed-world SPMD stack) dies when the
fleet resizes: a preemptible slice joining or leaving changes the world
size, and a checkpoint written N-way cannot be blindly restored M-way.
This module combines the pieces the repo already proved —
:class:`~apex_tpu.resilience.guard.TrainGuard`'s bitwise resume, the
:mod:`~apex_tpu.parallel.plan` cost-model search (AMP arXiv:2210.07297:
re-run the heterogeneity-aware search whenever the device pool
changes), and the 1/N canonical-flat optimizer layout of
:mod:`~apex_tpu.parallel.weight_update` (arXiv:2004.13336) — into an
elastic resume:

  1. **detect** — the checkpoint MANIFEST records the world size, the
     active plan knobs, and the flat-shard layout
     (:class:`~apex_tpu.resilience.ckpt.CheckpointManager` meta); the
     guard compares it against the live mesh at resume;
  2. **re-plan** — :func:`replan` re-runs ``plan.search()`` for the NEW
     chip count;
  3. **reshard** — :func:`reshard_payload` re-slices the N-way state
     into M-way shards.  The zero1/ZeRO flat layout is *canonical*:
     ``jax.device_get`` of the P("data")-sharded global buffer already
     gathers the shards into the canonical flat order, so the only
     world-dependent part is the trailing zero padding that rounds the
     used prefix up to whole per-shard chunks
     (``flattener_for(params, chunk=LANE * world)``).  Re-sharding is
     therefore a deterministic re-chunk
     (:func:`~apex_tpu.parallel.collectives.rechunk_flat`): keep the
     ``used`` prefix, re-pad to the M-way total — bitwise on every real
     element, for the master/moment buffers AND the int8 error-feedback
     residuals (an all-zero pad block quantizes with scale 0, so the
     residual is zero there too and its sum is preserved exactly).
     Replicated leaves (params, amp scaler, step counters) pass through
     unchanged;
  4. **resume** — the guard restores the resharded payload under the
     new mesh sharding and continues mid-epoch.

Guarantees (tests/L0/test_elastic.py): the N-way -> canonical-flat ->
M-way -> canonical-flat round trip is BITWISE for arbitrary (N, M)
including non-divisible pairs, and a kill-8-resume-4 run finishes with
params bitwise-identical to a clean 4-way run started from the same
checkpoint.  The 4 -> 8 *grow* path holds at fp32 tolerance when int8
EF residuals are in play — the reshard itself is still exact, but the
wider axis changes the dequant-sum reduction order of the very next
step, so step outputs (not the restored state) differ in the last ulp.

Opt-in is explicit: without :func:`install` (or ``TrainGuard(elastic=
...)``), a world-size mismatch at resume raises the typed
:class:`~apex_tpu.resilience.ckpt.WorldSizeMismatchError` naming both
counts — loud, never a silent mis-sliced restore.

Usage::

    import apex_tpu.elastic as elastic
    elastic.install()                      # process-default resharder
    ...
    cfg = GuardConfig(ckpt_dir=..., world_size=4,
                      ckpt_meta={"plan": plan.knobs(),
                                 "layout": su.layout_meta(params, 4)})
    TrainGuard(step_fn, cfg).run(state_4way, batches, num_steps)
    # an 8-way manifest in ckpt_dir reshards to 4-way and resumes

See docs/resilience.md "Elastic resume" for the manifest fields, the
``resize@N:M`` chaos fault, and the guarantees table.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import numpy as np

from ..resilience.ckpt import (ManifestCompatWarning, WorldSizeMismatchError,
                               META_DATA_KEY, META_LAYOUT_KEY,
                               META_PLAN_KEY, META_WORLD_KEY)
from ..parallel import collectives as _coll
from ..parallel import plan as _plan

__all__ = [
    "ElasticResume", "ManifestCompatWarning", "WorldSizeMismatchError",
    "can_reshard", "install", "installed", "repartition_data", "replan",
    "reshard_payload", "uninstall",
]


def _emit_default(name: str, **fields) -> None:
    """Event emission mirroring TrainGuard._emit: the process-default
    registry when one is installed, else a tracer instant — elastic
    events must land in whatever observability the run is using."""
    from ..telemetry import events as _events
    reg = _events.get_default()
    if reg is not None and reg.enabled:
        reg.event(name, **fields)
        return
    from ..telemetry import trace as _trace
    _trace.note_event(name, step=fields.get("step"), fields=fields)


def can_reshard(meta: dict) -> bool:
    """Does this manifest meta carry what a reshard needs?  False for
    manifests written by pre-elastic versions — callers degrade to
    same-world resume (with a :class:`ManifestCompatWarning`), never
    KeyError."""
    return bool(
        isinstance(meta, dict)
        and meta.get(META_WORLD_KEY)
        and isinstance(meta.get(META_LAYOUT_KEY), dict)
        and meta[META_LAYOUT_KEY].get("flat_total")
        and meta[META_LAYOUT_KEY].get("used") is not None)


def reshard_payload(template_state, payload: dict, saved_meta: dict,
                    live_world: int, *, emit=None) -> dict:
    """Re-slice a guard checkpoint payload written at ``saved_meta``'s
    world size into the ``live_world`` layout of ``template_state``.

    The payload is the guard's snapshot dict (``{"step": int, "leaves":
    [host arrays]}``).  Leaves are matched positionally against the
    live template (same pytree contract as ``TrainGuard._restore``):

      * a 1-D saved leaf of the saved canonical length
        (``layout.flat_total``) whose template twin is 1-D with a
        different length is a **flat-shard field** (master/moments) —
        re-chunked via
        :func:`~apex_tpu.parallel.collectives.rechunk_flat` (keep the
        ``used`` prefix, zero-pad to the live total);
      * a 2-D ``(saved_world, flat_total)`` saved leaf whose template
        twin is ``(live_world, live_total)`` is a stack of
        **per-replica EF residuals** — each row is the quantization
        error its replica has not yet fed back.  The pending correction
        is the SUM over replicas, so resharding collapses the
        re-chunked rows onto replica 0 (sequential fp32 accumulation —
        deterministic, and the residual sum is preserved exactly) and
        zeros the rest; the full correction rides replica 0's next
        quantized exchange;
      * a 2-D leaf matching the layout's optional ``stacked`` block
        (``{"rows": N, "row_total": T, "row_used": int|[int,...]}`` —
        what a pipeline-stage / expert-shard lattice writes, one flat
        shard per stage/expert row) whose template twin is 2-D with a
        DIFFERENT row lattice is a **stage/expert resize**
        (``resize@N:M``): each saved row's ``row_used`` prefix is
        validated + stripped of its canonical zero padding through
        :func:`~apex_tpu.parallel.collectives.rechunk_flat`, the
        prefixes concatenate into the one canonical flat sequence, and
        that sequence re-chunks into the live ``(rows', row_total')``
        lattice (contiguous fill, padding only at the global tail) —
        bitwise on every real element, round-trippable N -> M -> N.  A
        sequence that does not FIT the live lattice is a true model
        change and raises;
      * everything else (replicated params, scalar counters, amp
        scaler state) passes through unchanged;
      * any other shape disagreement is a real model/config change —
        raised as :class:`WorldSizeMismatchError` with detail, not
        silently "fixed".

    Emits one ``elastic.reshard`` event (+ span) naming both worlds and
    the number of fields re-sliced.
    """
    import jax
    from ..telemetry import trace as _trace

    if not can_reshard(saved_meta):
        raise WorldSizeMismatchError(
            saved_meta.get(META_WORLD_KEY) or 0, live_world,
            detail="manifest lacks the flat-shard layout fields")
    layout = saved_meta[META_LAYOUT_KEY]
    saved_world = int(saved_meta[META_WORLD_KEY])
    saved_total = int(layout["flat_total"])
    used = int(layout["used"])
    emit = emit or _emit_default

    tmpl_leaves = jax.tree_util.tree_leaves(template_state)
    saved = payload["leaves"]
    if len(saved) != len(tmpl_leaves):
        raise WorldSizeMismatchError(
            saved_world, live_world,
            detail=f"checkpoint has {len(saved)} leaves but the live "
                   f"state has {len(tmpl_leaves)} — the model/optimizer "
                   "configuration changed, not just the world size")

    t0 = time.perf_counter()
    resharded = 0
    out = []
    with _trace.span("elastic.reshard", step=payload.get("step"),
                     from_world=saved_world, to_world=live_world):
        for t, h in zip(tmpl_leaves, saved):
            tshape = tuple(getattr(t, "shape", ()) or ())
            hshape = tuple(getattr(h, "shape", ()) or ())
            if tshape == hshape or not hasattr(h, "dtype"):
                out.append(h)
                continue
            if (len(hshape) == 1 and len(tshape) == 1
                    and hshape[0] == saved_total):
                out.append(_coll.rechunk_flat(h, used=used,
                                              total=tshape[0]))
                resharded += 1
                continue
            stacked = layout.get("stacked")
            if (isinstance(stacked, dict) and len(hshape) == 2
                    and len(tshape) == 2
                    and hshape == (int(stacked.get("rows") or -1),
                                   int(stacked.get("row_total") or -1))):
                # stage/expert resize: per-row flat shards -> one
                # canonical sequence -> the live row lattice
                ru = stacked.get("row_used", stacked.get("row_total"))
                used_rows = ([int(u) for u in ru]
                             if isinstance(ru, (list, tuple))
                             else [int(ru)] * hshape[0])
                if len(used_rows) != hshape[0]:
                    raise WorldSizeMismatchError(
                        saved_world, live_world,
                        detail=f"stacked.row_used has {len(used_rows)} "
                               f"entries for {hshape[0]} rows")
                rows_arr = np.asarray(h)
                try:
                    parts = [_coll.rechunk_flat(rows_arr[i], used=u,
                                                total=u)
                             for i, u in enumerate(used_rows)]
                    flat = (np.concatenate(parts) if parts
                            else np.zeros((0,), rows_arr.dtype))
                    out.append(_coll.rechunk_flat(
                        flat, used=int(flat.shape[0]),
                        total=tshape[0] * tshape[1]).reshape(tshape))
                except ValueError as err:
                    # content that cannot live in the new lattice is a
                    # real model change, not a world-size change
                    raise WorldSizeMismatchError(
                        saved_world, live_world,
                        detail=f"stage/expert resize {hshape} -> "
                               f"{tshape}: {err}")
                resharded += 1
                continue
            if (len(hshape) == 2 and len(tshape) == 2
                    and hshape == (saved_world, saved_total)
                    and tshape[0] == live_world):
                acc = np.zeros((tshape[1],), np.asarray(h).dtype)
                for row in np.asarray(h):
                    acc = acc + _coll.rechunk_flat(row, used=used,
                                                   total=tshape[1])
                stack = np.zeros(tshape, acc.dtype)
                stack[0] = acc
                out.append(stack)
                resharded += 1
                continue
            raise WorldSizeMismatchError(
                saved_world, live_world,
                detail=f"leaf shape {hshape} cannot be resharded into "
                       f"{tshape} (not a canonical flat field of length "
                       f"{saved_total})")
    emit("elastic.reshard", step=payload.get("step"),
         from_world=saved_world, to_world=live_world,
         fields_resharded=resharded, flat_total_saved=saved_total,
         used=used, seconds=time.perf_counter() - t0)
    return {**payload, "leaves": out}


def repartition_data(saved_meta: dict, live_world: int, *,
                     emit=None) -> Optional[dict]:
    """Re-partition the data-plane shard assignment for a resume at a
    new ingest-world size — the data half of the optimizer reshard.

    The seekable data plane (``data.sharded``) makes this DETERMINISTIC
    and cheap: the global batch of any step depends only on
    ``(seed, epoch, step)``, never on the host count, so N→M
    re-assignment is just re-slicing the same record stream — no record
    dropped, none duplicated (``tests/L0/test_data_sharded.py`` proves
    the round trip).  What remains at resume time is validation + the
    audit event: the saved ``meta["data"]`` block must exist (else
    None — nothing to re-partition, e.g. a synthetic source) and the
    recorded ``global_batch`` must divide over ``live_world`` (else a
    typed :class:`WorldSizeMismatchError` with detail — a batch that
    cannot shard M ways is a configuration change, not a resize).

    Emits one ``elastic.data_repartition`` event naming both worlds,
    the cursor step being re-sought, and the per-host record count, and
    returns the new assignment facts (``from_world``/``to_world``/
    ``records_per_host``/``cursor``)."""
    data = saved_meta.get(META_DATA_KEY) if isinstance(saved_meta, dict) \
        else None
    if not isinstance(data, dict) or not data.get("global_batch"):
        return None
    emit = emit or _emit_default
    gb = int(data["global_batch"])
    from_world = int(data.get("world") or 1)
    live_world = int(live_world)
    if live_world < 1 or gb % live_world:
        raise WorldSizeMismatchError(
            saved_meta.get(META_WORLD_KEY) or from_world, live_world,
            detail=f"data-plane global_batch {gb} cannot be "
                   f"re-partitioned over {live_world} ingest hosts")
    cursor = data.get("cursor") if isinstance(data.get("cursor"), dict) \
        else {}
    out = {"from_world": from_world, "to_world": live_world,
           "global_batch": gb, "records_per_host": gb // live_world,
           "index_digest": data.get("index_digest"),
           "cursor": cursor}
    emit("elastic.data_repartition", step=cursor.get("step"),
         from_world=from_world, to_world=live_world, global_batch=gb,
         records_per_host=gb // live_world,
         index_digest=data.get("index_digest"))
    return out


def replan(chips: int, *, profile=None, saved_knobs: Optional[dict] = None,
           emit=None, **search_kw) -> Optional[_plan.Plan]:
    """Re-run the auto-parallel cost-model search for a NEW chip count
    (the AMP posture: the plan is a function of the device pool — when
    the pool changes, search again).  ``profile`` is a
    :class:`~apex_tpu.parallel.plan.ModelProfile`; None profiles the
    flagship step (an AOT compile — pass a profile on hot paths).
    Returns the ranked winner (None when nothing is feasible) and emits
    one ``elastic.replan`` event carrying the old knobs (when known)
    and the new winner's.

    Callers: the elastic resume path (the pool changed across a
    restart) and the run controller's mid-run ``replan_reshard``
    actuator (``apex_tpu.control`` — the pool didn't change but the
    measured goodput regime did; same search, same ``elastic.replan``
    span, so the goodput ledger meters the mid-run search as
    ``reshard`` badput)."""
    from ..telemetry import trace as _trace
    emit = emit or _emit_default
    if profile is None:
        profile, _, _ = _plan.flagship_profile()
    t0 = time.perf_counter()
    with _trace.span("elastic.replan", chips=int(chips)):
        ranked = _plan.search(profile, int(chips), **search_kw)
    winner = ranked[0] if ranked else None
    emit("elastic.replan", chips=int(chips),
         candidates=len(ranked),
         old_knobs=dict(saved_knobs) if saved_knobs else None,
         new_knobs=winner.knobs() if winner is not None else None,
         predicted_step_ms=(winner.predicted_step_ms
                            if winner is not None else None),
         seconds=time.perf_counter() - t0)
    return winner


@dataclasses.dataclass
class ElasticResume:
    """The guard-facing resharder: what ``TrainGuard(elastic=...)`` or
    the process default installed by :func:`install` calls when a
    resume crosses a chip-count change.

    ``profile`` (a :class:`~apex_tpu.parallel.plan.ModelProfile`)
    enables the re-plan step — ``plan.search()`` re-runs for the live
    chip count and the winner lands in ``last_plan`` (and the
    ``elastic.replan`` event).  Without a profile only the reshard
    runs; profiling inside a resume would hide an AOT compile in the
    recovery path.  ``search_kw`` forwards to ``plan.search``
    (``capacity_bytes``, ``schemes``, ...)."""
    profile: object = None
    search_kw: dict = dataclasses.field(default_factory=dict)
    last_plan: Optional[_plan.Plan] = None
    #: the data-plane re-partition of the last resume (None when the
    #: manifest carried no data block) — :func:`repartition_data`
    last_data: Optional[dict] = None

    def resume(self, template_state, payload: dict, saved_meta: dict,
               live_world: int, *, emit=None) -> dict:
        out = reshard_payload(template_state, payload, saved_meta,
                              live_world, emit=emit)
        # the optimizer reshard's data-plane twin: re-partition the
        # shard assignment for the new world (pure validation + audit
        # event — the addressing itself is world-free by construction)
        self.last_data = repartition_data(saved_meta, live_world,
                                          emit=emit)
        if self.profile is not None:
            self.last_plan = replan(
                live_world, profile=self.profile,
                saved_knobs=saved_meta.get(META_PLAN_KEY), emit=emit,
                **self.search_kw)
        return out


def install(profile=None, **search_kw) -> ElasticResume:
    """Make the process elastic: register an :class:`ElasticResume` as
    the guard's default resharder.  Returns the installed object;
    :func:`uninstall` reverses it."""
    from ..resilience import guard as _guard
    er = ElasticResume(profile=profile, search_kw=dict(search_kw))
    _guard.set_resharder(er)
    return er


def uninstall() -> None:
    """Remove the process-default resharder."""
    from ..resilience import guard as _guard
    _guard.set_resharder(None)


def installed() -> Optional[ElasticResume]:
    """The process-default resharder, if :func:`install` ran."""
    from ..resilience import guard as _guard
    return _guard.get_resharder()
