"""``TrainGuard`` — a self-resuming driver around any jitted step fn.

The repo's failure-handling fragments (amp skip-step, ZeRO
select-revert, atomic ``checkpoint.save``) become one operational layer
(SURVEY §5.3/§5.4): the guard owns the step loop and gives it

  * **checkpoint cadence** — every ``save_every_steps`` steps and/or
    ``save_every_seconds`` of wall clock, snapshots are taken at health-
    checked boundaries and written by a background thread (the step loop
    never blocks on disk);
  * **preemption safety** — SIGTERM/SIGINT (real, or injected via a
    ``preempt`` fault) become snapshot-then-clean-exit, so a preemption
    mid-run costs the steps since the last boundary, not the run;
  * **auto-resume** — a new ``run()`` over the same checkpoint dir picks
    up at the manifest's newest verified checkpoint (corrupt files are
    skipped), bitwise-identically when the batch source is
    step-addressable;
  * **escalation → rollback** — a non-finite-loss streak or a dynamic
    loss scale pinned at its floor (``amp.scaler.floor_pinned``) rolls
    the state back to the last good checkpoint with a bounded retry
    budget and exponential backoff;
  * **telemetry** — ``fault_injected`` / ``rollback`` / ``resumed`` /
    ``checkpoint_saved`` events through the PR-2 registry (the installed
    process default, or one passed in).

Step-fn contract: ``step_fn(state, batch) -> new_state`` or
``(new_state, loss, *aux)``; ``state`` is any pytree — an ``AmpState``,
a ``(amp_state, bn_state)`` carry, a plain dict.  The batch source is
either a callable ``batches(step) -> batch`` (step-addressable: resume
and rollback replay identical data — required for the bitwise-resume
guarantee) or a plain iterator (resume starts it from its current
position; rollback is impossible and aborts with a clear error).

Host-sync budget: the guard batches ALL its host reads (pending losses
+ the loss scale) into one ``jax.device_get`` per ``check_every`` steps
— the telemetry registry's batching discipline.  Snapshots add one
batched device read at checkpoint cadence.  A **disabled** guard
(``GuardConfig(enabled=False)`` or ``APEX_TPU_GUARD=0``) is a true
no-op: it calls the step fn and nothing else — zero extra host syncs
per step, no signal handlers, no threads, asserted by
``tests/L0/test_resilience.py``.
"""
from __future__ import annotations

import dataclasses
import os
import queue
import signal
import threading
import time
import warnings
from typing import Any, Callable, List, Optional, Tuple

import numpy as np

from . import faults as _faults
from .ckpt import (CheckpointManager, DataStreamMismatchError,
                   ManifestCompatWarning, WorldSizeMismatchError,
                   META_DATA_KEY, META_LAYOUT_KEY, META_PLAN_KEY,
                   META_WORLD_KEY)
from ..checkpoint import CheckpointError, flat_state_hint


class GuardAbort(RuntimeError):
    """The guard cannot make progress: rollback budget exhausted, no
    checkpoint to roll back to, or a rollback was needed on a
    non-replayable (iterator) batch source."""


def _env_enabled() -> bool:
    from ..telemetry.trace import env_flag   # the one boolean-env parser
    return env_flag("APEX_TPU_GUARD")


# -- elastic resharder hook ---------------------------------------------------
# apex_tpu.elastic.install() registers a process-default resharder here;
# TrainGuard(elastic=...) pins one per guard.  Anything with a
# ``resume(template, payload, saved_meta, live_world, emit=...) ->
# payload`` method qualifies.  Without one, a world-size mismatch at
# resume is a typed, LOUD failure (WorldSizeMismatchError), never a
# silent garbage restore.

_RESHARDER = None


def set_resharder(resharder):
    """Install ``resharder`` as the process default (None uninstalls).
    Returns the previous one so callers can restore it."""
    global _RESHARDER
    prev = _RESHARDER
    _RESHARDER = resharder
    return prev


def get_resharder():
    return _RESHARDER


def _infer_world(state) -> Optional[int]:
    """The state's mesh size: the device count of the first
    NamedSharding leaf (a shard_map/pmap-produced step carry is sharded
    over its mesh — replicated leaves included).  None for plain
    single-device state, where world-size bookkeeping is meaningless."""
    import jax
    from jax.sharding import NamedSharding
    for leaf in jax.tree_util.tree_leaves(state):
        sh = getattr(leaf, "sharding", None)
        if isinstance(sh, NamedSharding):
            return int(sh.mesh.devices.size)
    return None


@dataclasses.dataclass
class GuardConfig:
    """Policy knobs for :class:`TrainGuard`.

    ``check_every`` is the health-check cadence (steps per batched host
    read); checkpoint cadence is evaluated at those same boundaries so
    every checkpoint is health-screened before it is written.
    ``floor_patience`` counts consecutive *checks* (not steps) the
    dynamic loss scale sits at its floor before escalating; 0 disables
    that detector.  ``flight_dir`` is where flight-recorder dumps land
    on rollback/preempt/exception (default: the tracer's own directory,
    else next to the checkpoints).  ``enabled=None`` reads
    ``APEX_TPU_GUARD`` (default on).

    ``world_size`` pins the live world recorded in the checkpoint
    manifest (default: inferred from the state's mesh sharding);
    ``ckpt_meta`` is extra manifest meta merged in — the elastic-resume
    contract puts the plan knobs under ``"plan"`` and the
    ``ShardedUpdate.layout_meta`` dict under ``"layout"`` so a resume
    at a different chip count can reshard instead of crash."""
    ckpt_dir: Optional[str] = None
    save_every_steps: int = 0
    save_every_seconds: float = 0.0
    keep_last: int = 3
    check_every: int = 10
    nonfinite_streak: int = 3
    floor_patience: int = 0
    max_retries: int = 3
    backoff_seconds: float = 0.25
    save_on_exit: bool = True
    auto_resume: bool = True
    flight_dir: Optional[str] = None
    enabled: Optional[bool] = None
    world_size: Optional[int] = None
    ckpt_meta: Optional[dict] = None

    def __post_init__(self):
        if self.enabled is None:
            self.enabled = _env_enabled()
        if self.check_every < 1:
            raise ValueError("check_every must be >= 1")


@dataclasses.dataclass
class GuardReport:
    """What a :meth:`TrainGuard.run` did.  ``status`` is ``"completed"``
    (reached num_steps), ``"preempted"`` (SIGTERM/SIGINT/injected
    preemption — state snapshotted, rerun resumes), or ``"disabled"``."""
    status: str
    final_step: int
    resumed_from: Optional[int] = None
    rollbacks: int = 0
    faults_injected: int = 0
    checkpoints: int = 0
    #: an injected ``resize@N:M`` fault stopped the run: the target
    #: world size to bring it back up at (via apex_tpu.elastic)
    resize_to: Optional[int] = None
    #: the resume crossed a chip-count change and the checkpoint was
    #: resharded (saved world -> live world)
    resharded_from: Optional[int] = None
    #: the run-level goodput ledger doc (``telemetry.goodput``: every
    #: wall-clock second attributed to exactly one class) and the
    #: ``GOODPUT.json`` path it was written to — None when no tracer
    #: was active (the ledger streams off the default tracer's spans)
    goodput: Optional[dict] = None
    goodput_path: Optional[str] = None
    #: the run controller's decision-ledger doc (``apex_tpu.control``)
    #: and the ``CONTROL.json`` path it was written to — None when no
    #: enabled controller rode the run
    control: Optional[dict] = None
    control_path: Optional[str] = None
    #: the live OpenMetrics scrape URL (``telemetry.export``) this run
    #: served — None unless ``APEX_TPU_METRICS_PORT`` armed the
    #: endpoint (the run identity is stamped on the exporter, so a
    #: scrape names which run it is reading)
    export_url: Optional[str] = None


def _observed_save(manager: CheckpointManager, step: int, payload,
                   registry=None) -> str:
    """``manager.save`` wrapped in the checkpoint observability hooks
    (docs/telemetry.md): a ``ckpt.write`` span through the default
    tracer and write-duration / bytes-written gauges through
    ``registry`` (the guard's pinned registry, like every other guard
    emission) or the process default.  Runs on whichever thread saves —
    the background writer included — so both hooks are thread-safe
    (lock-protected tracer, atomic gauge assignment)."""
    from ..telemetry import events as _tel_events
    from ..telemetry import trace as _trace
    t0 = time.perf_counter()
    with _trace.span("ckpt.write", step=step):
        path = manager.save(step, payload)
    dur = time.perf_counter() - t0
    try:
        nbytes = os.path.getsize(path)
    except OSError:   # pragma: no cover - raced rotation
        nbytes = 0
    _tel_events.record_ckpt(dur, nbytes, reg=registry)
    return path


class _AsyncWriter:
    """Background checkpoint writer: the main loop hands (step, host
    payload) over a small bounded queue and keeps stepping while the
    pickle+write happens off-thread.  A write failure is re-raised at
    the next submit/drain — silently losing checkpoints would void the
    resume guarantee."""

    def __init__(self, manager: CheckpointManager, registry=None):
        self._manager = manager
        self._registry = registry
        self._q: "queue.Queue" = queue.Queue(maxsize=2)
        self._exc: Optional[BaseException] = None
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="apex-tpu-ckpt-writer")
        self._thread.start()
        self.written = 0

    def _loop(self):
        while True:
            item = self._q.get()
            try:
                if item is None:
                    return
                step, payload = item
                try:
                    _observed_save(self._manager, step, payload,
                                   registry=self._registry)
                    self.written += 1
                except BaseException as e:
                    self._exc = e
            finally:
                self._q.task_done()

    def _check(self):
        if self._exc is not None:
            exc, self._exc = self._exc, None
            raise exc

    def submit(self, step: int, payload) -> None:
        self._check()
        self._q.put((step, payload))

    def drain(self) -> None:
        """Block until every submitted checkpoint is on disk."""
        self._q.join()
        self._check()

    def close(self) -> None:
        self._q.put(None)
        self._thread.join(timeout=60.0)


def _find_scaler(state):
    """Locate a ScalerState for the floor detector: ``state.scalers[0]``
    on an AmpState, or on any element one level into a tuple/list/dict
    carry.  Explicit ``scaler_fn`` overrides this probe."""
    sc = getattr(state, "scalers", None)
    if sc:
        return sc[0]
    children = (state if isinstance(state, (tuple, list))
                else state.values() if isinstance(state, dict) else ())
    for el in children:
        sc = getattr(el, "scalers", None)
        if sc:
            return sc[0]
    return None


class TrainGuard:
    """The step driver.  See the module docstring for the contract.

    ``plan`` pins a :class:`~apex_tpu.resilience.faults.FaultPlan`
    (default: the installed/env plan at each ``run``); ``registry`` pins
    a telemetry registry (default: the process default at emit time);
    ``scaler_fn(state) -> ScalerState`` overrides the auto-probe for the
    floor detector; ``elastic`` pins a checkpoint resharder
    (:class:`apex_tpu.elastic.ElasticResume`; default: whatever
    ``apex_tpu.elastic.install()`` registered) so a resume across a
    chip-count change reshards instead of raising
    :class:`~apex_tpu.resilience.ckpt.WorldSizeMismatchError`;
    ``on_check(step, losses)`` is called with the
    resolved loss window at every health check (the example loops' print
    hook — the values are already host floats, printing costs nothing
    extra); ``controller`` pins an
    :class:`apex_tpu.control.RunController` that rides the same batched
    health-check window (``controller.on_window`` right after every
    batched read — the controller adds ZERO host syncs of its own, and
    a disabled/absent controller leaves the loop bitwise-untouched)."""

    def __init__(self, step_fn: Callable, config: GuardConfig, *,
                 plan=None, registry=None, scaler_fn=None, elastic=None,
                 on_check: Optional[Callable[[int, List[float]],
                                             None]] = None,
                 controller=None):
        self.step_fn = step_fn
        self.cfg = config
        self._plan = plan
        self._registry = registry
        self._scaler_fn = scaler_fn
        self._elastic = elastic
        self._on_check = on_check
        self._controller = controller
        self._stop = False
        self.manager = (CheckpointManager(config.ckpt_dir,
                                          keep_last=config.keep_last)
                        if config.enabled and config.ckpt_dir else None)

    # -- telemetry ----------------------------------------------------------
    def _emit(self, name: str, **fields) -> None:
        reg = self._registry
        if reg is None:
            from ..telemetry import events as _events
            reg = _events.get_default()
        if reg is not None and reg.enabled:
            reg.event(name, **fields)   # the registry copies the event
            return                      # into the flight ring itself
        from ..telemetry import trace as _trace
        _trace.note_event(name, step=fields.get("step"), fields=fields)

    def _flight_destination(self, recorder_directory):
        """The ONE dump-directory chain both flight paths share:
        ``cfg.flight_dir`` > the recorder's own directory > next to the
        checkpoints."""
        return (self.cfg.flight_dir or recorder_directory
                or (self.manager.directory if self.manager else None))

    def _dump_flight(self, reason: str, step: int, **fields):
        """Dump the flight recorder on a guard lifecycle failure
        (rollback / preempt / unhandled exception).  Destination:
        :meth:`_flight_destination`.  Best-effort — a failed dump never
        fails the run.  Returns the written path (or None)."""
        from ..telemetry import trace as _trace
        tr = _trace.get_tracer()
        if tr is None or not tr.enabled:
            return None
        directory = self._flight_destination(tr.recorder.directory)
        if directory is None:
            return None
        try:
            return tr.recorder.dump(reason, step=step, directory=directory,
                                    fields=fields)
        except Exception:   # disk full, or an off-schema ring entry —
            return None     # a failed dump must never mask the real
                            # error propagating through run()

    def _dump_oom(self, step: int, exc: BaseException):
        """The OOM post-mortem (``flight-oom-<ts>.json``): allocator
        report parsed from the error, the registry monitor's
        live-memory history, the registered static attribution, and the
        flight ring — written even when no tracer is installed (a
        crash artifact must not depend on tracing being on).
        Best-effort like :meth:`_dump_flight`; the OOM always
        re-raises either way."""
        from ..telemetry import memory as _tmem
        from ..telemetry import trace as _trace
        tr = _trace.get_tracer()
        recorder = tr.recorder if (tr is not None and tr.enabled) else None
        directory = self._flight_destination(
            recorder.directory if recorder is not None else None)
        if directory is None:
            return None
        reg = self._registry
        if reg is None:
            from ..telemetry import events as _events
            reg = _events.get_default()
        try:
            return _tmem.dump_oom(recorder, step=step, error=exc,
                                  directory=directory, registry=reg)
        except Exception:
            return None

    def _blocked_ckpt(self, step: int, fn):
        """Run a checkpoint operation the STEP LOOP waits on — a writer
        drain/submit or an inline anchor/exit save — inside a
        ``ckpt.exposed`` span + ``ckpt.exposed_ms`` meter
        (docs/telemetry.md Goodput ledger).  Only this boundary-blocked
        time charges the run's wall-clock ledger; the background
        writer's own ``ckpt.write`` duration is overlapped by design
        and stays out of the accounting, so a fully-overlapped
        background save contributes ~0 exposed ms."""
        from ..telemetry import events as _tel_events
        from ..telemetry import trace as _trace
        t0 = time.perf_counter()
        try:
            return fn()
        finally:
            dur = time.perf_counter() - t0
            _trace.note_span("ckpt.exposed", dur, step=step)
            _tel_events.record_ckpt_exposed(dur, reg=self._registry,
                                            step=step)

    def _finalize_goodput(self, ledger, tracer, prev_ledger, report):
        """Close out the run's goodput ledger (best-effort —
        observability must never mask the real error propagating
        through ``run()``): detach it from the tracer, restore the
        previously-installed process ledger, export the final
        ``goodput.fraction``/``badput.*`` gauges, and write the
        schema-valid ``GOODPUT.json`` run artifact on the
        flight-recorder destination chain — exit, preempt and crash
        all leave the artifact."""
        from ..telemetry import events as _tel_events
        from ..telemetry import goodput as _goodput
        ledger.detach(tracer)
        _goodput.install(prev_ledger)
        try:
            doc = ledger.snapshot(status=report.status)
            report.goodput = doc
            reg = self._registry
            if reg is None:
                reg = _tel_events.get_default()
            ledger.observe(reg, doc=doc)
            directory = self._flight_destination(
                tracer.recorder.directory if tracer is not None else None)
            if directory is not None:
                report.goodput_path = ledger.write(directory=directory,
                                                   doc=doc)
        except Exception:   # disk full / off-schema doc: the run's
            pass            # outcome must still propagate untouched

    def _finalize_control(self, ctl, tracer, report) -> None:
        """Close out the run controller's decision ledger (best-effort,
        like :meth:`_finalize_goodput`): snapshot the ``CONTROL.json``
        doc with the run's final status and write it on the same
        flight-recorder destination chain — exit, preempt and crash
        all leave the audit trail."""
        try:
            doc = ctl.snapshot(status=report.status)
            report.control = doc
            directory = self._flight_destination(
                tracer.recorder.directory
                if tracer is not None and tracer.enabled else None)
            if directory is not None:
                report.control_path = ctl.write(directory=directory,
                                                doc=doc)
        except Exception:   # the audit artifact must never mask the
            pass            # run's real outcome

    # -- controller actuation ------------------------------------------------
    def request_resize(self, target_world: int, *, step=None,
                       reason: str = "control") -> None:
        """A synthesized ``resize@N:M``: the run controller's
        quarantine actuator calls this from INSIDE the health-check
        boundary, so unlike the injected fault no signal is needed —
        record the target world in the report and flip the stop flag;
        the loop's existing preempt machinery does the
        snapshot-then-clean-exit, and the harness brings the run back
        up at ``target_world`` through the elastic reshard, exactly
        like a fleet resize."""
        rep = getattr(self, "_report", None)
        if rep is None:
            raise RuntimeError("request_resize outside an active "
                               "guarded run")
        rep.resize_to = int(target_world)
        self._emit("control.resize_requested", step=step,
                   target_world=int(target_world), reason=str(reason))
        self._stop = True

    # -- state <-> host ------------------------------------------------------
    def _snapshot(self, state, step: int) -> dict:
        """Host payload for ``state``: the leaf list (one batched device
        read), unflattened at restore against the live state's treedef —
        static pytree metadata (Properties, optimizer objects) is never
        pickled, so any AmpState snapshots cleanly."""
        import jax
        leaves = jax.tree_util.tree_leaves(state)
        host = jax.device_get(leaves)
        host = [np.asarray(x) if hasattr(x, "dtype") else x for x in host]
        return {"step": int(step), "leaves": host}

    def _restore(self, template, payload: dict):
        import jax
        leaves, treedef = jax.tree_util.tree_flatten(template)
        saved = payload["leaves"]
        if len(saved) != len(leaves):
            raise CheckpointError(
                f"checkpoint has {len(saved)} leaves but the live state "
                f"has {len(leaves)} — the model/optimizer configuration "
                "changed since the checkpoint was written"
                + flat_state_hint(leaves, saved))

        from jax.sharding import NamedSharding

        def put(t, h):
            if not (hasattr(t, "dtype") and hasattr(t, "shape")):
                return h
            arr = np.asarray(h)
            if tuple(arr.shape) != tuple(t.shape):
                raise CheckpointError(
                    f"checkpoint leaf shape {arr.shape} != live "
                    f"{tuple(t.shape)}")
            # keep an explicit mesh sharding; anything else is left to
            # jit's automatic placement (checkpoint.restore_like's rule)
            sh = getattr(t, "sharding", None)
            if not isinstance(sh, NamedSharding):
                sh = None
            return jax.device_put(arr.astype(t.dtype), sh)
        return jax.tree_util.tree_unflatten(
            treedef, [put(t, h) for t, h in zip(leaves, saved)])

    def _maybe_reshard(self, template, payload, saved_meta: dict,
                       live_world: Optional[int], report) -> dict:
        """Route a resume whose saved world size differs from the live
        one through the elastic resharder; same-world (or world-
        agnostic) resumes pass the payload through untouched.

        No resharder installed -> :class:`WorldSizeMismatchError`,
        LOUDLY, naming both counts — the alternative is a shape-
        coincidence restore that silently mis-slices the optimizer
        shards.  A pre-elastic manifest (no recorded world size /
        layout) degrades to same-world-only with a typed
        :class:`ManifestCompatWarning` instead of a KeyError."""
        resharder = (self._elastic if self._elastic is not None
                     else get_resharder())
        saved_world = saved_meta.get(META_WORLD_KEY)
        if not saved_world or not live_world:
            if resharder is not None and not saved_meta.get(META_WORLD_KEY):
                warnings.warn(
                    "checkpoint manifest records no world size (written "
                    "by a pre-elastic version): reshard unavailable, "
                    "same-world resume only", ManifestCompatWarning,
                    stacklevel=3)
            return payload
        saved_world, live_world = int(saved_world), int(live_world)
        if saved_world == live_world:
            return payload
        if resharder is None:
            raise WorldSizeMismatchError(saved_world, live_world)
        if not isinstance(saved_meta.get(META_LAYOUT_KEY), dict):
            warnings.warn(
                "checkpoint manifest records no flat-shard layout "
                "(written by a pre-elastic version): reshard "
                "unavailable, same-world resume only",
                ManifestCompatWarning, stacklevel=3)
            raise WorldSizeMismatchError(
                saved_world, live_world,
                detail="manifest lacks the flat-shard layout fields")
        payload = resharder.resume(template, payload, saved_meta,
                                   live_world, emit=self._emit)
        report.resharded_from = saved_world
        return payload

    # -- the data-plane cursor (docs/data.md) --------------------------------
    @staticmethod
    def _data_meta(batches) -> Optional[dict]:
        """The batch source's run-level data facts, when it speaks the
        seekable protocol (``data.sharded.ShardedLoader`` — a
        ``data_meta()`` method).  None for synthetic callables and
        plain iterators: the manifest simply carries no data block, as
        before."""
        meta_fn = getattr(batches, "data_meta", None)
        if not callable(meta_fn):
            return None
        try:
            meta = meta_fn()
        except Exception:   # a broken probe must not kill the run
            return None
        return meta if isinstance(meta, dict) else None

    def _record_cursor(self, batches, step: int) -> None:
        """Refresh the manifest's data-plane block with the cursor at
        ``step`` — pure host arithmetic on the loader's index, merged
        under the manager lock, so every manifest write names the
        stream position its newest checkpoint resumes at."""
        if self.manager is None:
            return
        cursor_fn = getattr(batches, "cursor", None)
        meta = self._data_meta(batches)
        if meta is None or not callable(cursor_fn):
            return
        try:
            meta = {**meta, "cursor": cursor_fn(int(step))}
        except Exception:
            return
        self.manager.update_meta({META_DATA_KEY: meta})

    @staticmethod
    def _check_data_stream(batches, saved_meta: dict) -> None:
        """A manifest that names a dataset index digest must be resumed
        against the SAME dataset: a digest mismatch raises the typed
        :class:`DataStreamMismatchError` instead of silently seeking a
        different stream.  Manifests without a data block (synthetic
        sources, older versions) pass through untouched."""
        saved = saved_meta.get(META_DATA_KEY)
        if not isinstance(saved, dict) or not saved.get("index_digest"):
            return
        live = TrainGuard._data_meta(batches)
        if live is None or not live.get("index_digest"):
            return   # source can't prove identity: degrade like before
        if str(live["index_digest"]) != str(saved["index_digest"]):
            raise DataStreamMismatchError(saved["index_digest"],
                                          live["index_digest"])

    # -- signals -------------------------------------------------------------
    def _install_handlers(self):
        if threading.current_thread() is not threading.main_thread():
            return None
        prev = {}

        def handler(signum, frame):
            self._stop = True
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                prev[sig] = signal.signal(sig, handler)
            except (ValueError, OSError):  # pragma: no cover - exotic host
                pass
        return prev

    @staticmethod
    def _restore_handlers(prev):
        if not prev:
            return
        for sig, old in prev.items():
            try:
                signal.signal(sig, old)
            except (ValueError, OSError):  # pragma: no cover
                pass

    # -- the loop ------------------------------------------------------------
    @staticmethod
    def _splitter(state):
        """Build the ``out -> (new_state, loss)`` splitter for THIS
        state shape.  A tuple return is only (new_state, loss, *aux)
        when it is NOT structurally the state itself — a step fn
        returning a bare ``(amp_state, bn_state)`` carry must not have
        its bn_state mistaken for a loss."""
        import jax
        if not isinstance(state, tuple):
            def split(out) -> Tuple[Any, Optional[Any]]:
                if isinstance(out, tuple) and len(out) >= 2:
                    return out[0], out[1]
                return out, None
            return split
        state_def = jax.tree_util.tree_structure(state)

        def split(out) -> Tuple[Any, Optional[Any]]:
            if isinstance(out, tuple) and len(out) >= 2 \
                    and jax.tree_util.tree_structure(out) != state_def:
                return out[0], out[1]
            return out, None
        return split

    def run(self, state, batches, num_steps: int, *, start_step: int = 0):
        """Drive ``num_steps`` steps (global indices ``start_step`` ..
        ``num_steps - 1``) and return ``(final_state, GuardReport)``."""
        cfg = self.cfg
        seekable = callable(batches)
        split = self._splitter(state)
        if not cfg.enabled:
            it = None if seekable else iter(batches)
            for step in range(start_step, num_steps):
                batch = batches(step) if seekable else next(it)
                state, _ = split(self.step_fn(state, batch))
            return state, GuardReport(status="disabled",
                                      final_step=num_steps)

        plan = self._plan if self._plan is not None else _faults.active_plan()
        it = None if seekable else iter(batches)
        report = GuardReport(status="completed", final_step=start_step)
        self._report = report   # request_resize targets the live run
        mgr = self.manager
        step = start_step
        # the run controller rides the health-check window below; a
        # disabled controller (APEX_TPU_CONTROL=0) is dropped HERE so
        # every touch point in the loop is skipped — the no-op contract
        ctl = self._controller
        if ctl is not None and not getattr(ctl, "enabled", False):
            ctl = None

        from ..telemetry import events as _tel_events
        from ..telemetry import goodput as _goodput
        from ..telemetry import trace as _trace

        live_world = cfg.world_size or _infer_world(state)
        # the live OpenMetrics endpoint (telemetry.export): armed only
        # when APEX_TPU_METRICS_PORT is set — otherwise maybe_start
        # allocates nothing (the disabled-mode contract).  Stamped with
        # this run's identity; shut down in the finally iff THIS run
        # started it (a pre-installed exporter outlives the run)
        from ..telemetry import export as _export
        _exp_owned = _export.get_exporter() is None
        _reg = (self._registry if self._registry is not None
                else _tel_events.get_default())
        exporter = _export.maybe_start(
            run_id=getattr(_reg, "run_id", None) or f"guard-{os.getpid()}")
        _exp_owned = _exp_owned and exporter is not None
        if exporter is not None:
            exporter.set_meta(world=live_world, pid=os.getpid())
            report.export_url = exporter.url
        if mgr is not None:
            meta = {}
            if live_world:
                meta[META_WORLD_KEY] = int(live_world)
            if cfg.ckpt_meta:
                meta.update(cfg.ckpt_meta)
            data_meta = self._data_meta(batches)
            if data_meta is not None:
                meta[META_DATA_KEY] = data_meta
            if meta:
                mgr.set_meta(meta)

        self._stop = False
        prev_handlers = self._install_handlers()
        writer = (_AsyncWriter(mgr, registry=self._registry)
                  if mgr is not None else None)
        pending: List[Tuple[int, Any]] = []   # (step, device loss)
        since_check = 0    # steps since the last boundary — NOT len(pending):
        # a loss-less step fn must still hit the checkpoint cadence
        self._streak = 0
        self._floor_checks = 0
        self._last_bad_step: Optional[int] = None
        self._last_losses: List[float] = []
        # the run-level goodput ledger (docs/telemetry.md Goodput
        # ledger): one per run, streaming off the default tracer's
        # spans/events, installed as the process ledger so every
        # Registry.flush exports live goodput.fraction / badput.*
        # gauges through its batched window.  The jax compilation
        # meter registers alongside (idempotent, one prefix check per
        # monitoring event) so a shape-churn retrace lands in the
        # ledger's recompile class instead of inflating "step time".
        # Finalized — gauges + GOODPUT.json on the flight destination
        # chain — in the finally below, so exit, preempt AND crash all
        # leave the run artifact.  No tracer (or a disabled one) means
        # no ledger: zero extra cost, the subsystem's bar.
        _tel_events.install_compile_listener()
        tracer = _trace.get_tracer()
        ledger = prev_ledger = None
        if tracer is not None and tracer.enabled:
            ledger = _goodput.GoodputLedger()
            ledger.attach(tracer)
            prev_ledger = _goodput.install(ledger)
        try:
            resumed_meta = None
            if mgr is not None and cfg.auto_resume:
                found = mgr.load_latest(with_meta=True)
                if found is not None and found[0] > start_step:
                    ck_step, payload, saved_meta = found
                    resumed_meta = saved_meta
                    # the data stream must be the SAME one the manifest
                    # cursor names — seeking a changed dataset would
                    # silently void the bitwise replay guarantee
                    self._check_data_stream(batches, saved_meta)
                    payload = self._maybe_reshard(state, payload,
                                                  saved_meta, live_world,
                                                  report)
                    with _trace.span("ckpt.restore", step=found[0]):
                        state = self._restore(state, payload)
                    step = min(ck_step, num_steps)
                    seek = getattr(batches, "seek", None)
                    if seekable and callable(seek):
                        seek(step)   # position any prefetch iteration too
                    report.resumed_from = ck_step
                    self._emit("resumed", step=ck_step)
                    if plan is not None:
                        # faults scheduled before the resume point
                        # already happened in the interrupted run; a
                        # re-armed env plan must not re-fire them (a
                        # re-firing preempt would wedge the run in a
                        # preempt/resume loop)
                        plan.skip_until(step)
            if ctl is not None:
                # attach AFTER the resume so an acted config recorded
                # in the interrupted run's manifest meta (a mid-action
                # preempt) is re-applied before any step runs
                ctl.arm(guard=self, manager=mgr, live_world=live_world,
                        saved_meta=resumed_meta)
            last_saved = step
            t_last_save = time.monotonic()
            if mgr is not None and step < num_steps:
                # rollback anchor: escalation before the first cadence
                # save must still have somewhere to go.  Inline (the
                # writer thread is idle this early), so the whole save
                # is boundary-blocked — metered as such
                self._record_cursor(batches, step)
                self._blocked_ckpt(step, lambda: _observed_save(
                    mgr, step, self._snapshot(state, step),
                    registry=self._registry))
                report.checkpoints += 1
            while step < num_steps:
                if plan is not None and not self._stop:
                    spec = plan.fire("resize", step)
                    if spec is not None:
                        # a simulated fleet resize: snapshot-then-clean-
                        # exit exactly like preempt, remembering the
                        # target world so the harness restarts at M
                        # chips and elastic reshards the checkpoint
                        report.faults_injected += 1
                        report.resize_to = int(spec.arg)
                        self._emit("fault_injected", kind="resize",
                                   step=step, target_world=int(spec.arg))
                        signal.raise_signal(signal.SIGTERM)
                if plan is not None and not self._stop \
                        and plan.fire("preempt", step) is not None:
                    report.faults_injected += 1
                    self._emit("fault_injected", kind="preempt", step=step)
                    signal.raise_signal(signal.SIGTERM)
                if self._stop:
                    break
                if plan is not None:
                    spec = plan.fire("goodput_degrade", step)
                    if spec is not None:
                        # sustained synthetic badput: sleep OUTSIDE any
                        # span, so the goodput ledger's exact partition
                        # attributes it to idle and the controller's
                        # windowed goodput_fraction sinks — the
                        # replan-policy chaos trigger
                        report.faults_injected += 1
                        self._emit("fault_injected", kind="goodput_degrade",
                                   step=step, seconds=float(spec.arg))
                        time.sleep(float(spec.arg))
                straggler_spec = (plan.fire("straggler", step)
                                  if plan is not None else None)
                if straggler_spec is not None:
                    report.faults_injected += 1
                    self._emit("fault_injected", kind="straggler",
                               step=step, factor=float(straggler_spec.arg))
                if plan is not None and plan.fire("oom", step) is not None:
                    # deterministic allocator exhaustion: the raise
                    # rides the normal exception path below, which
                    # recognizes OOM, writes the post-mortem, and
                    # re-raises — never a rollback (an OOM replays
                    # identically; retries would only burn the budget)
                    report.faults_injected += 1
                    self._emit("fault_injected", kind="oom", step=step)
                    from ..telemetry import memory as _tmem
                    raise _tmem.synthetic_oom(step)
                # the ledger's data_stall stream: time the step
                # boundary waits on its batch (a prefetched loader
                # returns instantly; a stalled one shows here)
                with _trace.span("data.fetch", step=step):
                    batch = batches(step) if seekable else next(it)
                if plan is not None:
                    for kind in ("nan", "inf"):
                        if plan.fire(kind, step) is not None:
                            batch = _faults.corrupt(batch, kind)
                            report.faults_injected += 1
                            self._emit("fault_injected", kind=kind,
                                       step=step)
                # the guard owns the loop, so it emits the train.step
                # span the ledger and the trace CLI decompose against
                # (Registry.step() emits the same name for loops it
                # wraps — the ledger unions overlaps, never counts
                # the same wall-clock twice)
                t_step = time.perf_counter() if ctl is not None else 0.0
                with _trace.span("train.step", step=step):
                    if straggler_spec is not None:
                        # the injected slowdown is real (slower) step
                        # time, inside the span — a straggler costs
                        # productive seconds, not badput
                        time.sleep(_faults.straggler_delay(
                            straggler_spec.arg))
                    state, loss = split(self.step_fn(state, batch))
                if ctl is not None and live_world and int(live_world) >= 2:
                    # per-device busy rows for the controller's leave-
                    # one-out straggler naming: host step timing spread
                    # over the emulated mesh, with the armed straggler
                    # fault's factor attributed to one deterministic
                    # device (plan.seed % world — on silicon,
                    # timeline.decompose rows replace this synthesis)
                    busy_ms = (time.perf_counter() - t_step) * 1e3
                    devs = {f"d{i}": busy_ms
                            for i in range(int(live_world))}
                    if straggler_spec is not None:
                        culprit = ((plan.seed if plan is not None else 0)
                                   % int(live_world))
                        devs[f"d{culprit}"] = busy_ms * max(
                            float(straggler_spec.arg), 1.0)
                    ctl.feed_device_stats(step, devs)
                if loss is not None:
                    pending.append((step, loss))
                step += 1
                since_check += 1
                if not (since_check >= cfg.check_every
                        or step >= num_steps or self._stop):
                    continue
                with _trace.span("guard.health_check", step=step):
                    healthy = self._health_check(state, pending)
                pending.clear()             # window consumed either way
                since_check = 0
                if healthy and ctl is not None and not self._stop:
                    # the controller's window: decide on the SAME
                    # batched read the health check just paid for —
                    # everything below is host arithmetic (zero device
                    # syncs, the host-sync lint holds apex_tpu/control/
                    # to that).  An action that stops the run
                    # (quarantine) flips self._stop; the standard
                    # preempt machinery below takes it from there.
                    with _trace.span("control.window", step=step):
                        ctl.on_window(step=step,
                                      losses=self._last_losses)
                if not healthy:
                    if writer is not None:  # newest ckpt must be on disk
                        self._blocked_ckpt(step, writer.drain)
                    state, step = self._rollback(state, report, seekable)
                    last_saved = min(last_saved, step)
                    continue
                if mgr is not None and not self._stop:
                    due = ((cfg.save_every_steps
                            and step - last_saved >= cfg.save_every_steps)
                           or (cfg.save_every_seconds
                               and time.monotonic() - t_last_save
                               >= cfg.save_every_seconds))
                    if due and step < num_steps:
                        self._record_cursor(batches, step)
                        # the snapshot host read + the (rarely blocking)
                        # queue hand-off is the boundary's whole exposed
                        # cost — the pickle+write overlaps off-thread
                        self._blocked_ckpt(
                            step, lambda: writer.submit(
                                step, self._snapshot(state, step)))
                        report.checkpoints += 1
                        last_saved = step
                        t_last_save = time.monotonic()
            if mgr is not None and (self._stop or cfg.save_on_exit):
                self._blocked_ckpt(step, writer.drain)
                self._record_cursor(batches, step)
                self._blocked_ckpt(step, lambda: _observed_save(
                    mgr, step, self._snapshot(state, step),
                    registry=self._registry))
                report.checkpoints += 1
            if self._stop:
                report.status = "preempted"
                self._emit("preempted", step=step)
                self._dump_flight("preempt", step)
            report.final_step = step
            if writer is not None:
                self._blocked_ckpt(step, writer.drain)
            return state, report
        except BaseException as e:
            # the crash flight recorder: whatever ran in the seconds
            # before an unhandled error (GuardAbort included) is written
            # out before the exception propagates.  An OOM (injected or
            # a real RESOURCE_EXHAUSTED) gets the richer post-mortem —
            # allocator report + live-memory history + static
            # attribution — instead of the generic dump
            from ..telemetry import memory as _tmem
            report.status = "crashed"   # the honest status the goodput
            # artifact records (the report itself never returns here)
            if _tmem.is_oom_error(e):
                self._emit("memory.oom", step=step, error=repr(e)[:200])
                self._dump_oom(step, e)
            else:
                self._dump_flight("exception", step, error=repr(e)[:200],
                                  error_type=type(e).__name__)
            raise
        finally:
            if writer is not None:
                writer.close()
            self._restore_handlers(prev_handlers)
            if ledger is not None:
                self._finalize_goodput(ledger, tracer, prev_ledger,
                                       report)
            if ctl is not None:
                self._finalize_control(ctl, tracer, report)
            if _exp_owned:
                _export.shutdown()
            self._report = None

    # -- health + rollback ---------------------------------------------------
    def _health_check(self, state, pending) -> bool:
        """ONE batched host read over the pending losses (+ loss scale);
        update the non-finite streak and floor counters; True = keep
        going, False = escalate to rollback."""
        import jax
        cfg = self.cfg
        scaler = (self._scaler_fn(state) if self._scaler_fn is not None
                  else _find_scaler(state))
        arrays = [loss for _, loss in pending]
        if scaler is not None and cfg.floor_patience:
            arrays = arrays + [scaler.loss_scale]
        self._last_losses: List[float] = []
        if not arrays:
            return True
        host = jax.device_get(arrays)
        losses = [float(v) for v in host[:len(pending)]]
        self._last_losses = losses   # the controller window's context
        # rides the SAME batched read — no second device_get
        for (st, _), v in zip(pending, losses):
            if np.isfinite(v):
                self._streak = 0
                self._last_bad_step = None   # a recovered transient must
                # not be named by a LATER, unrelated rollback's dump
            else:
                self._streak += 1
                self._last_bad_step = st   # the flight dump names it
        if scaler is not None and cfg.floor_patience:
            from ..amp import scaler as _scaler_mod
            pinned = _scaler_mod.floor_pinned(scaler, float(host[-1]))
            self._floor_checks = self._floor_checks + 1 if pinned else 0
        if self._on_check is not None and pending:
            self._on_check(pending[-1][0] + 1, losses)
        escalate = (self._streak >= cfg.nonfinite_streak
                    or (cfg.floor_patience
                        and self._floor_checks >= cfg.floor_patience))
        return not escalate

    def _rollback(self, state, report: GuardReport, seekable: bool):
        cfg = self.cfg
        why = ("non-finite loss streak" if self._streak
               >= cfg.nonfinite_streak else "loss scale pinned at floor")
        if not seekable:
            raise GuardAbort(
                f"escalation ({why}) needs a rollback, but the batch "
                "source is a plain iterator — pass a callable "
                "batches(step) so rolled-back steps can be replayed")
        if self.manager is None:
            raise GuardAbort(f"escalation ({why}) with no ckpt_dir "
                             "configured: nothing to roll back to")
        report.rollbacks += 1
        if report.rollbacks > cfg.max_retries:
            raise GuardAbort(
                f"rollback budget exhausted ({cfg.max_retries} retries) "
                f"— still escalating on {why}")
        found = self.manager.load_latest()
        if found is None:
            raise GuardAbort(f"escalation ({why}) but no readable "
                             f"checkpoint under {self.manager.directory}")
        ck_step, payload = found
        from ..telemetry import trace as _trace
        with _trace.span("ckpt.restore", step=ck_step, rollback=True):
            state = self._restore(state, payload)
        self._streak = 0
        self._floor_checks = 0
        self._emit("rollback", to_step=ck_step, attempt=report.rollbacks,
                   reason=why)
        self._dump_flight("rollback", ck_step, why=why,
                          attempt=report.rollbacks, to_step=ck_step,
                          bad_step=self._last_bad_step)
        self._last_bad_step = None     # consumed by this dump
        # the backoff sleep is part of the rollback's cost — the ledger
        # charges it to restore_replay, not idle
        with _trace.span("guard.backoff", step=ck_step,
                         attempt=report.rollbacks):
            time.sleep(cfg.backoff_seconds * (2 ** (report.rollbacks - 1)))
        return state, ck_step
