"""Host-side span tracing, flight recorder, and the slow-step sentinel.

The registry (PR 2) answers "what are the aggregate rates" and the
guard (PR 3) answers "recover and keep going"; this module answers
*what happened in the seconds before* — the timeline pillar the
reference devotes ``apex/pyprof`` to (SURVEY §5.1) and the layer
VERDICT weak #8 asks for when a scarce TPU window dies to an
undiagnosed stall.  Three pieces:

  * :class:`Tracer` — a thread-safe host span tracer.
    ``span("ckpt.write")`` works as a context manager and (via
    :func:`traced`) a decorator; timestamps come from the monotonic
    ``time.perf_counter_ns`` clock; completed spans export as
    Chrome-trace/Perfetto JSON (``ph: "X"`` complete events — the same
    format ``pyprof.parse`` reads back).  Disabled mode is a TRUE
    no-op: ``span()`` returns the shared :data:`NULL_SPAN` singleton —
    zero host syncs, zero allocation growth, asserted by
    ``tests/L0/test_trace.py`` (the registry's disabled-mode bar).
  * :class:`FlightRecorder` — a bounded ring of the last N
    spans/events/metric flushes.  ``dump(reason)`` writes a
    timestamped, schema-validated JSON file
    (``flight-<reason>-<ts>.json``); the resilience guard dumps it on
    rollback, preemption, scaler-floor escalation and unhandled
    exceptions, so the crash artifact names what ran just before.
  * :class:`SlowStepSentinel` — a rolling step-time baseline.  A
    z-score breach (a step suddenly 3x slower) dumps the flight
    recorder and can open a ONE-SHOT ``jax.profiler`` capture window
    over the next few steps — the anomaly-triggered profiler, so the
    expensive trace is captured exactly when the anomaly repeats.

Like the registry, this module imports no jax at module scope (jax
only appears inside the sentinel's optional profiler capture), so the
tooling that renders traces (``python -m apex_tpu.telemetry trace``)
never pays backend bring-up.  Library hooks route through the
process-default tracer (:func:`set_tracer`); with none installed every
hook is one attribute check.  One more :class:`Tracer`, the set-up record
(:func:`setup_tracer`), is always there and hears the set-up hooks only.
"""
from __future__ import annotations

import collections
import functools
import gzip
import json
import math
import os
import threading
import time
from typing import Any, Dict, List, Optional

__all__ = [
    "Tracer", "FlightRecorder", "SlowStepSentinel", "NULL_SPAN",
    "set_tracer", "get_tracer", "setup_tracer", "active", "span", "traced",
    "note_span", "note_event", "note_flush", "note_step", "note_counter",
    "load_chrome", "span_summary", "format_span_summary",
    "dump_violations", "cli",
]


def _clean(v):
    """Ring/dump field values must serialize: scalars pass; anything
    array-shaped becomes a shape/dtype TAG — ``repr`` on a device array
    materializes the value (a blocking host sync), which this subsystem
    exists to avoid, so the ring stores the metadata and the resolved
    value stays the flushed JSONL's job; everything else degrades to a
    short repr."""
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    if hasattr(v, "dtype"):
        return (f"<{type(v).__name__}{tuple(getattr(v, 'shape', ()))} "
                f"{v.dtype}>")
    return repr(v)[:80]


def _clean_fields(fields: Optional[dict]) -> dict:
    if not fields:
        return {}
    return {str(k): _clean(v) for k, v in fields.items()}


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

class _NullSpan:
    """The disabled-mode span: a shared singleton whose enter/exit do
    nothing and whose decorator form returns the function unchanged —
    the zero-overhead contract (no allocation, no clock read)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def __call__(self, fn):
        return fn


NULL_SPAN = _NullSpan()


class _Span:
    """One live span handle (context manager + decorator).  Handles
    nest LIFO within a thread; for concurrent threads create one handle
    per thread (``tracer.span(...)`` per ``with`` statement — the
    normal usage — does exactly that)."""

    __slots__ = ("_tracer", "name", "attrs", "_t0s")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs
        self._t0s: List[tuple] = []     # (start ns, parent) of each entry

    def __enter__(self):
        open_spans = self._tracer._open_spans()
        self._t0s.append((time.perf_counter_ns(),
                          open_spans[-1] if open_spans else None))
        open_spans.append(self.name)
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        t0, parent = self._t0s.pop() if self._t0s else (t1, None)
        open_spans = self._tracer._open_spans()
        if open_spans:
            open_spans.pop()
        self._tracer._record(self.name, t0, t1 - t0, self.attrs, parent)
        return False

    def __call__(self, fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with self._tracer.span(self.name, **self.attrs):
                return fn(*args, **kwargs)
        return wrapped


def env_flag(name: str, default: bool = True) -> bool:
    """Shared boolean-env vocabulary for the telemetry/resilience
    enable switches (``APEX_TPU_TRACE`` / ``APEX_TPU_TELEMETRY`` /
    ``APEX_TPU_GUARD``): 0/off/false/no disable — ONE parser, so the
    subsystems can't drift (the PR-3 ``_resolve_fuse`` bug was exactly
    two copies of this predicate disagreeing)."""
    return os.environ.get(name, "1" if default else "0").lower() not in (
        "0", "off", "false", "no")


def _env_enabled() -> bool:
    return env_flag("APEX_TPU_TRACE")


class FlightRecorder:
    """Bounded ring of the most recent trace entries (spans, events,
    metric flushes, instants).  ``dump()`` writes the ring as one
    timestamped JSON document so a crash/rollback leaves a black-box
    record of the seconds before it."""

    def __init__(self, capacity: int = 512, directory: Optional[str] = None):
        self.capacity = int(capacity)
        self.directory = directory
        self._ring: "collections.deque" = collections.deque(
            maxlen=self.capacity)
        self._lock = threading.Lock()
        self.total = 0          # entries ever recorded (incl. evicted)
        self.dumps = 0

    def record(self, entry: dict) -> None:
        with self._lock:
            self._ring.append(entry)
            self.total += 1

    def snapshot(self) -> List[dict]:
        with self._lock:
            return list(self._ring)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()

    def dump(self, reason: str, *, step: Optional[int] = None,
             directory: Optional[str] = None, path: Optional[str] = None,
             fields: Optional[dict] = None,
             sections: Optional[dict] = None) -> Optional[str]:
        """Write the ring to ``path`` (or a timestamped
        ``flight-<reason>-<ts>.json`` under ``directory`` /
        ``self.directory``).  Returns the written path, or None when no
        destination is configured — a recorder without a home must not
        litter the cwd.  ``sections`` adds whole top-level documents to
        the dump (the OOM post-mortem's ``oom`` section) — callers own
        their section's schema; the core keys cannot be clobbered."""
        entries = self.snapshot()
        doc = {
            "kind": "flight_recorder",
            "version": 1,
            "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "reason": str(reason),
            "step": None if step is None else int(step),
            "fields": _clean_fields(fields),
            "capacity": self.capacity,
            "n_entries": len(entries),
            "total_recorded": self.total,
            "entries": entries,
        }
        for key, value in (sections or {}).items():
            if key not in doc:
                doc[key] = value
        if path is None:
            d = directory or self.directory
            if d is None:
                return None
            os.makedirs(d, exist_ok=True)
            stamp = time.strftime("%Y%m%d-%H%M%S", time.gmtime())
            path = os.path.join(
                d, f"flight-{reason}-{stamp}-{os.getpid()}"
                   f"-{self.dumps}.json")
        bad = dump_violations(doc)
        if bad:   # writer-validates, the JsonlSink posture
            raise ValueError("flight-recorder dump fails its schema: "
                             + "; ".join(bad[:4]))
        tmp = f"{path}.tmp{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1)
        os.replace(tmp, path)
        self.dumps += 1
        return path


ENTRY_KINDS = ("span", "instant", "event", "metric_flush", "counter")

_is_str = lambda v: isinstance(v, str)
_is_num = lambda v: isinstance(v, (int, float)) and not isinstance(v, bool)
_is_int = lambda v: isinstance(v, int) and not isinstance(v, bool)


def dump_violations(doc: Any) -> List[str]:
    """Schema complaints for a flight-recorder dump (empty = valid)."""
    if not isinstance(doc, dict):
        return [f"dump is not an object: {type(doc).__name__}"]
    out = []
    if doc.get("kind") != "flight_recorder":
        out.append(f"bad kind {doc.get('kind')!r}")
    if doc.get("version") != 1:
        out.append(f"unknown version {doc.get('version')!r}")
    for key, pred in (("ts", _is_str), ("reason", _is_str),
                      ("capacity", _is_int), ("n_entries", _is_int)):
        if not pred(doc.get(key)):
            out.append(f"bad/missing {key!r}: {doc.get(key)!r}")
    if doc.get("step") is not None and not _is_int(doc.get("step")):
        out.append(f"bad step {doc.get('step')!r}")
    if not isinstance(doc.get("fields"), dict):
        out.append("fields must be a dict")
    entries = doc.get("entries")
    if not isinstance(entries, list):
        return out + ["entries must be a list"]
    if _is_int(doc.get("n_entries")) and doc["n_entries"] != len(entries):
        out.append(f"n_entries={doc['n_entries']} but "
                   f"{len(entries)} entries present")
    for i, e in enumerate(entries):
        if not isinstance(e, dict):
            out.append(f"entry[{i}] is not an object")
            continue
        k = e.get("kind")
        if k not in ENTRY_KINDS:
            out.append(f"entry[{i}]: unknown kind {k!r}")
            continue
        if not _is_str(e.get("name")):
            out.append(f"entry[{i}]: bad name {e.get('name')!r}")
        if k == "span" and not (_is_num(e.get("t_us"))
                                and _is_num(e.get("dur_us"))):
            out.append(f"entry[{i}]: span needs numeric t_us/dur_us")
        if k == "metric_flush" and not _is_int(e.get("n_records")):
            out.append(f"entry[{i}]: metric_flush needs n_records")
        if k == "counter":
            vals = e.get("values")
            if not (isinstance(vals, dict)
                    and all(_is_num(v) for v in vals.values())):
                out.append(f"entry[{i}]: counter needs a numeric "
                           f"values dict")
    return out


# ---------------------------------------------------------------------------
# the sentinel
# ---------------------------------------------------------------------------

class SlowStepSentinel:
    """Rolling step-time baseline with z-score anomaly detection.

    ``observe(step, seconds)`` keeps the last ``window`` step times;
    once ``warmup`` samples exist, a step whose z-score exceeds
    ``z_threshold`` AND is at least ``min_slowdown``x the rolling mean
    fires: the flight recorder is dumped (``reason="slow_step"``), a
    ``sentinel.slow_step`` event goes to the default registry, and —
    when ``profile_dir`` is set — a ONE-SHOT ``jax.profiler`` trace
    opens for the next ``profile_steps`` observed steps (at most
    ``max_captures`` windows per process, so an unlucky baseline can't
    fill a disk with traces).  Breaching samples are NOT added to the
    baseline (an anomaly must not normalize itself); ``cooldown``
    steps must pass between fires, and ``max_fires`` bounds the total
    — at the cap the sentinel ADOPTS the new regime (samples absorb
    into the baseline again), so a permanent legitimate slowdown can't
    fill a directory with one dump per cooldown for the rest of the
    run.  Dumps land in ``dump_dir``, else the tracer's
    ``flight_dir``, else ``profile_dir`` — with none of the three set
    the dump is skipped (the fire info's ``dump`` field says so) and
    only the event/instant land.
    """

    def __init__(self, *, window: int = 64, warmup: int = 16,
                 z_threshold: float = 4.0, min_slowdown: float = 1.5,
                 cooldown: int = 50, max_fires: int = 10,
                 dump_dir: Optional[str] = None,
                 profile_dir: Optional[str] = None,
                 profile_steps: int = 3, max_captures: int = 1):
        if warmup < 2:
            raise ValueError("warmup must be >= 2 (a std needs samples)")
        if warmup > window:
            raise ValueError(
                f"warmup ({warmup}) > window ({window}) would disarm the "
                "sentinel forever: the ring caps at window samples, so "
                "the warmup gate could never pass")
        self.window = collections.deque(maxlen=int(window))
        self.warmup = int(warmup)
        self.z_threshold = float(z_threshold)
        self.min_slowdown = float(min_slowdown)
        self.cooldown = int(cooldown)
        self.max_fires = int(max_fires)
        self.dump_dir = dump_dir
        self.profile_dir = profile_dir
        self.profile_steps = int(profile_steps)
        self.max_captures = int(max_captures)
        self.fires = 0
        self.captures = 0
        self._cooldown_left = 0
        self._capture_steps_left = 0
        self._capturing = False
        self._capture_tracer: Optional["Tracer"] = None

    def _stats(self):
        n = len(self.window)
        mean = sum(self.window) / n
        var = sum((v - mean) ** 2 for v in self.window) / n
        return mean, math.sqrt(var)

    # -- profiler capture (the one-shot window) -----------------------------
    def _start_capture(self, tracer: Optional["Tracer"] = None) -> bool:
        if (self.profile_dir is None or self._capturing
                or self.captures >= self.max_captures):
            return False
        try:
            import jax
            jax.profiler.start_trace(self.profile_dir)
        except Exception:      # profiler unavailable: the dump still lands
            return False
        self._capturing = True
        self._capture_tracer = tracer
        self._capture_steps_left = self.profile_steps
        self.captures += 1
        # a run that crashes or ends INSIDE the window (exactly when an
        # anomaly capture matters most) would otherwise never call
        # stop_trace and the profiler would flush nothing — close the
        # window at interpreter exit as the backstop
        import atexit
        atexit.register(self.stop_capture)
        return True

    def stop_capture(self) -> None:
        """Close an open profiler window now (idempotent) — called at
        the end of the profile_steps window, and registered as an
        atexit backstop so a crash mid-window still flushes the
        capture.  A flushed capture is then fed through the timeline
        decomposition (:mod:`~apex_tpu.telemetry.timeline`) and the
        per-step table dumped as a ``slow_step_timeline`` flight
        document — the slow-step dump names WHEN it happened; this one
        names WHERE the device time went."""
        if not self._capturing:
            return
        self._capturing = False
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception:
            return          # nothing flushed: nothing to decompose
        self._attach_timeline()

    def _attach_timeline(self) -> None:
        """Best-effort: decompose the just-flushed capture and attach
        the per-step device table to a flight dump ``sections`` block.
        Observability must never kill the train loop — any failure
        (profiler wrote nothing, no device lanes, full disk) is
        swallowed and the one-shot capture itself still stands."""
        tr = self._capture_tracer
        self._capture_tracer = None
        if tr is None or self.profile_dir is None:
            return
        try:
            from . import timeline as _timeline
            decomp = _timeline.summarize(self.profile_dir)
            if not decomp["devices"]:
                return
            led = getattr(tr, "ledger", None)
            if led is not None:
                # a device capture exists: the goodput ledger can carve
                # the MEASURED exposed-comm share out of step time
                led.set_decomposition(decomp)
            tr.recorder.dump(
                "slow_step_timeline",
                directory=(self.dump_dir or tr.recorder.directory
                           or self.profile_dir),
                fields={"profile_dir": self.profile_dir,
                        "n_devices": len(decomp["devices"]),
                        "exposed_comm_ms":
                            decomp["totals"]["exposed_comm_ms"]},
                sections={"timeline": {
                    "decomposition": decomp,
                    "table": _timeline.format_decomposition(decomp)}})
        except Exception:
            pass

    def _maybe_stop_capture(self) -> None:
        if not self._capturing:
            return
        self._capture_steps_left -= 1
        if self._capture_steps_left > 0:
            return
        self.stop_capture()

    def observe(self, step: int, seconds: float,
                tracer: Optional["Tracer"] = None,
                registry=None) -> Optional[dict]:
        """Feed one step time.  Returns the fire-info dict when the
        sentinel tripped, else None.  ``registry`` pins where the
        ``sentinel.slow_step`` event lands — ``Registry.step()`` passes
        ITSELF, so a run on a non-default registry still records the
        fire in its own JSONL; default: the process default."""
        self._maybe_stop_capture()
        in_cooldown = self._cooldown_left > 0
        if in_cooldown:
            self._cooldown_left -= 1
        if len(self.window) < self.warmup:
            self.window.append(seconds)
            return None
        mean, std = self._stats()
        z = (seconds - mean) / max(std, 1e-9)
        if z < self.z_threshold or seconds < mean * self.min_slowdown:
            self.window.append(seconds)
            return None
        # breach: do NOT absorb the outlier into the baseline — cooldown
        # suppresses only the FIRE, or a sustained regression would
        # normalize itself during its own cooldown and never fire again
        if self.fires >= self.max_fires:
            # fire budget spent: adopt the new regime so a permanent
            # legitimate slowdown stops breaching instead of dumping
            # once per cooldown forever
            self.window.append(seconds)
            return None
        if in_cooldown:
            return None
        self.fires += 1
        self._cooldown_left = self.cooldown
        tr = tracer if tracer is not None else get_tracer()
        info = {"step": int(step), "step_seconds": float(seconds),
                "baseline_mean_s": float(mean), "baseline_std_s": float(std),
                "z": float(z), "profile_started": self._start_capture(tr)}
        dump_path = None
        if tr is not None:
            tr.instant("sentinel.slow_step", **info)
            directory = (self.dump_dir or tr.recorder.directory
                         or self.profile_dir)
            try:
                dump_path = tr.recorder.dump("slow_step", step=step,
                                             directory=directory,
                                             fields=info)
            except Exception:  # a full disk (or an off-schema ring
                dump_path = None   # entry) must not kill the train loop
        info["dump"] = dump_path
        if registry is None:
            from . import events as _events
            registry = _events.get_default()
        if registry is not None and registry.enabled:
            registry.event("sentinel.slow_step", **info)
        return info


# ---------------------------------------------------------------------------
# the tracer
# ---------------------------------------------------------------------------

class Tracer:
    """Thread-safe host span tracer + flight recorder owner.

    Usage::

        tracer = trace.Tracer(flight_dir="flight/")
        trace.set_tracer(tracer)                 # library hooks report in
        with trace.span("ckpt.write", step=i):   # or tracer.span(...)
            ...
        tracer.write("run.trace.json")           # chrome://tracing / Perfetto

    ``ring`` bounds the flight recorder; ``max_spans`` bounds the full
    export buffer (oldest spans drop first — the ring still holds the
    newest, and ``dropped_spans`` counts the loss so a truncated export
    can't read as a complete one; with ``keep_first`` the buffer keeps
    the FIRST ``max_spans`` entries and drops what comes after, the
    bound of the set-up record, :func:`setup_tracer`).  ``enabled=None``
    reads ``APEX_TPU_TRACE`` (default on).  Disabled: ``span()`` returns
    :data:`NULL_SPAN` and every note is a no-op.

    A span records its ``parent``: the name of the innermost span of its
    thread that was open IN THIS TRACER when it began (None at the top);
    a post-hoc :meth:`add` takes the span open when it is noted.  It is a
    key of the exported event beside ``args`` and of the ring's entry.
    """

    def __init__(self, *, enabled: Optional[bool] = None, ring: int = 512,
                 max_spans: int = 100_000, flight_dir: Optional[str] = None,
                 sentinel: Optional[SlowStepSentinel] = None,
                 process_name: str = "apex_tpu", keep_first: bool = False):
        self.enabled = _env_enabled() if enabled is None else bool(enabled)
        self.recorder = FlightRecorder(ring, directory=flight_dir)
        self.sentinel = sentinel
        # run-level goodput ledger hook (telemetry.goodput): when a
        # GoodputLedger is attached, every completed span/event streams
        # into its wall-clock accounting LIVE — no dependence on the
        # bounded flight ring, so a long run's ledger never loses its
        # early intervals.  One attribute check when detached.
        self.ledger = None
        self.max_spans = int(max_spans)
        self.keep_first = bool(keep_first)
        self.process_name = process_name
        self.dropped_spans = 0
        # chrome-shaped, lock-protected; deque so eviction at max_spans
        # is O(1) — a list.pop(0) would make every span O(max_spans)
        # under the lock once the buffer fills (hot-path quadratic)
        self._events: "collections.deque" = collections.deque(
            maxlen=self.max_spans)
        self._threads: Dict[int, str] = {}
        self._lock = threading.Lock()
        self._pid = os.getpid()
        self._open = threading.local()     # .spans: names open, innermost last

    def _open_spans(self) -> List[str]:
        try:
            return self._open.spans
        except AttributeError:
            self._open.spans = []
            return self._open.spans

    # -- recording ----------------------------------------------------------
    def span(self, name: str, **attrs):
        """A context manager timing one span (also usable as a
        decorator).  Disabled tracer: the shared no-op singleton."""
        if not self.enabled:
            return NULL_SPAN
        return _Span(self, name, attrs)

    def add(self, name: str, dur_s: float, *, t0_ns: Optional[int] = None,
            **attrs) -> None:
        """Record an already-measured span ending now (the post-hoc
        form for code that timed itself, e.g. the loader's wait)."""
        if not self.enabled:
            return
        t1 = time.perf_counter_ns()
        dur_ns = max(int(dur_s * 1e9), 0)
        open_spans = self._open_spans()
        self._record(name, t1 - dur_ns if t0_ns is None else t0_ns,
                     dur_ns, attrs, open_spans[-1] if open_spans else None)

    def counter(self, name: str, step: Optional[int] = None,
                **values) -> None:
        """Record a Chrome counter sample (``ph: "C"``) — Perfetto
        renders one numeric track per ``values`` key under the span
        rows (the live-memory curve).  Non-numeric values are dropped
        rather than corrupting the track."""
        if not self.enabled:
            return
        vals = {str(k): float(v) for k, v in values.items()
                if isinstance(v, (int, float))
                and not isinstance(v, bool)}
        if not vals:
            return
        ev = {"ph": "C", "name": name,
              "ts": time.perf_counter_ns() / 1e3,
              "pid": self._pid, "args": vals}
        with self._lock:
            self._append(ev)
        rec = {"kind": "counter", "name": name, "values": vals}
        if step is not None:
            rec["step"] = int(step)
        self.recorder.record(rec)

    def instant(self, name: str, **attrs) -> None:
        """Record a zero-duration instant event (chrome ``ph: "i"``)."""
        if not self.enabled:
            return
        th = threading.current_thread()
        ev = {"ph": "i", "name": name, "ts": time.perf_counter_ns() / 1e3,
              "pid": self._pid, "tid": th.ident, "s": "t",
              "args": _clean_fields(attrs)}
        with self._lock:
            self._threads[th.ident] = th.name   # latest wins: the OS
            # recycles idents, and a stale name would mislabel the lane
            self._append(ev)
        self.recorder.record({"kind": "instant", "name": name,
                              "t_us": ev["ts"],
                              "attrs": ev["args"]})

    def _append(self, ev: dict) -> None:
        # caller holds the lock; the deque evicts the oldest itself
        if len(self._events) >= self.max_spans:
            self.dropped_spans += 1
            if self.keep_first:
                return
        self._events.append(ev)

    def _record(self, name: str, t0_ns: int, dur_ns: int,
                attrs: dict, parent: Optional[str] = None) -> None:
        th = threading.current_thread()
        args = _clean_fields(attrs)
        ev = {"ph": "X", "name": name, "cat": "host",
              "ts": t0_ns / 1e3, "dur": dur_ns / 1e3,
              "pid": self._pid, "tid": th.ident, "args": args,
              "parent": parent}
        with self._lock:
            self._threads[th.ident] = th.name   # latest wins (ident reuse)
            self._append(ev)
        self.recorder.record({"kind": "span", "name": name,
                              "t_us": ev["ts"], "dur_us": ev["dur"],
                              "thread": th.name, "attrs": args,
                              "parent": parent})
        led = self.ledger
        if led is not None:
            led.note_span(name, ev["ts"], ev["dur"],
                          step=args.get("step"))

    # -- ring-only notes (events / metric flushes from the registry) --------
    def note_event(self, name: str, step: Optional[int] = None,
                   fields: Optional[dict] = None) -> None:
        if not self.enabled:
            return
        self.recorder.record({"kind": "event", "name": name,
                              "step": None if step is None else int(step),
                              "fields": _clean_fields(fields)})
        led = self.ledger
        if led is not None:
            led.note_event(name, step=step, fields=fields)

    def note_flush(self, step: int, records: List[dict]) -> None:
        if not self.enabled:
            return
        names = sorted({r.get("name") for r in records
                        if isinstance(r.get("name"), str)})[:32]
        self.recorder.record({"kind": "metric_flush", "step": int(step),
                              "name": "registry.flush",
                              "n_records": len(records), "names": names})

    # -- export -------------------------------------------------------------
    def export(self) -> dict:
        """The Chrome-trace document (loads in chrome://tracing and
        Perfetto; ``pyprof.parse`` reads the same shape)."""
        with self._lock:
            events = list(self._events)
            threads = dict(self._threads)
        meta: List[dict] = [
            {"ph": "M", "name": "process_name", "pid": self._pid,
             "args": {"name": self.process_name}}]
        for tid, tname in threads.items():
            meta.append({"ph": "M", "name": "thread_name",
                         "pid": self._pid, "tid": tid,
                         "args": {"name": tname}})
        return {"displayTimeUnit": "ms",
                "droppedSpans": self.dropped_spans,
                "traceEvents": meta + events}

    def write(self, path: str) -> str:
        """Serialize :meth:`export` to ``path`` (gzip when it ends in
        ``.gz``).  Returns the path."""
        doc = self.export()
        opener = gzip.open if path.endswith(".gz") else open
        tmp = f"{path}.tmp{os.getpid()}"
        with opener(tmp, "wt") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        return path

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
        self.recorder.clear()

    @property
    def n_spans(self) -> int:
        with self._lock:
            return sum(1 for e in self._events if e.get("ph") == "X")


# ---------------------------------------------------------------------------
# process-default tracer + library hook shims
# ---------------------------------------------------------------------------

_default: Optional[Tracer] = None


def set_tracer(tracer: Optional[Tracer]) -> Optional[Tracer]:
    """Install ``tracer`` as the process default the library hooks
    (guard, loader, DDP, registry) report into; None uninstalls.
    Returns the previous default so callers can restore it."""
    global _default
    prev = _default
    _default = tracer
    return prev


def get_tracer() -> Optional[Tracer]:
    return _default


#: entries the set-up record keeps: the first ones, the rest are counted
SETUP_RECORD_ENTRIES = 4096
_setup = Tracer(enabled=True, max_spans=SETUP_RECORD_ENTRIES,
                keep_first=True, process_name="apex_tpu set-up")


def setup_tracer() -> Tracer:
    """The process's set-up record: what happened between ``import
    apex_tpu`` and the first step, kept apart from the default tracer and
    always on.  Only the set-up hooks write to it — ``setup.import``
    (``apex_tpu/__init__.py``), ``setup.state`` (the examples' state
    build) and the ``compile.*`` entries of ``events.
    install_compile_listener`` — so nothing lands in it from a step whose
    programs are compiled; every other library hook goes to the default
    tracer alone.  It keeps its first :data:`SETUP_RECORD_ENTRIES` entries
    and counts the rest in ``dropped_spans``; ``export()`` / ``write(path)``
    serve it like any tracer (docs/telemetry.md "Set-up record")."""
    return _setup


def active() -> bool:
    """True when a default tracer is installed and enabled — the fast
    guard every library hook checks first."""
    return _default is not None and _default.enabled


def span(name: str, **attrs):
    """Module-level span against the default tracer; the shared no-op
    singleton when none is installed (or it is disabled).  NOTE: this
    resolves the tracer at CALL time — for decorating a function at
    import time use :func:`traced`, which resolves per call."""
    tr = _default
    if tr is None or not tr.enabled:
        return NULL_SPAN
    return tr.span(name, **attrs)


def traced(name: Optional[str] = None, **attrs):
    """Decorator form: wraps ``fn`` in a span named ``name`` (default:
    the qualified function name), resolving the default tracer at each
    call — safe to apply at import time before any tracer exists."""
    def deco(fn):
        label = name or fn.__qualname__

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            tr = _default
            if tr is None or not tr.enabled:
                return fn(*args, **kwargs)
            with tr.span(label, **attrs):
                return fn(*args, **kwargs)
        return wrapped
    return deco


def note_span(name: str, dur_s: float, **attrs) -> None:
    """Post-hoc span into the default tracer (no-op when none)."""
    tr = _default
    if tr is None or not tr.enabled:
        return
    tr.add(name, dur_s, **attrs)


def note_event(name: str, step: Optional[int] = None,
               fields: Optional[dict] = None) -> None:
    tr = _default
    if tr is None or not tr.enabled:
        return
    tr.note_event(name, step=step, fields=fields)


def note_flush(step: int, records: List[dict]) -> None:
    tr = _default
    if tr is None or not tr.enabled:
        return
    tr.note_flush(step, records)


def note_counter(name: str, step: Optional[int] = None,
                 values: Optional[dict] = None) -> None:
    """Counter-track sample into the default tracer (no-op when none)
    — the memory monitor's flush hook."""
    tr = _default
    if tr is None or not tr.enabled or not values:
        return
    tr.counter(name, step=step, **values)


def note_step(step: int, seconds: float, registry=None) -> None:
    """Registry step hook: records a ``train.step`` span and feeds the
    sentinel (if the tracer carries one).  ``registry`` is the stepping
    registry, threaded through so a sentinel fire's event lands in the
    run's OWN record stream, not just the process default."""
    tr = _default
    if tr is None or not tr.enabled:
        return
    tr.add("train.step", seconds, step=step)
    if tr.sentinel is not None:
        tr.sentinel.observe(step, seconds, tracer=tr, registry=registry)


# ---------------------------------------------------------------------------
# trace file -> span summary (the `python -m apex_tpu.telemetry trace` CLI)
# ---------------------------------------------------------------------------

def load_chrome(path: str) -> List[dict]:
    """Load chrome-trace events from ``path``: a :meth:`Tracer.write`
    file, a jax-profiler run dir, or a *streaming* JSON-array file
    (a writer may append events without ever closing the array — the
    Trace Event Format explicitly allows it).  Returns the
    ``pyprof.parse`` event shape (complete spans only)."""
    if os.path.isdir(path):
        from ..pyprof import parse as _parse
        return _parse.load(path)
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        text = f.read()
    try:
        data = json.loads(text)
    except ValueError:
        # streaming array (one record per appended line, never closed):
        # recover line by line, DROPPING an unparseable tail — a writer
        # killed mid-append (disk full, host died) must lose
        # only its torn last record, never the hundreds of finished
        # spans before it
        data = []
        for line in text.splitlines():
            line = line.strip().rstrip(",")
            if not line or line in ("[", "]"):
                continue
            try:
                data.append(json.loads(line))
            except ValueError:
                continue
        if not data:
            raise ValueError(
                f"{path}: neither complete JSON nor a streaming "
                "chrome-trace array") from None
    raw = data.get("traceEvents", []) if isinstance(data, dict) else data
    from ..pyprof import parse as _parse
    return _parse.events_from_chrome(raw)


def _percentile(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    i = max(0, min(len(sorted_vals) - 1,
                   int(math.ceil(q * len(sorted_vals))) - 1))
    return sorted_vals[i]


def span_summary(events: List[dict]) -> List[dict]:
    """Per-name rollup over complete spans: count, total, SELF time
    (duration minus nested children — ``pyprof.parse``'s attribution)
    with p50/p99 over the per-span self times."""
    from ..pyprof import parse as _parse
    _parse._self_times(events)
    groups: Dict[str, List[dict]] = {}
    for e in events:
        groups.setdefault(e["name"], []).append(e)
    rows = []
    for name, evs in groups.items():
        selfs = sorted(max(e.get("self_us", e["dur"]), 0.0) for e in evs)
        rows.append({
            "name": name,
            "count": len(evs),
            "total_us": sum(e["dur"] for e in evs),
            "self_us": sum(selfs),
            "p50_self_us": _percentile(selfs, 0.50),
            "p99_self_us": _percentile(selfs, 0.99),
            "max_self_us": selfs[-1] if selfs else 0.0,
        })
    rows.sort(key=lambda r: -r["self_us"])
    total_self = sum(r["self_us"] for r in rows) or 1.0
    for r in rows:
        r["pct"] = 100.0 * r["self_us"] / total_self
    return rows


def format_span_summary(rows: List[dict], top: int = 25) -> str:
    """The pyprof-style table: one sorted row per span name."""
    head = (f"{'span':<36} {'count':>6} {'total ms':>10} {'self ms':>10} "
            f"{'p50 us':>9} {'p99 us':>9} {'%':>6}")
    lines = [f"span timeline summary ({sum(r['count'] for r in rows)} "
             f"spans, {len(rows)} names)", head, "-" * len(head)]
    for r in rows[:top]:
        name = r["name"] if len(r["name"]) <= 36 else r["name"][:33] + "..."
        lines.append(
            f"{name:<36} {r['count']:>6} {r['total_us'] / 1e3:>10.3f} "
            f"{r['self_us'] / 1e3:>10.3f} {r['p50_self_us']:>9.1f} "
            f"{r['p99_self_us']:>9.1f} {r['pct']:>6.1f}")
    if len(rows) > top:
        rest = sum(r["self_us"] for r in rows[top:])
        lines.append(f"{'... ' + str(len(rows) - top) + ' more names':<36} "
                     f"{'':>6} {'':>10} {rest / 1e3:>10.3f}")
    return "\n".join(lines)


def cli(argv=None) -> int:
    """``python -m apex_tpu.telemetry trace <file> [--top N]``."""
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m apex_tpu.telemetry trace",
        description="Render a span summary (per-name count/total/p50/p99 "
                    "self-time) from a chrome-trace file, a Tracer.write "
                    "export, a streaming never-closed event array, or a "
                    "jax-profiler run dir.")
    ap.add_argument("trace", help="trace file (.json / .json.gz) or "
                                  "profiler log dir")
    ap.add_argument("--top", type=int, default=25)
    args = ap.parse_args(argv)
    events = load_chrome(args.trace)
    if not events:
        print(f"no complete spans in {args.trace}")
        return 1
    dropped = getattr(events, "dropped_events", 0)
    if dropped:
        # the pyprof.parse droppedEvents counter: a truncated capture
        # must announce itself, not just render thin
        print(f"WARNING: {dropped} trace events dropped "
              "(missing ts/dur — truncated capture?)")
    print(format_span_summary(span_summary(events), top=args.top))
    return 0
