"""Device-timeline observability: exposed-comm accounting, per-device
step decomposition, and straggler detection (ISSUE 13).

Every span, meter, and collective counter in this package so far is
HOST-side: perf_counter spans, trace-time byte counts.  They say what
ran and how many bytes moved — not what the device was doing, and in
particular not how much collective time was EXPOSED (serialized after
compute) versus hidden behind it.  The ROADMAP's "communication/
computation overlap as a planner axis" item is blocked on exactly that
measurement: the planner's alpha-beta model (AMP, arXiv:2210.07297)
needs a real overlap factor, and compressed collectives (EQuARX,
arXiv:2506.17615) only pay off when the wire time they save was
exposed.  This module closes the measurement half of that loop:

  * :func:`device_lanes` — split a parsed ``jax.profiler`` trace (the
    ``pyprof.parse`` event shape ``telemetry.trace.load_chrome``
    already produces for profiler run dirs) into per-device lanes,
    classifying each device event with the existing
    :func:`~apex_tpu.telemetry.attrib.op_class` bins — a device op is
    either **collective** or **compute** (everything else);
  * :func:`decompose` — per device, per step: compute ms, total
    collective ms, **exposed collective ms** (the collective intervals
    NOT covered by same-device compute, by exact interval subtraction),
    and idle ms; plus cross-device skew and a straggler z-score per
    device (leave-one-out against the rest of the mesh) that flags
    ``timeline.straggler`` rows;
  * :func:`observe` — export the decomposition through a
    :class:`~apex_tpu.telemetry.registry.Registry` as
    ``step.device_compute_ms`` / ``step.exposed_comm_ms`` /
    ``step.device_idle_ms`` gauges (riding the registry's batched
    flush) and one ``timeline.straggler`` event per flagged row;
  * :func:`merge_host_device` — host Tracer spans and device lanes in
    ONE correlated Chrome/Perfetto timeline, rebased onto a shared
    epoch anchor (host ``perf_counter`` and the profiler's clock have
    unrelated zeros);
  * :func:`cli` — ``python -m apex_tpu.telemetry timeline
    <trace|profiler-dir>``: the per-step decomposition table and the
    per-device skew section (``--json`` for the machine-readable
    form).

The measured ``exposed_comm_fraction`` is the overlap factor
``parallel.plan``'s comm model takes (``predict(overlap_fraction=)`` /
``APEX_TPU_OVERLAP_FRACTION``: exposed dp comm = comm x fraction).

Like the rest of the tooling layer this module imports no jax at
module scope — rendering a profiler capture must never pay backend
bring-up.  All math is exact interval arithmetic over the trace's
microsecond timestamps (CPU-deterministic, oracle-tested in
``tests/L0/test_timeline.py``).
"""
from __future__ import annotations

import json
import math
import re
from typing import Dict, List, Optional, Sequence, Tuple

from .attrib import op_class

__all__ = [
    "device_lanes", "event_op_class", "is_collective_event",
    "step_windows", "decompose", "straggler_rows", "observe",
    "merge_host_device", "load_events", "summarize",
    "format_decomposition", "cli",
    "STRAGGLER_Z", "STRAGGLER_MIN_SLOWDOWN",
]

#: leave-one-out z-score a device's per-step busy time must exceed —
#: AND be at least STRAGGLER_MIN_SLOWDOWN x the rest-of-mesh mean (the
#: sentinel's two-gate posture: tiny-std noise must not flag)
STRAGGLER_Z = 3.0
STRAGGLER_MIN_SLOWDOWN = 1.2

#: the std floor for the leave-one-out z (relative to the rest-mean):
#: a perfectly uniform mesh has std 0 and would make any delta read as
#: z=inf — the floor makes "away from the mesh" mean a real slowdown
_Z_STD_FLOOR_FRAC = 0.02

# ---------------------------------------------------------------------------
# lane detection + event classification
# ---------------------------------------------------------------------------

#: process names the TensorBoard/jax XPlane export gives device
#: timelines ("/device:TPU:0", "TPU:0", "/device:GPU:0", ...)
_DEVICE_PROC_RE = re.compile(r"(/device:(?!CPU)|^TPU[: ]|^GPU[: ])",
                             re.IGNORECASE)

#: an HLO-shaped span name: "all-reduce.3", "fusion.12", "dot", ...
_HLO_NAME_RE = re.compile(r"^%?([a-z][a-z0-9_\-]*?)(?:\.\d+)?$")

#: opcodes that hint a lane is a device op timeline even when the
#: exporter did not name its process "/device:..." (CPU-backend
#: captures) — the common HLO vocabulary, incl. the async collective
#: start/done pairs
_HLO_HINT = frozenset((
    "fusion", "dot", "convolution", "add", "multiply", "subtract",
    "divide", "exp", "exponential", "log", "tanh", "rsqrt", "sqrt",
    "power", "negate", "select", "compare", "maximum", "minimum",
    "convert", "copy", "transpose", "broadcast", "reshape", "slice",
    "concatenate", "pad", "gather", "scatter", "dynamic-slice",
    "dynamic-update-slice", "iota", "reduce", "reduce-window",
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast", "send", "recv",
    "custom-call", "while", "sort", "bitcast", "tuple", "rng",
))


def _base_opcode(name: str) -> Optional[str]:
    """``"all-reduce-start.3"`` -> ``"all-reduce"``; None when the name
    is not HLO-shaped (a python frame, a runtime bookkeeping span)."""
    m = _HLO_NAME_RE.match(name.strip())
    if not m:
        return None
    base = m.group(1)
    # the CPU backend runs an unfused op as a one-op "wrapped_<opcode>"
    # computation and names its span after that
    base = base.removeprefix("wrapped_")
    # async collectives lower to start/done pairs on real devices; both
    # halves classify as their base collective
    for suffix in ("-start", "-done"):
        if base.endswith(suffix):
            base = base[: -len(suffix)]
    return base


def _is_hlo_hint(name: str) -> bool:
    """Does this span name look like a device HLO op?  Exact opcodes
    from the common vocabulary, plus XLA's named-fusion convention
    (``broadcast_add_fusion`` — the CPU/TPU exporters name fusions
    after their root chain)."""
    base = _base_opcode(name)
    if base is None:
        return False
    return base in _HLO_HINT or base.endswith("fusion")


def event_op_class(name: str) -> Optional[str]:
    """The :data:`~apex_tpu.telemetry.attrib.OP_CLASSES` bin for one
    device event name, or None for a non-HLO span.  ``fusion`` bins as
    pointwise (compute): classifying a fusion by content needs the HLO
    text, which a trace does not carry — for the exposed-comm split the
    only bin that matters is collective-vs-not."""
    base = _base_opcode(name)
    if base is None:
        return None
    return op_class(base)


def is_collective_event(name: str) -> bool:
    return event_op_class(name) == "collective"


def device_lanes(events: Sequence[dict]) -> Dict[str, List[dict]]:
    """Per-device event lists from parsed trace events (the
    ``pyprof.parse`` shape).  Primary rule: every process whose display
    name looks like a device timeline (``/device:TPU:0``...) is one
    lane, all its threads merged — exposed-comm subtraction is a
    same-DEVICE property, not per-core-thread.  Fallback (CPU-backend
    captures, whose exporter may not name device processes): any
    (process, thread) lane where at least half the span names parse as
    HLO opcodes is treated as a device lane named ``process:thread``.
    """
    by_proc: Dict[str, List[dict]] = {}
    for e in events:
        proc = str(e.get("process", e.get("pid")))
        if _DEVICE_PROC_RE.search(proc):
            by_proc.setdefault(proc, []).append(e)
    if by_proc:
        return {k: sorted(v, key=lambda e: e["ts"])
                for k, v in sorted(by_proc.items())}
    # fallback: sniff HLO-shaped lanes.  Runtime bookkeeping spans
    # (ThreadpoolListener/ThunkExecutor/"X::Y" frames) ride the same
    # thread as the ops on CPU captures — they neither qualify a lane
    # nor count against it
    from ..pyprof.parse import _NOISE_PREFIXES
    by_lane: Dict[Tuple, List[dict]] = {}
    for e in events:
        by_lane.setdefault((str(e.get("process")), str(e.get("thread"))),
                           []).append(e)
    out: Dict[str, List[dict]] = {}
    for (proc, thread), evs in sorted(by_lane.items()):
        considered = [e for e in evs
                      if "::" not in e["name"]
                      and not e["name"].startswith(_NOISE_PREFIXES)]
        hlo = sum(1 for e in considered if _is_hlo_hint(e["name"]))
        if hlo and hlo * 2 >= len(considered):
            out[f"{proc}:{thread}"] = sorted(evs, key=lambda e: e["ts"])
    return out


# ---------------------------------------------------------------------------
# exact interval arithmetic (all times in trace microseconds)
# ---------------------------------------------------------------------------

def _merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Sorted union of half-open intervals (empty/negative spans drop)."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _subtract(a: List[Tuple[float, float]],
              b: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """``a - b`` for MERGED interval lists: the parts of ``a`` no
    interval of ``b`` covers — the exposed-comm core ("collective
    intervals not overlapped by same-device compute")."""
    out: List[Tuple[float, float]] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            bs, be = b[k]
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _clip(intervals: List[Tuple[float, float]], t0: float,
          t1: float) -> List[Tuple[float, float]]:
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def _total_us(intervals: List[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


# ---------------------------------------------------------------------------
# step windows
# ---------------------------------------------------------------------------

#: host span names that delimit one training step on the shared
#: timeline (``Registry.step()`` emits ``train.step``)
_STEP_SPAN_NAMES = frozenset(("train.step", "step"))


def step_windows(events: Sequence[dict]) -> List[Tuple[int, float, float]]:
    """``(step, t0_us, t1_us)`` windows to decompose against.  Host
    ``train.step`` spans (merged timelines carry them) win; without
    any, the whole device extent is ONE window (step 0) — a one-step
    profiled capture is exactly that."""
    marks = []
    for e in events:
        if e.get("name") in _STEP_SPAN_NAMES and e.get("dur", 0) > 0:
            step = e.get("args", {}).get("step")
            marks.append((int(step) if isinstance(step, (int, float))
                          else len(marks), e["ts"], e["ts"] + e["dur"]))
    if marks:
        return sorted(marks, key=lambda w: w[1])
    lanes = device_lanes(events)
    spans = [e for evs in lanes.values() for e in evs]
    if not spans:
        return []
    t0 = min(e["ts"] for e in spans)
    t1 = max(e["ts"] + e["dur"] for e in spans)
    return [(0, t0, t1)]


# ---------------------------------------------------------------------------
# the decomposition
# ---------------------------------------------------------------------------

def decompose(events: Sequence[dict],
              windows: Optional[List[Tuple[int, float, float]]] = None, *,
              z_threshold: float = STRAGGLER_Z,
              min_slowdown: float = STRAGGLER_MIN_SLOWDOWN) -> dict:
    """Per-device, per-step decomposition of a parsed device trace.

    For each device lane and step window: ``compute_ms`` (union of
    non-collective device op intervals), ``comm_ms`` (union of
    collective intervals), ``exposed_comm_ms`` (collective minus
    compute, exact interval subtraction — fully-hidden collectives
    contribute 0, fully-exposed their whole duration), ``busy_ms``
    (union of both) and ``idle_ms`` (window minus busy: host stalls,
    infeed waits, scheduling gaps).  Cross-device: per-step
    ``skew_ms`` (max - min busy) and straggler rows
    (:func:`straggler_rows`).  Returns a JSON-serializable dict; the
    ``totals.exposed_comm_fraction`` field is the overlap factor the
    planner consumes."""
    lanes = device_lanes(events)
    if windows is None:
        windows = step_windows(events)
    per_lane = {
        dev: {
            "comm": _merge([(e["ts"], e["ts"] + e["dur"]) for e in evs
                            if is_collective_event(e["name"])]),
            "compute": _merge([(e["ts"], e["ts"] + e["dur"]) for e in evs
                               if event_op_class(e["name"])
                               not in (None, "collective")]),
        }
        for dev, evs in lanes.items()
    }
    steps = []
    for step, t0, t1 in windows:
        devs = {}
        for dev, iv in per_lane.items():
            comm = _clip(iv["comm"], t0, t1)
            compute = _clip(iv["compute"], t0, t1)
            exposed = _subtract(comm, compute)
            busy = _merge(comm + compute)
            row = {
                "compute_ms": _total_us(compute) / 1e3,
                "comm_ms": _total_us(comm) / 1e3,
                "exposed_comm_ms": _total_us(exposed) / 1e3,
                "busy_ms": _total_us(busy) / 1e3,
                "idle_ms": max(t1 - t0 - _total_us(busy), 0.0) / 1e3,
            }
            devs[dev] = {k: round(v, 6) for k, v in row.items()}
        busys = [d["busy_ms"] for d in devs.values()]
        steps.append({
            "step": int(step),
            "t0_us": float(t0),
            "dur_ms": round((t1 - t0) / 1e3, 6),
            "devices": devs,
            "skew_ms": round(max(busys) - min(busys), 6) if busys else 0.0,
        })
    stragglers = straggler_rows(steps, z_threshold=z_threshold,
                                min_slowdown=min_slowdown)
    per_device = {}
    for dev in lanes:
        rows = [s["devices"][dev] for s in steps if dev in s["devices"]]
        zs = [r["z"] for r in stragglers if r["device"] == dev]
        per_device[dev] = {
            "steps": len(rows),
            "compute_ms": round(sum(r["compute_ms"] for r in rows), 6),
            "comm_ms": round(sum(r["comm_ms"] for r in rows), 6),
            "exposed_comm_ms": round(sum(r["exposed_comm_ms"]
                                         for r in rows), 6),
            "idle_ms": round(sum(r["idle_ms"] for r in rows), 6),
            "busy_ms": round(sum(r["busy_ms"] for r in rows), 6),
            "straggler_score": round(max(zs), 3) if zs else 0.0,
            "straggler_steps": sorted(r["step"] for r in stragglers
                                      if r["device"] == dev),
        }
    comm = sum(d["comm_ms"] for d in per_device.values())
    exposed = sum(d["exposed_comm_ms"] for d in per_device.values())
    totals = {
        "compute_ms": round(sum(d["compute_ms"]
                                for d in per_device.values()), 6),
        "comm_ms": round(comm, 6),
        "exposed_comm_ms": round(exposed, 6),
        "idle_ms": round(sum(d["idle_ms"] for d in per_device.values()), 6),
        # None (not 0.0) when nothing collective ran: a fraction from a
        # comm-free capture must not be mistaken for "fully hidden"
        "exposed_comm_fraction": (round(exposed / comm, 6) if comm > 0
                                  else None),
    }
    return {
        "kind": "device_timeline",
        "version": 1,
        "devices": sorted(lanes),
        "n_steps": len(steps),
        "steps": steps,
        "per_device": per_device,
        "totals": totals,
        "stragglers": stragglers,
        "dropped_events": int(getattr(events, "dropped_events", 0)),
    }


def straggler_rows(steps: List[dict], *,
                   z_threshold: float = STRAGGLER_Z,
                   min_slowdown: float = STRAGGLER_MIN_SLOWDOWN
                   ) -> List[dict]:
    """Per-step leave-one-out straggler detection: device ``d`` in step
    ``s`` is flagged when its busy time z-scores ``z_threshold`` away
    from the REST of the mesh (std floored at
    ``_Z_STD_FLOOR_FRAC x rest-mean`` so a uniform mesh doesn't read
    noise as infinite z) AND is at least ``min_slowdown`` x the rest's
    mean — both gates, the sentinel posture.

    Consumers: the timeline CLI's skew table, and the run controller's
    quarantine policy (``apex_tpu.control``), which feeds per-window
    rows through this same detector and resizes around a device the
    z-score names persistently — the naming logic lives HERE, once."""
    out = []
    for s in steps:
        devs = s["devices"]
        if len(devs) < 2:
            continue
        for dev, row in devs.items():
            rest = [r["busy_ms"] for d, r in devs.items() if d != dev]
            mean = sum(rest) / len(rest)
            var = sum((v - mean) ** 2 for v in rest) / len(rest)
            std = max(math.sqrt(var), _Z_STD_FLOOR_FRAC * mean, 1e-9)
            z = (row["busy_ms"] - mean) / std
            if z >= z_threshold and row["busy_ms"] >= mean * min_slowdown:
                out.append({
                    "step": s["step"], "device": dev,
                    "busy_ms": row["busy_ms"],
                    "mesh_mean_ms": round(mean, 6),
                    "mesh_std_ms": round(std, 6),
                    "z": round(z, 3),
                })
    return out


# ---------------------------------------------------------------------------
# registry export: gauges ride the batched flush, stragglers are events
# ---------------------------------------------------------------------------

def observe(decomp: dict, registry) -> None:
    """Export a decomposition through ``registry``: the mean
    per-device-step components as ``step.device_compute_ms`` /
    ``step.device_comm_ms`` / ``step.exposed_comm_ms`` /
    ``step.device_idle_ms`` gauges (plain floats — they resolve in the
    registry's ONE batched flush read, adding no host sync), the
    overlap factor as ``step.exposed_comm_fraction``, and one
    ``timeline.straggler`` event per flagged row."""
    if registry is None or not getattr(registry, "enabled", False):
        return
    n = sum(d["steps"] for d in decomp["per_device"].values())
    if n:
        for gauge, key in (("step.device_compute_ms", "compute_ms"),
                           ("step.device_comm_ms", "comm_ms"),
                           ("step.exposed_comm_ms", "exposed_comm_ms"),
                           ("step.device_idle_ms", "idle_ms")):
            registry.gauge(gauge).set(decomp["totals"][key] / n)
    frac = decomp["totals"]["exposed_comm_fraction"]
    if frac is not None:
        registry.gauge("step.exposed_comm_fraction").set(frac)
    for row in decomp["stragglers"]:
        registry.event("timeline.straggler", **row)


# ---------------------------------------------------------------------------
# correlated host + device timeline
# ---------------------------------------------------------------------------

def merge_host_device(host, device_events: Sequence[dict], *,
                      host_offset_us: Optional[float] = None) -> dict:
    """One Chrome/Perfetto document holding host Tracer spans AND the
    device lanes.  ``host`` is a :meth:`Tracer.export` doc (or its
    ``traceEvents`` list); ``device_events`` the parsed profiler-dir
    events.  The two clocks share no epoch (``perf_counter_ns`` vs the
    profiler's), so host timestamps are rebased by
    ``host_offset_us`` — defaulting to aligning the earliest host event
    with the earliest device event (the shared anchor: the host loop
    and the capture window start together in a one-shot capture).
    Device lanes keep their pids; host lanes are remapped clear of
    them."""
    if isinstance(host, dict):
        host_events = [e for e in host.get("traceEvents", [])
                       if e.get("ph") in ("X", "i", "C")]
    else:
        host_events = [dict(e) for e in host]
    dev_spans = [e for e in device_events if e.get("dur") is not None]
    if host_offset_us is None:
        h0 = min((e["ts"] for e in host_events), default=0.0)
        d0 = min((e["ts"] for e in dev_spans), default=0.0)
        host_offset_us = d0 - h0
    used_pids = {e.get("pid") for e in dev_spans}
    host_pid = 1
    while host_pid in used_pids:
        host_pid += 1
    out: List[dict] = [{"ph": "M", "name": "process_name", "pid": host_pid,
                        "args": {"name": "host:apex_tpu"}}]
    dev_pids: Dict[str, int] = {}
    for e in dev_spans:
        proc = str(e.get("process", e.get("pid")))
        pid = e.get("pid")
        if proc not in dev_pids:
            dev_pids[proc] = pid
            out.append({"ph": "M", "name": "process_name", "pid": pid,
                        "args": {"name": proc}})
            out.append({"ph": "M", "name": "thread_name", "pid": pid,
                        "tid": e.get("tid"),
                        "args": {"name": str(e.get("thread", ""))}})
        out.append({"ph": "X", "name": e["name"], "cat": "device",
                    "ts": e["ts"], "dur": e["dur"], "pid": pid,
                    "tid": e.get("tid"), "args": e.get("args", {})})
    for e in host_events:
        if e.get("ph") == "M":
            continue
        if "ph" in e:
            ev = dict(e)
        else:
            # the parsed (pyprof.parse) shape: rebuild a complete event
            ev = {"ph": "X", "name": e.get("name", "?"),
                  "dur": float(e.get("dur", 0.0)), "cat": "host",
                  "tid": e.get("tid"), "args": e.get("args", {})}
        ev["pid"] = host_pid
        ev["ts"] = float(e.get("ts", 0.0)) + host_offset_us
        out.append(ev)
    return {"displayTimeUnit": "ms", "traceEvents": out}


# ---------------------------------------------------------------------------
# loading / rendering / CLI
# ---------------------------------------------------------------------------

def load_events(path: str):
    """Parsed events from a trace file or jax-profiler run dir —
    delegated to :func:`telemetry.trace.load_chrome`, the one loader
    that accepts every trace shape this repo writes."""
    from . import trace as _trace
    return _trace.load_chrome(path)


def summarize(path: str, **kwargs) -> dict:
    """:func:`decompose` over whatever ``path`` holds."""
    return decompose(load_events(path), **kwargs)


def format_decomposition(decomp: dict, top_steps: int = 24) -> str:
    """The human form: per-step decomposition table (device means) and
    the per-device skew section."""
    devs = decomp["devices"]
    lines = [f"device timeline decomposition ({len(devs)} devices, "
             f"{decomp['n_steps']} steps)"]
    if decomp.get("dropped_events"):
        lines.append(f"  WARNING: {decomp['dropped_events']} trace events "
                     "dropped (truncated capture?)")
    head = (f"{'step':<6}{'dur ms':>10}{'compute':>10}{'comm':>10}"
            f"{'exposed':>10}{'idle':>10}{'skew':>9}")
    lines += [head, "-" * len(head)]
    for s in decomp["steps"][:top_steps]:
        n = max(len(s["devices"]), 1)

        def mean(key, _s=s, _n=n):
            return sum(d[key] for d in _s["devices"].values()) / _n

        lines.append(f"{s['step']:<6}{s['dur_ms']:>10.3f}"
                     f"{mean('compute_ms'):>10.3f}{mean('comm_ms'):>10.3f}"
                     f"{mean('exposed_comm_ms'):>10.3f}"
                     f"{mean('idle_ms'):>10.3f}{s['skew_ms']:>9.3f}")
    if decomp["n_steps"] > top_steps:
        lines.append(f"... {decomp['n_steps'] - top_steps} more steps")
    t = decomp["totals"]
    frac = t["exposed_comm_fraction"]
    lines.append(
        f"totals: compute {t['compute_ms']:.3f} ms  comm {t['comm_ms']:.3f}"
        f" ms  exposed {t['exposed_comm_ms']:.3f} ms"
        + (f" (fraction {frac:.3f})" if frac is not None
           else " (no collectives)")
        + f"  idle {t['idle_ms']:.3f} ms")
    lines.append("")
    lines.append("per-device skew:")
    dhead = (f"{'device':<32}{'steps':>6}{'busy ms':>11}{'exposed':>10}"
             f"{'idle':>9}{'z':>7}  straggler steps")
    lines += [dhead, "-" * len(dhead)]
    for dev in devs:
        d = decomp["per_device"][dev]
        name = dev if len(dev) <= 32 else "..." + dev[-29:]
        flagged = (",".join(str(s) for s in d["straggler_steps"])
                   if d["straggler_steps"] else "-")
        lines.append(f"{name:<32}{d['steps']:>6}{d['busy_ms']:>11.3f}"
                     f"{d['exposed_comm_ms']:>10.3f}{d['idle_ms']:>9.3f}"
                     f"{d['straggler_score']:>7.2f}  {flagged}")
    if decomp["stragglers"]:
        lines.append(f"{len(decomp['stragglers'])} timeline.straggler "
                     "row(s) flagged")
    return "\n".join(lines)


def cli(argv=None) -> int:
    """``python -m apex_tpu.telemetry timeline <trace|profiler-dir>``."""
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m apex_tpu.telemetry timeline",
        description="Per-device step decomposition (compute / comm / "
                    "EXPOSED comm / idle ms, interval-exact) + straggler "
                    "skew from a jax-profiler run dir or any chrome-trace "
                    "file the trace loader accepts.")
    ap.add_argument("trace", help="profiler run dir or trace file "
                                  "(.json / .json.gz)")
    ap.add_argument("--host", default=None,
                    help="a Tracer.write export to merge into a "
                         "correlated host+device timeline")
    ap.add_argument("--out", default=None,
                    help="write the merged chrome timeline here "
                         "(requires --host)")
    ap.add_argument("--json", action="store_true",
                    help="print the decomposition as one JSON document")
    ap.add_argument("--z", type=float, default=STRAGGLER_Z,
                    help="straggler z-score threshold")
    ap.add_argument("--top", type=int, default=24, help="step rows shown")
    args = ap.parse_args(argv)

    events = load_events(args.trace)
    host_events = load_events(args.host) if args.host else None
    if host_events is not None:
        merged_doc = merge_host_device(
            [e for e in host_events], events)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(merged_doc, f)
        # step windows come from the merged view (host train.step spans
        # now share the device epoch)
        from ..pyprof import parse as _parse
        events = _parse.events_from_chrome(merged_doc["traceEvents"])
    decomp = decompose(events, z_threshold=args.z)
    if not decomp["devices"]:
        print(f"no device lanes found in {args.trace}")
        return 1
    if args.json:
        print(json.dumps(decomp))
    else:
        print(format_decomposition(decomp, top_steps=args.top))
        if args.host and args.out:
            print(f"\nmerged timeline: {args.out}")
    return 0
