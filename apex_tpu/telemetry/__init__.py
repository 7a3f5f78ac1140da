"""apex_tpu.telemetry — training-telemetry subsystem.

Ten pieces (see docs/telemetry.md):

  * :mod:`registry`  — counters/gauges/histograms/meters with a
    host-sync-batching ``step()`` context, rank-0-gated JSONL emission
    validated against the committed record :data:`SCHEMA`, and a true
    no-op disabled mode;
  * :mod:`events`    — structured events wired into the existing hook
    points (amp scaler halve/double transitions, DDP collective meters,
    loader queue gauges) through a process-default registry;
  * :mod:`trace`     — host-side span tracer (Chrome/Perfetto export),
    the bounded flight-recorder ring the resilience guard dumps on
    rollback/preempt/crash, and the slow-step sentinel that can open a
    one-shot ``jax.profiler`` capture on a step-time anomaly;
  * :mod:`attrib`    — per-op FLOPs/bytes attribution over the compiled
    HLO (the per-fusion refinement of ``pyprof.prof.cost_report``),
    with blas/conv/pointwise/reduction/collective op-class rollups;
  * :mod:`memory`    — peak-HBM attribution from ``memory_analysis()``
    + an HLO liveness sweep (``memory_table``/``memory_model``), live
    ``device.memory_stats`` gauges polled at registry-flush cadence
    (Chrome counter tracks under the span rows), and the OOM
    post-mortem (``flight-oom-*.json``) the resilience guard writes on
    ``RESOURCE_EXHAUSTED``;
  * :mod:`timeline`  — device-timeline decomposition over parsed
    ``jax.profiler`` captures: per-device/per-step compute vs total vs
    EXPOSED collective ms (exact interval subtraction), idle/stall
    time, cross-device straggler z-scores (``timeline.straggler``
    events), a correlated host+device Chrome merge, and the measured
    ``exposed_comm_fraction`` (the planner's overlap factor,
    ``APEX_TPU_OVERLAP_FRACTION``);
  * :mod:`goodput`   — the run-level goodput ledger: every wall-clock
    second of a run attributed to exactly one class (productive step
    compute, exposed collective, data stall, exposed checkpoint save,
    restore+rollback replay, recompilation, elastic reshard, idle) by
    exact interval arithmetic over the streams above; exported as
    ``goodput.fraction``/``badput.*`` gauges through the batched
    flush and as the ``GOODPUT.json`` run artifact the guard writes on
    exit/preempt/crash;
  * :mod:`fleet`     — N per-host run dirs merged into one
    writer-validated ``FLEET.json``: interval-union fleet goodput with
    every host's per-class partition re-asserted, cross-host step skew,
    leave-one-out host straggler z-scores (timeline's estimator),
    control-action/flight-dump correlation, and an N-way merged Chrome
    doc (one lane group per host on a shared epoch);
  * :mod:`export`    — live pull-based OpenMetrics endpoint
    (``APEX_TPU_METRICS_PORT`` gated, 127.0.0.1, default off) serving
    the snapshot each ``Registry.flush`` resolves — zero extra host
    syncs, a true no-op when disabled;
  * :mod:`report`    — JSONL → step-metrics summary +
    ``python -m apex_tpu.telemetry`` CLI (``trace <file>`` renders the
    span-timeline summary, ``mem`` the peak-HBM table, ``timeline``
    the per-device step decomposition, ``goodput`` the run ledger,
    ``fleet`` the merged multi-host view).

The reference has no counterpart: its observability is rank-0 prints
and an ``AverageMeter`` whose docstring warns that printing costs an
allreduce+sync (``examples/imagenet/main_amp.py:363-390``).  This
subsystem is the registry that warning asks for, and the prerequisite
for the comms-efficiency work (EQuARX-style quantized collectives,
cross-replica sharding) that needs per-collective byte/step-time
accounting before it can claim a win.
"""
from . import trace
from . import registry
from . import events
from . import memory
from . import timeline
from . import goodput
from . import fleet
from . import export
from .registry import (SCHEMA, Registry, Counter, Gauge, Histogram,
                       AverageMeter, Throughput, JsonlSink, MemorySink,
                       NULL_METRIC, record_violations, records_violations)
from .events import (set_default, get_default, active, observe_scaler,
                     observe_amp, record_collective, record_loader,
                     record_ckpt)
from .trace import (Tracer, FlightRecorder, SlowStepSentinel, NULL_SPAN,
                    set_tracer, get_tracer, span, traced)
from .memory import (MemoryMonitor, memory_table, memory_model,
                     format_memory_table)
from .goodput import GoodputLedger, goodput_violations, FAULT_BADPUT
from .fleet import build_fleet, fleet_violations
from .export import MetricsExporter

__all__ = [
    "trace", "registry", "events", "memory", "timeline", "goodput",
    "fleet", "export",
    "SCHEMA",
    "Registry",
    "Counter", "Gauge",
    "Histogram", "AverageMeter", "Throughput", "JsonlSink", "MemorySink",
    "NULL_METRIC", "record_violations", "records_violations",
    "set_default", "get_default", "active", "observe_scaler",
    "observe_amp", "record_collective", "record_loader", "record_ckpt",
    "Tracer", "FlightRecorder", "SlowStepSentinel", "NULL_SPAN",
    "set_tracer", "get_tracer", "span", "traced",
    "MemoryMonitor", "memory_table", "memory_model",
    "format_memory_table",
    "GoodputLedger", "goodput_violations", "FAULT_BADPUT",
    "build_fleet", "fleet_violations", "MetricsExporter",
]
