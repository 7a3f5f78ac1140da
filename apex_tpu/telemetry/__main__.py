"""``python -m apex_tpu.telemetry`` — render a run's JSONL (or run the
instrumented-transformer demo) into the per-op FLOPs/bytes table and the
step-metrics summary; ``python -m apex_tpu.telemetry trace <file>``
renders the span-timeline summary from a Chrome-trace file (a
``Tracer.write`` export, a streaming never-closed event array, or a
jax-profiler run dir); ``python -m apex_tpu.telemetry mem [artifact]``
renders the per-class peak-HBM attribution table (the flagship
transformer step or a ``flight-oom-*.json`` post-mortem);
``python -m apex_tpu.telemetry timeline <trace|profiler-dir>`` renders
the per-device step decomposition (compute / comm / exposed-comm / idle ms + straggler
skew) from a device trace; ``python -m apex_tpu.telemetry goodput
<jsonl|run-dir>`` renders the run-level goodput ledger (wall-clock
badput attribution) from a ``GOODPUT.json`` artifact or a run's
exported gauges; ``python -m apex_tpu.telemetry fleet <dir> [dir...]``
merges N per-host run dirs into the one-fleet view (goodput by host,
step skew, stragglers, control actions) and can write the
``FLEET.json`` artifact + N-way merged timeline.  See ``report.main``
for the flags."""
from .report import main

if __name__ == "__main__":
    raise SystemExit(main())
