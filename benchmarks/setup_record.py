"""Where set-up went, read from the program's own set-up record.

``apex_tpu.telemetry.trace.setup_tracer()`` keeps, from ``import apex_tpu``
on, one entry for each phase jax reports of each program it builds or loads
(``compile.jaxpr_trace`` / ``compile.jaxpr_to_mlir_module`` /
``compile.backend_compile``, with the program's ``fun_name`` and, on the
last, ``cache`` = ``hit`` | ``miss`` | ``none``) and the spans
``setup.import`` and ``setup.state``.  This module cuts that record where
set-up ends and sums it for the five ``setup_*`` / ``step_trace_lower_s``
readers of ``layer_metrics/``.

*The cut.*  ``Run`` carries no timestamp, and readers earlier in the manifest
compile probes of their own (``optimizer_step_ms``, the routing probes) before
these run.  So set-up ends with the last ``compile.*`` entry of the STEP's
program: on the chip the program that took most device time on the traced
steps' ``XLA Modules`` lines (what ``reduce.step_window`` picks; ``jit_``
stripped gives its ``fun_name``), in a rehearsal ``train_step``, which is
what every adapter's step is called.  Later entries are the probes' and do
not count.

*The sums.*  jax's intervals nest: a program traced inside another's trace
(every ``jnp`` function is a ``jit`` of its own) and an operation run eagerly
while a program is traced each report their own interval inside the outer
one.  So times are taken by interval arithmetic and not by adding durations:
trace + lower is the union of those intervals minus what builds cover, a
program's row in the table is its SELF time, and the parts add up to no more
than the wall clock they were taken from.

A program without the record (a parent commit) reads ``None`` everywhere.
"""
from __future__ import annotations

import collections
import os

from benchmarks import reduce

#: the step's program where there is no trace to name it (a rehearsal)
STEP_PROGRAM = "train_step"
TOP = 10

_PHASES = {"compile.jaxpr_trace": "trace",
           "compile.jaxpr_to_mlir_module": "lower",
           "compile.backend_compile": "build"}


def program(fun_name: str) -> str:
    """One name for a program's three phases: jax traces ``train_step``,
    lowers and builds ``jit(train_step)``, and the device runs
    ``jit_train_step``."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name.removeprefix("jit_")


def step_program(run) -> str:
    """The step's program: by device time over the traced steps where there
    is a trace, by name where there is none."""
    if run.trace is None:
        return STEP_PROGRAM
    from jax.profiler import ProfileData
    busy = collections.Counter()
    for path in reduce.find_xplanes(
            os.path.join(run.manifest.root, ".bench_trace")):
        for plane in ProfileData.from_file(path).planes:
            if not reduce.DEVICE_PLANE.match(plane.name):
                continue
            for line in plane.lines:
                if line.name == reduce.MODULES_LINE:
                    for ev in line.events:
                        busy[reduce.base_name(ev.name)] += ev.duration_ns
    if not busy:
        return STEP_PROGRAM
    return program(busy.most_common(1)[0][0])


def _entries(spans) -> list:
    """The record's ``compile.*`` spans as ``reduce.Event``s named by their
    program; ``stats`` holds the phase and what the cache did."""
    out = []
    for e in spans:
        phase = _PHASES.get(e["name"])
        if phase is not None:
            args = e.get("args", {})
            out.append(reduce.Event(
                program(args.get("fun_name", "?")), e["ts"] * 1e3,
                e["dur"] * 1e3, {"phase": phase, "cache": args.get("cache")}))
    return out


def summarize(spans, step: str, dropped: int = 0):
    """The record up to the end of the last entry of program ``step``, as a
    dict of sums (seconds) and the table's rows; None where the record holds
    no entry of that program."""
    entries = _entries(spans)
    ends = [e.start_ns + e.dur_ns for e in entries if e.name == step]
    if not ends:
        return None
    cut = max(ends)
    kept = [e for e in entries if e.start_ns + e.dur_ns <= cut]
    builds = [e for e in kept if e.stats["phase"] == "build"]
    built = reduce.merge(reduce.span(e) for e in builds)

    def traced_and_lowered(events) -> float:
        return reduce.total(reduce.subtract(reduce.merge(
            reduce.span(e) for e in events if e.stats["phase"] != "build"),
            built)) / 1e9

    rows = collections.defaultdict(collections.Counter)
    for e, self_ns in reduce.self_times(kept):
        row = rows[e.name]
        row[e.stats["phase"] + "_s"] += self_ns / 1e9
        if e.stats["phase"] == "build":
            row["programs"] += 1
            row[e.stats["cache"]] += 1
    for row in rows.values():
        row["self_s"] = row["trace_s"] + row["lower_s"] + row["build_s"]

    def first(name):
        return next((e for e in spans if e["name"] == name
                     and (e["ts"] + e["dur"]) * 1e3 <= cut), None)
    imported, state = first("setup.import"), first("setup.state")
    caches = collections.Counter(e.stats["cache"] for e in builds)
    return {
        "step": step,
        "import_s": imported["dur"] / 1e6 if imported else None,
        "jax_preloaded": (imported["args"].get("jax_preloaded")
                          if imported else None),
        "state_s": state["dur"] / 1e6 if state else None,
        "programs": len(builds),
        "hits": caches["hit"], "misses": caches["miss"],
        "uncached": caches["none"],
        "trace_lower_s": traced_and_lowered(kept),
        "load_compile_s": sum(e.dur_ns for e in builds) / 1e9,
        "step_trace_lower_s": traced_and_lowered(
            e for e in kept if e.name == step),
        "dropped": dropped,
        "rows": sorted(rows.items(), key=lambda kv: -kv[1]["self_s"]),
    }


def _say(record, times: bool) -> None:
    """The ``[bench] set-up:`` lines; off the chip (``times`` false) the
    counts and the names, in order of cost, and no time."""
    def s(x):
        if x is None:
            return "no span"
        return f"{x:.2f} s" if times else "not measured"
    print(f"[bench] set-up: import {s(record['import_s'])} (jax preloaded: "
          f"{record['jax_preloaded']}), state {s(record['state_s'])}, "
          f"{record['programs']} programs ({record['hits']} hit, "
          f"{record['misses']} miss, {record['uncached']} uncached), trace + "
          f"lower {s(record['trace_lower_s'])} (the step's "
          f"{s(record['step_trace_lower_s'])}), load / compile "
          f"{s(record['load_compile_s'])}; up to the last entry of "
          f"{record['step']}; {record['dropped']} entries dropped; the "
          f"{TOP} costliest programs of {len(record['rows'])} names (self "
          "time):", flush=True)
    for name, row in record["rows"][:TOP]:
        what = ", ".join(f"{row[k]} {k}" for k in ("hit", "miss", "none")
                         if row[k]) or "no build under this name"
        cost = (f"  trace {row['trace_s']:.3f}  lower {row['lower_s']:.3f}  "
                f"load / compile {row['build_s']:.3f}" if times else "")
        print(f"[bench] set-up:   {name} x{row['programs']}{cost}  ({what})",
              flush=True)


def record(run):
    """The run's set-up record, summarized once and printed; None where the
    program keeps none or it holds nothing of the step's program."""
    if hasattr(run, "setup_record"):
        return run.setup_record
    from apex_tpu.telemetry import trace
    run.setup_record = None
    if not hasattr(trace, "setup_tracer"):
        return None
    doc = trace.setup_tracer().export()
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    step = step_program(run)
    run.setup_record = summarize(spans, step, doc["droppedSpans"])
    if run.setup_record is None:
        print(f"[bench] set-up: the record holds no entry of the step's "
              f"program {step!r} ({len(spans)} entries, "
              f"{doc['droppedSpans']} dropped)", flush=True)
    else:
        _say(run.setup_record, run.on_chip)
    return run.setup_record


def seconds(run, key: str):
    """A time of the record: on the chip only."""
    found = record(run)
    if found is None or not run.on_chip:
        return None
    return found[key]
