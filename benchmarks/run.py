#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

One process: set-up (state built on the device from the seed, the reference
check, every program compiled or loaded from the cache, three warm-up steps),
a measured window of ``--seconds``, one JSON object as the last line of
stdout.  ``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1``
runs the same window, then a few steps under the profiler, and prints the
per-layer metrics and a breakdown.

It needs the chip: without a TPU, with another number of devices than the
cell names, or with a ``device_kind`` that ``peaks.json`` does not list, it
prints why on stderr and exits non-zero without a result line.  There is no
CPU switch on the command line; ``benchmarks/tests`` rehearses the same code
on the CPU by calling :func:`run_cell` with ``rehearse=True``, which reports
counts only — never a time, a rate or a share.

Everything that belongs to one configuration, one cell or one per-layer
metric is a file found by its name in ``BENCHMARK.json`` (``configs/``,
``workloads/``, ``jobs/``, ``reference/``, ``layer_metrics/`` under any
directory of ``paths``); this file knows no model.
"""
from __future__ import annotations

import time

_T_START = time.perf_counter()     # set-up is counted from here

import argparse
import json
import math
import os
import shutil
import statistics
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

WARMUP_STEPS = 3
TRACE_LEAD_IN = 2      # steps run under the profiler before the traced ones
TRACE_STEPS = 8
PROBE_REPEATS = 10
GIB = 2.0 ** 30


class Refused(Exception):
    """The run cannot produce a result here; the CLI exits non-zero."""


def say(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - _T_START:6.1f}s] {msg}", flush=True)


# ---------------------------------------------------------------------------
# the manifest and the files it names
# ---------------------------------------------------------------------------

class Manifest:
    """``BENCHMARK.json`` and the directories it lists."""

    def __init__(self, path: str):
        self.root = os.path.dirname(os.path.abspath(path))
        with open(path) as f:
            self.doc = json.load(f)
        self.dirs = [os.path.join(self.root, p) for p in self.doc["paths"]]
        # the harness's own directory last, so a cell that lives elsewhere
        # still finds the shared job adapters and readers
        if BENCH_DIR not in self.dirs:
            self.dirs.append(BENCH_DIR)

    def entry(self, section: str, name: str) -> dict:
        for item in self.doc[section]:
            if item["name"] == name:
                return item
        raise Refused(f"no {section} entry named {name!r}; known: "
                      f"{[i['name'] for i in self.doc[section]]}")

    def find(self, *parts: str) -> str:
        for d in self.dirs:
            path = os.path.join(d, *parts)
            if os.path.exists(path):
                return path
        raise Refused(f"{os.path.join(*parts)} not found under {self.dirs}")

    def load_json(self, *parts: str) -> dict:
        with open(self.find(*parts)) as f:
            return json.load(f)

    def metrics_for(self, section: str, workload: str) -> list:
        """The section's metrics for this cell: all of them but those that
        list other cells under the contract's optional ``workloads`` key."""
        return [m for m in self.doc[section]
                if workload in m.get("workloads", [workload])]


def load_peaks(kind: str) -> dict:
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        kinds = json.load(f)["kinds"]
    if kind not in kinds:
        raise Refused(f"device_kind {kind!r} is not in peaks.json "
                      f"(known: {sorted(kinds)}): add its published peaks")
    return kinds[kind]


# ---------------------------------------------------------------------------
# what jax compiled, and when
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts lowerings (one per program jax had to build or fetch: a new
    shape in the window shows here even when the persistent cache answers)
    and the persistent cache's hits and misses."""

    def __init__(self):
        import jax.monitoring
        self.lowerings = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, name, secs, **_):
        if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowerings += 1

    def _on_event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def memory_footprint(devices):
    """Bytes the fullest chip needs for the job, or None where the backend
    keeps no count (the CPU).  Call it when the device is quiet.

    The TPU allocator keeps two books (seen on the chip, PR 22): arrays are
    ``bytes_in_use``, with a high-water mark ``peak_bytes_in_use``; the
    scratch a loaded program works in is ``bytes_reserved``, held for as
    long as the program stays loaded and NOT part of ``bytes_in_use``.  A
    training step's activations are scratch.  So the footprint is the larger
    of the arrays' high-water mark and arrays plus scratch as they stand."""
    stats = [d.memory_stats() for d in devices]
    if not all(stats):
        return None
    say("device memory: " + "; ".join(
        f"in use {s['bytes_in_use']} (peak {s['peak_bytes_in_use']}) "
        f"reserved {s.get('bytes_reserved')} (peak "
        f"{s.get('peak_bytes_reserved')}) limit {s.get('bytes_limit')}"
        for s in stats))
    return max(max(int(s["peak_bytes_in_use"]),
                   int(s["bytes_in_use"]) + int(s.get("bytes_reserved", 0)))
               for s in stats)


def enable_compile_cache(root: str) -> str:
    """``JAX_COMPILATION_CACHE_DIR`` where it is set, else a fixed directory
    in the checkout; every program is cached, however quick its compile."""
    import jax
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

class Run:
    """What the per-layer readers (``layer_metrics/<name>.py``) are given."""

    def __init__(self, manifest, job, peaks, chips, on_chip):
        self.manifest, self.job, self.peaks = manifest, job, peaks
        self.chips, self.on_chip = chips, on_chip
        self.state = job.state
        self.steps = 0                 # steps in the measured window
        self.window_s = None
        self.samples_per_s = None
        self.dispatch_ms = []          # host time of each non-blocking step
        self.skipped_steps = None
        self.trace = None              # reduce.Trace of the traced steps
        self._values = {}

    def metric(self, name: str):
        """The value of per-layer metric ``name`` (None: nothing to read);
        read once, so one reader can build on another."""
        if name not in self._values:
            from benchmarks.job import load_module
            reader = load_module(
                self.manifest.find("layer_metrics", name + ".py"),
                "bench_layer_metric_" + name)
            value = reader.read(self)
            if value is not None and not math.isfinite(value):
                raise ValueError(f"per-layer metric {name} read {value}")
            self._values[name] = value
        return self._values[name]

    def time_blocked(self, fn, carry, *args, repeats=PROBE_REPEATS):
        """Median seconds of ``carry = fn(carry, *args)``, each call waited
        for; the first call compiles and is not counted.  Off the chip the
        one call is made and no time is taken."""
        import jax
        carry = jax.block_until_ready(fn(carry, *args))
        if not self.on_chip:
            return None, carry
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            carry = jax.block_until_ready(fn(carry, *args))
            times.append(time.perf_counter() - t0)
        return statistics.median(times), carry


def _window(run, seconds, sync_every, first_index):
    """Dispatch steps without waiting, wait for the loss every
    ``sync_every`` steps, stop at the first such wait after ``seconds``."""
    import jax
    job, ring = run.job, run.job.batches
    state, losses, chunks = run.state, [], []
    i = first_index
    t0 = t_chunk = time.perf_counter()
    while True:
        for _ in range(sync_every):
            t = time.perf_counter()
            state, loss = job.step(state, ring[i % len(ring)])
            run.dispatch_ms.append((time.perf_counter() - t) * 1e3)
            losses.append(loss)
            i += 1
        jax.block_until_ready(loss)
        now = time.perf_counter()
        chunks.append(sync_every * job.samples_per_step / (now - t_chunk))
        t_chunk = now
        if now - t0 >= seconds:
            break
    run.state, run.steps, run.window_s = state, i - first_index, now - t0
    run.samples_per_s = run.steps * job.samples_per_step / run.window_s
    return [float(x) for x in jax.device_get(losses)], chunks, i


def off_path(losses) -> int:
    """Losses that are no number or lie above the window's first one."""
    return sum(1 for x in losses if not x <= losses[0])


def losses_fell(losses, chunk: int) -> bool:
    """The window learned: the median of its last ``chunk`` losses (what a
    loop reads between two waits; half the window where it is shorter than
    two chunks) is below the median of its first, and at most a tenth of its
    losses lie above the first one — none in a window of under ten steps.

    Why a tenth and not none.  ``bert_large.dp4_s512 --seed 5`` prints 5.62,
    15.50, 15.49, 15.34, 5.45 (steps 58-60), above an untrained model's
    ln(vocabulary), on batches it had read normally before.  Measured on the
    chip (PR 22; the same trajectory on one chip, where the event shows
    too): on the parameters as they stand before those steps the plain
    float32 reference reads 15.5156 where the program reads 15.5158, with
    XLA attention and XLA loss in place of the kernels 15.5161, and the
    bfloat16 model copy is the float32 master rounded.  The parameters
    really say 15.5: an excursion of the optimizer (LAMB at the example's lr
    of 1e-3, no warm-up), not a fault of the program, and the window's rate
    is a correct program's rate.  Medians, so that three such steps do not
    decide whether a chunk of twelve learned; the tenth, so that a window
    cannot be mostly excursion and pass."""
    k = max(1, min(chunk, len(losses) // 2))
    fell = statistics.median(losses[-k:]) < statistics.median(losses[:k])
    return fell and off_path(losses) <= len(losses) // 10


def _traced_steps(run, trace_dir, first_index):
    """A few steady steps under the profiler; returns the trace's lines."""
    import jax
    from benchmarks import reduce
    job, ring = run.job, run.job.batches
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # spans come from TraceAnnotation
    state, i = run.state, first_index
    with jax.profiler.trace(trace_dir, profiler_options=options):
        for _ in range(TRACE_LEAD_IN):
            state, loss = job.step(state, ring[i % len(ring)])
            i += 1
        for n in range(TRACE_STEPS):
            with jax.profiler.StepTraceAnnotation("bench_step", step_num=n):
                with jax.profiler.TraceAnnotation("bench.input"):
                    batch = ring[i % len(ring)]
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    state, loss = job.step(state, batch)
            i += 1
        with jax.profiler.TraceAnnotation("bench.sync"):
            jax.block_until_ready(loss)
    run.state = state
    lines = []
    for path in reduce.find_xplanes(trace_dir):
        lines += reduce.read_xplane(path)
    return lines


def _take_devices(workload, chips, rehearse):
    """``(devices, peaks row)`` for the cell, or :class:`Refused`: the chip
    and exactly the cell's number of them — or, for a rehearsal, that many
    CPU devices and no peaks."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if rehearse:
        if dev.platform == "tpu" or len(devices) < chips:
            raise Refused(f"a rehearsal wants {chips} CPU device(s); jax "
                          f"found {len(devices)} x {dev.platform}")
        say("REHEARSAL on the CPU: counts only, no time, rate or share")
        return devices[:chips], None
    if dev.platform != "tpu":
        raise Refused(f"the benchmark needs a TPU and jax found platform "
                      f"{dev.platform!r} ({dev.device_kind})")
    if len(devices) != chips:
        raise Refused(f"cell {workload} runs on {chips} chip(s) and jax "
                      f"found {len(devices)}")
    return devices, load_peaks(dev.device_kind)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             manifest_path: str = os.path.join(ROOT, "BENCHMARK.json"),
             rehearse: bool = False) -> dict:
    """Run the cell and return the result object (the last line).  Raises
    :class:`Refused` where no result can be had."""
    manifest = Manifest(manifest_path)
    cell = manifest.entry("workloads", workload)
    with open(os.path.join(manifest.root, manifest.entry(
            "configs", cell["config"])["file"])) as f:
        config = json.load(f)
    traffic = manifest.load_json("workloads", workload + ".json")
    chips = cell["chips"]

    # the cell measures the program's defaults: nothing in the environment
    # may pick another kernel or block size
    for key in [k for k in os.environ if k.startswith("APEX_TPU_")]:
        say(f"ignoring {key} from the environment")
        del os.environ[key]
    try:
        import apex_tpu  # noqa: F401  (the system under test)
    except ImportError as e:
        raise Refused(f"the program is not in this checkout: {e}")

    import jax
    from benchmarks.job import load_module
    devices, peaks = _take_devices(workload, chips, rehearse)
    dev = devices[0]
    on_chip = dev.platform == "tpu"
    counter = CompileCounter()
    cache_dir = None if rehearse else enable_compile_cache(manifest.root)
    say(f"jax {jax.__version__}  {dev.platform}  {dev.device_kind}  "
        f"x{len(devices)}  cell {workload}  seed {seed}  cache {cache_dir}")

    # -- set-up -------------------------------------------------------------
    adapter = load_module(manifest.find("jobs", config["job"] + ".py"),
                          "bench_job_" + config["job"])
    job = adapter.build(config, traffic, seed, devices, manifest.find(
        "reference", config["reference"] + ".py"))
    say(f"job built; reference check: {json.dumps(job.reference)}")
    run = Run(manifest, job, peaks, chips, on_chip)
    with job.scope():
        index = 0
        for _ in range(WARMUP_STEPS):
            run.state, loss = job.step(run.state,
                                       job.batches[index % len(job.batches)])
            jax.block_until_ready(loss)
            index += 1
        applied0 = job.applied_steps(run.state)
        lowerings0 = counter.lowerings
        say(f"warmed up: {WARMUP_STEPS} steps")
        setup_s = time.perf_counter() - _T_START

        # -- the measured window ---------------------------------------------
        losses, chunks, index = _window(run, seconds, traffic["sync_every"],
                                        index)
        compiled_in_window = counter.lowerings - lowerings0
        applied = job.applied_steps(run.state) - applied0
        run.skipped_steps = run.steps - applied
        replicas_ok = (job.replicas_agree(run.state)
                       if job.replicas_agree else True)
        memory_peak = memory_footprint(devices)

        if on_chip:
            say("chunk rates (samples/s): "
                + " ".join(f"{c:.2f}" for c in chunks))
        say(f"window: {run.steps} steps, losses first "
            f"{losses[0]:.4f} last {losses[-1]:.4f}; skipped "
            f"{run.skipped_steps}; programs built in the window "
            f"{compiled_in_window}; cache hits {counter.hits} misses "
            f"{counter.misses}")

        # -- correct ----------------------------------------------------------
        say("losses: " + " ".join(f"{x:.3f}" for x in losses))
        non_finite = sum(1 for x in losses if not math.isfinite(x))
        if off_path(losses):
            say(f"{off_path(losses)} of {len(losses)} losses lie above the "
                "window's first (see losses_fell)")
        checks = {
            "reference": bool(job.reference["ok"]),
            "losses_finite": non_finite == 0,
            "loss_fell": losses_fell(losses, traffic["sync_every"]),
            "nothing_compiled_in_window": compiled_in_window == 0,
            "no_skipped_steps": job.skips_allowed or run.skipped_steps == 0,
            "replicas_agree": replicas_ok,
        }
        say(f"checks: {json.dumps(checks)}")

        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devices), "memory_peak_bytes": memory_peak}
        result = {"correct": all(checks.values()), "attempted": run.steps,
                  "failed": max(run.skipped_steps, non_finite),
                  "metrics": {}, "device": device}

        # -- metrics ----------------------------------------------------------
        if not trace:
            values = {"samples_per_s": run.samples_per_s if on_chip else None,
                      "peak_hbm_gib": (memory_peak / GIB
                                       if on_chip and memory_peak else None),
                      "setup_s": setup_s if on_chip else None}
            wanted = manifest.metrics_for("end_to_end", workload)
        else:
            if on_chip:
                from benchmarks import reduce
                trace_dir = os.path.join(manifest.root, ".bench_trace",
                                         workload)
                run.trace = reduce.Trace(
                    _traced_steps(run, trace_dir, index), TRACE_STEPS)
                if not run.trace:
                    raise Refused(f"the trace in {trace_dir} holds no "
                                  "device operations")
                device["busy_s"] = run.trace.busy_s
                device["window_s"] = run.trace.window_s
                result["breakdown"] = {
                    "device_ops": run.trace.top_ops(10),
                    "idle_gaps": run.trace.idle_gaps(10)}
            wanted = manifest.metrics_for("per_layer", workload)
            values = {m["name"]: run.metric(m["name"]) for m in wanted}
            memory_footprint(devices)      # logged: what the probes added
        for m in wanted:
            if values.get(m["name"]) is not None:
                result["metrics"][m["name"]] = {
                    "value": values[m["name"]], "unit": m["unit"]}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except Refused as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
