"""apex_tpu.telemetry.trace — span tracer, flight recorder, sentinel
(ISSUE 5).

The acceptance gates:

  * the disabled tracer is an asserted TRUE no-op: zero host syncs and
    zero allocation growth over 1k spans (the registry's bar);
  * a guard-driven chaos run with an injected ``nan@5x3`` burst leaves
    a schema-valid flight-recorder dump naming the faulting step;
  * the emitted trace JSON is Chrome/Perfetto-loadable, and
    ``python -m apex_tpu.telemetry trace <file>`` renders the span
    summary from a trace produced by a real guard-driven run;
  * the slow-step sentinel fires on a synthetic step-time spike and NOT
    on steady noise.
"""
import gc
import glob
import json
import os
import subprocess
import sys
import threading
import tracemalloc

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.resilience import GuardConfig, TrainGuard, faults
from apex_tpu.telemetry import MemorySink, Registry, events, trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(autouse=True)
def _no_defaults():
    """Tracers/registries/plans must not leak between tests."""
    prev_tr = trace.set_tracer(None)
    prev_reg = events.set_default(None)
    prev_plan = faults.install(None)
    yield
    trace.set_tracer(prev_tr)
    events.set_default(prev_reg)
    faults.install(prev_plan)


# ---------------------------------------------------------------------------
# the tracer
# ---------------------------------------------------------------------------

def test_span_context_and_decorator_export_chrome_json(tmp_path):
    tr = trace.Tracer()
    with tr.span("outer", step=3):
        with tr.span("inner"):
            pass

    @trace.traced("decorated", tag="x")
    def work():
        return 7

    trace.set_tracer(tr)
    assert work() == 7
    doc = tr.export()
    assert doc["displayTimeUnit"] == "ms"
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    names = [e["name"] for e in spans]
    assert names == ["inner", "outer", "decorated"]   # close order
    outer = next(e for e in spans if e["name"] == "outer")
    inner = next(e for e in spans if e["name"] == "inner")
    # nesting: inner lies within outer on the same thread
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert outer["args"] == {"step": 3}
    # process/thread metadata present (what Perfetto names lanes from)
    metas = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "M"}
    assert {"process_name", "thread_name"} <= metas
    # every complete event is Perfetto-loadable: numeric ts/dur, ids set
    for e in spans:
        assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
        assert e["pid"] == os.getpid() and e["tid"] is not None
    # the file round-trips through the loader
    p = str(tmp_path / "t.trace.json")
    tr.write(p)
    assert json.load(open(p))["traceEvents"]           # plain JSON
    evs = trace.load_chrome(p)
    assert {e["name"] for e in evs} == {"outer", "inner", "decorated"}


def test_tracer_thread_safety_distinct_tids():
    tr = trace.Tracer()
    barrier = threading.Barrier(4)   # all threads alive at once, so the
    # OS cannot recycle an exited thread's ident mid-test

    def worker(i):
        barrier.wait()
        for _ in range(50):
            with tr.span(f"w{i}"):
                pass

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spans = [e for e in tr.export()["traceEvents"] if e.get("ph") == "X"]
    assert len(spans) == 200
    assert len({e["tid"] for e in spans}) == 4
    # every span is intact (no torn records under concurrency)
    assert all(e["dur"] >= 0.0 and e["name"].startswith("w")
               for e in spans)


def test_disabled_tracer_is_true_noop_zero_syncs_zero_allocs(monkeypatch):
    """The acceptance gate: a disabled tracer adds NO host sync and NO
    allocation growth over 1k spans — span() hands back the shared
    singleton and records nothing."""
    syncs = []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: syncs.append("block") or x)
    monkeypatch.setattr(jax, "device_get",
                        lambda x: syncs.append("get") or x)
    tr = trace.Tracer(enabled=False)
    trace.set_tracer(tr)
    assert tr.span("x") is trace.NULL_SPAN
    assert trace.span("x") is trace.NULL_SPAN

    def burn():
        for i in range(1000):
            with tr.span("hot"):
                pass
            with trace.span("hot.module"):
                pass
            trace.note_span("post", 0.001)
            trace.note_event("ev", step=i)
            trace.note_step(i, 0.001)
            tr.instant("never")

    burn()                       # warm up allocator/caches first
    gc.collect()
    tracemalloc.start()
    snap1 = tracemalloc.take_snapshot()
    burn()
    gc.collect()
    snap2 = tracemalloc.take_snapshot()
    tracemalloc.stop()
    # zero allocation GROWTH over 1k spans: nothing in trace.py (or the
    # burn loop) allocates per span — any surviving stat is a handful of
    # constant-count tracemalloc bookkeeping entries, never O(spans)
    per_span = [s for s in snap2.compare_to(snap1, "lineno")
                if s.count_diff >= 100
                and s.traceback and "tracemalloc" not in
                s.traceback[0].filename]
    assert per_span == [], [str(s) for s in per_span]
    assert syncs == []                          # zero host syncs
    assert tr.n_spans == 0
    assert tr.recorder.total == 0
    assert tr.export()["traceEvents"][0]["ph"] == "M"   # metadata only


def test_env_var_disables_tracer(monkeypatch):
    monkeypatch.setenv("APEX_TPU_TRACE", "off")
    assert trace.Tracer().enabled is False
    monkeypatch.setenv("APEX_TPU_TRACE", "1")
    assert trace.Tracer().enabled is True
    monkeypatch.setenv("APEX_TPU_TRACE", "0")
    assert trace.Tracer(enabled=True).enabled is True   # explicit wins


def test_max_spans_drops_oldest_and_counts():
    tr = trace.Tracer(max_spans=10)
    for i in range(25):
        with tr.span(f"s{i}"):
            pass
    doc = tr.export()
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert len(spans) == 10
    assert spans[0]["name"] == "s15"            # oldest dropped
    assert doc["droppedSpans"] == 15            # truncation is visible


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_flight_recorder_ring_bounds_and_dump_schema(tmp_path):
    tr = trace.Tracer(ring=8, flight_dir=str(tmp_path))
    for i in range(20):
        with tr.span("s", i=i):
            pass
    tr.note_event("ev", step=3, fields={"x": 1, "arr": object()})
    tr.note_flush(4, [{"name": "loss"}, {"name": "examples"}])
    snap = tr.recorder.snapshot()
    assert len(snap) == 8                       # bounded
    assert tr.recorder.total == 22              # evictions counted
    path = tr.recorder.dump("unit_test", step=9, fields={"why": "test"})
    doc = json.load(open(path))
    assert trace.dump_violations(doc) == []
    assert doc["reason"] == "unit_test" and doc["step"] == 9
    kinds = {e["kind"] for e in doc["entries"]}
    assert {"span", "event", "metric_flush"} <= kinds
    ev = next(e for e in doc["entries"] if e["kind"] == "event")
    # non-scalar fields degrade to reprs (no device resolution at note)
    assert isinstance(ev["fields"]["arr"], str)
    # validator actually complains about drift
    assert trace.dump_violations({"kind": "flight_recorder"})
    bad = dict(doc, entries=[{"kind": "span", "name": "x"}])
    assert any("t_us" in v for v in trace.dump_violations(bad))


def test_flight_recorder_without_directory_skips_dump():
    tr = trace.Tracer()
    with tr.span("s"):
        pass
    assert tr.recorder.dump("nowhere") is None  # never litters the cwd


# ---------------------------------------------------------------------------
# the chaos acceptance: guard-driven dump + trace + CLI
# ---------------------------------------------------------------------------

def _sgd_step():
    @jax.jit
    def step(w, batch):
        g = jax.grad(lambda w: jnp.sum((w - batch) ** 2))(w)
        finite = jnp.all(jnp.isfinite(g))
        return jnp.where(finite, w - 0.1 * g, w), jnp.sum((w - batch) ** 2)
    return step


def _batch_at(i):
    return jnp.asarray(np.random.RandomState(i).randn(4).astype(np.float32))


def test_chaos_nan_burst_rollback_leaves_flight_dump_naming_step(tmp_path):
    """THE acceptance gate: an injected ``nan@5x3`` burst escalates to a
    rollback, and the guard leaves a schema-valid flight-recorder dump
    next to the checkpoints that names the faulting steps — both in the
    dump fields (bad_step) and in the recorded fault_injected events."""
    tr = trace.Tracer()
    trace.set_tracer(tr)
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    plan = faults.parse("nan@5x3")
    # check_every=4 puts the burst (steps 5,6,7) at a window END: the
    # streak is 3 when the health check reads it, so it escalates
    g = TrainGuard(_sgd_step(),
                   GuardConfig(ckpt_dir=str(tmp_path), save_every_steps=5,
                               check_every=4, nonfinite_streak=3,
                               backoff_seconds=0.01, enabled=True),
                   plan=plan, registry=reg)
    w, rep = g.run(jnp.zeros(4), _batch_at, 20)
    assert rep.status == "completed" and rep.rollbacks == 1
    dumps = glob.glob(str(tmp_path / "flight-rollback-*.json"))
    assert len(dumps) == 1
    doc = json.load(open(dumps[0]))
    assert trace.dump_violations(doc) == []
    assert doc["reason"] == "rollback"
    assert doc["fields"]["why"] == "non-finite loss streak"
    assert doc["fields"]["bad_step"] == 7       # last faulting step
    injected = [e["fields"]["step"] for e in doc["entries"]
                if e["kind"] == "event" and e["name"] == "fault_injected"]
    assert injected == [5, 6, 7]                # the whole burst, in order
    # the ring also holds the guard's operational spans
    span_names = {e["name"] for e in doc["entries"] if e["kind"] == "span"}
    assert {"ckpt.write", "ckpt.restore", "guard.health_check"} <= span_names


def test_guard_exception_dump(tmp_path):
    """An unhandled step-fn exception still leaves the black box."""
    tr = trace.Tracer()
    trace.set_tracer(tr)

    calls = {"n": 0}

    def step(w, b):
        calls["n"] += 1
        if calls["n"] == 3:
            raise RuntimeError("cosmic ray")
        return w + b, jnp.sum(w)

    g = TrainGuard(step, GuardConfig(ckpt_dir=str(tmp_path), check_every=2,
                                     enabled=True))
    with pytest.raises(RuntimeError, match="cosmic ray"):
        g.run(jnp.zeros(2), lambda i: jnp.ones(2), 10)
    dumps = glob.glob(str(tmp_path / "flight-exception-*.json"))
    assert len(dumps) == 1
    doc = json.load(open(dumps[0]))
    assert trace.dump_violations(doc) == []
    assert doc["fields"]["error_type"] == "RuntimeError"
    assert "cosmic ray" in doc["fields"]["error"]


def test_guard_preempt_dump_and_ckpt_gauges(tmp_path):
    """Injected preemption dumps the recorder; the background writer's
    checkpoint saves land write-duration/bytes gauges in the
    process-default registry (the satellite)."""
    tr = trace.Tracer()
    trace.set_tracer(tr)
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    events.set_default(reg)
    plan = faults.parse("preempt@7")
    g = TrainGuard(_sgd_step(),
                   GuardConfig(ckpt_dir=str(tmp_path), save_every_steps=3,
                               check_every=3, enabled=True), plan=plan)
    _, rep = g.run(jnp.zeros(4), _batch_at, 20)
    assert rep.status == "preempted"
    assert glob.glob(str(tmp_path / "flight-preempt-*.json"))
    vals = reg.read()
    assert vals["ckpt.write_ms"] > 0.0
    assert vals["ckpt.bytes_written"] > 0.0


def test_ckpt_gauges_honor_guard_pinned_registry(tmp_path):
    """A guard constructed with registry=reg (no process default) must
    meter its checkpoint writes into THAT registry, like every other
    guard emission (code-review finding)."""
    trace.set_tracer(trace.Tracer())
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    assert events.get_default() is None
    g = TrainGuard(_sgd_step(),
                   GuardConfig(ckpt_dir=str(tmp_path), save_every_steps=4,
                               check_every=4, enabled=True), registry=reg)
    _, rep = g.run(jnp.zeros(4), _batch_at, 12)
    assert rep.status == "completed"
    vals = reg.read()
    assert vals["ckpt.write_ms"] > 0.0 and vals["ckpt.bytes_written"] > 0.0


def test_sentinel_rejects_warmup_larger_than_window():
    with pytest.raises(ValueError, match="disarm"):
        trace.SlowStepSentinel(window=8, warmup=16)


def test_cli_trace_renders_guard_driven_span_summary(tmp_path):
    """ISSUE acceptance: ``python -m apex_tpu.telemetry trace <file>``
    renders the per-name count/total/p50/p99 self-time summary from a
    trace produced by a real guard-driven run, and the file loads as
    plain Chrome-trace JSON."""
    tr = trace.Tracer()
    trace.set_tracer(tr)
    g = TrainGuard(_sgd_step(),
                   GuardConfig(ckpt_dir=str(tmp_path / "ck"),
                               save_every_steps=4, check_every=4,
                               enabled=True))
    _, rep = g.run(jnp.zeros(4), _batch_at, 12)
    assert rep.status == "completed"
    path = str(tmp_path / "guard.trace.json")
    tr.write(path)
    doc = json.load(open(path))                 # chrome://tracing-loadable
    assert any(e.get("ph") == "X" for e in doc["traceEvents"])
    r = subprocess.run(
        [sys.executable, "-m", "apex_tpu.telemetry", "trace", path],
        capture_output=True, text=True, cwd=ROOT, timeout=180,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT})
    assert r.returncode == 0, r.stderr[-2000:]
    assert "span timeline summary" in r.stdout
    assert "ckpt.write" in r.stdout
    assert "p50 us" in r.stdout and "p99 us" in r.stdout


def test_cli_trace_profiler_dir_fixture(tmp_path):
    """ISSUE 13 satellite: the trace CLI's jax-profiler-DIR branch on a
    run-dir fixture (the TensorBoard ``plugins/profile/<run>/*.trace.
    json.gz`` layout) — previously only exercised implicitly — plus the
    new droppedEvents visibility for torn records."""
    import gzip
    d = tmp_path / "plugins" / "profile" / "run_1"
    d.mkdir(parents=True)
    events = [
        {"ph": "M", "name": "process_name", "pid": 10,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "X", "name": "fusion.1", "ts": 0, "dur": 100, "pid": 10,
         "tid": 1, "args": {}},
        {"ph": "X", "name": "all-reduce.2", "ts": 50, "dur": 100,
         "pid": 10, "tid": 1, "args": {}},
        {"ph": "X", "name": "torn-span", "pid": 10, "tid": 1},  # no ts/dur
    ]
    with gzip.open(d / "host.trace.json.gz", "wt") as f:
        json.dump({"displayTimeUnit": "ms", "traceEvents": events}, f)
    r = subprocess.run(
        [sys.executable, "-m", "apex_tpu.telemetry", "trace",
         str(tmp_path)],
        capture_output=True, text=True, cwd=ROOT, timeout=180,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT})
    assert r.returncode == 0, r.stderr[-2000:]
    assert "span timeline summary" in r.stdout
    assert "fusion.1" in r.stdout and "all-reduce.2" in r.stdout
    # the torn record is announced, not silently thin
    assert "1 trace events dropped" in r.stdout


def test_load_chrome_streaming_array(tmp_path):
    """A streaming timeline is a NEVER-CLOSED JSON array (crash-safe
    appends); the loader must read it anyway."""
    p = tmp_path / "watch.json"
    p.write_text('[\n'
                 '{"name":"watch.smoke","ph":"X","ts":0,"dur":5,'
                 '"pid":1,"tid":1,"args":{"rc":0}},\n'
                 '{"name":"watch.bench","ph":"X","ts":6,"dur":9,'
                 '"pid":1,"tid":1,"args":{"rc":0}},\n')
    evs = trace.load_chrome(str(p))
    assert [e["name"] for e in evs] == ["watch.smoke", "watch.bench"]
    rows = trace.span_summary(evs)
    assert rows[0]["name"] == "watch.bench" and rows[0]["self_us"] == 9.0
    # a TORN trailing record (writer killed mid-append) loses only
    # itself, never the finished spans before it
    p.write_text(p.read_text() + '{"name":"watch.tr')
    evs2 = trace.load_chrome(str(p))
    assert [e["name"] for e in evs2] == ["watch.smoke", "watch.bench"]


def test_thread_lane_name_updates_on_ident_reuse():
    """OS thread idents get recycled: the exported lane name must be
    the LATEST thread to use the ident, or Perfetto mislabels every
    later span on that lane (code-review finding)."""
    tr = trace.Tracer()
    th = threading.current_thread()
    old = th.name
    try:
        th.name = "first-owner"
        with tr.span("a"):
            pass
        th.name = "second-owner"
        with tr.span("b"):
            pass
    finally:
        th.name = old
    lanes = [e for e in tr.export()["traceEvents"]
             if e.get("ph") == "M" and e["name"] == "thread_name"]
    assert [l["args"]["name"] for l in lanes] == ["second-owner"]


# ---------------------------------------------------------------------------
# registry wiring: spans + ring from the step context
# ---------------------------------------------------------------------------

def test_registry_step_feeds_tracer_and_ring():
    tr = trace.Tracer()
    trace.set_tracer(tr)
    reg = Registry(sink=MemorySink(), flush_interval=2, rank0_only=False)
    f = jax.jit(lambda x: x + 1)
    for i in range(4):
        with reg.step():
            y = f(jnp.ones((2,)))
            reg.gauge("loss").set(y.sum())
        reg.event("custom", code=i)
    reg.flush()
    spans = [e for e in tr.export()["traceEvents"]
             if e.get("ph") == "X" and e["name"] == "train.step"]
    assert len(spans) == 4
    assert spans[0]["args"]["step"] == 1
    kinds = [e["kind"] for e in tr.recorder.snapshot()]
    assert "event" in kinds and "metric_flush" in kinds and "span" in kinds


# ---------------------------------------------------------------------------
# the sentinel
# ---------------------------------------------------------------------------

def test_sentinel_fires_on_spike_not_on_steady_noise(tmp_path):
    tr = trace.Tracer(flight_dir=str(tmp_path))
    rng = np.random.RandomState(0)
    s = trace.SlowStepSentinel(window=32, warmup=16, z_threshold=4.0,
                               cooldown=10)
    # steady noise: 10ms +- 0.5ms never fires
    for i in range(200):
        assert s.observe(i, 1e-2 + 5e-4 * rng.randn()) is None
    assert s.fires == 0
    # a 3x spike fires, dumps, and does NOT poison the baseline
    info = s.observe(200, 3e-2, tracer=tr)
    assert info is not None and info["z"] > 4.0
    assert info["step"] == 200
    assert s.fires == 1
    assert info["dump"] and os.path.exists(info["dump"])
    doc = json.load(open(info["dump"]))
    assert trace.dump_violations(doc) == []
    assert doc["reason"] == "slow_step"
    assert doc["fields"]["step_seconds"] == pytest.approx(3e-2)
    # baseline unchanged: the next normal step is quiet
    assert s.observe(201, 1e-2) is None


def test_sentinel_max_fires_adopts_new_regime(tmp_path):
    """A permanent legitimate slowdown stops dumping once the fire
    budget is spent: the sentinel adopts the new baseline instead of
    writing one flight dump per cooldown forever (code-review
    finding)."""
    tr = trace.Tracer(flight_dir=str(tmp_path))
    s = trace.SlowStepSentinel(window=16, warmup=8, z_threshold=4.0,
                               cooldown=2, max_fires=2)
    for i in range(12):
        s.observe(i, 1e-2)
    fires = 0
    for i in range(12, 60):                    # permanent 3x regime
        if s.observe(i, 3e-2, tracer=tr) is not None:
            fires += 1
    assert fires == 2 and s.fires == 2         # bounded, not one per cooldown
    assert len(glob.glob(str(tmp_path / "flight-slow_step-*.json"))) == 2
    # the baseline adopted the regime: window now holds 3e-2 samples
    assert max(s.window) == pytest.approx(3e-2)


def test_sentinel_dump_falls_back_to_profile_dir(tmp_path):
    """A sentinel on a tracer WITHOUT flight_dir still lands its dump:
    dump_dir > tracer flight_dir > profile_dir (code-review finding —
    the black box must not be silently lost)."""
    tr = trace.Tracer()                       # no flight_dir
    s = trace.SlowStepSentinel(window=16, warmup=8, z_threshold=4.0,
                               profile_dir=str(tmp_path), max_captures=0)
    for i in range(12):
        s.observe(i, 1e-2)
    info = s.observe(12, 5e-2, tracer=tr)
    assert info["dump"] is not None
    doc = json.load(open(info["dump"]))
    assert trace.dump_violations(doc) == []
    assert os.path.dirname(info["dump"]) == str(tmp_path)
    # explicit dump_dir wins over profile_dir
    d2 = tmp_path / "dd"
    s2 = trace.SlowStepSentinel(window=16, warmup=8, z_threshold=4.0,
                                dump_dir=str(d2),
                                profile_dir=str(tmp_path), max_captures=0)
    for i in range(12):
        s2.observe(i, 1e-2)
    info2 = s2.observe(12, 5e-2, tracer=tr)
    assert os.path.dirname(info2["dump"]) == str(d2)


def test_sentinel_cooldown_and_registry_event():
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    events.set_default(reg)
    s = trace.SlowStepSentinel(window=16, warmup=8, z_threshold=4.0,
                               cooldown=5)
    for i in range(20):
        s.observe(i, 1e-2)
    assert s.observe(20, 5e-2) is not None
    # inside the cooldown a repeat spike is absorbed silently
    assert s.observe(21, 5e-2) is None
    evs = [r for r in reg.flush() if r.get("kind") == "event"]
    assert [e["name"] for e in evs] == ["sentinel.slow_step"]
    assert evs[0]["fields"]["step"] == 20


def test_sentinel_one_shot_profiler_capture(monkeypatch, tmp_path):
    """A breach opens ONE jax.profiler window for profile_steps observed
    steps; later breaches never re-open it (max_captures)."""
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop", None)))
    s = trace.SlowStepSentinel(window=16, warmup=8, z_threshold=4.0,
                               cooldown=2, profile_dir=str(tmp_path),
                               profile_steps=3, max_captures=1)
    for i in range(12):
        s.observe(i, 1e-2)
    info = s.observe(12, 5e-2)
    assert info["profile_started"] is True
    assert calls == [("start", str(tmp_path))]
    s.observe(13, 1e-2)
    s.observe(14, 1e-2)
    assert calls[-1][0] == "start"              # window still open
    s.observe(15, 1e-2)                         # 3rd observed step closes
    assert calls[-1] == ("stop", None)
    for i in range(16, 22):
        s.observe(i, 1e-2)
    info2 = s.observe(22, 8e-2)                 # fires again, no capture
    assert info2 is not None and info2["profile_started"] is False
    assert sum(1 for c in calls if c[0] == "start") == 1


def test_sentinel_sustained_regression_refires_after_cooldown():
    """A persistent 3x regression must not normalize itself during its
    own cooldown: breaching samples stay out of the baseline, so the
    sentinel fires AGAIN once the cooldown expires (code-review
    finding)."""
    s = trace.SlowStepSentinel(window=32, warmup=8, z_threshold=4.0,
                               cooldown=10)
    for i in range(40):
        s.observe(i, 1e-2)
    assert s.observe(40, 3e-2) is not None      # regression begins
    for i in range(41, 51):                     # cooldown: still 3x slow
        assert s.observe(i, 3e-2) is None       # suppressed, not absorbed
    info = s.observe(51, 3e-2)                  # cooldown over: refires
    assert info is not None
    assert info["baseline_mean_s"] == pytest.approx(1e-2, rel=0.1)
    assert s.fires == 2


def test_ring_event_device_array_becomes_tag_not_repr():
    """A device-array event field in the flight ring is stored as a
    shape/dtype TAG — repr() would materialize it (a blocking host
    sync, the exact thing the subsystem must not add)."""
    tr = trace.Tracer()
    trace.set_tracer(tr)
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    loss = jnp.ones((3,), jnp.float32).sum()             # device scalar
    reg.event("e", loss=loss, tag="ok")
    entry = [e for e in tr.recorder.snapshot() if e["kind"] == "event"][0]
    assert entry["fields"]["tag"] == "ok"
    assert entry["fields"]["loss"].startswith("<")       # tag, not value
    assert "float32" in entry["fields"]["loss"]
    assert "3." not in entry["fields"]["loss"]           # unmaterialized
    # the flushed JSONL still resolves the value (the batched read)
    rec = [r for r in reg.flush() if r.get("kind") == "event"][0]
    assert rec["fields"]["loss"] == pytest.approx(3.0)


def test_sentinel_stop_capture_closes_open_window(monkeypatch, tmp_path):
    """A run ending INSIDE the profile window must still flush the
    capture: stop_capture() (the atexit backstop) closes it, and is
    idempotent."""
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    s = trace.SlowStepSentinel(window=16, warmup=8, z_threshold=4.0,
                               profile_dir=str(tmp_path), profile_steps=50)
    for i in range(12):
        s.observe(i, 1e-2)
    assert s.observe(12, 5e-2)["profile_started"] is True
    # the run "ends" here, far inside the 50-step window
    s.stop_capture()
    assert calls == ["start", "stop"]
    s.stop_capture()                          # idempotent
    assert calls == ["start", "stop"]
    import atexit
    atexit.unregister(s.stop_capture)         # don't leak into teardown


def test_sentinel_registry_integration_via_note_step():
    """A registry step() that suddenly takes 4x longer trips the
    sentinel attached to the default tracer — and the fire event lands
    in the STEPPING registry (not just the process default), so a run
    on a pinned registry still records it (code-review finding)."""
    s = trace.SlowStepSentinel(window=16, warmup=8, z_threshold=4.0,
                               cooldown=100)
    tr = trace.Tracer(sentinel=s)
    trace.set_tracer(tr)
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    assert events.get_default() is None           # pinned, not default
    for i, dt in enumerate([1e-2] * 12 + [8e-2]):
        trace.note_step(i, dt, registry=reg)
    assert s.fires == 1
    evs = [r for r in reg.flush() if r.get("kind") == "event"]
    assert [e["name"] for e in evs] == ["sentinel.slow_step"]


def test_registry_metric_creation_thread_safe_under_flush():
    """The guard's background writer mints gauges while the main
    thread flushes: metric creation must not tear the flush loop
    ('dictionary changed size during iteration') and no update may be
    lost to a double-created metric (code-review finding)."""
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    stop = threading.Event()
    errs = []

    def minter():
        i = 0
        try:
            while not stop.is_set() and i < 3000:
                reg.gauge(f"g{i % 400}").set(float(i))
                i += 1
        except BaseException as e:   # surfaced below
            errs.append(e)

    th = threading.Thread(target=minter)
    th.start()
    try:
        for _ in range(200):
            reg.flush()
    finally:
        stop.set()
        th.join()
    assert errs == []
    reg.flush()
    assert len([k for k in reg.read() if k.startswith("g")]) == 400
