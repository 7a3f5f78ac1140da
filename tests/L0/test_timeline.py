"""apex_tpu.telemetry.timeline — device-timeline observability
(ISSUE 13).

The acceptance gates:

  * on a synthetic two-lane device trace with known overlap, the
    decomposition recovers exposed-comm ms EXACTLY (interval-
    subtraction oracle: fully-hidden, fully-exposed, and
    partial-overlap collectives);
  * a straggling device z-scores away from the mesh and lands a
    ``timeline.straggler`` event; a uniform mesh stays quiet;
  * ``step.device_compute_ms`` / ``step.exposed_comm_ms`` /
    ``step.device_idle_ms`` gauges ride the Registry's batched flush
    as schema-valid records;
  * ``python -m apex_tpu.telemetry timeline <profiler-dir>`` renders
    the decomposition from a jax-profiler run-dir fixture;
  * a measured ``exposed_comm_fraction`` handed to
    ``parallel.plan.predict`` as its overlap factor changes the
    predicted exposed-comm time;
  * a closing SlowStepSentinel capture window feeds the profiler dir
    through the decomposition and attaches the per-step table to a
    flight-dump ``sections`` block.
"""
import gzip
import json
import os
import subprocess
import sys

import pytest

from apex_tpu.telemetry import (MemorySink, Registry, records_violations,
                                timeline, trace)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def dev(name, ts, dur, device=0, args=None):
    """One parsed device event (the pyprof.parse shape)."""
    return {"name": name, "ts": float(ts), "dur": float(dur),
            "pid": device + 10, "tid": 1,
            "process": f"/device:TPU:{device}", "thread": "XLA Op",
            "args": args or {}}


def host(name, ts, dur, step=None):
    args = {} if step is None else {"step": step}
    return {"name": name, "ts": float(ts), "dur": float(dur),
            "pid": 1, "tid": 1, "process": "apex_tpu",
            "thread": "MainThread", "args": args}


# ---------------------------------------------------------------------------
# interval arithmetic oracle
# ---------------------------------------------------------------------------

def test_interval_merge_and_subtract_oracle():
    m = timeline._merge([(10, 20), (15, 30), (40, 50), (50, 60), (5, 6)])
    assert m == [(5, 6), (10, 30), (40, 60)]
    # subtraction: exact complements, adjacent bounds excluded
    assert timeline._subtract([(0, 100)], [(20, 30), (50, 60)]) == \
        [(0, 20), (30, 50), (60, 100)]
    assert timeline._subtract([(10, 20)], [(0, 100)]) == []
    assert timeline._subtract([(10, 20)], []) == [(10, 20)]
    assert timeline._subtract([(10, 20), (30, 40)], [(15, 35)]) == \
        [(10, 15), (35, 40)]


def test_event_op_class_bins_and_async_pairs():
    assert timeline.event_op_class("all-reduce.7") == "collective"
    assert timeline.event_op_class("all-reduce-start.7") == "collective"
    assert timeline.event_op_class("reduce-scatter-done.2") == "collective"
    assert timeline.event_op_class("dot.3") == "blas"
    assert timeline.event_op_class("fusion.12") == "pointwise"
    assert timeline.event_op_class("copy.1") == "memory"
    # non-HLO spans (python frames, runtime noise) classify as None
    assert timeline.event_op_class("$main.py:12 train") is None
    assert timeline.event_op_class("Thread 7") is None


def test_decompose_exposed_comm_oracle():
    """THE acceptance oracle: known overlap recovers exactly.

    device 0: compute [0,100), collective [50,150)  -> exposed 50us
    device 1: compute [0,100), collective [20, 60)  -> fully hidden, 0
    device 2: no compute,      collective [200,260) -> fully exposed 60
    """
    evs = [
        dev("fusion.1", 0, 100, device=0),
        dev("all-reduce.2", 50, 100, device=0),
        dev("fusion.1", 0, 100, device=1),
        dev("all-reduce.2", 20, 40, device=1),
        dev("all-reduce-start.9", 200, 60, device=2),
    ]
    d = timeline.decompose(evs)
    assert d["devices"] == ["/device:TPU:0", "/device:TPU:1",
                            "/device:TPU:2"]
    assert d["n_steps"] == 1                   # one-shot capture window
    rows = d["steps"][0]["devices"]
    assert rows["/device:TPU:0"]["exposed_comm_ms"] == pytest.approx(0.050)
    assert rows["/device:TPU:0"]["comm_ms"] == pytest.approx(0.100)
    assert rows["/device:TPU:0"]["compute_ms"] == pytest.approx(0.100)
    assert rows["/device:TPU:0"]["busy_ms"] == pytest.approx(0.150)
    assert rows["/device:TPU:1"]["exposed_comm_ms"] == 0.0     # hidden
    assert rows["/device:TPU:2"]["exposed_comm_ms"] == \
        pytest.approx(0.060)                                    # exposed
    t = d["totals"]
    assert t["exposed_comm_ms"] == pytest.approx(0.110)
    assert t["comm_ms"] == pytest.approx(0.200)
    assert t["exposed_comm_fraction"] == pytest.approx(0.55)
    # idle = window minus busy, never negative
    window_ms = d["steps"][0]["dur_ms"]
    for r in rows.values():
        assert r["idle_ms"] == pytest.approx(window_ms - r["busy_ms"])


def test_decompose_split_collective_pieces_sum_exactly():
    """A collective split across multiple device events (async chunks)
    still subtracts exactly — interval math, not per-event guesses."""
    evs = [
        dev("fusion.1", 0, 80),
        dev("all-reduce.1", 40, 30),       # [40,70): hidden
        dev("all-reduce.2", 70, 30),       # [70,100): 10 hidden, 20 exposed
    ]
    d = timeline.decompose(evs)
    r = d["steps"][0]["devices"]["/device:TPU:0"]
    assert r["exposed_comm_ms"] == pytest.approx(0.020)
    assert r["comm_ms"] == pytest.approx(0.060)


def test_comm_free_capture_has_null_fraction():
    d = timeline.decompose([dev("fusion.1", 0, 100)])
    assert d["totals"]["comm_ms"] == 0.0
    assert d["totals"]["exposed_comm_fraction"] is None


def test_step_windows_from_host_train_step_spans():
    """Host ``train.step`` spans (a merged timeline) delimit the
    windows; device activity decomposes per step."""
    evs = [
        host("train.step", 0, 100, step=1),
        host("train.step", 100, 100, step=2),
        dev("fusion.1", 10, 50),               # step 1 compute
        dev("all-reduce.1", 120, 40),          # step 2, fully exposed
    ]
    d = timeline.decompose(evs)
    assert [s["step"] for s in d["steps"]] == [1, 2]
    s1, s2 = d["steps"]
    assert s1["devices"]["/device:TPU:0"]["compute_ms"] == \
        pytest.approx(0.050)
    assert s1["devices"]["/device:TPU:0"]["comm_ms"] == 0.0
    assert s2["devices"]["/device:TPU:0"]["exposed_comm_ms"] == \
        pytest.approx(0.040)


def test_cpu_capture_fallback_sniffs_hlo_lanes():
    """A capture whose exporter did not name device processes (CPU
    backend) still decomposes: lanes that are mostly HLO-shaped names
    are treated as device lanes; python threads are not."""
    evs = [
        {"name": "fusion.1", "ts": 0.0, "dur": 100.0, "pid": 1, "tid": 2,
         "process": "/host:CPU", "thread": "XLA Op", "args": {}},
        {"name": "all-reduce.3", "ts": 50.0, "dur": 100.0, "pid": 1,
         "tid": 2, "process": "/host:CPU", "thread": "XLA Op", "args": {}},
        {"name": "$main.py:1 step", "ts": 0.0, "dur": 500.0, "pid": 1,
         "tid": 9, "process": "/host:CPU", "thread": "python", "args": {}},
    ]
    d = timeline.decompose(evs)
    assert len(d["devices"]) == 1
    assert d["totals"]["exposed_comm_ms"] == pytest.approx(0.050)


# ---------------------------------------------------------------------------
# stragglers
# ---------------------------------------------------------------------------

def _mesh_step_events(busy_us_per_dev, step_ts=0.0):
    evs = []
    for i, busy in enumerate(busy_us_per_dev):
        evs.append(dev("fusion.1", step_ts, busy, device=i))
    return evs


def test_straggler_flagged_and_uniform_mesh_quiet():
    # uniform mesh: nothing flags
    d = timeline.decompose(_mesh_step_events([100, 101, 99, 100]))
    assert d["stragglers"] == []
    # one device 2x slower: flagged with a leave-one-out z
    d2 = timeline.decompose(_mesh_step_events([100, 100, 100, 200]))
    assert len(d2["stragglers"]) == 1
    row = d2["stragglers"][0]
    assert row["device"] == "/device:TPU:3"
    assert row["z"] >= timeline.STRAGGLER_Z
    assert row["busy_ms"] == pytest.approx(0.200)
    assert d2["per_device"]["/device:TPU:3"]["straggler_score"] == row["z"]
    assert d2["per_device"]["/device:TPU:3"]["straggler_steps"] == [0]
    # skew is max-min busy
    assert d2["steps"][0]["skew_ms"] == pytest.approx(0.100)


def test_straggler_min_slowdown_gate():
    """A statistically-significant but tiny delta must not flag — the
    sentinel's two-gate posture (z AND min_slowdown)."""
    d = timeline.decompose(_mesh_step_events([100, 100, 100, 110]))
    assert d["stragglers"] == []               # 1.1x < 1.2x floor


def test_observe_exports_gauges_and_straggler_events():
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    evs = [
        dev("fusion.1", 0, 100, device=0),
        dev("all-reduce.2", 50, 100, device=0),
        dev("fusion.1", 0, 300, device=1),     # straggler vs device 0
        dev("fusion.1", 0, 100, device=2),
        dev("fusion.1", 0, 100, device=3),
    ]
    d = timeline.decompose(evs)
    timeline.observe(d, reg)
    records = reg.flush()
    assert records_violations(records) == []    # schema-valid through
    gauges = {r["name"]: r["value"] for r in records
              if r.get("kind") == "metric" and r.get("type") == "gauge"}
    n = sum(x["steps"] for x in d["per_device"].values())
    assert gauges["step.device_compute_ms"] == \
        pytest.approx(d["totals"]["compute_ms"] / n)
    assert gauges["step.exposed_comm_ms"] == \
        pytest.approx(d["totals"]["exposed_comm_ms"] / n)
    assert gauges["step.device_idle_ms"] == \
        pytest.approx(d["totals"]["idle_ms"] / n)
    assert gauges["step.exposed_comm_fraction"] == \
        pytest.approx(d["totals"]["exposed_comm_fraction"])
    events = [r for r in records if r.get("kind") == "event"
              and r["name"] == "timeline.straggler"]
    assert len(events) == 1
    assert events[0]["fields"]["device"] == "/device:TPU:1"


def test_observe_disabled_registry_is_noop():
    reg = Registry(sink=MemorySink(), enabled=False)
    timeline.observe(timeline.decompose([dev("fusion.1", 0, 10)]), reg)
    timeline.observe(timeline.decompose([dev("fusion.1", 0, 10)]), None)
    assert reg.flush() == []


# ---------------------------------------------------------------------------
# merged host + device timeline (shared epoch anchor)
# ---------------------------------------------------------------------------

def test_merge_host_device_shared_anchor_and_windows():
    tr = trace.Tracer()
    with tr.span("train.step", step=1):
        pass
    doc = tr.export()
    dev_evs = [dev("fusion.1", 5000, 100), dev("all-reduce.1", 5100, 50)]
    merged = timeline.merge_host_device(doc, dev_evs)
    evs = merged["traceEvents"]
    names = {e.get("name") for e in evs if e.get("ph") == "X"}
    assert {"train.step", "fusion.1", "all-reduce.1"} <= names
    # the host span was rebased onto the device epoch (anchor: earliest
    # host event aligns with earliest device event)
    hostspan = next(e for e in evs if e.get("name") == "train.step")
    assert hostspan["ts"] == pytest.approx(5000.0)
    # device lanes keep their pids; the host got a fresh one
    devspan = next(e for e in evs if e.get("name") == "fusion.1")
    assert hostspan["pid"] != devspan["pid"]
    procs = {e["args"]["name"] for e in evs
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    assert "host:apex_tpu" in procs and "/device:TPU:0" in procs
    # and the merged doc round-trips the parser: host step windows now
    # segment the device activity
    from apex_tpu.pyprof import parse
    d = timeline.decompose(parse.events_from_chrome(evs))
    assert d["n_steps"] >= 1 and d["devices"] == ["/device:TPU:0"]


# ---------------------------------------------------------------------------
# profiler-dir fixture + CLI
# ---------------------------------------------------------------------------

def _write_profiler_dir(root, trace_events):
    """A jax-profiler run-dir fixture: the TensorBoard layout
    ``<dir>/plugins/profile/<run>/<host>.trace.json.gz``."""
    d = os.path.join(str(root), "plugins", "profile", "run_1")
    os.makedirs(d, exist_ok=True)
    path = os.path.join(d, "host.trace.json.gz")
    with gzip.open(path, "wt") as f:
        json.dump({"displayTimeUnit": "ms", "traceEvents": trace_events},
                  f)
    return path


def _chrome(name, ts, dur, pid, tid=1):
    return {"ph": "X", "name": name, "ts": ts, "dur": dur, "pid": pid,
            "tid": tid, "args": {}}


def _fixture_trace_events():
    return [
        {"ph": "M", "name": "process_name", "pid": 10,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "name": "process_name", "pid": 11,
         "args": {"name": "/device:TPU:1"}},
        {"ph": "M", "name": "thread_name", "pid": 10, "tid": 1,
         "args": {"name": "XLA Op"}},
        _chrome("fusion.1", 0, 100, 10),
        _chrome("all-reduce.2", 50, 100, 10),    # 50us exposed
        _chrome("fusion.1", 0, 100, 11),
        _chrome("all-reduce.2", 20, 40, 11),     # hidden
    ]


def test_summarize_profiler_dir_fixture(tmp_path):
    _write_profiler_dir(tmp_path, _fixture_trace_events())
    d = timeline.summarize(str(tmp_path))
    assert d["devices"] == ["/device:TPU:0", "/device:TPU:1"]
    assert d["totals"]["exposed_comm_ms"] == pytest.approx(0.050)
    assert d["totals"]["exposed_comm_fraction"] == \
        pytest.approx(0.050 / 0.140)


def test_cli_timeline_renders_table_and_json(tmp_path):
    """``python -m apex_tpu.telemetry timeline <profiler-dir>``: the
    per-step decomposition table + per-device skew section; ``--json``
    emits the machine form."""
    _write_profiler_dir(tmp_path, _fixture_trace_events())
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT}
    r = subprocess.run(
        [sys.executable, "-m", "apex_tpu.telemetry", "timeline",
         str(tmp_path)],
        capture_output=True, text=True, cwd=ROOT, timeout=180, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "device timeline decomposition" in r.stdout
    assert "exposed" in r.stdout and "per-device skew" in r.stdout
    assert "/device:TPU:0" in r.stdout
    rj = subprocess.run(
        [sys.executable, "-m", "apex_tpu.telemetry", "timeline",
         str(tmp_path), "--json"],
        capture_output=True, text=True, cwd=ROOT, timeout=180, env=env)
    assert rj.returncode == 0, rj.stderr[-2000:]
    doc = json.loads(rj.stdout)
    assert doc["kind"] == "device_timeline"
    assert doc["totals"]["exposed_comm_ms"] == pytest.approx(0.050)


def test_cli_timeline_no_device_lanes_rc1(tmp_path):
    p = tmp_path / "hostonly.json"
    p.write_text(json.dumps({"traceEvents": [
        {"ph": "X", "name": "$frame", "ts": 0, "dur": 10, "pid": 1,
         "tid": 1, "args": {}}]}))
    r = subprocess.run(
        [sys.executable, "-m", "apex_tpu.telemetry", "timeline", str(p)],
        capture_output=True, text=True, cwd=ROOT, timeout=180,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT})
    assert r.returncode == 1
    assert "no device lanes" in r.stdout


# ---------------------------------------------------------------------------
# the overlap factor: measured fraction -> plan.predict
# ---------------------------------------------------------------------------


def test_overlap_fraction_reaches_plan_predict(monkeypatch):
    """A measured exposed-comm fraction handed to the planner (env pin
    or argument) makes plan.predict charge only the exposed dp comm,
    changing the predicted step time by exactly the hidden part."""
    from apex_tpu.parallel import plan as planmod
    monkeypatch.delenv(planmod.ENV_OVERLAP, raising=False)
    prof_model = planmod.ModelProfile(
        name="oracle", flops=1e12, bytes_accessed=1e11,
        params_bytes=400 << 20, optimizer_bytes=800 << 20,
        activations_bytes=1 << 30, batch_bytes=64 << 20,
        temps_bytes=1 << 28, output_bytes=4096)
    p_full = planmod.predict(prof_model, planmod.Plan(dp=8),
                             platform="tpu_v5e")
    assert p_full.breakdown["overlap_fraction"] == 1.0
    assert p_full.breakdown["dp_comm_exposed_ms"] == \
        pytest.approx(p_full.breakdown["dp_comm_ms"])

    monkeypatch.setenv(planmod.ENV_OVERLAP, "0.25")
    p_pinned = planmod.predict(prof_model, planmod.Plan(dp=8),
                               platform="tpu_v5e")
    assert p_pinned.breakdown["overlap_fraction"] == 0.25
    assert p_pinned.breakdown["dp_comm_exposed_ms"] == \
        pytest.approx(0.25 * p_pinned.breakdown["dp_comm_ms"])
    # the overlap factor changes the predicted step time by exactly the
    # hidden comm
    hidden = p_full.breakdown["dp_comm_ms"] * 0.75
    assert p_full.predicted_step_ms - p_pinned.predicted_step_ms == \
        pytest.approx(hidden, rel=1e-6)
    # explicit argument beats the env pin
    p_exp = planmod.predict(prof_model, planmod.Plan(dp=8),
                            platform="tpu_v5e", overlap_fraction=0.5)
    assert p_exp.breakdown["overlap_fraction"] == 0.5


def test_overlap_env_pin_beats_builtin(monkeypatch):
    from apex_tpu.parallel import plan as planmod
    monkeypatch.delenv(planmod.ENV_OVERLAP, raising=False)
    assert planmod.resolve_overlap_fraction() == 1.0
    monkeypatch.setenv(planmod.ENV_OVERLAP, "0.7")
    assert planmod.resolve_overlap_fraction() == 0.7
    assert planmod.resolve_overlap_fraction(0.1) == 0.1   # arg wins
    # clamped to [0, 1]
    assert planmod.resolve_overlap_fraction(7.0) == 1.0


# ---------------------------------------------------------------------------
# sentinel: capture-close feeds the decomposition into a flight dump
# ---------------------------------------------------------------------------

def test_sentinel_capture_close_attaches_timeline_dump(monkeypatch,
                                                       tmp_path):
    """When the one-shot profiler window closes, the sentinel feeds the
    capture through the timeline decomposition and dumps the per-step
    table as a ``slow_step_timeline`` flight document — the slow-step
    dump says WHEN, this one says WHERE the device time went."""
    import jax
    prof_dir = tmp_path / "anomaly"
    prof_dir.mkdir()
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    # the fake stop writes what a real flush would: a run-dir capture
    monkeypatch.setattr(
        jax.profiler, "stop_trace",
        lambda: _write_profiler_dir(prof_dir, _fixture_trace_events()))
    tr = trace.Tracer(flight_dir=str(tmp_path / "flight"))
    s = trace.SlowStepSentinel(window=16, warmup=8, z_threshold=4.0,
                               profile_dir=str(prof_dir),
                               profile_steps=2)
    for i in range(12):
        s.observe(i, 1e-2, tracer=tr)
    info = s.observe(12, 5e-2, tracer=tr)
    assert info["profile_started"] is True
    s.observe(13, 1e-2, tracer=tr)
    s.observe(14, 1e-2, tracer=tr)                # window closes here
    import atexit
    atexit.unregister(s.stop_capture)
    import glob
    dumps = glob.glob(str(tmp_path / "flight" /
                          "flight-slow_step_timeline-*.json"))
    assert len(dumps) == 1
    doc = json.load(open(dumps[0]))
    assert trace.dump_violations(doc) == []       # core schema intact
    tl = doc["timeline"]
    assert tl["decomposition"]["totals"]["exposed_comm_ms"] == \
        pytest.approx(0.050)
    assert "device timeline decomposition" in tl["table"]
    assert doc["fields"]["n_devices"] == 2


def test_sentinel_capture_close_without_trace_is_silent(monkeypatch,
                                                        tmp_path):
    """An empty capture dir (profiler flushed nothing) must not dump a
    timeline document nor raise — best-effort all the way down."""
    import jax
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    tr = trace.Tracer(flight_dir=str(tmp_path))
    s = trace.SlowStepSentinel(window=16, warmup=8, z_threshold=4.0,
                               profile_dir=str(tmp_path / "empty"),
                               profile_steps=1)
    for i in range(12):
        s.observe(i, 1e-2, tracer=tr)
    assert s.observe(12, 5e-2, tracer=tr)["profile_started"] is True
    s.observe(13, 1e-2, tracer=tr)
    import atexit
    atexit.unregister(s.stop_capture)
    import glob
    assert glob.glob(str(tmp_path / "flight-slow_step_timeline-*")) == []
