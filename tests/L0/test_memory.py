"""apex_tpu.telemetry.memory — peak-HBM attribution, live gauges, OOM
post-mortem (ISSUE 6).

The acceptance gates:

  * the HLO liveness sweep is CPU-deterministic on a tiny jitted train
    step, and its per-class table PARTITIONS the peak exactly;
  * the disabled/unsupported memory layer is a true zero-sync/zero-alloc
    no-op (the registry's bar);
  * ``APEX_TPU_FAULTS="oom@7"`` under TrainGuard yields exactly one
    schema-valid ``flight-oom-*.json`` carrying the attribution table
    and ``bad_step=7``, and the run RE-RAISES (no rollback retry burn);
  * ``python -m apex_tpu.telemetry mem`` renders a per-class peak-HBM
    table whose total matches the liveness sweep on the flagship
    transformer step.
"""
import gc
import glob
import json
import os
import subprocess
import sys
import tracemalloc

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.resilience import GuardConfig, TrainGuard, faults
from apex_tpu.telemetry import (MemorySink, Registry, events, memory,
                                report, trace)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(autouse=True)
def _no_defaults():
    """Tracers/registries/plans/attributions must not leak."""
    prev_tr = trace.set_tracer(None)
    prev_reg = events.set_default(None)
    prev_plan = faults.install(None)
    prev_attr = memory.set_attribution(None)
    yield
    trace.set_tracer(prev_tr)
    events.set_default(prev_reg)
    faults.install(prev_plan)
    memory.set_attribution(prev_attr)


def _opt_state():
    return {"model_params": {"w": jnp.ones((64, 64))},
            "opt": {"m": jnp.zeros((64, 64)), "v": jnp.zeros((64, 64))}}


def _opt_step(state, x):
    """A tiny jitted train step with a real params/optimizer/batch
    split, so the sweep's arg classification has something to find."""
    g = jax.grad(lambda w: (jnp.tanh(x @ w) @ w).sum())(
        state["model_params"]["w"])
    m = state["opt"]["m"] * 0.9 + g
    new_w = state["model_params"]["w"] - 0.01 * m
    return ({"model_params": {"w": new_w},
             "opt": {"m": m, "v": state["opt"]["v"]}},
            (x @ state["model_params"]["w"]).sum())


# ---------------------------------------------------------------------------
# static attribution
# ---------------------------------------------------------------------------

def test_liveness_sweep_partitions_peak_on_tiny_train_step():
    state = _opt_state()
    x = jnp.ones((8, 64))
    t = memory.memory_table(_opt_step, state, x)
    assert t["peak_bytes"] > 0
    assert 0 <= t["peak_index"] < t["n_instructions"]
    # THE invariant: the per-class table partitions the sweep's peak
    assert sum(t["by_class"].values()) == t["peak_bytes"]
    assert set(t["by_class"]) <= set(memory.MEM_CLASSES)
    # the keypath metadata classified the state: weights and moments
    # land in their own classes, the batch in its
    assert t["by_class"]["params"] == 64 * 64 * 4
    assert t["by_class"]["optimizer"] == 2 * 64 * 64 * 4
    assert t["by_class"]["batch"] == 8 * 64 * 4
    # FLOPs joined from attrib.parse_hlo onto the live rows
    assert any(r["flops"] > 0 for r in t["live_at_peak"])
    # deterministic: the same compile walks to the same answer
    t2 = memory.memory_table(_opt_step, state, x)
    assert t2["peak_bytes"] == t["peak_bytes"]
    assert t2["by_class"] == t["by_class"]
    # compiled memory_analysis rides alongside on the CPU backend
    assert t["stats"] is not None and t["stats"]["argument_bytes"] > 0


_HLO_TEMPLATE = """HloModule jit_step, is_scheduled=true{alias}

ENTRY %main.9 (Arg_0.1: f32[256,256], Arg_1.2: f32[4,4]) -> f32[256,256] {{
  %Arg_0.1 = f32[256,256]{{1,0}} parameter(0), metadata={{op_name="state['model_params']['w']"}}
  %negate.3 = f32[256,256]{{1,0}} negate(f32[256,256]{{1,0}} %Arg_0.1)
  %Arg_1.2 = f32[4,4]{{1,0}} parameter(1), metadata={{op_name="x"}}
  %tanh.4 = f32[4,4]{{1,0}} tanh(f32[4,4]{{1,0}} %Arg_1.2)
  ROOT %exponential.5 = f32[256,256]{{1,0}} exponential(f32[256,256]{{1,0}} %negate.3)
}}
"""


def test_liveness_donated_args_release_buffers():
    """Donated parameters die at last use instead of living to program
    end — the sweep reads the input_output_alias header, or every
    in-place update would double-count its state.  Handcrafted HLO so
    the schedule (and therefore the difference) is deterministic."""
    plain = memory.hlo_liveness(_HLO_TEMPLATE.format(alias=""))
    donated = memory.hlo_liveness(_HLO_TEMPLATE.format(
        alias=", input_output_alias={ {}: (0, {}, may-alias) }"))
    n = 256 * 256 * 4
    # non-donated: the param is caller-owned and stays live under the
    # negate/exp chain -> param + negate + output all overlap at the end
    assert plain["peak_bytes"] >= 3 * n
    # donated: the param dies after %negate.3 consumes it
    assert donated["peak_bytes"] < plain["peak_bytes"]
    assert donated["peak_bytes"] >= 2 * n
    for t in (plain, donated):
        assert sum(t["by_class"].values()) == t["peak_bytes"]


_HLO_TUPLE_LOOP = """HloModule jit_loop, is_scheduled=true

ENTRY %main.9 (Arg_0.1: f32[256,256], Arg_1.2: f32[256,256]) -> f32[4,4] {
  %Arg_0.1 = f32[256,256]{1,0} parameter(0), metadata={op_name="a"}
  %Arg_1.2 = f32[256,256]{1,0} parameter(1), metadata={op_name="b"}
  %negate.3 = f32[256,256]{1,0} negate(f32[256,256]{1,0} %Arg_0.1)
  %negate.4 = f32[256,256]{1,0} negate(f32[256,256]{1,0} %Arg_1.2)
  %tuple.5 = (f32[256,256]{1,0}, f32[256,256]{1,0}) tuple(f32[256,256]{1,0} %negate.3, f32[256,256]{1,0} %negate.4)
  %constant.6 = f32[4,4]{1,0} constant({...})
  %tanh.7 = f32[4,4]{1,0} tanh(f32[4,4]{1,0} %constant.6)
  %custom-call.8 = f32[4,4]{1,0} custom-call(f32[4,4]{1,0} %tanh.7, (f32[256,256]{1,0}, f32[256,256]{1,0}) %tuple.5), custom_call_target="consume"
  ROOT %exponential.9 = f32[4,4]{1,0} exponential(f32[4,4]{1,0} %custom-call.8)
}
"""


def test_liveness_tuple_use_keeps_every_element_alive():
    """A consumer of a mid-graph tuple (a while loop's carry, a
    custom-call) must extend the lifetime of ALL its elements — an
    alias collapsed to element 0 would silently understate the peak
    the planner and the OOM dump consume."""
    t = memory.hlo_liveness(_HLO_TUPLE_LOOP)
    n = 256 * 256 * 4
    by_op = {r["op"]: r for r in t["live_at_peak"]}
    # the tuple consumer sits at index 7: BOTH negates must survive to
    # it (an element-0-only alias would end negate.4 at the tuple)
    assert by_op["negate.3"]["last_use"] == 7
    assert by_op["negate.4"]["last_use"] == 7
    assert t["peak_bytes"] >= 4 * n          # 2 params + 2 negates
    assert sum(t["by_class"].values()) == t["peak_bytes"]


def test_memory_model_contract_and_registration():
    state = _opt_state()
    t = memory.memory_table(_opt_step, state, jnp.ones((8, 64)))
    model = memory.memory_model(table=t)
    for key in ("peak_hbm_bytes", "params_bytes", "optimizer_bytes",
                "activations_bytes", "temps_bytes", "output_bytes",
                "by_class", "top", "peak_op"):
        assert key in model, key
    assert model["peak_hbm_bytes"] == t["peak_bytes"]
    assert model["params_bytes"] == t["by_class"]["params"]
    assert json.loads(json.dumps(model)) == model   # planner-consumable
    # register=True (the default) installs it for the OOM post-mortem
    assert memory.get_attribution() is model
    model2 = memory.memory_model(table=t, register=False)
    assert memory.get_attribution() is model       # unchanged


def test_format_memory_table_renders_classes_and_total():
    t = memory.memory_table(_opt_step, _opt_state(), jnp.ones((8, 64)))
    text = memory.format_memory_table(t, top=4)
    assert "peak-HBM attribution" in text
    for cls in ("params", "optimizer", "temps"):
        assert cls in text
    assert "liveness-sweep peak" in text
    assert "memory_analysis" in text


def test_classify_arg_paths():
    assert memory.classify_arg("state['model_params']['w']") == "params"
    assert memory.classify_arg(r"state[\'opt\'][\'m\']") == "optimizer"
    assert memory.classify_arg("state.master_params['fc']") == "optimizer"
    assert memory.classify_arg("state.scalers[0].loss_scale") == "optimizer"
    assert memory.classify_arg("tokens") == "batch"
    assert memory.classify_arg("x") == "batch"
    assert memory.classify_arg("mystery_arg") == "args"


# ---------------------------------------------------------------------------
# live gauges
# ---------------------------------------------------------------------------

class _FakeDevice:
    def __init__(self, stats):
        self._stats = stats
        self.calls = 0

    def memory_stats(self):
        self.calls += 1
        return self._stats


def test_monitor_disabled_is_zero_sync_zero_alloc():
    dev = _FakeDevice({"bytes_in_use": 1})
    mon = memory.MemoryMonitor(enabled=False, device=dev)
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False,
                   memory=False)

    def burn():
        for _ in range(1000):
            assert mon.poll() is None
            assert mon.observe_flush(reg) is None

    burn()                          # warm allocator/caches first
    gc.collect()
    tracemalloc.start()
    snap1 = tracemalloc.take_snapshot()
    burn()
    gc.collect()
    snap2 = tracemalloc.take_snapshot()
    tracemalloc.stop()
    per_call = [s for s in snap2.compare_to(snap1, "lineno")
                if s.count_diff >= 100
                and s.traceback and "tracemalloc" not in
                s.traceback[0].filename]
    assert per_call == [], [str(s) for s in per_call]
    assert dev.calls == 0           # the allocator was never touched
    assert mon.snapshot() == []


def test_monitor_unsupported_backend_probes_exactly_once():
    dev = _FakeDevice(None)         # a backend with no allocator stats
    mon = memory.MemoryMonitor(enabled=True, device=dev)
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False,
                   memory=False)
    for _ in range(50):
        assert mon.observe_flush(reg) is None
    assert dev.calls == 1           # one probe, then cached unsupported
    assert mon.supported is False


def test_registry_flush_emits_mem_gauges_and_counter_track(tmp_path):
    dev = _FakeDevice({"bytes_in_use": 1000, "peak_bytes_in_use": 2000,
                       "largest_alloc_size": 500, "bytes_limit": 4000})
    tr = trace.Tracer()
    trace.set_tracer(tr)
    sink = MemorySink()
    reg = Registry(sink=sink, flush_interval=0, rank0_only=False,
                   memory=memory.MemoryMonitor(enabled=True, device=dev))
    with reg.step():
        reg.gauge("loss").set(1.0)
    reg.flush()
    names = {r["name"]: r["value"] for r in sink.records
             if r.get("type") == "gauge"}
    assert names["mem.bytes_in_use"] == 1000.0
    assert names["mem.peak_bytes_in_use"] == 2000.0
    assert names["mem.largest_alloc_bytes"] == 500.0
    # records stay schema-valid (the sink validated on write) and the
    # summary's memory line reads them back
    s = report.summarize(sink.records)
    assert s["mem_peak_bytes"] == 2000.0
    assert s["mem_in_use_bytes"] == 1000.0
    assert "memory" in report.format_summary(s)
    # the counter track landed in the chrome export (ph "C") AND the
    # flight ring (the OOM dump shows the curve), schema-valid
    counters = [e for e in tr.export()["traceEvents"]
                if e.get("ph") == "C"]
    assert counters and counters[0]["name"] == "device_mem"
    assert counters[0]["args"]["bytes_in_use"] == 1000.0
    ring = [e for e in tr.recorder.snapshot() if e["kind"] == "counter"]
    assert ring and ring[0]["values"]["peak_bytes_in_use"] == 2000.0
    path = tr.recorder.dump("check", directory=str(tmp_path))
    assert trace.dump_violations(json.load(open(path))) == []
    # the monitor's history feeds the post-mortem
    mon = reg._memory
    assert mon.snapshot()[-1]["bytes_in_use"] == 1000.0


def test_registry_disabled_never_builds_a_monitor(monkeypatch):
    reg = Registry(sink=MemorySink(), enabled=False)
    assert reg._memory is None
    monkeypatch.setenv("APEX_TPU_TELEMETRY_MEM", "0")
    reg2 = Registry(sink=MemorySink(), rank0_only=False)
    assert reg2._memory is None     # env-disabled default monitor


# ---------------------------------------------------------------------------
# OOM post-mortem
# ---------------------------------------------------------------------------

def test_parse_allocator_report_real_shape():
    text = (
        "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
        "memory in memory space hbm. Used 18.50G of 15.48G hbm.\n"
        "Out of memory while trying to allocate 4294967296 bytes.\n"
        "Largest program allocations in hbm:\n"
        "  1. Size: 4.00G\n"
        "     Operator: op_name=\"jit(train_step)/jit(main)/dot_general\""
        " source_file=\"train.py\"\n"
        "     Shape: bf16[8,512,64,24]{3,2,1,0:T(8,128)(2,1)}\n"
        "     Unpadded size: 4.00G\n"
        "     Allocation type: HLO temp\n"
        "  2. Size: 512.00M\n"
        "     Operator: op_name=\"jit(train_step)/transpose\"\n"
        "     Shape: f32[128,1024,1024]{2,1,0}\n"
        "     Allocation type: HLO temp\n")
    rep = memory.parse_allocator_report(text)
    assert rep["requested_bytes"] == 4294967296
    assert len(rep["allocations"]) == 2
    a0 = rep["allocations"][0]
    assert a0["size_bytes"] == 4 * 10 ** 9
    assert "dot_general" in a0["operator"]
    assert a0["shape"].startswith("bf16[8,512,64,24]")
    assert a0["alloc_type"] == "HLO temp"
    assert rep["allocations"][1]["size_bytes"] == 512 * 10 ** 6
    # garbage degrades to an empty report, never a crash
    assert memory.parse_allocator_report("no report here") == {
        "requested_bytes": None, "allocations": []}


def test_is_oom_error_recognizes_injected_and_real():
    assert memory.is_oom_error(memory.synthetic_oom(7))
    assert memory.is_oom_error(RuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory while trying to allocate ..."))
    assert memory.is_oom_error(RuntimeError("Ran out of memory: "
                                            "out of memory in hbm"))
    assert not memory.is_oom_error(RuntimeError("NaN loss"))
    assert not memory.is_oom_error(ValueError("bad shape"))


def test_chaos_oom_at_7_dumps_post_mortem_and_reraises(monkeypatch,
                                                      tmp_path):
    """THE acceptance gate: APEX_TPU_FAULTS="oom@7" under TrainGuard
    yields exactly one schema-valid flight-oom-*.json containing the
    attribution table and bad_step=7, and the run re-raises without
    burning a rollback retry."""
    monkeypatch.setenv("APEX_TPU_FAULTS", "oom@7")
    tr = trace.Tracer()
    trace.set_tracer(tr)
    sink = MemorySink()
    reg = Registry(sink=sink, flush_interval=0, rank0_only=False)
    # the registered static attribution (what a run computes up front)
    model = memory.memory_model(_opt_step, _opt_state(), jnp.ones((8, 64)))

    @jax.jit
    def step(w, batch):
        return w - 0.1 * batch, jnp.sum(w)

    g = TrainGuard(step, GuardConfig(ckpt_dir=str(tmp_path),
                                     save_every_steps=5, check_every=2,
                                     enabled=True),
                   registry=reg)
    with pytest.raises(memory.InjectedOomError):
        g.run(jnp.zeros(4),
              lambda i: jnp.asarray(np.random.RandomState(i)
                                    .randn(4).astype(np.float32)), 20)

    dumps = glob.glob(str(tmp_path / "flight-oom-*.json"))
    assert len(dumps) == 1                       # exactly one
    doc = json.load(open(dumps[0]))
    assert memory.oom_violations(doc) == []      # schema-valid
    assert doc["reason"] == "oom"
    assert doc["fields"]["bad_step"] == 7
    sec = doc["oom"]
    assert sec["bad_step"] == 7
    assert sec["error_type"] == "InjectedOomError"
    # the attribution table rode along
    assert sec["attribution"]["peak_hbm_bytes"] == model["peak_hbm_bytes"]
    assert sec["attribution"]["by_class"] == model["by_class"]
    # the synthetic allocator report parsed into structured allocations
    assert sec["requested_bytes"] == 2 ** 31
    assert sec["allocations"] and \
        sec["allocations"][0]["operator"] == "injected/oom/fault"
    # the ring names the injected fault at its step
    injected = [e for e in doc["entries"]
                if e["kind"] == "event" and e["name"] == "fault_injected"]
    assert [e["fields"]["step"] for e in injected] == [7]
    # no rollback retry burn: the guard re-raised instead of restoring
    reg.flush()
    evs = [r["name"] for r in sink.records if r.get("kind") == "event"]
    assert "rollback" not in evs
    assert "memory.oom" in evs
    s = report.summarize(sink.records)
    assert s["oom_events"] == 1 and s["rollbacks"] == 0
    assert "oom events 1" in report.format_summary(s)
    # no generic exception dump shadowing the post-mortem
    assert glob.glob(str(tmp_path / "flight-exception-*.json")) == []


def test_dump_oom_without_tracer_still_lands(tmp_path):
    """A crash artifact must not depend on tracing being on: the guard
    falls back to a fresh empty ring next to the checkpoints."""
    @jax.jit
    def step(w, batch):
        return w + batch, jnp.sum(w)

    g = TrainGuard(step, GuardConfig(ckpt_dir=str(tmp_path),
                                     check_every=4, enabled=True),
                   plan=faults.parse("oom@3"))
    with pytest.raises(memory.InjectedOomError):
        g.run(jnp.zeros(4), lambda i: jnp.ones(4), 10)
    dumps = glob.glob(str(tmp_path / "flight-oom-*.json"))
    assert len(dumps) == 1
    doc = json.load(open(dumps[0]))
    assert memory.oom_violations(doc) == []
    assert doc["oom"]["bad_step"] == 3
    assert doc["n_entries"] == 0                 # untraced: empty ring


def test_real_resource_exhausted_text_takes_oom_path(tmp_path):
    """A step fn raising a REAL-shaped RESOURCE_EXHAUSTED (not the
    injected kind) still gets the post-mortem, not the generic dump."""
    msg = ("RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
           "1073741824 bytes.\n  1. Size: 1.00G\n"
           "     Operator: op_name=\"jit(step)/big_dot\"\n")

    def step(w, batch):
        raise RuntimeError(msg)

    g = TrainGuard(step, GuardConfig(ckpt_dir=str(tmp_path),
                                     check_every=4, enabled=True))
    with pytest.raises(RuntimeError):
        g.run(jnp.zeros(4), lambda i: jnp.ones(4), 10)
    dumps = glob.glob(str(tmp_path / "flight-oom-*.json"))
    assert len(dumps) == 1
    doc = json.load(open(dumps[0]))
    assert doc["oom"]["requested_bytes"] == 1073741824
    assert doc["oom"]["allocations"][0]["operator"] == "jit(step)/big_dot"
    assert glob.glob(str(tmp_path / "flight-exception-*.json")) == []


def test_faults_grammar_accepts_oom():
    plan = faults.parse("oom@7;nan@3")
    assert [s.kind for s in plan.specs] == ["oom", "nan"]
    assert plan.fire("oom", 6) is None
    assert plan.fire("oom", 7).kind == "oom"
    assert plan.fire("oom", 8) is None           # one-shot consumed


# ---------------------------------------------------------------------------
# the CLI (the acceptance's rendering gate)
# ---------------------------------------------------------------------------

def test_cli_mem_table_total_matches_liveness_sweep():
    """`python -m apex_tpu.telemetry mem` renders a per-class peak-HBM
    table whose total matches the liveness sweep on the flagship
    transformer step."""
    from apex_tpu.telemetry.report import demo_step_fn
    cfg = dict(layers=1, batch=2, seq=16)
    train_step, state, make_batch = demo_step_fn(**cfg)
    tokens, targets = make_batch(0)
    t = memory.memory_table(train_step, state, tokens, targets,
                            jnp.asarray(1.0, jnp.float32))
    assert sum(t["by_class"].values()) == t["peak_bytes"]
    # the flagship's O5 state classifies: bf16 model params, fp32
    # masters+moments as optimizer state, the token batch
    assert t["by_class"]["params"] > 0
    assert t["by_class"]["optimizer"] > t["by_class"]["params"]
    assert t["by_class"]["batch"] > 0

    r = subprocess.run(
        [sys.executable, "-m", "apex_tpu.telemetry", "mem",
         "--layers", "1", "--batch", "2", "--seq", "16"],
        capture_output=True, text=True, cwd=ROOT, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT})
    assert r.returncode == 0, r.stderr[-2000:]
    assert "peak-HBM attribution" in r.stdout
    assert "optimizer" in r.stdout and "activations" in r.stdout
    # the CLI's rendered total IS the sweep's peak for the same config
    expected = memory._human(t["peak_bytes"], "B")
    assert f"{expected} (= liveness-sweep peak)" in r.stdout
    assert "memory_model: peak" in r.stdout


def test_cli_mem_renders_oom_dump(tmp_path):
    # an OOM dump round-trips through the renderer
    memory.set_attribution({"peak_hbm_bytes": 999,
                            "by_class": {"params": 999}})
    path = memory.dump_oom(step=7, error=memory.synthetic_oom(7),
                           directory=str(tmp_path))
    r = subprocess.run(
        [sys.executable, "-m", "apex_tpu.telemetry", "mem", path],
        capture_output=True, text=True, cwd=ROOT, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT})
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OOM post-mortem" in r.stdout
    assert "bad_step=7" in r.stdout

    # anything else is a clean rc=1, not a traceback
    other = tmp_path / "other.json"
    other.write_text(json.dumps({"detail": {}}))
    assert memory.cli([str(other)]) == 1
