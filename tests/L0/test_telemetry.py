"""apex_tpu.telemetry — registry, events, attrib, report (ISSUE 3).

Covers the satellite checklist: counters/histograms fed from ``jax.jit``
outputs on CPU, rank-0 gating, scaler-overflow events across a
forced-inf step, the loader queue-depth gauge, JSONL round-trip through
the SCHEMA validator — plus the acceptance gate: the disabled-mode path
adds NO host sync around the jitted step, and the
``python -m apex_tpu.telemetry`` CLI renders the per-op table and the
step-metrics summary from an instrumented transformer run.
"""
import functools
import json
import os
import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp

from apex_tpu import telemetry
from apex_tpu.telemetry import (JsonlSink, MemorySink, Registry, events,
                                record_violations, records_violations)
from apex_tpu.telemetry import report as treport

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(autouse=True)
def _no_default_registry():
    """Hooks must not leak a default registry between tests."""
    prev = events.set_default(None)
    yield
    events.set_default(prev)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_counters_gauges_histograms_under_jit():
    """Metric updates accept jitted device outputs and aggregate
    correctly once flushed (no value is read before the flush)."""
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    f = jax.jit(lambda x: (x * 2).sum())
    for i in range(3):
        y = f(jnp.ones((4,)) * i)            # device scalar
        reg.counter("total").add(y)
        reg.gauge("last").set(y)
        reg.histogram("h").observe(y)
        reg.counter("n").add(1)
    vals = reg.read()
    assert vals["total"] == pytest.approx(0.0 + 8.0 + 16.0)
    assert vals["last"] == pytest.approx(16.0)
    assert vals["n"] == 3
    recs = reg.flush()
    hist = [r for r in recs if r.get("name") == "h"][0]
    assert hist["stats"]["count"] == 3
    assert hist["stats"]["max"] == pytest.approx(16.0)
    assert records_violations(recs) == []


def test_step_context_batches_host_reads_per_flush_interval(monkeypatch):
    """6 steps at flush_interval=3 -> exactly 2 batched host reads, each
    resolving every pending device value at once."""
    sink = MemorySink()
    reg = Registry(sink=sink, flush_interval=3, rank0_only=False)
    f = jax.jit(lambda x: x + 1)
    gets = []
    real_get = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: gets.append(1) or real_get(x))
    for i in range(6):
        with reg.step():
            y = f(jnp.ones((2,)))
            reg.gauge("loss").set(y.sum())
            reg.counter("examples").add(2)
    assert len(gets) == 2                      # one batched read per flush
    assert len(sink.records) > 0
    steps = [r for r in sink.records if r.get("name") == "step_time_ms"]
    assert sum(r["stats"]["count"] for r in steps) == 6


def test_disabled_mode_is_true_noop_zero_host_syncs(monkeypatch, tmp_path):
    """The acceptance gate: with telemetry disabled, wrapping the jitted
    step adds NO host sync (no block_until_ready, no device_get), stores
    nothing, and never touches the sink."""
    syncs = []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: syncs.append("block") or x)
    monkeypatch.setattr(jax, "device_get",
                        lambda x: syncs.append("get") or x)
    path = tmp_path / "never.jsonl"
    reg = Registry(sink=JsonlSink(str(path)), enabled=False)
    step = jax.jit(lambda x: x * 2)
    for _ in range(4):
        with reg.step():
            y = step(jnp.ones((8,)))
            reg.gauge("loss").set(y)
            reg.counter("examples").add(8)
            reg.histogram("h").observe(y)
            reg.event("e", x=1)
    # observe_scaler with a disabled registry must not device_get either
    from apex_tpu.amp import scaler
    s0 = scaler.init()
    s1 = scaler.update(s0, jnp.asarray(False))
    assert events.observe_scaler(reg, s0, s1) is None
    assert events.observe_scaler(None, s0, s1) is None
    assert reg.flush() == []
    assert syncs == []                         # zero host syncs
    assert reg._metrics == {}                  # nothing stored
    assert not path.exists()                   # sink never opened
    assert reg.counter("a") is telemetry.NULL_METRIC
    # the null metric mirrors the full metric surface (same defaults),
    # so enabled-mode code runs unchanged when telemetry is off
    reg.counter("a").add()
    reg.meter("m").update(3.0)
    assert reg.meter("m").avg == 0.0
    assert str(reg.meter("m")) == "<telemetry disabled>"
    reg.meter("m").reset()


def test_env_var_disables_registry(monkeypatch):
    monkeypatch.setenv("APEX_TPU_TELEMETRY", "0")
    assert Registry().enabled is False
    monkeypatch.setenv("APEX_TPU_TELEMETRY", "1")
    assert Registry().enabled is True
    # explicit argument wins over the env
    monkeypatch.setenv("APEX_TPU_TELEMETRY", "0")
    assert Registry(enabled=True).enabled is True


def test_rank0_gating_single_process(monkeypatch):
    """Off-rank-0 the sink stays silent (aggregation continues); the
    single-process default is rank 0 = emit."""
    from apex_tpu.utils import logging as ulog
    sink = MemorySink()
    reg = Registry(sink=sink, flush_interval=0)
    reg.counter("c").add(1)
    monkeypatch.setattr(ulog, "is_rank0", lambda: False)
    reg.flush()
    assert sink.records == []                  # gated off-rank
    assert reg.read()["c"] == 1                # but still aggregated
    monkeypatch.setattr(ulog, "is_rank0", lambda: True)
    reg.counter("c").add(1)
    reg.flush()
    assert any(r.get("name") == "c" and r["value"] == 2
               for r in sink.records)


def test_meter_behind_registry_and_logging_reexport():
    """AverageMeter/Throughput moved into telemetry.registry; the
    utils.logging import path keeps working, and a registry-attached
    meter lands in the record stream."""
    from apex_tpu.utils.logging import AverageMeter, Throughput
    assert AverageMeter is telemetry.AverageMeter
    assert Throughput is telemetry.Throughput
    m = AverageMeter("loss")
    m.update(2.0)
    m.update(4.0)
    assert m.avg == pytest.approx(3.0)

    sink = MemorySink()
    reg = Registry(sink=sink, flush_interval=0, rank0_only=False)
    reg.meter("speed").update(100.0)
    reg.flush()
    rec = [r for r in sink.records if r.get("name") == "speed"][0]
    assert rec["type"] == "meter" and rec["avg"] == pytest.approx(100.0)
    assert records_violations(sink.records) == []


# ---------------------------------------------------------------------------
# events: scaler transitions, collectives, loader
# ---------------------------------------------------------------------------

def test_scaler_overflow_event_across_forced_inf_step():
    """A forced-inf gradient through the REAL jitted amp pipeline halves
    the scale and emits exactly one amp.overflow event."""
    from apex_tpu import amp
    from apex_tpu.optimizers import FusedSGD
    params = {"w": jnp.ones((4,), jnp.float32)}
    state = amp.initialize(params, FusedSGD(lr=0.1), opt_level="O2",
                           verbosity=0)

    @jax.jit
    def step(state, grads):
        return amp.amp_step(state, grads)

    sink = MemorySink()
    reg = Registry(sink=sink, flush_interval=0, rank0_only=False)
    new = step(state, {"w": jnp.full((4,), jnp.inf, jnp.float16)})
    kinds = events.observe_amp(reg, state, new)
    assert kinds == ["overflow"]
    finite = step(new, {"w": jnp.ones((4,), jnp.float16)})
    assert events.observe_amp(reg, new, finite) == ["steady"]
    reg.flush()
    evs = [r for r in sink.records if r.get("kind") == "event"]
    assert len(evs) == 1 and evs[0]["name"] == "amp.overflow"
    assert evs[0]["fields"]["new_scale"] == pytest.approx(
        evs[0]["fields"]["old_scale"] / 2)
    assert reg.read()["amp.overflow_steps"] == 1
    assert records_violations(sink.records) == []


def test_scaler_growth_event_at_scale_window():
    from apex_tpu.amp import scaler
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    s0 = scaler.init(scale_window=2)
    s1 = scaler.update(s0, jnp.asarray(True))
    assert events.observe_scaler(reg, s0, s1) == "steady"
    s2 = scaler.update(s1, jnp.asarray(True))
    assert events.observe_scaler(reg, s1, s2) == "grew"
    recs = reg.flush()
    ev = [r for r in recs if r.get("kind") == "event"][0]
    assert ev["name"] == "amp.loss_scale_doubled"
    assert ev["fields"]["after_steps"] == 2


def test_transition_kind_clamped_edges():
    from apex_tpu.amp.scaler import transition_kind
    assert transition_kind(8.0, 4.0, 3, 0) == "overflow"
    assert transition_kind(4.0, 8.0, 1999, 0) == "grew"
    assert transition_kind(8.0, 8.0, 5, 6) == "steady"
    # halve clamped at min_loss_scale: only the streak reset shows
    assert transition_kind(1.0, 1.0, 7, 0, scale_window=2000) == "overflow"
    # double clamped at max_loss_scale: window reached, NOT an overflow
    assert transition_kind(2.0 ** 24, 2.0 ** 24, 1999, 0,
                           scale_window=2000) == "steady"
    # with the policy bounds, an overflow at the FLOOR is classified
    # correctly even when the streak happened to sit at window-1 (at the
    # floor a finite window-reached step would have doubled, so an
    # unchanged scale must be an overflow) — code-review finding
    assert transition_kind(1.0, 1.0, 1999, 0, scale_window=2000,
                           min_loss_scale=1.0,
                           max_loss_scale=2.0 ** 24) == "overflow"
    assert transition_kind(2.0 ** 24, 2.0 ** 24, 1999, 0, scale_window=2000,
                           min_loss_scale=1.0,
                           max_loss_scale=2.0 ** 24) == "steady"


def test_observe_scaler_overflow_at_min_scale_window_edge():
    """End-to-end: a scaler pinned at min_loss_scale overflowing on the
    exact window-1 streak still emits amp.overflow (observe_scaler
    passes the state's policy bounds through)."""
    from apex_tpu.amp import scaler
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    s0 = scaler.ScalerState(
        loss_scale=jnp.asarray(1.0, jnp.float32),
        unskipped=jnp.asarray(1, jnp.int32), scale_window=2)
    s1 = scaler.update(s0, jnp.asarray(False))        # overflow at floor
    assert float(s1.loss_scale) == 1.0                # clamped
    assert events.observe_scaler(reg, s0, s1) == "overflow"
    assert reg.read()["amp.overflow_steps"] == 1


def test_collective_meter_records_bytes_and_calls():
    """allreduce_tree reports payload bytes + leaf count into the
    default registry (trace-time semantics documented in events.py)."""
    from jax.sharding import PartitionSpec as P
    from apex_tpu.parallel import create_mesh
    from apex_tpu.parallel.distributed import allreduce_tree
    from jax import shard_map
    mesh = create_mesh({"data": 8})
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    events.set_default(reg)

    @functools.partial(shard_map, mesh=mesh, in_specs=P("data"),
                       out_specs=P("data"))
    def reduce(x):
        return allreduce_tree({"w": x, "b": x})["w"]

    reduce(jnp.arange(8, dtype=jnp.float32))
    vals = reg.read()
    assert vals["ddp.allreduce_calls"] == 1
    # per-shard payload: two f32 leaves of one element each
    assert vals["ddp.allreduce_bytes"] == 8
    assert vals["ddp.allreduce_leaves"] == 2
    recs = reg.flush()
    ev = [r for r in recs if r.get("name") == "ddp.allreduce"][0]
    assert ev["fields"]["axis"] == "data"
    assert records_violations(recs) == []


def test_collective_meter_skips_already_summed_leaves():
    """vma-pre-summed leaves emit no psum, so they must not inflate the
    byte meter (code-review finding): only the varying leaf counts."""
    from jax.sharding import PartitionSpec as P
    from apex_tpu.parallel import create_mesh
    from apex_tpu.parallel.distributed import allreduce_tree
    from jax import shard_map
    mesh = create_mesh({"data": 8})
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    events.set_default(reg)

    @functools.partial(shard_map, mesh=mesh, in_specs=(P("data"), P()),
                       out_specs=P("data"))
    def reduce(x, r):
        out = allreduce_tree({"w": x, "b": r})
        return out["w"] + out["b"]

    reduce(jnp.arange(8, dtype=jnp.float32), jnp.ones((), jnp.float32))
    vals = reg.read()
    if vals.get("ddp.allreduce_leaves") is not None and \
            vals["ddp.allreduce_leaves"] < 2:
        # vma typing active: the replicated leaf was skipped
        assert vals["ddp.allreduce_leaves"] == 1
        assert vals["ddp.allreduce_bytes"] == 4
    else:
        # jax without vma typing psums both leaves — both counted
        assert vals["ddp.allreduce_bytes"] == 8


def test_collective_meter_free_when_no_registry():
    """Without a default registry the hook is inert — allreduce_tree
    still works and nothing is recorded anywhere."""
    from jax.sharding import PartitionSpec as P
    from apex_tpu.parallel import create_mesh
    from apex_tpu.parallel.distributed import allreduce_tree
    from jax import shard_map
    mesh = create_mesh({"data": 8})
    assert events.get_default() is None

    @functools.partial(shard_map, mesh=mesh, in_specs=P("data"),
                       out_specs=P("data"))
    def reduce(x):
        return allreduce_tree({"w": x})["w"]

    out = reduce(jnp.ones(8, jnp.float32))
    assert float(out.sum()) == 8.0


def test_loader_queue_depth_gauge():
    """The python-ring loader reports wait + depth per dequeued batch."""
    from apex_tpu.data.loader import NativeLoader, SyntheticSource
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    events.set_default(reg)
    loader = NativeLoader(SyntheticSource(shape=(4,), n_classes=3),
                          batch_size=2, steps=5, device_put=False)
    batches = list(loader._iter_python())
    assert len(batches) == 5
    vals = reg.read()
    assert vals["loader.queue_depth"] is not None
    assert vals["loader.queue_depth"] >= 0
    # one wait sample per dequeue (incl. the end sentinel)
    assert vals["loader.wait_ms"]["cum_count"] + \
        len(vals["loader.wait_ms"]["window"]) >= 5


# ---------------------------------------------------------------------------
# JSONL round-trip + schema
# ---------------------------------------------------------------------------

def test_jsonl_roundtrip_through_schema_validator(tmp_path):
    path = str(tmp_path / "run.jsonl")
    reg = Registry(sink=JsonlSink(path), flush_interval=2,
                   rank0_only=False, run_id="t")
    for i in range(4):
        with reg.step():
            reg.counter("examples").add(8)
            reg.gauge("loss").set(1.0 / (i + 1))
    reg.event("custom", code=7, note="ok")
    reg.close()
    recs = treport.load_records(path, validate=True)   # raises on drift
    assert records_violations(recs) == []
    assert recs[0]["kind"] == "meta" and recs[0]["run"] == "t"
    summary = treport.summarize(recs)
    assert summary["steps"] == 4
    assert summary["step_time_ms"]["count"] == 4
    assert summary["items_total"] == 32
    text = treport.format_summary(summary)
    assert "step-metrics summary" in text and "overflow events" in text


def test_jsonl_sink_refuses_off_schema_records(tmp_path):
    sink = JsonlSink(str(tmp_path / "x.jsonl"))
    with pytest.raises(ValueError, match="schema"):
        sink.write([{"kind": "metric", "name": "x"}])   # missing fields
    assert not (tmp_path / "x.jsonl").exists()


def test_record_schema_violations():
    good_metric = {"kind": "metric", "ts": "2026-08-04T00:00:00Z",
                   "step": 1, "name": "c", "type": "counter", "value": 2.0}
    assert record_violations(good_metric) == []
    assert record_violations({"kind": "nope"})
    assert record_violations({**good_metric, "mystery": 1})
    assert record_violations({**good_metric, "value": "high"})
    hist = {"kind": "metric", "ts": "t", "step": 0, "name": "h",
            "type": "histogram",
            "stats": {"count": 1, "sum": 1.0, "min": 1.0, "max": 1.0,
                      "mean": 1.0}}
    assert record_violations(hist) == []
    assert record_violations(
        {**hist, "stats": {"count": 1}})       # missing stat keys
    ev = {"kind": "event", "ts": "t", "step": 0, "name": "e",
          "fields": {"a": 1, "b": "x"}}
    assert record_violations(ev) == []
    assert record_violations({**ev, "fields": {"a": [1, 2]}})


def test_load_records_skips_bad_lines_unless_validating(tmp_path):
    p = tmp_path / "r.jsonl"
    good = {"kind": "event", "ts": "t", "step": 0, "name": "e",
            "fields": {}}
    p.write_text(json.dumps(good) + "\n{broken\n"
                 + json.dumps({"kind": "bogus"}) + "\n")
    recs = treport.load_records(str(p))
    assert len(recs) == 1
    with pytest.raises(ValueError):
        treport.load_records(str(p), validate=True)


# ---------------------------------------------------------------------------
# attrib: per-op FLOPs/bytes from the compiled HLO
# ---------------------------------------------------------------------------

def test_attrib_op_table_matmul():
    from apex_tpu.telemetry import attrib

    def f(x, w):
        return jnp.tanh(x @ w).sum()

    table = attrib.op_table(f, jnp.ones((8, 16)), jnp.ones((16, 32)))
    rows = {r["opcode"]: r for r in table["rows"]}
    assert "dot" in rows
    # 2 * M*N*K = 2 * 8*32*16
    assert rows["dot"]["flops"] == pytest.approx(2 * 8 * 32 * 16)
    assert rows["dot"]["bytes"] >= (8 * 16 + 16 * 32 + 8 * 32) * 4
    assert table["total_flops"] > 0
    # joined against the compiler's own cost model (same order)
    assert table["module_flops"] == pytest.approx(table["total_flops"],
                                                  rel=0.5)
    text = attrib.format_op_table(table, top=5)
    assert "per-op cost attribution" in text and "dot" in text


def test_summary_counts_resilience_events():
    """ISSUE 5 satellite: the guard's fault_injected / rollback /
    resumed / preempted events (PR 3) show up in summarize() and the
    rendered summary instead of being dropped."""
    def ev(name, step, **fields):
        return {"kind": "event", "ts": "t", "step": step, "name": name,
                "fields": fields}
    recs = [ev("fault_injected", 5, kind="nan"),
            ev("fault_injected", 6, kind="nan"),
            ev("rollback", 8, to_step=0, attempt=1, reason="streak"),
            ev("resumed", 8),
            ev("preempted", 12),
            ev("sentinel.slow_step", 9, z=5.2)]
    s = treport.summarize(recs)
    assert s["faults_injected"] == 2
    assert s["rollbacks"] == 1
    assert s["resumes"] == 1
    assert s["preemptions"] == 1
    assert s["sentinel_fires"] == 1
    text = treport.format_summary(s)
    assert "resilience" in text
    assert "faults injected 2" in text and "rollbacks 1" in text
    # a clean run stays compact: no resilience line at all
    clean = treport.format_summary(treport.summarize([]))
    assert "resilience" not in clean


def test_guard_run_events_flow_into_cli_summary(tmp_path):
    """End-to-end: a real guard-driven chaos run's registry JSONL
    renders with the resilience counts."""
    import numpy as np
    from apex_tpu.resilience import GuardConfig, TrainGuard, faults

    @jax.jit
    def step(w, batch):
        g = jax.grad(lambda w: jnp.sum((w - batch) ** 2))(w)
        finite = jnp.all(jnp.isfinite(g))
        return jnp.where(finite, w - 0.1 * g, w), jnp.sum((w - batch) ** 2)

    path = str(tmp_path / "guard.jsonl")
    reg = Registry(sink=JsonlSink(path), flush_interval=0, rank0_only=False)
    plan = faults.parse("nan@5x3")
    g = TrainGuard(step, GuardConfig(ckpt_dir=str(tmp_path / "ck"),
                                     save_every_steps=5, check_every=4,
                                     nonfinite_streak=3,
                                     backoff_seconds=0.01, enabled=True),
                   plan=plan, registry=reg)
    batch_at = lambda i: jnp.asarray(
        np.random.RandomState(i).randn(4).astype(np.float32))
    _, rep = g.run(jnp.zeros(4), batch_at, 20)
    assert rep.rollbacks == 1
    reg.close()
    s = treport.summarize(treport.load_records(path, validate=True))
    assert s["faults_injected"] == 3 and s["rollbacks"] == 1
    assert "rollbacks 1" in treport.format_summary(s)


def test_attrib_op_class_rollup():
    """ISSUE 5 satellite (VERDICT missing #7): ops bin into the pyprof
    prof/ class vocabulary and the table carries a per-class rollup."""
    from apex_tpu.telemetry import attrib

    assert attrib.op_class("dot") == "blas"
    assert attrib.op_class("convolution") == "conv"
    assert attrib.op_class("reduce") == "reduction"
    assert attrib.op_class("all-reduce") == "collective"
    assert attrib.op_class("transpose") == "memory"
    assert attrib.op_class("tanh") == "pointwise"
    assert attrib.op_class("custom-call") == "other"

    def f(x, w):
        return jnp.tanh(x @ w).sum()

    table = attrib.op_table(f, jnp.ones((8, 16)), jnp.ones((16, 32)))
    by_class = table["by_class"]
    assert set(by_class) <= set(attrib.OP_CLASSES)
    assert by_class["blas"]["flops"] == pytest.approx(2 * 8 * 32 * 16)
    # pct shares sum to ~100 over the classes present
    assert sum(c["pct_flops"] for c in by_class.values()) == \
        pytest.approx(100.0)
    # every row carries its class
    assert all(r["class"] in attrib.OP_CLASSES for r in table["rows"])
    text = attrib.format_op_table(table, top=5)
    assert "per-class rollup" in text and "blas" in text


def test_attrib_fusion_classified_by_content():
    """A fusion wrapping a reduction is reduction work, not pointwise —
    the fused computation's content decides the class."""
    from apex_tpu.telemetry import attrib
    hlo = """
HloModule m

%fused_reduce (p: f32[64]) -> f32[] {
  %p = f32[64] parameter(0)
  %c = f32[] constant(0)
  ROOT %r = f32[] reduce(f32[64] %p, f32[] %c), dimensions={0}
}

ENTRY %main (x: f32[64]) -> f32[] {
  %x = f32[64] parameter(0)
  ROOT %f = f32[] fusion(f32[64] %x), kind=kInput, calls=%fused_reduce
}
"""
    rows = attrib.parse_hlo(hlo)
    fusion = [r for r in rows if r["opcode"] == "fusion"]
    assert fusion and fusion[0]["class"] == "reduction"
    # a fusion of PURE data movement is memory work, not pointwise
    # (code-review finding: transpose/copy fusions must not launder
    # into the pointwise bucket)
    hlo_mem = hlo.replace(
        "%fused_reduce (p: f32[64]) -> f32[] {\n"
        "  %p = f32[64] parameter(0)\n"
        "  %c = f32[] constant(0)\n"
        "  ROOT %r = f32[] reduce(f32[64] %p, f32[] %c), dimensions={0}\n"
        "}",
        "%fused_reduce (p: f32[64]) -> f32[] {\n"
        "  %p = f32[64] parameter(0)\n"
        "  ROOT %r = f32[] reshape(f32[64] %p)\n"
        "}")
    rows2 = attrib.parse_hlo(hlo_mem)
    fusion2 = [r for r in rows2 if r["opcode"] == "fusion"]
    assert fusion2 and fusion2[0]["class"] == "memory"


def test_attrib_rows_sorted_and_shared_ceilings():
    from apex_tpu.pyprof.prof import HW_CEILINGS
    from apex_tpu.telemetry import attrib

    def f(x):
        return (x @ x.T).mean() + jnp.exp(x).sum()

    table = attrib.op_table(f, jnp.ones((16, 64)))
    flops = [r["flops"] for r in table["rows"]]
    assert flops == sorted(flops, reverse=True)
    ceil = HW_CEILINGS[table["platform"]]
    assert table["peak_flops"] == ceil["peak_flops"]
    for r in table["rows"]:
        assert r["projected_us"] >= 0.0
    # no collectives in a single-device program -> empty sub-table
    assert table["collectives"]["rows"] == []
    assert table["collectives"]["total_logical_bytes"] == 0


def test_attrib_collectives_subtable_logical_bytes():
    """ISSUE 10 satellite: ``op_table`` surfaces per-collective logical
    bytes in a ``collectives`` sub-table, so the planner's comm model
    can be calibrated against what the compiled program actually
    exchanges.  Under shard_map the shapes are per-partition — the
    per-device payload the alpha-beta model predicts."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from apex_tpu.parallel.mesh import create_mesh
    from apex_tpu.telemetry import attrib

    n_dev = len(jax.devices())
    mesh = create_mesh({"data": n_dev})
    elems = 2048

    def f(x):
        return jax.lax.psum(x, "data")

    sm = shard_map(f, mesh=mesh, in_specs=(P("data"),),
                   out_specs=P("data"))
    table = attrib.op_table(sm, jnp.ones((n_dev, elems)))
    coll = table["collectives"]
    ar = coll["by_opcode"]["all-reduce"]
    # an all-reduce's logical payload is the per-device buffer, both
    # in and out
    assert ar["logical_bytes"] == elems * 4
    assert ar["in_bytes"] == elems * 4
    assert ar["out_bytes"] == elems * 4
    assert coll["total_logical_bytes"] >= elems * 4
    # the sub-table renders in the formatted output
    assert "per-collective logical bytes" in attrib.format_op_table(table)


# ---------------------------------------------------------------------------
# the CLI acceptance path (subprocess: the real __main__)
# ---------------------------------------------------------------------------

def test_cli_renders_per_op_table_and_step_summary(tmp_path):
    """ISSUE acceptance: ``python -m apex_tpu.telemetry`` renders a
    per-op FLOPs/bytes table plus the step-metrics summary (step time,
    overflow events, collective bytes, loader depth) from a JSONL
    produced by instrumenting the flagship transformer train step on
    CPU — then the written JSONL renders again standalone."""
    out_jsonl = str(tmp_path / "demo.jsonl")
    r = subprocess.run(
        [sys.executable, "-m", "apex_tpu.telemetry", "--steps", "4",
         "--layers", "1", "--seq", "16", "--batch", "2", "--top", "5",
         "--out", out_jsonl],
        capture_output=True, text=True, cwd=ROOT, timeout=420,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT})
    assert r.returncode == 0, r.stderr[-2000:]
    assert "per-op cost attribution" in r.stdout
    assert "step-metrics summary" in r.stdout
    assert "overflow events     1" in r.stdout       # the forced-inf step
    assert "collective bytes" in r.stdout
    assert "loader wait" in r.stdout
    # the JSONL is schema-valid and renders standalone
    recs = treport.load_records(out_jsonl, validate=True)
    assert records_violations(recs) == []
    r2 = subprocess.run(
        [sys.executable, "-m", "apex_tpu.telemetry", out_jsonl],
        capture_output=True, text=True, cwd=ROOT, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT})
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "step-metrics summary" in r2.stdout


# ---------------------------------------------------------------------------
# report degrade paths (ISSUE 15 satellite): empty / partial / torn-tail
# JSONL streams — the paths existed but were untested
# ---------------------------------------------------------------------------

def test_summarize_empty_stream_renders(tmp_path):
    """An empty (or all-blank) JSONL is a valid degenerate run: zero
    records, a summary full of zeros/Nones, and a render that does not
    crash on any missing field."""
    p = tmp_path / "empty.jsonl"
    p.write_text("")
    recs = treport.load_records(str(p))
    assert recs == []
    s = treport.summarize(recs)
    assert s["steps"] == 0 and s["step_time_ms"] is None
    assert s["loss_scale"] is None and s["goodput_fraction"] is None
    text = treport.format_summary(s)
    assert "step-metrics summary" in text and "n/a" in text
    # blank lines only: same degenerate path
    p.write_text("\n\n   \n")
    assert treport.load_records(str(p)) == []


def test_summarize_partial_stream_events_only(tmp_path):
    """A stream holding ONLY events (a run that died before its first
    metric flush) still summarizes: the resilience line counts them and
    every metric aggregate degrades to its empty default."""
    p = tmp_path / "partial.jsonl"
    recs = [{"kind": "event", "ts": "2026-01-01T00:00:00Z", "step": 3,
             "name": "fault_injected", "fields": {"kind": "nan"}},
            {"kind": "event", "ts": "2026-01-01T00:00:01Z", "step": 4,
             "name": "rollback", "fields": {"to_step": 2}}]
    p.write_text("".join(json.dumps(r) + "\n" for r in recs))
    s = treport.summarize(treport.load_records(str(p)))
    assert s["faults_injected"] == 1 and s["rollbacks"] == 1
    assert s["steps"] == 4 and s["step_time_ms"] is None
    assert s["collective_bytes"] == 0.0
    text = treport.format_summary(s)
    assert "resilience" in text and "rollbacks 1" in text


def test_load_records_torn_tail_and_off_schema(tmp_path):
    """A writer killed mid-append loses ONLY its torn last line (and
    any off-schema record is skipped, not fatal) — unless the caller
    opts into validate=True, which names the bad line."""
    p = tmp_path / "torn.jsonl"
    good = {"kind": "metric", "ts": "2026-01-01T00:00:00Z", "step": 1,
            "name": "step_time_ms", "type": "histogram",
            "stats": {"count": 1, "sum": 2.0, "min": 2.0, "max": 2.0,
                      "mean": 2.0}, "cum_count": 1}
    off_schema = {"kind": "metric", "ts": "x"}   # missing required keys
    p.write_text(json.dumps(good) + "\n"
                 + json.dumps(off_schema) + "\n"
                 + '{"kind": "metric", "ts": "2026-01-01T00')   # torn
    recs = treport.load_records(str(p))
    assert len(recs) == 1 and recs[0]["name"] == "step_time_ms"
    s = treport.summarize(recs)
    assert s["step_time_ms"]["count"] == 1
    treport.format_summary(s)
    with pytest.raises(ValueError):
        treport.load_records(str(p), validate=True)


def test_summary_goodput_line_folds_next_to_resilience(tmp_path):
    """The goodput line (ISSUE 15): exported ledger gauges in the
    stream render as `goodput fraction ... badput: ...` alongside the
    resilience/memory lines."""
    ts = "2026-01-01T00:00:00Z"
    recs = [
        {"kind": "metric", "ts": ts, "step": 9, "name":
         "goodput.fraction", "type": "gauge", "value": 0.82},
        {"kind": "metric", "ts": ts, "step": 9, "name":
         "badput.data_stall_ms", "type": "gauge", "value": 120.5},
        {"kind": "metric", "ts": ts, "step": 9, "name":
         "badput.recompile_ms", "type": "gauge", "value": 0.0},
        {"kind": "event", "ts": ts, "step": 4, "name": "rollback",
         "fields": {}},
    ]
    assert records_violations(recs) == []
    s = treport.summarize(recs)
    assert s["goodput_fraction"] == pytest.approx(0.82)
    assert s["badput_ms"]["data_stall"] == pytest.approx(120.5)
    text = treport.format_summary(s)
    assert "goodput             fraction 0.820" in text
    assert "data stall 120.5ms" in text
    assert "recompile" not in text        # zero classes stay quiet
