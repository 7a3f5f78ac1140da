"""Run-level goodput ledger (ISSUE 15): wall-clock badput attribution.

What is proven here:

  * the partition ORACLE: hand-fed span streams decompose into the
    declared classes with fixed priority, and the classes partition the
    wall EXACTLY (the ``memory.by_class`` proof standard);
  * replay bookkeeping: a rollback restore re-arms the replay window
    and the re-stepped ground charges ``restore_replay``;
  * the measured exposed-comm carve from a timeline decomposition;
  * ``FAULT_BADPUT`` completeness: every registered fault kind declares
    its badput class — a new ``faults.KINDS`` entry without a mapping
    fails here;
  * the disabled ledger is a true no-op (zero host syncs, zero
    per-record allocation growth — the registry's bar);
  * the ``jax.monitoring`` compile listener meters ``compile.count`` /
    ``compile.ms`` and feeds the ledger's ``recompile`` class;
  * ``ckpt.exposed`` meters ONLY boundary-blocked checkpoint time — a
    fully-overlapped background save contributes ~0 exposed ms;
  * THE chaos acceptance on the 8-dev CPU mesh: guarded flagship runs
    under ``preempt@N``, a NaN-burst rollback, ``loader_stall`` and
    ``resize@N:M`` each write a schema-valid ``GOODPUT.json`` whose
    classes partition measured wall-clock exactly, with each injected
    fault landing in its declared badput class, ``goodput.fraction``
    < 1 under faults and ~1 on a clean run; the ``goodput`` CLI
    renders the same numbers from the artifact.
"""
import functools
import gc
import json
import os
import time
import tracemalloc

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import apex_tpu.elastic as elastic
from apex_tpu.models import TransformerConfig, transformer_init, \
    transformer_loss
from apex_tpu.optimizers import FusedAdam
from apex_tpu.parallel import create_mesh
from apex_tpu.parallel import plan as plan_mod
from apex_tpu.parallel import weight_update as wu
from jax import shard_map
from apex_tpu.resilience import CheckpointManager, GuardConfig, \
    TrainGuard, faults
from apex_tpu.resilience.guard import _AsyncWriter
from apex_tpu.telemetry import MemorySink, Registry, goodput
from apex_tpu.telemetry import events as events_mod
from apex_tpu.telemetry import trace as trace_mod
from apex_tpu.telemetry.report import format_summary, load_records, \
    summarize
from apex_tpu.utils.pallas import to_varying

MS = 1000.0   # trace timestamps are microseconds


@pytest.fixture(autouse=True)
def _clean_state():
    prev_tr = trace_mod.set_tracer(None)
    prev_reg = events_mod.set_default(None)
    prev_led = goodput.install(None)
    prev_plan = faults.install(None)
    yield
    trace_mod.set_tracer(prev_tr)
    events_mod.set_default(prev_reg)
    goodput.install(prev_led)
    faults.install(prev_plan)


def _partition_exact(doc):
    total = sum(r["ms"] for r in doc["classes"].values())
    assert abs(total - doc["wall_ms"]) <= max(1e-3, 1e-6 * doc["wall_ms"]), \
        (total, doc["wall_ms"])


# ---------------------------------------------------------------------------
# the partition oracle
# ---------------------------------------------------------------------------

def test_partition_oracle_priorities_exact():
    led = goodput.GoodputLedger()
    t0 = led.t0_us
    led.note_span("train.step", t0 + 10 * MS, 20 * MS, step=0)    # [10,30)
    led.note_span("compile.backend_compile", t0 + 20 * MS, 5 * MS)
    led.note_span("ckpt.exposed", t0 + 40 * MS, 5 * MS)
    led.note_span("data.fetch", t0 + 50 * MS, 10 * MS)
    led.note_span("loader.fill", t0 + 50 * MS, 30 * MS)   # producer thread:
    led.note_span("ckpt.write", t0 + 55 * MS, 30 * MS)    # both EXCLUDED
    led.note_span("bench.headline", t0 + 70 * MS, 10 * MS)  # unattributed
    doc = led.snapshot(now_us=t0 + 100 * MS)
    c = {k: v["ms"] for k, v in doc["classes"].items()}
    # the compile inside the step span charges recompile, NOT step time
    assert c["recompile"] == pytest.approx(5.0)
    assert c["productive"] == pytest.approx(15.0)
    assert c["ckpt_exposed"] == pytest.approx(5.0)
    assert c["data_stall"] == pytest.approx(10.0)
    assert c["restore_replay"] == 0.0 and c["reshard"] == 0.0
    # the unattributed bench span and the excluded background spans all
    # read as idle — visible, never silently absorbed into productive
    assert c["idle"] == pytest.approx(65.0)
    assert doc["wall_ms"] == pytest.approx(100.0)
    assert doc["goodput_fraction"] == pytest.approx(0.15)
    _partition_exact(doc)
    assert goodput.goodput_violations(doc) == []


def test_overlapping_same_class_spans_union_not_double_count():
    led = goodput.GoodputLedger()
    t0 = led.t0_us
    # the guard's train.step and a Registry.step() wrapper overlap
    led.note_span("train.step", t0 + 10 * MS, 20 * MS, step=0)
    led.note_span("train.step", t0 + 12 * MS, 10 * MS, step=0)
    doc = led.snapshot(now_us=t0 + 40 * MS)
    assert doc["classes"]["productive"]["ms"] == pytest.approx(20.0)
    _partition_exact(doc)


def test_replay_reclassifies_restepped_ground():
    led = goodput.GoodputLedger()
    t0 = led.t0_us
    for s in range(5):                                    # steps 0..4
        led.note_span("train.step", t0 + (10 + s * 10) * MS, 8 * MS,
                      step=s)
    led.note_span("ckpt.restore", t0 + 60 * MS, 5 * MS)   # rollback
    led.note_event("rollback")
    for s in range(2, 5):                                 # replay 2..4
        led.note_span("train.step", t0 + (70 + (s - 2) * 10) * MS,
                      8 * MS, step=s)
    led.note_span("train.step", t0 + 100 * MS, 8 * MS, step=5)  # new
    doc = led.snapshot(now_us=t0 + 120 * MS)
    assert doc["steps"] == 9 and doc["replayed_steps"] == 3
    assert doc["classes"]["restore_replay"]["ms"] == pytest.approx(
        5.0 + 3 * 8.0)
    assert doc["classes"]["productive"]["ms"] == pytest.approx(
        5 * 8.0 + 8.0)
    _partition_exact(doc)
    assert goodput.goodput_violations(doc) == []


def test_plain_resume_restore_counts_without_replay():
    led = goodput.GoodputLedger()
    t0 = led.t0_us
    led.note_span("ckpt.restore", t0 + 5 * MS, 10 * MS)
    led.note_event("resumed")
    # a fresh process resumes at step 40: nothing is replay
    led.note_span("train.step", t0 + 20 * MS, 10 * MS, step=40)
    doc = led.snapshot(now_us=t0 + 40 * MS)
    assert doc["classes"]["restore_replay"]["ms"] == pytest.approx(10.0)
    assert doc["replayed_steps"] == 0
    assert doc["counts"]["resumes"] == 1
    assert goodput.goodput_violations(doc) == []


def test_decomposition_carves_measured_exposed_comm():
    led = goodput.GoodputLedger()
    t0 = led.t0_us
    led.note_span("train.step", t0 + 10 * MS, 10 * MS, step=0)
    led.note_span("train.step", t0 + 30 * MS, 10 * MS, step=1)
    led.set_decomposition({
        "totals": {"exposed_comm_fraction": 0.25},
        "steps": [{"step": 0, "devices": {
            "d0": {"busy_ms": 8.0, "exposed_comm_ms": 4.0}}}]})
    doc = led.snapshot(now_us=t0 + 50 * MS)
    # step 0 uses its own measured fraction (4/8 = 0.5 -> 5 ms of 10);
    # step 1 has no window in the capture -> the overall fraction
    assert doc["classes"]["exposed_comm"]["ms"] == pytest.approx(7.5)
    assert doc["classes"]["productive"]["ms"] == pytest.approx(12.5)
    _partition_exact(doc)
    # without a capture the class honestly reads 0 (not "fully hidden")
    led2 = goodput.GoodputLedger()
    led2.note_span("train.step", led2.t0_us + MS, 10 * MS, step=0)
    assert led2.snapshot()["classes"]["exposed_comm"]["ms"] == 0.0


def test_pipeline_bubble_carve_oracle():
    """The pp engine's static fill/drain fraction carves
    ``pipeline_bubble`` out of each productive step span (from the END
    of the span — exposed comm carves the start), and the partition
    stays exact."""
    led = goodput.GoodputLedger()
    t0 = led.t0_us
    for s in range(3):
        led.note_span("train.step", t0 + (10 + s * 20) * MS, 10 * MS,
                      step=s)
    led.set_pipeline_bubble(1.0 / 3.0)    # S=2, M=2: (S-1)/(M+S-1)
    doc = led.snapshot(now_us=t0 + 80 * MS)
    assert doc["classes"]["pipeline_bubble"]["ms"] == pytest.approx(10.0)
    assert doc["classes"]["productive"]["ms"] == pytest.approx(20.0)
    _partition_exact(doc)
    assert goodput.goodput_violations(doc) == []


def test_pipeline_bubble_zero_for_non_pp():
    """No pp plan ever feeds the ledger -> the class honestly reads 0
    (not "no bubble measured" ambiguity)."""
    led = goodput.GoodputLedger()
    led.note_span("train.step", led.t0_us + MS, 10 * MS, step=0)
    doc = led.snapshot(now_us=led.t0_us + 20 * MS)
    assert doc["classes"]["pipeline_bubble"]["ms"] == 0.0
    assert doc["classes"]["productive"]["ms"] == pytest.approx(10.0)
    assert goodput.goodput_violations(doc) == []
    # a disabled ledger's setter is a no-op
    led2 = goodput.GoodputLedger(enabled=False)
    led2.set_pipeline_bubble(0.5)
    assert led2._bubble_frac == 0.0


def test_pipeline_bubble_composes_with_exposed_comm():
    """Both carves on the same step span: exposed takes the start,
    bubble takes the end, productive keeps the middle — and the three
    still partition the span exactly (priority subtraction)."""
    led = goodput.GoodputLedger()
    t0 = led.t0_us
    led.note_span("train.step", t0 + 10 * MS, 10 * MS, step=0)
    led.set_decomposition({"totals": {"exposed_comm_fraction": 0.2},
                           "steps": []})
    led.set_pipeline_bubble(0.3)
    doc = led.snapshot(now_us=t0 + 30 * MS)
    assert doc["classes"]["exposed_comm"]["ms"] == pytest.approx(2.0)
    assert doc["classes"]["pipeline_bubble"]["ms"] == pytest.approx(3.0)
    assert doc["classes"]["productive"]["ms"] == pytest.approx(5.0)
    _partition_exact(doc)
    assert goodput.goodput_violations(doc) == []


def test_interval_cap_drops_visibly():
    led = goodput.GoodputLedger(max_intervals=3)
    t0 = led.t0_us
    for i in range(6):
        led.note_span("data.fetch", t0 + i * 10 * MS, MS, step=i)
    doc = led.snapshot(now_us=t0 + 100 * MS)
    assert doc["dropped_intervals"] == 3
    assert doc["classes"]["data_stall"]["ms"] == pytest.approx(3.0)


# ---------------------------------------------------------------------------
# the fault-kind -> badput-class contract
# ---------------------------------------------------------------------------

def test_fault_badput_mapping_complete():
    """Every registered fault kind (incl. future ones) must declare its
    expected badput class: adding a ``faults.KINDS`` entry without a
    ledger mapping fails tier-1 right here."""
    assert set(goodput.FAULT_BADPUT) == set(faults.KINDS), (
        "faults.KINDS and goodput.FAULT_BADPUT drifted apart — every "
        "fault kind must declare the badput class its injection lands "
        "in (or ABORT for run-terminating kinds)")
    valid = set(goodput.BADPUT_CLASSES) | {goodput.ABORT}
    for kind, cls in goodput.FAULT_BADPUT.items():
        assert cls in valid, (kind, cls)
    # a fault can never be declared "productive"
    assert "productive" not in set(goodput.FAULT_BADPUT.values())
    # the pp engine's schedule class is a declared badput class (it is
    # carved from the static schedule, never from a fault injection —
    # no fault kind may claim it)
    assert "pipeline_bubble" in goodput.BADPUT_CLASSES
    assert "pipeline_bubble" not in set(goodput.FAULT_BADPUT.values())


# ---------------------------------------------------------------------------
# schema gates
# ---------------------------------------------------------------------------

def _valid_doc():
    led = goodput.GoodputLedger()
    t0 = led.t0_us
    led.note_span("train.step", t0 + MS, 10 * MS, step=0)
    led.note_span("ckpt.restore", t0 + 12 * MS, 2 * MS)
    led.note_event("rollback")
    led.note_span("train.step", t0 + 15 * MS, 5 * MS, step=0)  # replay
    return led.snapshot(now_us=t0 + 30 * MS)


def test_goodput_violations_gates():
    doc = _valid_doc()
    assert goodput.goodput_violations(doc) == []
    # a class whose ms was inflated breaks the partition
    bad = json.loads(json.dumps(doc))
    bad["classes"]["data_stall"]["ms"] += 5.0
    assert any("partition" in v for v in goodput.goodput_violations(bad))
    # fractions must sit in [0, 1]
    bad = json.loads(json.dumps(doc))
    bad["classes"]["idle"]["fraction"] = 1.5
    assert any("outside [0, 1]" in v
               for v in goodput.goodput_violations(bad))
    # rollbacks metered => replay badput present
    bad = json.loads(json.dumps(doc))
    bad["wall_ms"] -= bad["classes"]["restore_replay"]["ms"]
    bad["classes"]["restore_replay"]["ms"] = 0.0
    bad["classes"]["restore_replay"]["fraction"] = 0.0
    assert any("rollbacks metered" in v
               for v in goodput.goodput_violations(bad))
    # replay badput without any restore metered is unattributable
    bad = json.loads(json.dumps(doc))
    bad["counts"]["rollbacks"] = 0
    assert any("no rollback/resume" in v
               for v in goodput.goodput_violations(bad))
    # a missing class key is off-schema
    bad = json.loads(json.dumps(doc))
    del bad["classes"]["reshard"]
    assert any("off-schema" in v for v in goodput.goodput_violations(bad))
    assert goodput.goodput_violations([]) != []
    assert goodput.goodput_violations({"kind": "nope"}) != []


# ---------------------------------------------------------------------------
# disabled mode: the registry's bar
# ---------------------------------------------------------------------------

def test_disabled_ledger_zero_syncs_zero_allocs(monkeypatch):
    syncs = []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: syncs.append("block") or x)
    monkeypatch.setattr(jax, "device_get",
                        lambda x: syncs.append("get") or x)
    led = goodput.GoodputLedger(enabled=False)

    def burn():
        for i in range(1000):
            led.note_span("train.step", 100.0 * i, 50.0, step=i)
            led.note_span("compile.backend_compile", 100.0 * i, 10.0)
            led.note_event("rollback")

    burn()                      # warm allocator/caches first
    gc.collect()
    tracemalloc.start()
    snap1 = tracemalloc.take_snapshot()
    burn()
    gc.collect()
    snap2 = tracemalloc.take_snapshot()
    tracemalloc.stop()
    per_rec = [s for s in snap2.compare_to(snap1, "lineno")
               if s.count_diff >= 100 and s.traceback
               and "tracemalloc" not in s.traceback[0].filename]
    assert per_rec == [], [str(s) for s in per_rec]
    assert syncs == []
    assert led.counts["rollbacks"] == 0
    doc = led.snapshot()
    assert doc["wall_ms"] == 0.0 and doc["steps"] == 0


def test_enabled_ledger_never_syncs(monkeypatch):
    """The ledger touches only host perf_counter microseconds — even
    enabled, snapshot/observe perform zero device syncs."""
    syncs = []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: syncs.append("block") or x)
    monkeypatch.setattr(jax, "device_get",
                        lambda x: syncs.append("get") or x)
    led = goodput.GoodputLedger()
    for i in range(100):
        led.note_span("train.step", led.t0_us + i * MS, MS, step=i)
    led.snapshot()
    assert syncs == []


# ---------------------------------------------------------------------------
# the compile listener (recompile as first-class badput)
# ---------------------------------------------------------------------------

def test_compile_listener_meters_and_feeds_ledger():
    assert events_mod.install_compile_listener() is True
    assert events_mod.install_compile_listener() is True   # idempotent
    tr = trace_mod.Tracer(enabled=True)
    trace_mod.set_tracer(tr)
    led = goodput.GoodputLedger()
    led.attach(tr)
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    prev = events_mod.set_default(reg)
    try:
        f = jax.jit(lambda x: x * 3 + 2)
        f(jnp.ones((11,)))
        f(jnp.ones((23,)))       # shape churn: a second compile
        jax.block_until_ready(f(jnp.ones((23,))))   # cache hit: free
        read = reg.read()
        assert read["compile.count"] >= 2
        assert read["compile.ms"] > 0
    finally:
        events_mod.set_default(prev)
        led.detach(tr)
    doc = led.snapshot()
    assert doc["classes"]["recompile"]["ms"] > 0
    assert doc["counts"]["compiles"] >= 2
    _partition_exact(doc)


# ---------------------------------------------------------------------------
# ckpt.exposed: only boundary-blocked time charges the wall
# ---------------------------------------------------------------------------

def test_ckpt_exposed_overlapped_save_is_near_zero(tmp_path):
    """The ISSUE's regression gate: a fully-overlapped background save
    contributes ~0 exposed ms, while a drain that actually waits on the
    writer meters the real block."""
    mgr = CheckpointManager(str(tmp_path))
    real_save = mgr.save
    mgr.save = lambda step, payload: (time.sleep(0.12),
                                      real_save(step, payload))[1]
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    g = TrainGuard(lambda s, b: (s, None), GuardConfig(enabled=True),
                   registry=reg)
    w = _AsyncWriter(mgr, registry=reg)
    try:
        # fully overlapped: submit hands off, "step work" runs while the
        # writer writes, the drain then finds the queue already empty
        g._blocked_ckpt(0, lambda: w.submit(0, {"step": 0, "leaves": []}))
        time.sleep(0.2)
        g._blocked_ckpt(0, w.drain)
        overlapped = reg.read()["ckpt.exposed_ms_total"]
        assert overlapped < 60.0, overlapped          # ~0 of the 120 ms
        # blocking: drain immediately after submit waits the write out
        g._blocked_ckpt(1, lambda: w.submit(1, {"step": 1, "leaves": []}))
        g._blocked_ckpt(1, w.drain)
        blocked = reg.read()["ckpt.exposed_ms_total"] - overlapped
        assert blocked >= 90.0, blocked
        assert reg.read()["ckpt.write_ms"] >= 100.0   # the bg duration
    finally:
        w.close()


# ---------------------------------------------------------------------------
# registry flush export
# ---------------------------------------------------------------------------

def test_registry_flush_exports_installed_ledger_gauges():
    led = goodput.GoodputLedger()
    led.note_span("train.step", led.t0_us + MS, 5 * MS, step=0)
    goodput.install(led)
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    recs = reg.flush()
    names = {r["name"] for r in recs if r.get("kind") == "metric"}
    assert "goodput.fraction" in names
    assert "badput.idle_ms" in names and "badput.recompile_ms" in names
    # goodput=False pins the export off for registries that must not
    # carry ambient gauges (the bench leg registries' memory=False rule)
    reg2 = Registry(sink=MemorySink(), flush_interval=0,
                    rank0_only=False, goodput=False)
    names2 = {r.get("name") for r in reg2.flush()}
    assert "goodput.fraction" not in names2
    # the summary folds the goodput line next to resilience/memory
    s = summarize(recs)
    assert s["goodput_fraction"] is not None
    assert "goodput" in format_summary(s)


# ---------------------------------------------------------------------------
# THE chaos acceptance (8-dev CPU mesh): flagship runs under the four
# declared faults, GOODPUT.json schema-valid, classes partition exactly
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def demo():
    """The flagship transformer demo step (amp O5 dynamic scale),
    compile warmed OUTSIDE the measured windows."""
    from apex_tpu.telemetry import report as treport
    train_step, state0, raw_batch = treport.demo_step_fn(
        layers=1, batch=4, seq=32, d_model=32)

    def step_fn(st, batch):
        tokens, targets, boost = batch
        return train_step(st, tokens, targets, boost)

    def make_batch(i):
        # the float boost leaf rides in the BATCH so an injected ``nan``
        # fault (which poisons float leaves only — the tokens are int32
        # and immune) propagates to a non-finite loss, exactly like
        # corrupted real input would
        tokens, targets = raw_batch(i)
        return tokens, targets, jnp.ones((), jnp.float32)

    state0, _ = step_fn(state0, make_batch(0))
    jax.block_until_ready(jax.tree_util.tree_leaves(state0))
    return step_fn, state0, make_batch


def _run_guarded(step_fn, state0, batches, tmp_path, *, plan=None,
                 steps=12, sub="run", **cfg_kw):
    tr = trace_mod.Tracer(enabled=True, flight_dir=str(tmp_path / sub))
    prev = trace_mod.set_tracer(tr)
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    try:
        cfg = GuardConfig(ckpt_dir=str(tmp_path / sub / "ck"),
                          save_every_steps=4, check_every=2,
                          backoff_seconds=0.01, enabled=True, **cfg_kw)
        g = TrainGuard(step_fn, cfg, plan=plan, registry=reg)
        state, rep = g.run(state0, batches, steps)
    finally:
        trace_mod.set_tracer(prev)
    return state, rep, reg


def test_chaos_goodput_clean_run_fraction_near_one(demo, tmp_path):
    step_fn, state0, make_batch = demo
    _, rep, _ = _run_guarded(step_fn, state0, make_batch, tmp_path)
    doc = rep.goodput
    assert doc is not None and rep.status == "completed"
    assert goodput.goodput_violations(doc) == []
    _partition_exact(doc)
    # ~1: no fault badput at all, and the overwhelming share of the
    # wall is productive step+sync time (python glue is the idle rest)
    assert doc["classes"]["restore_replay"]["ms"] == 0.0
    assert doc["classes"]["reshard"]["ms"] == 0.0
    assert doc["replayed_steps"] == 0
    assert doc["goodput_fraction"] > 0.6, doc


def test_chaos_goodput_nan_rollback_and_loader_stall(demo, tmp_path):
    step_fn, state0, make_batch = demo
    plan = faults.parse("loader_stall@3:0.3;nan@6x2")

    def batches(i):
        # the loader-stall shim (faults.maybe_stall is what the real
        # loaders call inside their timed wait); the guard's data.fetch
        # span wraps this call, so the stall lands in data_stall
        faults.maybe_stall(i, plan=plan)
        return make_batch(i)

    _, rep, reg = _run_guarded(step_fn, state0, batches, tmp_path,
                               plan=plan, nonfinite_streak=2)
    assert rep.status == "completed" and rep.rollbacks >= 1
    doc = rep.goodput
    assert doc is not None
    assert goodput.goodput_violations(doc) == []
    _partition_exact(doc)                       # the core assert
    # each injected fault landed in its DECLARED badput class
    assert goodput.FAULT_BADPUT["nan"] == "restore_replay"
    assert doc["classes"]["restore_replay"]["ms"] > 0.0
    assert doc["replayed_steps"] >= 1
    assert goodput.FAULT_BADPUT["loader_stall"] == "data_stall"
    assert doc["classes"]["data_stall"]["ms"] >= 200.0   # the 300ms stall
    assert doc["goodput_fraction"] < 1.0
    assert doc["counts"]["rollbacks"] == rep.rollbacks
    assert doc["counts"]["faults_injected"] >= 2
    # the artifact is on disk, schema-valid, and carries the SAME numbers
    assert rep.goodput_path is not None
    assert os.path.basename(rep.goodput_path) == goodput.ARTIFACT_NAME
    disk = json.load(open(rep.goodput_path))
    assert goodput.goodput_violations(disk) == []
    assert disk["goodput_fraction"] == doc["goodput_fraction"]
    assert disk["classes"] == doc["classes"]
    # the pinned registry's JSONL stream carries the exported gauges
    recs = reg.flush()
    gz = {r["name"]: r["value"] for r in recs
          if r.get("kind") == "metric" and r.get("type") == "gauge"}
    assert gz["goodput.fraction"] == pytest.approx(doc["goodput_fraction"])
    assert gz["badput.data_stall_ms"] == pytest.approx(
        doc["classes"]["data_stall"]["ms"])
    s = summarize(recs)
    assert s["goodput_fraction"] == pytest.approx(doc["goodput_fraction"])
    assert "goodput" in format_summary(s)
    assert "data stall" in format_summary(s)


def test_chaos_goodput_preempt_then_resume(demo, tmp_path):
    step_fn, state0, make_batch = demo
    plan = faults.parse("preempt@5")
    _, r1, _ = _run_guarded(step_fn, state0, make_batch, tmp_path,
                            plan=plan, sub="pre")
    assert r1.status == "preempted" and r1.final_step == 5
    doc1 = r1.goodput
    assert doc1["status"] == "preempted"
    assert goodput.goodput_violations(doc1) == []
    _partition_exact(doc1)
    # the preempt's snapshot-then-exit save is boundary-blocked time
    assert doc1["classes"]["ckpt_exposed"]["ms"] > 0.0

    # the RESUMED run: the preempt fault's declared badput class
    # (restore_replay) shows up as the restore cost
    _, r2, _ = _run_guarded(step_fn, state0, make_batch, tmp_path,
                            plan=plan, sub="pre")
    assert r2.status == "completed" and r2.resumed_from == 5
    doc2 = r2.goodput
    assert goodput.goodput_violations(doc2) == []
    _partition_exact(doc2)
    assert goodput.FAULT_BADPUT["preempt"] == "restore_replay"
    assert doc2["classes"]["restore_replay"]["ms"] > 0.0
    assert doc2["counts"]["resumes"] == 1
    assert doc2["replayed_steps"] == 0     # resume is not replay


# -- the resize leg: zero1 flagship on the CPU mesh, 4 -> 2 chips -----------

def _tiny_cfg():
    return TransformerConfig(vocab_size=64, max_len=16, num_layers=1,
                             d_model=32, num_heads=2, d_ff=64,
                             dtype=jnp.float32)


def _resize_batch(step):
    rng = np.random.RandomState(2000 + step)
    return jnp.asarray(rng.randint(0, 64, (4, 16)).astype("int32"))


def _build_zero1(world):
    """(state0, step_fn, layout): ``world``-way zero1 (fp32) DDP step
    over the first ``world`` CPU devices — the flat-shard layout the
    elastic reshard re-slices at resume (test_elastic's harness, minus
    the int8 EF residual: the goodput proof needs the reshard spans,
    not the quantization)."""
    mesh = create_mesh({"data": world}, jax.devices()[:world])
    cfg = _tiny_cfg()
    params0 = transformer_init(jax.random.PRNGKey(0), cfg)
    su = wu.ShardedUpdate(FusedAdam(lr=1e-2, impl="fused"),
                          axis_name="data")
    pspec = jax.tree_util.tree_map(lambda _: P(), params0)
    sspec = su.state_pspecs(params0, world)

    @functools.partial(shard_map, mesh=mesh, in_specs=(pspec,),
                       out_specs=sspec)
    def init_s(p):
        return su.init(p)

    def body(params, state, tokens):
        pv = jax.tree_util.tree_map(
            lambda p: to_varying(p, ("data",)), params)
        loss, grads = jax.value_and_grad(lambda p: transformer_loss(
            p, {"tokens": tokens, "targets": tokens}, cfg))(pv)
        params, state = su.step(state, grads, params)
        return params, state, jax.lax.pmean(loss, "data")

    jstep = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(pspec, sspec, P("data")),
        out_specs=(pspec, sspec, P())))
    state0 = jax.jit(init_s)(params0)

    def step_fn(state, batch):
        params, opt_state = state
        params, opt_state, loss = jstep(params, opt_state, batch)
        return (params, opt_state), loss

    return (params0, state0), step_fn, su.layout_meta(params0, world)


def _tiny_profile():
    return plan_mod.ModelProfile(
        name="tiny", flops=1e9, bytes_accessed=1e8,
        params_bytes=1 << 20, optimizer_bytes=3 << 20,
        activations_bytes=1 << 20, batch_bytes=1 << 16,
        temps_bytes=1 << 18, output_bytes=1 << 10, platform="cpu")


def test_chaos_goodput_resize_lands_in_reshard(tmp_path):
    state4, step4, layout4 = _build_zero1(4)
    state2, step2, layout2 = _build_zero1(2)
    d = tmp_path / "rz"

    def gcfg(world, layout):
        return dict(world_size=world,
                    ckpt_meta={"plan": {"dp": world}, "layout": layout},
                    save_every_steps=2, nonfinite_streak=3)

    plan = faults.parse("resize@4:2")
    tr = trace_mod.Tracer(enabled=True, flight_dir=str(d))
    prev = trace_mod.set_tracer(tr)
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    try:
        g1 = TrainGuard(step4, GuardConfig(
            ckpt_dir=str(d / "ck"), check_every=2, enabled=True,
            **gcfg(4, layout4)), plan=plan, registry=reg)
        _, r1 = g1.run(state4, _resize_batch, 8)
        assert r1.status == "preempted" and r1.resize_to == 2
        assert goodput.goodput_violations(r1.goodput) == []

        er = elastic.ElasticResume(profile=_tiny_profile())
        g2 = TrainGuard(step2, GuardConfig(
            ckpt_dir=str(d / "ck"), check_every=2, enabled=True,
            **gcfg(2, layout2)), plan=plan, registry=reg, elastic=er)
        _, r2 = g2.run(state2, _resize_batch, 8)
    finally:
        trace_mod.set_tracer(prev)
    assert r2.status == "completed" and r2.resharded_from == 4
    doc = r2.goodput
    assert goodput.goodput_violations(doc) == []
    _partition_exact(doc)
    # the resize fault's declared class carries the reshard + replan
    assert goodput.FAULT_BADPUT["resize"] == "reshard"
    assert doc["classes"]["reshard"]["ms"] > 0.0
    assert doc["counts"]["reshards"] == 1
    assert doc["counts"]["replans"] == 1
    assert doc["classes"]["restore_replay"]["ms"] > 0.0   # the restore
    assert doc["goodput_fraction"] < 1.0


# ---------------------------------------------------------------------------
# the CLI: same numbers from the artifact
# ---------------------------------------------------------------------------

def test_goodput_cli_renders_artifact_and_jsonl(tmp_path, capsys):
    doc = _valid_doc()
    led = goodput.GoodputLedger()
    path = led.write(directory=str(tmp_path), doc=doc)
    assert os.path.basename(path) == "GOODPUT.json"
    # run-dir form
    assert goodput.cli([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "goodput ledger" in out
    assert f"{doc['goodput_fraction']:.4f}" in out
    for cls in goodput.CLASSES:
        assert cls in out
    # --json round-trips the doc bit-for-bit
    assert goodput.cli([path, "--json"]) == 0
    assert json.loads(capsys.readouterr().out) == doc
    # JSONL form: a run stream carrying the exported gauges renders too
    led2 = goodput.GoodputLedger()
    led2.note_span("train.step", led2.t0_us + MS, 5 * MS, step=0)
    goodput.install(led2)
    from apex_tpu.telemetry import JsonlSink
    jl = str(tmp_path / "run.jsonl")
    reg = Registry(sink=JsonlSink(jl), flush_interval=0, rank0_only=False)
    reg.close()
    goodput.install(None)
    assert goodput.cli([jl]) == 0
    assert "goodput ledger" in capsys.readouterr().out
    # junk is a clean rc=1, not a traceback
    junk = tmp_path / "junk.txt"
    junk.write_text("not a ledger\n")
    assert goodput.cli([str(junk)]) == 1
