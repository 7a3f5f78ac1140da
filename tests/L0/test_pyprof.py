"""pyprof shim tests — annotate API + the prof (cost-analysis) mode.

Reference analog: ``tests/L0/run_pyprof_nvtx`` / ``run_pyprof_data`` —
the profiler's API surface is unit-tested without a GPU profiler attached
(SURVEY §4).  Here: annotate works inside and outside jit, and
``prof.cost_report`` returns a sane FLOPs/bytes roofline report for a
known workload.
"""
import jax
import jax.numpy as jnp
import pytest

from apex_tpu import pyprof
from apex_tpu.pyprof import prof


def test_init_and_annotate_outside_jit(capsys):
    pyprof.init()
    assert pyprof.is_initialized()
    out = capsys.readouterr().out
    assert "jax.profiler" in out
    with pyprof.annotate("region", step=3):
        x = jnp.ones((4,)) * 2
    assert float(x.sum()) == 8.0


def test_annotate_inside_jit_names_scope():
    @jax.jit
    def f(x):
        with pyprof.annotate("hot_matmul"):
            return x @ x

    x = jnp.ones((8, 8))
    # the named scope must appear in the op metadata of the lowered module
    # (plain as_text() strips location info; debug_info keeps it)
    lowered = jax.jit(lambda x: f(x)).lower(x)
    try:
        hlo = lowered.as_text(debug_info=True)
    except TypeError:
        # pre-debug_info jax strips locations from the stablehlo text;
        # the compiled executable's HLO keeps op metadata either way
        hlo = "\n".join(m.to_string() for m in lowered.compile()
                        .runtime_executable().hlo_modules())
    assert "hot_matmul" in hlo
    assert float(f(x)[0, 0]) == 8.0


def test_annotate_function_decorator():
    @pyprof.annotate_function(name="wrapped")
    def g(x):
        return x + 1

    assert float(g(jnp.float32(1.0))) == 2.0


def test_cost_report_matmul_flops():
    n = 64

    def f(a, b):
        return a @ b

    a = jnp.ones((n, n), jnp.float32)
    rep = prof.cost_report(f, a, a)
    assert rep["platform"] == jax.devices()[0].platform
    # an n^3 matmul is 2*n^3 FLOPs; cost models may fold constants but
    # must land within 2x of the analytic count
    analytic = 2 * n ** 3
    assert analytic / 2 <= rep["flops"] <= analytic * 2, rep["flops"]
    assert rep["bytes_accessed"] > 0
    assert rep["arithmetic_intensity"] > 0
    assert rep["projected_ms"] > 0
    text = prof.format_report(rep)
    assert "flops" in text and "roofline" in text


def test_cost_report_scales_with_problem_size():
    def f(a, b):
        return a @ b

    small = prof.cost_report(f, jnp.ones((32, 32)), jnp.ones((32, 32)))
    big = prof.cost_report(f, jnp.ones((128, 128)), jnp.ones((128, 128)))
    # 4x dim => 64x flops
    assert big["flops"] > 10 * small["flops"]


def test_measured_vs_projected_runs():
    def f(a):
        return jnp.sum(a * 2.0)

    rep = prof.measured_vs_projected(f, jnp.ones((256, 256)), iters=3)
    assert rep["measured_ms"] > 0
    assert "utilisation" in rep


def test_trace_capture(tmp_path):
    d = str(tmp_path / "trace")
    try:
        with pyprof.trace(d):
            jnp.ones((16,)).sum().block_until_ready()
    except Exception as e:   # profiler unavailable in sandboxed CI
        pytest.skip(f"profiler capture unavailable: {e}")
    import os
    found = [f for _, _, fs in os.walk(d) for f in fs]
    assert found, "trace produced no files"


# ---- parse (trace -> per-op table) -----------------------------------------

def _fake_events():
    # one XLA thread: fusion(10..110us) containing dot(20..80us);
    # python thread span must be excluded by default
    return [
        {"name": "fusion.1", "ts": 10.0, "dur": 100.0, "pid": 1, "tid": 2,
         "process": "/device:TPU:0", "thread": "XLA Op", "args": {}},
        {"name": "dot.3", "ts": 20.0, "dur": 60.0, "pid": 1, "tid": 2,
         "process": "/device:TPU:0", "thread": "XLA Op", "args": {}},
        {"name": "$main.py:1 step", "ts": 0.0, "dur": 500.0, "pid": 1,
         "tid": 9, "process": "/host:CPU", "thread": "python", "args": {}},
    ]


def test_parse_self_time_nesting():
    from apex_tpu.pyprof import parse
    table = parse.op_table(_fake_events())
    by = {r["name"]: r for r in table}
    assert "$main.py:1 step" not in by          # python excluded by default
    assert by["dot.3"]["self_us"] == 60.0
    assert by["fusion.1"]["self_us"] == 40.0    # 100 - 60 child
    assert abs(sum(r["pct"] for r in table) - 100.0) < 1e-6
    txt = parse.format_table(table)
    assert "dot.3" in txt

    withpy = {r["name"]: r for r in parse.op_table(
        _fake_events(), include_python=True)}
    assert "$main.py:1 step" in withpy


def test_events_from_chrome_counts_dropped_events():
    """ISSUE 13 satellite: complete ("X") records missing ts/dur —
    a profiler killed mid-flush writes torn records — are DROPPED and
    counted into the returned list's ``dropped_events`` (mirroring the
    Tracer's ``droppedSpans``), never silently parsed as phantom spans
    at the trace origin."""
    from apex_tpu.pyprof import parse
    raw = [
        {"ph": "X", "name": "ok", "ts": 0.0, "dur": 5.0, "pid": 1,
         "tid": 1},
        {"ph": "X", "name": "no_dur", "ts": 1.0, "pid": 1, "tid": 1},
        {"ph": "X", "name": "no_ts", "dur": 2.0, "pid": 1, "tid": 1},
        {"ph": "C", "name": "counter", "pid": 1},   # not "X": not counted
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "p"}},
    ]
    evs = parse.events_from_chrome(raw)
    assert [e["name"] for e in evs] == ["ok"]
    assert evs.dropped_events == 2
    # a clean trace counts zero
    assert parse.events_from_chrome(raw[:1]).dropped_events == 0


def test_parse_equal_bound_twins_not_negative():
    """Two spans with identical (ts, dur) on one thread — seen in real
    Chrome traces for zero/equal-length nested spans — must not debit
    each other (arbitrary parent/child order was driving self_us
    negative and skewing pct)."""
    from apex_tpu.pyprof import parse
    evs = [
        {"name": "outer", "ts": 0.0, "dur": 100.0, "pid": 1, "tid": 2,
         "process": "/device:TPU:0", "thread": "tensorflow", "args": {}},
        {"name": "twin_a", "ts": 10.0, "dur": 20.0, "pid": 1, "tid": 2,
         "process": "/device:TPU:0", "thread": "tensorflow", "args": {}},
        {"name": "twin_b", "ts": 10.0, "dur": 20.0, "pid": 1, "tid": 2,
         "process": "/device:TPU:0", "thread": "tensorflow", "args": {}},
    ]
    table = parse.op_table(evs, include_noise=True)
    by = {r["name"]: r for r in table}
    # outer debited once for the twin pair; the twins resolve as a
    # (degenerate) parent/child chain with clamped debits — totals sum
    # to wall time, nothing goes negative
    assert by["outer"]["self_us"] == 80.0
    assert by["twin_a"]["self_us"] == 0.0
    assert by["twin_b"]["self_us"] == 20.0
    assert all(r["self_us"] >= 0 for r in table)
    assert sum(r["self_us"] for r in table) == 100.0


def test_parse_real_capture(tmp_path):
    from apex_tpu.pyprof import parse
    d = str(tmp_path / "tr")
    try:
        with pyprof.trace(d):
            for _ in range(2):
                (jnp.ones((128, 128)) @ jnp.ones((128, 128))
                 ).block_until_ready()
    except Exception as e:
        pytest.skip(f"profiler capture unavailable: {e}")
    events = parse.load(d)
    assert events, "trace parsed to zero events"
    table = parse.op_table(events)
    assert table, "no non-python ops in trace"
    # the matmul must show up on an XLA/runtime thread
    assert any("dot" in r["name"] for r in table), \
        [r["name"] for r in table[:10]]


def test_resolve_ceilings_generations_and_env(monkeypatch):
    """Per-TPU-generation ceilings rows chosen by ``device_kind`` plus
    the documented APEX_TPU_CEILINGS override; a device the table does
    not carry is an error, never another chip's numbers."""
    monkeypatch.delenv(prof.ENV_CEILINGS, raising=False)
    # every row carries the full silicon key set (the planner reads all
    # of them); num_slices is topology, override-only — a row carrying
    # it would defeat plan.search()'s live-mesh detection (ISSUE 12)
    for name, row in prof.HW_CEILINGS.items():
        assert set(row) == set(prof.CEILING_KEYS) - {"num_slices"}, name
    monkeypatch.setenv(prof.ENV_CEILINGS, "num_slices=2")
    assert prof.resolve_ceilings("tpu_v5e")["num_slices"] == 2
    monkeypatch.delenv(prof.ENV_CEILINGS)
    # a TPU resolves through its device_kind, as jax reports it
    assert prof.ceilings_row("TPU v5 lite") == "tpu_v5e"
    assert prof.resolve_ceilings("TPU v5 lite") == \
        prof.HW_CEILINGS["tpu_v5e"]
    assert prof.ceilings_row("TPU v4") == "tpu_v4"
    # the live device resolves too (the CPU mesh the tests run on)
    assert prof.ceilings_row() == "cpu"
    assert prof.resolve_ceilings(jax.devices()[0]) == \
        prof.HW_CEILINGS["cpu"]
    # no generic "tpu" row, and an unknown device is an error — not the
    # cpu row, not v5e's numbers under another chip's name
    for unknown in ("tpu", "quantum", "TPU v9000"):
        with pytest.raises(ValueError, match="no hardware ceilings"):
            prof.resolve_ceilings(unknown)
    # named-row override (shorthand resolves to the tpu_* row)
    monkeypatch.setenv(prof.ENV_CEILINGS, "v5p")
    assert prof.resolve_ceilings("tpu_v5e")["peak_flops"] == \
        prof.HW_CEILINGS["tpu_v5p"]["peak_flops"]
    # row + key override, applied left to right
    monkeypatch.setenv(prof.ENV_CEILINGS, "v4,ici_bw=5e10")
    c = prof.resolve_ceilings("tpu_v5e")
    assert c["peak_bw"] == prof.HW_CEILINGS["tpu_v4"]["peak_bw"]
    assert c["ici_bw"] == 5e10
    # a typo'd key or row fails loudly, never silently
    monkeypatch.setenv(prof.ENV_CEILINGS, "peak_floops=1e12")
    with pytest.raises(ValueError, match="unknown ceiling"):
        prof.resolve_ceilings("tpu_v5e")
    monkeypatch.setenv(prof.ENV_CEILINGS, "v9000")
    with pytest.raises(ValueError, match="unknown ceilings row"):
        prof.resolve_ceilings("tpu_v5e")
