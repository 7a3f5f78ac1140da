"""The five set-up metrics (``setup_import_s``, ``setup_programs``,
``setup_trace_lower_s``, ``setup_load_compile_s``, ``step_trace_lower_s``):
the cut and the interval arithmetic of ``benchmarks/setup_record.py`` on a
hand-made record, the readers on a rehearsal of the tiny BERT cell, and the
five manifest entries against the contract's spelling rules."""
import json
import os
import shutil
import types

import pytest

from benchmarks import run, setup_record
from benchmarks.tests import test_rehearsal

HERE = os.path.dirname(os.path.abspath(__file__))
NEW = ("setup_import_s", "setup_programs", "setup_trace_lower_s",
       "setup_load_compile_s", "step_trace_lower_s")


def _span(name, t0_s, dur_s, **args):
    """A record entry as ``Tracer.export()`` has it (microseconds)."""
    return {"ph": "X", "name": name, "ts": t0_s * 1e6, "dur": dur_s * 1e6,
            "args": args, "parent": None}


# seconds on one thread.  init: traced 10..11, lowered 11..12, loaded 12..13.
# train_step: traced 20..26 with an operation run eagerly inside it (traced
# inside the outer trace: no entry; lowered 22..22.5, compiled 22.5..23),
# lowered 26..28, loaded 28..29.  probe: everything after 29 is no set-up.
_RECORD = [
    _span("setup.import", 0, 8, jax_preloaded=False),
    _span("compile.jaxpr_trace", 10, 1, fun_name="init"),
    _span("compile.jaxpr_to_mlir_module", 11, 1, fun_name="jit(init)"),
    _span("compile.backend_compile", 12, 1, fun_name="jit(init)",
          cache="hit", retrieval_s=0.9),
    _span("setup.state", 9.5, 4),
    _span("compile.jaxpr_to_mlir_module", 22, 0.5, fun_name="jit(iota)"),
    _span("compile.backend_compile", 22.5, 0.5, fun_name="jit(iota)",
          cache="miss"),
    _span("compile.jaxpr_trace", 20, 6, fun_name="train_step"),
    _span("compile.jaxpr_to_mlir_module", 26, 2, fun_name="jit(train_step)"),
    _span("compile.backend_compile", 28, 1, fun_name="jit(train_step)",
          cache="hit", retrieval_s=0.8),
    _span("compile.jaxpr_trace", 40, 1, fun_name="update"),
    _span("compile.jaxpr_to_mlir_module", 41, 1, fun_name="jit(update)"),
    _span("compile.backend_compile", 42, 5, fun_name="jit(update)",
          cache="none"),
]


def test_program_names_the_three_phases_alike():
    assert setup_record.program("train_step") == "train_step"
    assert setup_record.program("jit(train_step)") == "train_step"
    assert setup_record.program("jit_train_step") == "train_step"
    assert setup_record.program("jit(<lambda>)") == "<lambda>"


def test_sums_of_a_hand_made_record():
    got = setup_record.summarize(_RECORD, "train_step", dropped=3)
    assert got["step"] == "train_step" and got["dropped"] == 3
    assert got["import_s"] == 8.0 and got["jax_preloaded"] is False
    assert got["state_s"] == 4.0
    # init, iota, train_step; the probe's program lies after the cut
    assert got["programs"] == 3
    assert (got["hits"], got["misses"], got["uncached"]) == (2, 1, 0)
    assert got["load_compile_s"] == pytest.approx(1 + 0.5 + 1)
    # init 2 + the step's 20..28 less the eager build 22.5..23 (the eager
    # operation's own lowering lies inside the step's trace: counted once)
    assert got["trace_lower_s"] == pytest.approx(2 + 8 - 0.5)
    assert got["step_trace_lower_s"] == pytest.approx(8 - 0.5)
    # the parts never add up to more than the wall clock they came from
    assert got["import_s"] + got["trace_lower_s"] + got["load_compile_s"] \
        <= 29
    rows = dict(got["rows"])
    assert list(rows)[0] == "train_step"          # by self time
    assert rows["train_step"]["trace_s"] == pytest.approx(6 - 1)
    assert rows["train_step"]["lower_s"] == pytest.approx(2)
    assert rows["train_step"]["build_s"] == pytest.approx(1)
    assert rows["iota"]["miss"] == 1 and rows["iota"]["programs"] == 1
    assert "update" not in rows


def test_a_record_without_the_steps_program_reads_nothing():
    assert setup_record.summarize(_RECORD, "another_step") is None
    assert setup_record.summarize([], "train_step") is None


@pytest.fixture
def manifest_path(tmp_path):
    """The tiny manifest plus the real one's five new entries."""
    shutil.copytree(os.path.join(HERE, "tiny", "cells"),
                    str(tmp_path / "cells"))
    with open(test_rehearsal.TINY) as f:
        doc = json.load(f)
    with open(test_rehearsal.REAL) as f:
        new = [m for m in json.load(f)["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in new] == list(NEW)
    doc["per_layer"] += new
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(doc, indent=1))
    return str(path)


def test_the_five_entries_are_spelt_as_the_contract_says(manifest_path):
    test_rehearsal.test_manifest_is_spelt_as_the_contract_says(manifest_path)
    with open(test_rehearsal.REAL) as f:
        real = json.load(f)
    assert [m["name"] for m in real["per_layer"][-5:]] == list(NEW)
    e2e = {m["name"] for m in real["end_to_end"]}
    for m in real["per_layer"][-5:]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves"}                 # every cell: no list
        assert m["moves"] == "setup_s" and m["moves"] in e2e
        assert m["layer"] == "entry_loop" and m["better"] == "lower"
        assert m["source"] in test_rehearsal._SOURCES
        assert m["source"] == ("program_counter"
                               if m["name"] == "setup_programs"
                               else "program_span")


def test_rehearsal_reads_the_count_and_no_time(manifest_path, capsys):
    import jax
    import numpy as np
    from apex_tpu.telemetry import trace
    trace.setup_tracer().clear()       # earlier tests' programs
    result = run.run_cell("tiny_bert.s128", 0, 0.3, True,
                          manifest_path=manifest_path, rehearse=True)
    assert result["correct"] is True
    metrics = result["metrics"]
    assert metrics["setup_programs"]["unit"] == "programs"
    programs = metrics["setup_programs"]["value"]
    assert programs >= 1
    assert not set(NEW) - {"setup_programs"} & set(metrics)
    out = capsys.readouterr().out
    assert out.count("[bench] set-up: import") == 1        # printed once
    assert "[bench] set-up:   train_step x1" in out
    assert " s," not in out.split("[bench] set-up: import")[1].split("\n")[0]

    # a program built after the step's (a reader's probe) is not counted
    def spans():
        return [e for e in trace.setup_tracer().export()["traceEvents"]
                if e.get("ph") == "X"]
    before = setup_record.summarize(spans(), "train_step")
    assert before["programs"] == programs
    n = len(spans())
    jax.block_until_ready(jax.jit(lambda x: x * 5.0 - 2.0)(
        np.ones(9, np.float32)))
    assert len(spans()) == n + 3
    after = setup_record.summarize(spans(), "train_step")
    assert after["programs"] == programs
    assert after["load_compile_s"] == before["load_compile_s"]


def test_a_program_without_the_record_reads_none(monkeypatch):
    """What the parent commit is to these readers: no ``setup_tracer``."""
    from apex_tpu.telemetry import trace
    from benchmarks.job import load_module
    monkeypatch.delattr(trace, "setup_tracer")
    stand_in = types.SimpleNamespace(trace=None, on_chip=True, manifest=None)
    for name in NEW:
        reader = load_module(os.path.join(
            os.path.dirname(HERE), "layer_metrics", name + ".py"),
            "bench_layer_metric_" + name)
        assert reader.read(stand_in) is None
