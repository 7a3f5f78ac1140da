"""The set-up record (docs/telemetry.md "Set-up record", ISSUE 36):

  * ``trace.setup_tracer()`` keeps the FIRST entries and counts the rest;
  * a span names its ``parent``, per thread and per tracer;
  * jax's compile events land in it by program name: one trace / lower /
    build triple a program, nothing on a cached call, no entry for a
    program traced inside another's trace;
  * what the persistent cache did rides on the build's entry, and a load
    from the cache is no ``compile.count``;
  * ``import apex_tpu`` leaves ``setup.import``, the bert example's
    ``run_standard`` leaves ``setup.state`` around its programs;
  * the default tracer and an attached ``GoodputLedger`` still hear every
    ``compile.*`` span.
"""
import importlib.util
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.telemetry import (GoodputLedger, MemorySink, Registry, events,
                                trace)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(autouse=True)
def _no_defaults():
    prev_tr = trace.set_tracer(None)
    prev_reg = events.set_default(None)
    yield
    trace.set_tracer(prev_tr)
    events.set_default(prev_reg)


def _spans(tracer, name=None):
    return [e for e in tracer.export()["traceEvents"]
            if e.get("ph") == "X" and (name is None or e["name"] == name)]


def _compiles_of(tracer, fun):
    """``[(phase, args, parent)]`` of program ``fun``, in record order."""
    return [(e["name"][len("compile."):], e["args"], e["parent"])
            for e in _spans(tracer)
            if e["name"].startswith("compile.")
            and e["args"].get("fun_name") in (fun, f"jit({fun})")]


# ---------------------------------------------------------------------------
# the tracer: which entries it keeps, and who a span's parent is
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("keep_first,kept", [(True, ["s0", "s1", "s2"]),
                                             (False, ["s2", "s3", "s4"])])
def test_record_keeps_the_first_entries_and_counts_the_dropped(keep_first,
                                                               kept):
    tr = trace.Tracer(enabled=True, max_spans=3, keep_first=keep_first)
    for i in range(5):
        with tr.span(f"s{i}"):
            pass
    assert [e["name"] for e in _spans(tr)] == kept
    assert tr.dropped_spans == tr.export()["droppedSpans"] == 2


def test_setup_tracer_is_one_bounded_always_on_tracer():
    rec = trace.setup_tracer()
    assert rec is trace.setup_tracer() and isinstance(rec, trace.Tracer)
    assert rec.enabled and rec.keep_first
    assert rec.max_spans == trace.SETUP_RECORD_ENTRIES == 4096
    assert rec is not trace.get_tracer()
    # this process imported apex_tpu: the span of that import is there
    # unless earlier tests of this worker filled the record
    if not rec.dropped_spans:
        assert len(_spans(rec, "setup.import")) == 1


def test_span_names_the_innermost_open_span_as_its_parent():
    tr = trace.Tracer(enabled=True)
    other = trace.Tracer(enabled=True)
    with tr.span("outer"):
        with tr.span("inner"):
            tr.add("noted", 0.001)
            with other.span("elsewhere"):       # another tracer's stack
                pass
        tr.add("noted.late", 0.001)
    tr.add("noted.top", 0.001)
    parents = {e["name"]: e["parent"] for e in _spans(tr)}
    assert parents == {"outer": None, "inner": "outer", "noted": "inner",
                       "noted.late": "outer", "noted.top": None}
    assert _spans(other)[0]["parent"] is None
    # the ring's entry carries it too, and the document still serializes
    ring = {e["name"]: e["parent"] for e in tr.recorder.snapshot()}
    assert ring["inner"] == "outer"
    json.dumps(tr.export())


def test_parents_are_per_thread():
    tr = trace.Tracer(enabled=True)
    inside = threading.Event()
    done = threading.Event()

    def worker():
        inside.wait(10)
        with tr.span("worker.span"):
            pass
        done.set()

    t = threading.Thread(target=worker)
    t.start()
    with tr.span("main.span"):
        inside.set()
        assert done.wait(10)
    t.join(10)
    assert not t.is_alive()
    parents = {e["name"]: e["parent"] for e in _spans(tr)}
    assert parents == {"worker.span": None, "main.span": None}


# ---------------------------------------------------------------------------
# jax's compile events, by program
# ---------------------------------------------------------------------------

def test_a_program_leaves_one_triple_and_cached_calls_leave_nothing():
    assert events.install_compile_listener() is True
    rec = trace.setup_tracer()
    rec.clear()

    @jax.jit
    def setup_record_probe(x):
        return jnp.tanh(x) * 2.0 + jnp.sum(x)     # jnp calls: nested traces

    x = np.ones((5, 3), np.float32)
    setup_record_probe(x)
    setup_record_probe(x)
    got = _compiles_of(rec, "setup_record_probe")
    assert [phase for phase, _, _ in got] == [
        "jaxpr_trace", "jaxpr_to_mlir_module", "backend_compile"]
    assert got[0][1] == {"fun_name": "setup_record_probe"}
    assert got[2][1]["cache"] in ("none", "miss")
    assert all(parent is None for _, _, parent in got)
    # the jnp functions traced inside it left no entry of their own
    assert [e["args"]["fun_name"] for e in _spans(rec, "compile.jaxpr_trace")
            ] == ["setup_record_probe"]
    n = len(_spans(rec))
    for _ in range(1000):
        out = setup_record_probe(x)
    jax.block_until_ready(out)
    assert len(_spans(rec)) == n


def test_default_tracer_and_goodput_ledger_still_hear_compiles():
    events.install_compile_listener()
    rec = trace.setup_tracer()
    rec.clear()
    tr = trace.Tracer(enabled=True)
    trace.set_tracer(tr)
    led = GoodputLedger()
    led.attach(tr)
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    events.set_default(reg)
    try:
        @jax.jit
        def setup_record_both(x):
            return jnp.cos(x) - 1.0

        with tr.span("user.block"):
            jax.block_until_ready(setup_record_both(np.ones(7, np.float32)))
    finally:
        led.detach(tr)
    for tracer, parent in ((rec, None), (tr, "user.block")):
        got = _compiles_of(tracer, "setup_record_both")
        assert [p for p, _, _ in got if p != "jaxpr_trace"] == [
            "jaxpr_to_mlir_module", "backend_compile"]
        assert got[-1][1]["cache"] in ("none", "miss")
        # each tracer names the span open in ITS OWN stack
        assert {par for _, _, par in got} == {parent}
    # the default tracer hears the nested traces too, as before
    assert len(_spans(tr, "compile.jaxpr_trace")) \
        > len(_spans(rec, "compile.jaxpr_trace")) == 1
    doc = led.snapshot()
    assert doc["classes"]["recompile"]["ms"] > 0
    assert doc["counts"]["compiles"] >= 3
    read = reg.read()
    assert read["compile.count"] == 1 and read["compile.ms"] > 0
    assert "compile.cache_hits" not in read


# ---------------------------------------------------------------------------
# a fresh process: the import's span, and what the persistent cache did
# ---------------------------------------------------------------------------

_CHILD = """
import json, sys
import numpy as np
{preload}
import apex_tpu
import jax
from apex_tpu.telemetry import MemorySink, Registry, events, trace
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
events.set_default(reg)

@jax.jit
def cached_probe(x):
    return x * 3.0 + 1.0

jax.block_until_ready(cached_probe(np.ones(16, np.float32)))
spans = [e for e in trace.setup_tracer().export()["traceEvents"]
         if e.get("ph") == "X"]
print(json.dumps({{"spans": spans, "read": reg.read()}}))
"""


def _child(cache_dir, preload=""):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD.format(preload=preload),
         str(cache_dir)], env=env, capture_output=True, text=True,
        timeout=300, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def cold_and_warm(tmp_path_factory):
    cache_dir = tmp_path_factory.mktemp("jax_cache")
    return _child(cache_dir), _child(cache_dir, preload="import jax")


def test_import_leaves_its_span(cold_and_warm):
    for out, preloaded in zip(cold_and_warm, (False, True)):
        first = out["spans"][0]
        assert first["name"] == "setup.import" and first["parent"] is None
        assert first["args"] == {"jax_preloaded": preloaded}
        assert first["dur"] > 0
        # nothing of the import lies outside it
        assert all(e["ts"] >= first["ts"] for e in out["spans"])


def test_a_cache_hit_is_named_and_is_no_compilation(cold_and_warm):
    cold, warm = cold_and_warm

    def build(out):
        (e,) = [e for e in out["spans"]
                if e["name"] == "compile.backend_compile"]
        assert e["args"]["fun_name"] == "jit(cached_probe)"
        return e["args"]

    assert build(cold)["cache"] == "miss" and "retrieval_s" not in build(cold)
    assert cold["read"]["compile.count"] == 1
    assert "compile.cache_hits" not in cold["read"]
    assert build(warm)["cache"] == "hit"
    assert 0 < build(warm)["retrieval_s"] < 60
    assert warm["read"].get("compile.count", 0) == 0
    assert warm["read"]["compile.cache_hits"] == 1
    assert warm["read"]["compile.ms"] > 0


# ---------------------------------------------------------------------------
# the example's state build
# ---------------------------------------------------------------------------

def test_run_standard_leaves_setup_state_around_its_programs():
    from apex_tpu.models import TransformerConfig
    from apex_tpu.parallel import create_mesh, use_mesh
    spec = importlib.util.spec_from_file_location(
        "pretrain_for_setup_record",
        os.path.join(ROOT, "examples", "bert", "pretrain.py"))
    pretrain = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pretrain)
    args = pretrain.parse_args(["--seq-len", "16", "--batch-size", "2",
                                "--layers", "1", "--d-model", "32",
                                "--heads", "2", "--vocab", "64"])
    cfg = TransformerConfig(
        vocab_size=args.vocab, max_len=args.seq_len, num_layers=args.layers,
        d_model=args.d_model, num_heads=args.heads, d_ff=4 * args.d_model,
        dtype=jnp.bfloat16, remat=args.remat, attn_impl=args.attn)
    mesh = create_mesh({"data": 1}, devices=jax.devices()[:1])
    rec = trace.setup_tracer()
    rec.clear()
    with use_mesh(mesh):
        state, step = pretrain.run_standard(args, cfg, mesh)
        (span,) = _spans(rec, "setup.state")
        assert span["parent"] is None
        children = [e for e in _spans(rec) if e["parent"] == "setup.state"]
        assert children and all(e["name"].startswith("compile.")
                                and span["ts"] <= e["ts"] for e in children)
        # the one-program state build is among them, by name
        built = [e["args"]["fun_name"] for e in children
                 if e["name"] == "compile.backend_compile"]
        assert "jit(<lambda>)" in built
        # the step's own programs come after the span, as top-level entries
        tokens, targets, weights = pretrain.synthetic_mlm(
            np.random.RandomState(0), args.batch_size, args.seq_len,
            cfg.vocab_size)
        state, loss = step(state, {"tokens": tokens, "targets": targets,
                                   "weights": weights})
        assert np.isfinite(float(loss))
    got = _compiles_of(rec, "train_step")
    assert [p for p, _, _ in got] == ["jaxpr_trace", "jaxpr_to_mlir_module",
                                      "backend_compile"]
    assert all(parent is None for _, _, parent in got)
