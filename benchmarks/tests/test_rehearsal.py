"""Every cell's code path end to end on the CPU at a tiny size, by calling the
harness as a function (the command line has no CPU switch).

The tiny cells live in ``tests/tiny/`` — their own ``BENCHMARK.json``, their
own directory of configurations, cell files and one per-layer reader
(``window_steps``) — which is also the proof that a new configuration, cell
and per-layer metric are new files plus manifest entries: nothing under
``benchmarks/`` proper names them.
"""
import json
import os
import re
import subprocess
import sys

import pytest

from benchmarks import run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "tiny", "BENCHMARK.json")
REAL = os.path.join(ROOT, "BENCHMARK.json")

#: per-layer metrics that are counts, and so may be reported off the chip
COUNTS = {"amp_skipped_steps", "window_steps"}


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("cell,seconds", [("tiny_bert.s128", 0.5),
                                          ("tiny_bert.dp4_s128", 0.5),
                                          ("tiny_resnet.b8", 3.0)])
def test_cell_rehearses(cell, seconds, trace):
    result = run.run_cell(cell, 0, seconds, trace, manifest_path=TINY,
                          rehearse=True)
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    json.dumps(result)
    assert result["correct"] is True, result
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == (4 if "dp4" in cell else 1)
    # off the chip: counts only, never a time, a rate or a share
    if trace:
        # the added reader, in the cells its entry lists under "workloads"
        assert ("window_steps" in result["metrics"]) == ("bert" in cell)
        assert set(result["metrics"]) <= COUNTS
    else:
        assert result["metrics"] == {}


def test_dp4_step_reduces_the_gradient_trees_bytes():
    """What ``comm_bytes_per_step`` is printed beside on the chip, held to
    the program's own meter: ``allreduce_grads`` counts the bytes it reduces
    when the step is traced (``ddp.allreduce_bytes``).  2 layers, d 64, d_ff
    256, vocab 512, 128 positions, every gradient in bfloat16."""
    from apex_tpu.telemetry import MemorySink, Registry, events
    from benchmarks import flops
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    previous = events.set_default(reg)
    try:
        result = run.run_cell("tiny_bert.dp4_s128", 1, 0.2, True,
                              manifest_path=TINY, rehearse=True)
    finally:
        events.set_default(previous)
    assert result["correct"] is True
    metered = reg.read()
    d, f, layers = 64, 256, 2
    per_layer = (3 * d * d + 3 * d) + (d * d + d) + (d * f + f) \
        + (f * d + d) + 4 * d
    n = 512 * d + 128 * d + 2 * d + layers * per_layer + 2 * d
    assert flops.allreduce_payload_bytes([(n, 2)]) == 2 * n
    assert metered["ddp.allreduce_calls"] == 1       # traced once
    assert metered["ddp.allreduce_bytes"] == 2 * n


# the window of ``bert_large.dp4_s512 --seed 5`` as the chip printed it (PR
# 22): three losses above an untrained model's, then back on the trend.  The
# float32 reference read the same 15.5 on those parameters (see
# ``run.losses_fell``): an excursion of the optimizer, not a fault
_SEED_5 = [float(x) for x in """
 8.410 8.146 8.023 7.914 7.830 7.763 7.707 7.675 7.617 7.559 7.521 7.483
 7.422 7.374 7.306 7.268 7.221 7.164 7.138 7.105 7.042 7.008 6.949 6.915
 6.869 6.811 6.785 6.743 6.691 6.656 6.600 6.568 6.524 6.470 6.438 6.397
 6.349 6.313 6.258 6.227 6.185 6.130 6.099 6.060 6.017 5.978 5.923 5.889
 5.848 5.793 5.759 5.719 5.672 5.616 15.502 15.493 15.336 5.445 5.443 5.427
 """.split()]


def test_loss_rule():
    healthy = _SEED_5[:54] + [5.58, 5.54, 5.49] + _SEED_5[57:]
    assert run.losses_fell(healthy, 12) and run.off_path(healthy) == 0
    # the measured excursion: 3 of 60 above the first loss, under a tenth
    assert run.off_path(_SEED_5) == 3
    assert run.losses_fell(_SEED_5, 12)
    # ... but not a window that is excursion for more than a tenth
    assert not run.losses_fell(_SEED_5[:50] + [15.5] * 7 + _SEED_5[57:], 12)
    # ... and none at all in a window of under ten steps (s512 has six)
    assert not run.losses_fell([8.42, 8.30, 15.5, 7.99, 7.90, 7.79], 2)
    assert not run.losses_fell(_SEED_5[:40] + [float("nan")] * 8, 12)
    assert not run.losses_fell([5.0, 5.0, 5.0, 5.0], 2)      # learned nothing
    assert not run.losses_fell([5.0, 5.5, 5.6, 5.7] * 5, 2)  # rose
    assert run.losses_fell([8.42, 8.30, 8.11, 7.99, 7.90, 7.79], 2)
    assert run.losses_fell([3.0, 2.0], 16)       # shorter than two chunks
    # resnet50.b256 as printed: a bump under the first loss is no excursion
    assert run.losses_fell([4.355, 3.711, 3.959, 3.812, 3.253, 2.765], 3)


def test_command_line_refuses_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "bert_large.s512", "--seed", "0", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_unknown_device_kind_is_an_error():
    assert run.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(run.Refused, match="not in peaks.json"):
        run.load_peaks("TPU v9 imaginary")


def test_manifest_names_files_that_exist():
    """Every entry of the real ``BENCHMARK.json`` resolves, and the contract's
    limits that can be checked here hold."""
    manifest = run.Manifest(REAL)
    doc = manifest.doc
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    configs = {c["name"] for c in doc["configs"]}
    for c in doc["configs"]:
        with open(os.path.join(manifest.root, c["file"])) as f:
            config = json.load(f)
        manifest.find("jobs", config["job"] + ".py")
        manifest.find("reference", config["reference"] + ".py")
        assert config["reference_tolerance"]["why"]
        assert config["reduced"] == c["reduced"]
    pairs = set()
    for w in doc["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert len(w["why"]) <= 200
        manifest.load_json("workloads", w["name"] + ".json")
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(doc["workloads"])
    four = [w for w in doc["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(doc["workloads"]) // 4)
    e2e = {m["name"] for m in doc["end_to_end"]}
    assert "setup_s" in e2e
    for m in doc["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in doc["per_layer"]:
        assert m["moves"] in e2e
        manifest.find("layer_metrics", m["name"] + ".py")


_NAME = re.compile(r"[A-Za-z0-9][A-Za-z0-9_.-]{0,63}")
_LAYER = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
_PATH = re.compile(r"[A-Za-z0-9_./-]{1,200}")
_METRIC_KEYS = {"name", "unit", "better", "source"}
_SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.mark.parametrize("path", [REAL, TINY], ids=["real", "tiny"])
def test_manifest_is_spelt_as_the_contract_says(path):
    """The limits the driver checks before any run: names, layers, paths,
    the keys of each entry.  (PR 22's first manifest was refused over a layer
    called ``entry loop``.)"""
    with open(path) as f:
        text = f.read()
    assert len(text.encode()) <= 64 * 1024
    doc = json.loads(text)
    assert 1 <= len(doc["command"]) <= 32
    assert 1 <= len(doc["paths"]) <= 16
    for p in doc["paths"] + [c["file"] for c in doc["configs"]]:
        assert _PATH.fullmatch(p) and not p.startswith("/"), p
        assert ".." not in p.split("/"), p
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}, c
        assert any(c["file"].startswith(p + "/") for p in doc["paths"]), c
    for w in doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}, w
    for m in doc["end_to_end"]:
        assert _METRIC_KEYS | {"bound"} <= set(m) \
            <= _METRIC_KEYS | {"bound", "workloads"}, m
    for m in doc["per_layer"]:
        assert _METRIC_KEYS | {"layer", "moves"} <= set(m) \
            <= _METRIC_KEYS | {"layer", "moves", "workloads"}, m
        assert _LAYER.fullmatch(m["layer"]), m
        assert m["source"] in _SOURCES, m
    cells = {w["name"] for w in doc["workloads"]}
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert m["better"] in ("higher", "lower"), m
        assert set(m.get("workloads", cells)) <= cells, m
    for k in ("configs", "workloads"):
        for x in doc[k]:
            assert len(x["why"]) <= 200, x
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in doc[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert _NAME.fullmatch(n), n
    # a full check with all 24 cells fits the driver's 43200 seconds
    assert 1 <= doc["run_seconds"] <= 51
    assert (2 + 14 * 24) * (doc["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_files_under_paths_have_plain_names():
    listed = subprocess.run(["git", "ls-files", "benchmarks"], cwd=ROOT,
                            capture_output=True, text=True)
    if listed.returncode or not listed.stdout:
        pytest.skip("not a git checkout")
    for name in listed.stdout.split("\n")[:-1]:
        assert _PATH.fullmatch(name), name
