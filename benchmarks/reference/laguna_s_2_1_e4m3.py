#!/usr/bin/env python3
"""The control of ``laguna_s_2_1``'s ``reference_tolerance``: the plain
reference (``laguna_s_2_1.py`` beside this file) computed in the nearest
precision below the configuration's bfloat16 — its parameters and the input
of every norm rounded to an 8-bit float (e4m3: 4 exponent and 3 mantissa
bits), gradients straight through the rounding, everything else float32 as
it was.

It has the reference's interface (:func:`loss`, :func:`routing`), so the
cell's own job adapter takes it in the plain reference's place:

    python3 benchmarks/reference/laguna_s_2_1_e4m3.py --seed <n>

builds the cell's job as ``benchmarks/run.py`` does (same configuration,
traffic, seed, sample of ``reference_samples`` sequences, same
``job.reference_outcome`` and limits) with THIS module as the reference and
prints the reference check as one JSON line.  A tolerance that tells the
program from a coarser one reads ``"ok": false`` there; the exit code is 1
where the control passes the limits (they are then too loose), else 0.  It
needs the chip, as the cell does; no window is run.

The rounding is ``jax.lax.reduce_precision``, not a pair of converts: XLA
removes a round trip through a narrower type.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

import jax

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.job import load_module  # noqa: E402

CELL = "laguna_s_2_1.ep32_s8192"

#: a copy of the plain reference of this module's own: its ``_rms`` is
#: swapped while :func:`loss` traces, the adapter's copy stays plain
_plain = load_module(os.path.join(HERE, "laguna_s_2_1.py"),
                     "bench_reference_laguna_s_2_1_for_e4m3")
_plain_rms = _plain._rms


def e4m3(x):
    """``x`` rounded to 4 exponent and 3 mantissa bits; the gradient passes."""
    return x + jax.lax.stop_gradient(
        jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3) - x)


@contextlib.contextmanager
def _rounded_norms():
    _plain._rms = lambda x, gain, eps: _plain_rms(e4m3(x), gain, eps)
    try:
        yield
    finally:
        _plain._rms = _plain_rms


def loss(params, batch, model):
    with _rounded_norms():
        return _plain.loss(jax.tree_util.tree_map(e4m3, params), batch,
                           model)


def routing(params, batch, model):
    with _rounded_norms():
        return _plain.routing(jax.tree_util.tree_map(e4m3, params), batch,
                              model)


def outcome(seed: int, manifest_path: str, cell: str = CELL) -> dict:
    """The cell's reference check with this module as the reference."""
    from benchmarks import run as bench
    manifest = bench.Manifest(manifest_path)
    entry = manifest.entry("workloads", cell)
    with open(os.path.join(manifest.root, manifest.entry(
            "configs", entry["config"])["file"])) as f:
        config = json.load(f)
    traffic = manifest.load_json("workloads", cell + ".json")
    adapter = load_module(manifest.find("jobs", config["job"] + ".py"),
                          "bench_job_" + config["job"])
    return adapter.build(config, traffic, seed, jax.devices()[:entry["chips"]],
                         os.path.abspath(__file__)).reference


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    from benchmarks import run as bench
    bench.enable_compile_cache(ROOT)
    check = outcome(args.seed, os.path.join(ROOT, "BENCHMARK.json"))
    print(json.dumps({"control": "e4m3", "seed": args.seed, **check}),
          flush=True)
    return 1 if check["ok"] else 0


if __name__ == "__main__":
    sys.exit(main())
