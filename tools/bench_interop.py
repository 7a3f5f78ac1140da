#!/usr/bin/env python
"""Measure the torch-interop bridge's per-step cost (docs/interop.md).

Three configurations over the same ~25M-param tensor list (CPU):

  packed   — TorchFusedOptimizer + FusedAdam(impl='fused'): one threaded
             C++ pack (csrc/host_pack.cpp) -> step_flat -> one unpack;
  per-leaf — TorchFusedOptimizer + FusedAdam(impl='xla'): the fallback
             copy path (per-leaf DLPack import + full param re-read);
  torch    — torch.optim.Adam, the pure-torch baseline the bridge must
             stay comparable to for the hand-off to be worth it.

Reference anchor: the deprecated contrib interop surface
``apex/contrib/optimizers/fused_adam.py:175`` (step(grads=, scale=)).

Run: ``JAX_PLATFORMS=cpu python tools/bench_interop.py [--params 25]``
Prints one JSON line with per-step ms for each configuration.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# CPU-only measurement of host-side packing: pin the platform before
# any jax op so the script never takes an accelerator
from apex_tpu.utils.platform import force_cpu

force_cpu()


def make_tensors(torch, n_million):
    """A BERT-base-ish mix: a few big matrices + many small vectors."""
    g = torch.Generator().manual_seed(0)
    import math
    shapes = []
    total = int(n_million * 1e6)
    while sum(math.prod(s) for s in shapes) < total * 0.9:
        shapes += [(1024, 1024), (4096, 1024), (1024,), (1024,)]
    params = [torch.nn.Parameter(torch.randn(*s, generator=g) * 0.02)
              for s in shapes]
    for p in params:
        p.grad = torch.randn(*p.shape, generator=g) * 0.01
    return params


def time_steps(stepfn, n_warm=2, n_time=10):
    for _ in range(n_warm):
        stepfn()
    t0 = time.perf_counter()
    for _ in range(n_time):
        stepfn()
    return (time.perf_counter() - t0) / n_time * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--params", type=float, default=25.0,
                    help="model size in millions of parameters")
    args = ap.parse_args()

    import torch
    from apex_tpu.interop import TorchFusedOptimizer
    from apex_tpu.optimizers import FusedAdam

    out = {"metric": "interop_step_ms", "backend": "cpu"}

    params = make_tensors(torch, args.params)
    out["n_params"] = int(sum(p.numel() for p in params))
    out["n_tensors"] = len(params)

    opt = TorchFusedOptimizer(params, FusedAdam(lr=1e-3, impl="fused"))
    out["packed_ms"] = round(time_steps(lambda: opt.step()), 2)

    params2 = make_tensors(torch, args.params)
    opt2 = TorchFusedOptimizer(params2, FusedAdam(lr=1e-3, impl="xla"))
    out["per_leaf_ms"] = round(time_steps(lambda: opt2.step()), 2)

    params3 = make_tensors(torch, args.params)
    topt = torch.optim.Adam(params3, lr=1e-3)
    out["torch_adam_ms"] = round(time_steps(topt.step), 2)

    print(json.dumps(out))


if __name__ == "__main__":
    main()
