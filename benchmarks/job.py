"""What a job adapter (``jobs/<kind>.py``) hands the harness, and the few
helpers every adapter needs.

An adapter's ``build(config, traffic, seed, devices, reference_path)`` drives
the program through its normal entry point and returns a :class:`Job`.  The
harness knows nothing about models: it steps ``job.step`` over
``job.batches``, and the per-layer readers take shapes and counts from
``job.facts``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import os
from typing import Any, Callable, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass
class Job:
    state: Any                      # opaque training state, threaded by step
    step: Callable                  # (state, batch) -> (state, loss), async
    batches: list                   # the ring the window cycles through
    samples_per_step: int           # over all the cell's chips
    flops_per_sample: float         # forward + backward, recompute excluded
    applied_steps: Callable         # state -> optimizer updates applied (int)
    skips_allowed: bool             # a dynamic loss scaler may skip a step
    reference: dict                 # outcome of the reference check
    optimizer_probe: Callable       # state -> (update, state, grads)
    facts: dict                     # shapes and counts for per-layer readers
    replicas_agree: Optional[Callable] = None   # state -> bool, multi-chip
    scope: Callable = contextlib.nullcontext    # entered around every step


def load_module(path: str, name: str):
    """A python file as a module, by path: examples, job adapters, references
    and per-layer readers are all found by name in a directory, none of them
    is a package."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_example(rel_path: str):
    """The shipped example at ``rel_path`` — the entry point a user runs."""
    name = "bench_example_" + os.path.basename(rel_path)[:-3]
    return load_module(os.path.join(ROOT, rel_path), name)


def expect_widths(what: str, got: dict, want: dict) -> None:
    """The program's configuration must have the sizes the cell's
    configuration file states: a cell never runs other widths under a
    published model's name."""
    wrong = {k: (got.get(k), v) for k, v in want.items() if got.get(k) != v}
    if wrong:
        raise ValueError(f"{what}: the program builds (got, configured) "
                         f"{wrong}")


def global_norm(tree):
    """sqrt(Σ x²) over every leaf, accumulated in float32."""
    import jax
    import jax.numpy as jnp
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree_util.tree_leaves(tree)))


def abs_sum(tree):
    """Σ |x| over every leaf in float32: a checksum that says two parameter
    sets hold the same values."""
    import jax
    import jax.numpy as jnp
    return sum(jnp.sum(jnp.abs(x.astype(jnp.float32)))
               for x in jax.tree_util.tree_leaves(tree))


def scalars(fn, *args) -> dict:
    """``fn(*args)``'s dict of device scalars as python floats."""
    import jax
    return {k: float(v) for k, v in jax.device_get(fn(*args)).items()}


def reference_outcome(system: dict, reference: dict, tolerance: dict) -> dict:
    """Compare the system's ``loss`` / ``grad_norm`` / ``param_abs_sum`` on
    the sample with the plain reference's.  ``tolerance`` comes from the
    configuration file, which also says why it is what it is."""
    def rel(key):
        return abs(system[key] - reference[key]) / max(
            abs(reference[key]), 1e-30)
    errors = {"loss_rel": rel("loss"), "grad_norm_rel": rel("grad_norm"),
              "param_abs_sum_rel": rel("param_abs_sum")}
    limits = {"loss_rel": tolerance["loss_rel"],
              "grad_norm_rel": tolerance["grad_norm_rel"],
              # same seed, same init, float32 on both sides: only the order
              # of a sum differs
              "param_abs_sum_rel": 1e-4}
    ok = all(errors[k] <= limits[k] for k in limits)   # NaN compares false
    return {"ok": ok, "errors": errors, "limits": limits,
            "system": system, "reference": reference}
