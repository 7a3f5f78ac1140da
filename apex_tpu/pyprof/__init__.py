"""Profiling shim — the ``apex.pyprof`` analog over jax's profiler.

The reference's pyprof has three parts (SURVEY §5.1): (a) ``nvtx.init()``
monkey-patches every torch fn to push NVTX ranges encoding op/args/shapes
(``apex/pyprof/nvtx/nvmarker.py:27-222``); (b) ``parse`` reads nvprof SQLite;
(c) ``prof`` maps kernels to layers and computes FLOPs/bytes.

On TPU, (b) and (c) are owned by XLA + Perfetto/TensorBoard: a captured
trace already attributes time to named HLO ops with cost-analysis FLOPs.
What remains useful — and what this module provides — is the *annotation
API*: name regions of your step so they show up in the trace, plus
start/stop/trace helpers the examples call with ``--prof``.

    from apex_tpu import pyprof
    pyprof.init()                        # banner + no-op patching (parity)
    with pyprof.annotate("fwd"):         # named range in the trace
        loss = model(x)
    pyprof.start_trace("/tmp/trace")     # Perfetto/TensorBoard capture
    ... steps ...
    pyprof.stop_trace()

``annotate`` works both inside jit (becomes a ``jax.named_scope`` on the
lowered HLO) and outside (becomes a ``TraceAnnotation`` wall-time range).

The library names the blocks of a training step itself, from a fixed
vocabulary (:data:`SCOPES`): a device trace then says which block an
instruction belongs to (``op_name="jit(train_step)/.../apex.attn/
apex.flash/..."``; backward and recompute show as ``transpose(jvp(apex.attn))``
and ``rematted_computation`` in the same path).  See ``docs/pyprof.md``.
"""
from __future__ import annotations

import contextlib
import os
import re

import jax

#: The ``apex.*`` scopes the library enters, outermost first where they nest.
#: ``annotate`` refuses any other name that starts with ``apex.``: a reader
#: of a trace (``benchmarks/scopes.py``) can rely on exactly these.
SCOPES = (
    "apex.embed",          # models.transformer: token + position lookup, norm
    "apex.attn",           # _layer: ln1, QKV, head transposes, core, out proj
    "apex.flash",          # contrib.multihead_attn.flash, inside apex.attn
    "apex.mlp",            # _layer: ln2, the two matmuls, GELU, residual
    "apex.head",           # final norm and the vocabulary projection
    "apex.loss",           # transformer_loss: logits reshape -> weighted mean
    "apex.amp_step",       # amp.frontend.amp_step_multi, whole body
    "apex.unscale",        # inside it: unscale to f32 + the finite reduction
    "apex.opt_update",     # inside it: optimizer.step / step_flat + skip select
    "apex.model_copy",     # inside it: master -> model-precision copy
    "apex.ddp_allreduce",  # parallel.DistributedDataParallel.allreduce_grads
    "apex.conv",           # models.lfm2: norm, gated short convolution, residual
    "apex.moe",            # models.lfm2: norm, parallel.expert.routed_experts
    "apex.router",         # inside it: scores, top-k, weights (float32)
    "apex.experts",        # inside it: the grouped products and their gate
    "apex.ssm",            # models.nemotron_h: a whole Mamba-2 block
    "apex.ssm_scan",       # inside it: discretisation and the chunked scan
    "apex.latent",         # inside apex.moe: the two latent projections
    "apex.shared_expert",  # inside apex.moe: the expert every token passes
    "apex.gdn",            # models.qwen3_next: a whole Gated DeltaNet mixer
    "apex.gdn_rule",       # inside it: decays and the chunked delta rule
    "apex.mla",            # models.glm4_moe_lite: a whole latent-attention mixer
    "apex.mtp",            # models.glm4_moe_lite: the MTP join, block, head, loss
)

# jax strips debug info - where a named scope lives - before it hashes the
# persistent compile cache's key (cache_key.py:_canonicalize_ir).  On jax 0.9.0
# (ISSUE 24) f compiled bare, then under named_scope("apex.attn") against one
# cache directory, was a HIT whose text read op_name="jit(f)/dot_general": a
# profile of a cached step shows the names it was first compiled with.  So the
# key holds the metadata, and file names are written relative to the checkout
# (the parent of the apex_tpu package), else every checkout path would be a
# cold compile.  At import, not on the first ``annotate``: a seeded init, a
# reference check and the step then get the same kind of key in every run.
# A value the user has set stays, where it can be told from the default: jax
# keeps no record of who set a flag, so an explicit False is honoured from the
# environment (JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY=0) or from a
# jax.config.update made after this import.
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if "JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY" not in os.environ:
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
if jax.config.jax_hlo_source_file_canonicalization_regex is None:
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      re.escape(_ROOT + os.sep))


class _State:
    initialized = False
    trace_dir = None


_state = _State()   # process-wide, like the reference's patched namespaces


def init(enable_function_stack: bool = False) -> None:
    """API-parity entry point (``pyprof.nvtx.init``, nvmarker.py:206-222).

    The reference monkey-patches the framework so every op pushes a marker;
    under jit every HLO op is already named by its traceback — there is
    nothing to patch.  This prints the analogous banner and records that
    profiling was requested (``is_initialized``)."""
    print("apex_tpu.pyprof: jax.profiler owns op-level attribution on TPU "
          "(XLA names every HLO from its Python traceback); use "
          "annotate()/start_trace()/stop_trace() for custom ranges.")
    _state.initialized = True


def is_initialized() -> bool:
    return _state.initialized


@contextlib.contextmanager
def annotate(name: str, **attrs):
    """Named range visible in profiler traces.

    Inside a jit trace this contributes a ``jax.named_scope`` (op-name
    prefix in the HLO/XPlane); outside it opens a host ``TraceAnnotation``
    wall-clock range.  ``attrs`` are appended to the name (the reference
    encodes args into the NVTX message, nvmarker.py:46-108).  A name that
    starts with ``apex.`` must be one of :data:`SCOPES`."""
    if name.startswith("apex.") and name not in SCOPES:
        raise ValueError(f"{name!r} is not in apex_tpu.pyprof.SCOPES "
                         f"{SCOPES}: the apex.* vocabulary is fixed")
    if attrs:
        name = name + "|" + ",".join(f"{k}={v}" for k, v in attrs.items())
    with jax.named_scope(name), jax.profiler.TraceAnnotation(name):
        yield


def annotate_function(fn=None, *, name: str | None = None):
    """Decorator form of :func:`annotate` (the reference's per-function
    wrapper, nvmarker.py:110-130)."""
    import functools

    def deco(f):
        label = name or getattr(f, "__name__", "fn")

        @functools.wraps(f)
        def wrapped(*args, **kwargs):
            with annotate(label):
                return f(*args, **kwargs)
        return wrapped
    return deco(fn) if fn is not None else deco


def start_trace(log_dir: str) -> None:
    """Begin a profiler capture (TensorBoard/Perfetto-readable)."""
    jax.profiler.start_trace(log_dir)
    _state.trace_dir = log_dir


def stop_trace() -> None:
    jax.profiler.stop_trace()


@contextlib.contextmanager
def trace(log_dir: str):
    """Scoped capture: ``with pyprof.trace(dir): ...steps...``"""
    start_trace(log_dir)
    try:
        yield
    finally:
        stop_trace()


def cost_report(fn, *args, **kwargs):
    """FLOPs/bytes/roofline report for a compiled step — see
    :mod:`apex_tpu.pyprof.prof` (the reference's ``prof`` mode analog)."""
    from . import prof as _prof
    return _prof.cost_report(fn, *args, **kwargs)


def server(port: int = 9999):
    """Live-attach profiling server (``jax.profiler.start_server``) — the
    'nvprof attach' analog; connect from TensorBoard's profile tab."""
    return jax.profiler.start_server(port)
