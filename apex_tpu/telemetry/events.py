"""Structured event stream wired into the existing hook points.

Three producers feed the registry (ISSUE: amp scaler transitions, DDP
collective meters, loader queue gauges):

  * **amp scaler** — the scaler is pure pytree state updated *inside*
    the jitted step, so transitions are observed host-side by comparing
    the pre/post ``ScalerState`` (one batched ``device_get`` for the
    scalars): :func:`observe_scaler` / :func:`observe_amp` classify
    halve (overflow), double (scale_window growth) and steady steps via
    ``amp.scaler.transition_kind`` and emit ``amp.overflow`` /
    ``amp.loss_scale_doubled`` events plus the ``amp.loss_scale`` gauge.
  * **DDP collectives** — ``parallel.distributed.allreduce_tree`` calls
    :func:`record_collective` with the payload bytes, leaf count and
    host wall time of each reduction it builds; the ZeRO
    reduce-scatter/allgather paths report through the same hook
    (``op=``).  With a compressed scheme selected
    (``parallel.collectives``) the hook also carries the WIRE bytes,
    payload dtype and scheme, feeding the
    ``*_compressed_bytes``/``*_compression_ratio`` meters.  Under
    ``jit`` the call fires at *trace* time (the collective itself fuses
    into the step, so bytes/calls are per-traced-program facts and the
    wall time is dispatch cost); in eager/shard_map-debug use it is
    per-call.  The on-device collective time belongs to the profiler,
    not this meter — documented in docs/telemetry.md.
  * **data loader** — ``data.loader.NativeLoader`` reports the consumer
    wait per batch and (python-ring path) the queue depth after each
    dequeue via :func:`record_loader`.

All hooks route through the process-default registry
(:func:`apex_tpu.telemetry.set_default`); with none installed every hook
is a single attribute check and an early return — instrumented library
code stays free when telemetry is off.
"""
from __future__ import annotations

import collections
import threading
from typing import Optional

from . import registry as _registry
from . import trace as _trace


# -- default-registry plumbing (lives here so the hooks avoid importing
#    the package __init__ back into themselves) -----------------------------

_default: Optional[_registry.Registry] = None


def set_default(reg: Optional[_registry.Registry]):
    """Install ``reg`` as the process-default registry the library hooks
    (DDP, loader) report into.  Pass None to uninstall.  Returns the
    previous default so callers can restore it."""
    global _default
    prev = _default
    _default = reg
    return prev


def get_default() -> Optional[_registry.Registry]:
    return _default


def active() -> bool:
    """True when a default registry is installed and enabled — the fast
    guard every library hook checks first."""
    return _default is not None and _default.enabled


def metering() -> bool:
    """True when EITHER a default registry or a default tracer is
    installed — instrumented library code (the DDP collective meter)
    measures when anything downstream will consume it, and stays free
    otherwise."""
    return active() or _trace.active()


# -- amp scaler transitions --------------------------------------------------

def observe_scaler(reg, prev, new, *, loss_id: int = 0) -> Optional[str]:
    """Classify one scaler update (host-side, after the jitted step) and
    emit the matching event/metrics into ``reg``.

    ``prev``/``new`` are the ``ScalerState`` before/after ``amp_step``
    (or ``scaler.update``).  One batched ``device_get`` reads the four
    scalars — gated on the registry being enabled, so an instrumented
    loop with telemetry off pays NO host sync here (the subsystem's
    disabled-mode contract).  Returns the transition kind ("overflow" |
    "grew" | "steady"), or None when disabled (nothing was read).
    """
    if reg is None or not reg.enabled:
        return None
    import jax
    from ..amp import scaler as _scaler
    with _trace.span("amp.observe_scaler", loss_id=loss_id):
        ps, ns, pu, nu = (float(v) for v in jax.device_get(
            (prev.loss_scale, new.loss_scale, prev.unskipped, new.unskipped)))
    kind = _scaler.transition_kind(ps, ns, pu, nu,
                                   scale_window=prev.scale_window,
                                   min_loss_scale=prev.min_loss_scale,
                                   max_loss_scale=prev.max_loss_scale)
    reg.gauge("amp.loss_scale").set(ns)
    if kind == "overflow":
        reg.counter("amp.overflow_steps").add(1)
        reg.event("amp.overflow", loss_id=loss_id,
                  old_scale=ps, new_scale=ns)
    elif kind == "grew":
        reg.event("amp.loss_scale_doubled", loss_id=loss_id,
                  old_scale=ps, new_scale=ns, after_steps=int(pu) + 1)
    return kind


def observe_amp(reg, prev_state, new_state):
    """Per-loss :func:`observe_scaler` over two ``AmpState`` bundles
    (the host-side companion to the jitted ``amp.amp_step``).  Returns
    the list of transition kinds, one per scaler."""
    return [observe_scaler(reg, p, n, loss_id=i)
            for i, (p, n) in enumerate(zip(prev_state.scalers,
                                           new_state.scalers))]


# -- library hooks (no-ops without a default registry) -----------------------

def record_collective(axis_name: str, nbytes: int, n_leaves: int,
                      seconds: float, *, wire_bytes=None, dtype=None,
                      scheme=None, op: str = "allreduce",
                      family: Optional[str] = None) -> None:
    """Collective meter: bytes reduced + wall time per
    ``allreduce_tree``/``Reducer.reduce`` call (``op="allreduce"``), per
    ZeRO collective (``op="reduce_scatter"``/``"allgather"``), and per
    DDP weight-update-sharding collective (``op="reduce_scatter"``/
    ``"param_allgather"`` with ``family="ddp"`` —
    ``parallel.weight_update``).  ``family`` prefixes the metric names;
    it defaults to ``"ddp"`` for the allreduce and ``"zero"``
    otherwise, preserving the historical names.  See module docstring
    for the trace-time semantics under jit.

    Compression accounting (docs/telemetry.md): ``nbytes`` is the
    LOGICAL payload (what an uncompressed reduction would move);
    ``wire_bytes`` is what the selected collective scheme actually
    ships (defaults to ``nbytes`` — uncompressed).  ``dtype`` labels
    the wire payload ("int8", "bfloat16", ... or "mixed"), ``scheme``
    names the collective scheme.  Counters:
    ``<family>.<op>_compressed_bytes`` accumulates the wire bytes and
    the ``<family>.<op>_compression_ratio`` gauge carries the per-call
    logical/wire ratio, so a run's compression win is provable from the
    JSONL alone."""
    wire = int(nbytes if wire_bytes is None else wire_bytes)
    if family is None:
        family = "ddp" if op == "allreduce" else "zero"
    name = f"{family}.{op}"
    extra = {}
    if dtype is not None:
        extra["dtype"] = str(dtype)
    if scheme is not None:
        extra["scheme"] = str(scheme)
    _trace.note_span(name, seconds, axis=axis_name,
                     bytes=int(nbytes), leaves=int(n_leaves),
                     wire_bytes=wire, **extra)
    if not active():
        return
    reg = _default
    reg.counter(f"{name}_calls").add(1)
    reg.counter(f"{name}_bytes").add(nbytes)
    reg.counter(f"{name}_compressed_bytes").add(wire)
    if op == "allreduce":
        reg.counter("ddp.allreduce_leaves").add(n_leaves)
    if wire:
        reg.gauge(f"{name}_compression_ratio").set(nbytes / wire)
    reg.histogram(f"{name}_host_ms").observe(seconds * 1e3)
    reg.event(name, axis=axis_name, bytes=int(nbytes),
              leaves=int(n_leaves), host_ms=seconds * 1e3,
              wire_bytes=wire, **extra)


FLASH_BWD_PATHS = ("whole_key", "projection", "resident", "partials",
                   "split", "window", "xla")


def record_flash_bwd(path: str, bq: Optional[int] = None,
                     bk: Optional[int] = None,
                     nk: Optional[int] = None) -> None:
    """Which flash-attention backward a traced program holds
    (``contrib.multihead_attn.flash``): one call per traced backward —
    trace time, like :func:`record_collective` under jit — with the
    ``path`` taken (:data:`FLASH_BWD_PATHS`) and, for the Pallas paths,
    the fused chain's tile ``(bq, bk)`` and the number of k blocks a head
    ``nk`` the choice was made from (nk = 1 is ``whole_key``).  Counters
    ``flash.bwd_calls.<path>`` and one ``flash.bwd`` event."""
    if not active():
        return
    if path not in FLASH_BWD_PATHS:
        raise ValueError(f"path must be one of {FLASH_BWD_PATHS}, "
                         f"got {path!r}")
    reg = _default
    reg.counter(f"flash.bwd_calls.{path}").add(1)
    tile = {} if bq is None else {"bq": int(bq), "bk": int(bk),
                                  "nk": int(nk)}
    reg.event("flash.bwd", path=path, **tile)


def record_moe_layout(experts: int, held: int, top_k: int,
                      buffer_rows: int, sum_rows: int) -> None:
    """The routing layout a traced program holds
    (``parallel.expert.routed_experts``): one call per traced expert layer
    — trace time, like :func:`record_flash_bwd` — with the number of
    experts routed over, how many of them are held here, the experts a
    token takes and the rows of the dispatch buffer (twice the held
    experts' even share of the tokens x top_k assignments; a load past it is
    walked again, so none can be dropped) and ``sum_rows``, the rows the
    gathers of one sum back to the tokens write (buffer + tokens in row
    space, tokens x top_k a slot at a time).  Counter
    ``moe.layers_traced`` and one ``moe.layout`` event."""
    if not active():
        return
    reg = _default
    reg.counter("moe.layers_traced").add(1)
    reg.event("moe.layout", experts=int(experts), held=int(held),
              top_k=int(top_k), buffer_rows=int(buffer_rows),
              sum_rows=int(sum_rows))


def record_ssm_layout(heads: int, chunk: int, chunks: int) -> None:
    """The scan layout a traced program holds (``models.nemotron_h``): one
    call per traced Mamba-2 layer — trace time, like
    :func:`record_moe_layout` — with the heads held here, the steps of a
    chunk and the chunks a sequence is cut into (the length of the
    recurrence across chunks).  Counter ``ssm.layers_traced`` and one
    ``ssm.layout`` event."""
    if not active():
        return
    reg = _default
    reg.counter("ssm.layers_traced").add(1)
    reg.event("ssm.layout", heads=int(heads), chunk=int(chunk),
              chunks=int(chunks))


GDN_RULE_PATHS = ("kernel", "jnp")


def record_gdn_rule(path: str) -> None:
    """Which form of the gated delta rule a traced call took
    (``models.qwen3_next.gated_delta_rule``): one call per traced rule —
    trace time, like :func:`record_flash_bwd` — with ``path`` ``"kernel"``
    (the Pallas pair of ``ops.gated_delta_rule``) or ``"jnp"`` (the shapes
    it does not take).  Counter ``gdn.rule_calls.<path>`` and one
    ``gdn.rule`` event."""
    if not active():
        return
    if path not in GDN_RULE_PATHS:
        raise ValueError(f"path must be one of {GDN_RULE_PATHS}, "
                         f"got {path!r}")
    reg = _default
    reg.counter(f"gdn.rule_calls.{path}").add(1)
    reg.event("gdn.rule", path=path)


UPDATE_PATHS = ("leafwise", "flat")


def record_update_path(path: str) -> None:
    """Which form of the optimizer update a traced step took
    (``amp.amp_step``, ``parallel.weight_update.ShardedUpdate.step``): one
    call per traced update — trace time, like :func:`record_gdn_rule` —
    with ``path`` ``"leafwise"`` (state and gradients are trees, each leaf
    updated in its own layout: what a replicated update takes) or
    ``"flat"`` (one buffer a field, what a sharded update slices).  Counter
    ``optimizer.update_path.<path>`` and one ``optimizer.update`` event."""
    if not active():
        return
    if path not in UPDATE_PATHS:
        raise ValueError(f"path must be one of {UPDATE_PATHS}, "
                         f"got {path!r}")
    reg = _default
    reg.counter(f"optimizer.update_path.{path}").add(1)
    reg.event("optimizer.update", path=path)


#: the newest steps' ``rows`` as :func:`record_expert_rows` was given them
#: (numpy (expert layers, held) int arrays), oldest first
EXPERT_ROWS_KEPT = 64
_expert_rows: "collections.deque" = collections.deque(maxlen=EXPERT_ROWS_KEPT)


def record_expert_rows(rows, dropped, walks, slots=None) -> None:
    """Step side of the routing meter: what one forward pass sent the held
    experts.  ``rows`` (expert layers, held) are the assignments each held
    expert of each layer was sent, ``dropped`` the held assignments that
    found no row in the buffer (0), ``walks`` (expert layers,) the times
    each layer went over its buffer (1 where the load fit), ``slots``
    (expert layers,; None where the caller has none) the most held
    assignments any token of each layer has.  A model
    calls it through ``jax.debug.callback`` once a forward pass, and only
    where :func:`active` was true when the step was traced.  Counters
    ``moe.rows_held`` / ``moe.rows_dropped`` / ``moe.walks``, histograms
    ``moe.load_max_over_mean`` (the fullest held expert over the mean, worst
    layer) and ``moe.slots_max`` (the fullest token's held assignments,
    worst layer), and the array itself in :func:`expert_rows`."""
    if not active():
        return
    import numpy as np
    rows = np.asarray(rows).reshape(-1, np.shape(rows)[-1])
    _expert_rows.append(rows)
    reg = _default
    reg.counter("moe.rows_held").add(int(rows.sum()))
    reg.counter("moe.rows_dropped").add(int(np.sum(dropped)))
    reg.counter("moe.walks").add(int(np.sum(walks)))
    reg.histogram("moe.load_max_over_mean").observe(float(
        (rows.max(axis=1) / np.maximum(rows.mean(axis=1), 1e-9)).max()))
    if slots is not None:
        reg.histogram("moe.slots_max").observe(float(np.max(slots)))


def expert_rows() -> list:
    """The last :data:`EXPERT_ROWS_KEPT` steps' rows, oldest first."""
    return list(_expert_rows)


def record_loader(depth: Optional[int], wait_seconds: float) -> None:
    """Loader meter: consumer wait per batch, ring/queue depth after the
    dequeue (None when the native ring can't report it)."""
    _trace.note_span("loader.wait", wait_seconds,
                     **({} if depth is None else {"depth": depth}))
    if not active():
        return
    reg = _default
    reg.histogram("loader.wait_ms").observe(wait_seconds * 1e3)
    if depth is not None:
        reg.gauge("loader.queue_depth").set(depth)
        reg.histogram("loader.depth_samples").observe(depth)


def record_loader_retry(batch_index: int, attempt: int, waited_s: float,
                        next_wait_s: float) -> None:
    """One bounded-retry attempt inside the loader's timed wait
    (docs/data.md stall hardening): the consumer saw an empty queue for
    a full wait window and is waiting again with a doubled budget
    instead of escalating yet.  ``loader.retry`` event + ``loader.
    retries`` counter; retries exhausted still raise the typed
    ``LoaderStallError``, so the event stream tells a healed hiccup
    from a real wedge."""
    _trace.note_event("loader.retry", step=int(batch_index),
                      fields={"attempt": int(attempt),
                              "waited_ms": waited_s * 1e3,
                              "next_wait_ms": next_wait_s * 1e3})
    if not active():
        return
    reg = _default
    reg.counter("loader.retries").add(1)
    reg.event("loader.retry", batch=int(batch_index), attempt=int(attempt),
              waited_ms=waited_s * 1e3, next_wait_ms=next_wait_s * 1e3)


def record_shard_checksum(shard: str, offset=None) -> None:
    """A shard failed its CRC32 check (``data.sharded`` — bit rot or an
    injected ``shard_corrupt`` fault): ``data.checksum_failed`` event +
    counter, emitted just before the typed ``ShardChecksumError``
    propagates so the failure is visible in the JSONL even when the
    run dies on it.  ``offset`` is the record offset within the shard
    the failing read wanted (None for a whole-shard verify sweep)."""
    fields = {"shard": str(shard)}
    if offset is not None:
        fields["offset"] = int(offset)
    _trace.note_event("data.checksum_failed", fields=fields)
    if not active():
        return
    reg = _default
    reg.counter("data.checksum_failures").add(1)
    reg.event("data.checksum_failed", **fields)


def record_update_sharding(state_bytes_per_replica: int,
                           world: int) -> None:
    """Weight-update-sharding gauges (``parallel.weight_update``):
    optimizer-state bytes actually held per replica under the current
    sharding, and the shard count — the 1/N memory win as a metered
    fact (a static shape property read at trace time, so it costs one
    attribute check with no registry installed)."""
    if not active():
        return
    reg = _default
    reg.gauge("ddp.opt_state_bytes_per_replica").set(
        float(state_bytes_per_replica))
    reg.gauge("ddp.update_shard_world").set(float(world))


def record_ckpt_exposed(seconds: float, reg=None, step=None) -> None:
    """Boundary-blocked checkpoint time (docs/telemetry.md Goodput
    ledger): the wall-clock the STEP LOOP actually waited on checkpoint
    machinery — writer drains/submits and the inline anchor/exit saves
    — as opposed to :func:`record_ckpt`'s ``ckpt.write_ms``, which is
    the background writer's own (overlapped) duration.  ``ckpt.
    exposed_ms`` gauge carries the last blocking occurrence and the
    ``ckpt.exposed_ms_total`` counter accumulates the run total, so a
    fully-overlapped background save provably contributes ~0."""
    if reg is None:
        reg = _default
    if reg is None or not reg.enabled:
        return
    reg.gauge("ckpt.exposed_ms").set(seconds * 1e3)
    reg.counter("ckpt.exposed_ms_total").add(seconds * 1e3)


def record_ckpt(seconds: float, nbytes: int, reg=None) -> None:
    """Checkpoint-write meter, called from the guard's BACKGROUND
    writer thread after each ``CheckpointManager.save``: write duration
    and bytes-written gauges (gauge set is a single atomic assignment,
    so the off-thread emit never races the main thread's flush).
    ``reg`` pins a registry (a guard constructed with ``registry=...``
    must meter into IT, like every other guard emission); default: the
    process default."""
    if reg is None:
        reg = _default
    if reg is None or not reg.enabled:
        return
    reg.gauge("ckpt.write_ms").set(seconds * 1e3)
    reg.gauge("ckpt.bytes_written").set(float(nbytes))


# -- jax compilation meter (docs/telemetry.md Goodput ledger, Set-up record) ---
# Recompilation is a first-class badput source: a shape-churn retrace
# silently inflates "step time" unless compile time is metered on its
# own.  ``jax.monitoring`` publishes per-phase compile durations
# (`/jax/core/compile/{jaxpr_trace,jaxpr_to_mlir_module,backend_compile}
# _duration`, each with the program's ``fun_name``); the listener turns
# each into a post-hoc ``compile.<phase>`` span in the set-up record
# (``trace.setup_tracer()``, always) and through the default tracer (when
# one is installed: it streams into an attached GoodputLedger as
# ``recompile`` badput), and accumulates ``compile.ms`` / ``compile.count``
# / ``compile.cache_hits`` counters through the default registry.
#
# jax times ``backend_compile`` around ``compile_or_get_cached``, so a
# persistent-cache HIT fires it too.  What the cache did arrives inside
# that interval, on the same thread, as events of its own
# (`/jax/compilation_cache/{cache_hits,cache_misses}`,
# `cache_retrieval_time_sec`): they are held per thread until the interval
# closes and land on its span as ``cache`` = ``hit`` (loaded) | ``miss``
# (compiled and written) | ``none`` (the persistent cache neither served
# nor stored it: no directory, or an entry under jax's size / compile-time
# thresholds) and, on a hit, ``retrieval_s``.
#
# jax's intervals nest: every ``jnp`` function is a ``jit`` of its own and
# reports a ``jaxpr_trace`` inside the trace of the program that calls it
# (thousands a model, tens of microseconds each; 5560 entries before the
# step's own in the CPU rehearsal of the smallest ResNet cell).  jax marks
# the START of each phase with a scalar event of the same name, so the
# listener keeps a depth per thread, and a trace that closes inside another
# open phase leaves no entry in the set-up record: the outer interval
# covers it, and the record's bound is spent on programs.  (The default
# tracer and the counters hear every event, as before.)
#
# The listeners register ONCE per process (jax.monitoring has no
# unregister short of clearing everyone's listeners), ``import apex_tpu``
# does it, and cost one prefix check per monitoring event; jax fires none
# on a cached ``jit`` call, so a warmed-up step sees no code of this.

_COMPILE_EVENT_PREFIX = "/jax/core/compile/"
_CACHE_EVENT_PREFIX = "/jax/compilation_cache/"
_CACHE_OUTCOMES = {"cache_misses": "miss", "cache_hits": "hit"}
_compile_listener_installed = False
_compiling = threading.local()      # .depth: open phases; .seen: see below


def _cache_seen() -> dict:
    """What the persistent cache said inside this thread's open
    ``backend_compile`` interval."""
    seen = getattr(_compiling, "seen", None)
    if seen is None:
        seen = _compiling.seen = {}
    return seen


def _on_compile_start(event, value, **kw) -> None:
    if isinstance(event, str) and event.startswith(_COMPILE_EVENT_PREFIX):
        _compiling.depth = getattr(_compiling, "depth", 0) + 1


def _on_cache_event(event, **kw) -> None:
    if not isinstance(event, str) \
            or not event.startswith(_CACHE_EVENT_PREFIX):
        return
    outcome = _CACHE_OUTCOMES.get(event[len(_CACHE_EVENT_PREFIX):])
    if outcome is not None:
        _cache_seen()["cache"] = outcome


def _on_compile_event(event, duration_secs, **kw) -> None:
    if not isinstance(event, str):
        return
    if event == _CACHE_EVENT_PREFIX + "cache_retrieval_time_sec":
        _cache_seen()["retrieval_s"] = float(duration_secs)
        return
    if not event.startswith(_COMPILE_EVENT_PREFIX):
        return
    phase = event[len(_COMPILE_EVENT_PREFIX):]
    if phase.endswith("_duration"):
        phase = phase[: -len("_duration")]
    # phases of this thread still open around the one that closes (a
    # listener installed inside a phase sees its end without its start)
    _compiling.depth = outer = max(getattr(_compiling, "depth", 1) - 1, 0)
    attrs = {}
    if kw.get("fun_name") is not None:
        attrs["fun_name"] = str(kw["fun_name"])
    if phase == "backend_compile":
        seen = _cache_seen()
        attrs.update({"cache": "none", **seen})
        seen.clear()
    # post-hoc span ending now: the listener fires right as the phase
    # completes, so the interval lands where the compile actually ran
    if not (outer and phase == "jaxpr_trace"):
        _trace.setup_tracer().add(f"compile.{phase}", float(duration_secs),
                                  **attrs)
    _trace.note_span(f"compile.{phase}", float(duration_secs), **attrs)
    if not active():
        return
    reg = _default
    reg.counter("compile.ms").add(float(duration_secs) * 1e3)
    if phase == "backend_compile":
        # one backend_compile per program built or loaded (trace/lowering
        # phases also fire for retraces): a load from the persistent cache
        # is no compilation, so the honest compile COUNT leaves it out
        if attrs["cache"] == "hit":
            reg.counter("compile.cache_hits").add(1)
        else:
            reg.counter("compile.count").add(1)


def install_compile_listener() -> bool:
    """Register the jax compilation meter (idempotent; returns True
    when the listener is active).  Import of jax is deferred to here —
    the tooling layer must never pay backend bring-up."""
    global _compile_listener_installed
    if _compile_listener_installed:
        return True
    try:
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(
            _on_compile_event)
        jax.monitoring.register_event_listener(_on_cache_event)
        jax.monitoring.register_scalar_listener(_on_compile_start)
    except Exception:   # pragma: no cover - monitoring API unavailable
        return False
    _compile_listener_installed = True
    return True
