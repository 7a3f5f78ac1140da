"""ctypes bindings + iterator for the native prefetch engine
(csrc/prefetch.cpp) — the reference ``data_prefetcher``/DALI-stage analog.

Contract:
  * ``ArraySource``: samples gathered from a caller-owned contiguous array
    (typically ``np.memmap``) at a seeded per-epoch shuffle; batches arrive
    in deterministic order for any worker count.
  * ``SyntheticSource``: C++-generated uniform data/labels (the examples'
    synthetic-ImageNet mode) — batch assembly costs zero Python time.
  * The loader yields DEVICE arrays: each host buffer is handed to
    ``jax.device_put`` and released back to the ring immediately after the
    transfer is dispatched, so workers refill it while the step runs.
"""
from __future__ import annotations

import ctypes
import dataclasses
import warnings
from typing import Optional, Tuple

import numpy as np

from ..utils import native

_lib = None
_lib_tried = False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _lib_tried
    if _lib_tried:
        return _lib
    _lib_tried = True
    try:
        so, _ = native.build("prefetch.cpp", "libapex_tpu_prefetch")
    except native.NativeBuildError as err:
        warnings.warn(f"data.loader: native prefetch engine unavailable, "
                      f"using the python ring instead ({err})",
                      RuntimeWarning)
        return None
    lib = ctypes.CDLL(so)
    lib.pf_create.restype = ctypes.c_void_p
    lib.pf_create.argtypes = [
        ctypes.c_char_p, ctypes.c_void_p, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int32, ctypes.c_uint64]
    lib.pf_acquire.restype = ctypes.c_int32
    lib.pf_acquire.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_int64)]
    lib.pf_release.argtypes = [ctypes.c_void_p, ctypes.c_int32]
    lib.pf_destroy.argtypes = [ctypes.c_void_p]
    _lib = lib
    return _lib


def native_available() -> bool:
    return _load() is not None


class LoaderStallError(RuntimeError):
    """The loader waited longer than ``wait_timeout`` for a batch — a
    wedged/stalled input source (or an injected ``loader_stall`` fault).
    Raised so the training driver (``resilience.TrainGuard`` or the
    caller) can act instead of hanging silently."""


def _fault_stall(step: int) -> float:
    """Resilience fault-injection shim (``loader_stall`` kind): sleeps
    and returns the injected stall seconds when a fault is scheduled at
    this batch index.  One cheap plan probe per batch when no plan is
    configured; import kept local so the loader stays importable
    without the apex_tpu package root."""
    try:
        from ..resilience import faults as _faults
    except ImportError:  # pragma: no cover - standalone module use
        return 0.0
    return _faults.maybe_stall(step)


def _record_loader(depth, wait_s) -> None:
    """Telemetry loader meter (docs/telemetry.md): consumer wait per
    batch + ring/queue depth after the dequeue (also a ``loader.wait``
    span when a tracer is installed).  A single attribute check when no
    default registry/tracer is installed; import kept local so the
    loader stays importable without the apex_tpu package root."""
    try:
        from ..telemetry import events as _tel_events
    except ImportError:  # pragma: no cover - standalone module use
        return
    _tel_events.record_loader(depth, wait_s)


def _record_retry(batch_index, attempt, waited_s, next_wait_s) -> None:
    """Telemetry for one bounded-retry attempt inside the timed wait
    (``loader.retry`` event + counter): the stall did not escalate YET
    — the consumer is waiting again with a doubled budget.  Import kept
    local like every other hook so the loader stays importable without
    the apex_tpu package root."""
    try:
        from ..telemetry import events as _tel_events
    except ImportError:  # pragma: no cover - standalone module use
        return
    _tel_events.record_loader_retry(batch_index, attempt, waited_s,
                                    next_wait_s)


def _timed_get(q, batch_index: int, wait_timeout, stall_retries: int):
    """The consumer-side dequeue discipline shared by the python ring
    and :class:`~apex_tpu.data.sharded.ShardedLoader`: injected
    ``loader_stall`` faults count against the first wait window; an
    empty queue is retried up to ``stall_retries`` times with
    exponentially growing budgets (each attempt metered as a
    ``loader.retry`` event) before the typed :class:`LoaderStallError`;
    a batch that ARRIVES after the total allowed budget is the same
    wedge signal, detected post-hoc.  Returns ``(item, wait_seconds)``.
    """
    import queue as _q
    import time as _time
    t0 = _time.perf_counter()
    _fault_stall(batch_index)    # injected stall counts as wait
    if wait_timeout is None:
        return q.get(), _time.perf_counter() - t0
    allowed = wait_timeout
    budget = max(wait_timeout - (_time.perf_counter() - t0), 0.0)
    attempt = 0
    while True:
        try:
            item = q.get(timeout=budget)
            break
        except _q.Empty:
            if attempt >= stall_retries:
                raise LoaderStallError(
                    f"loader stalled: no batch within {wait_timeout}s "
                    f"(+{attempt} backoff retries) on batch "
                    f"{batch_index}") from None
            attempt += 1
            budget = wait_timeout * (2 ** (attempt - 1))
            allowed += budget
            _record_retry(batch_index, attempt,
                          _time.perf_counter() - t0, budget)
    wait = _time.perf_counter() - t0
    if wait > allowed:
        # a batch that ARRIVED late (e.g. an injected stall with a
        # still-full ring) is the same wedge signal as an empty queue —
        # detect it post-hoc like the native path does
        raise LoaderStallError(
            f"loader stalled {wait:.2f}s (> wait_timeout={wait_timeout}s"
            + (f" + {attempt} retries" if attempt else "")
            + f") on batch {batch_index}")
    return item, wait


def _note_fill_span(batch_index, fill_s) -> None:
    """Producer-side ``loader.fill`` span (docs/telemetry.md tracing):
    how long each batch took to ASSEMBLE, recorded from the fill
    thread — the other half of the wait/fill pair a stall diagnosis
    needs.  No-op (one attribute check) without an installed tracer."""
    try:
        from ..telemetry import trace as _trace
    except ImportError:  # pragma: no cover - standalone module use
        return
    _trace.note_span("loader.fill", fill_s, batch=batch_index)


def _put_checking_stop(q, item, stop) -> bool:
    """put() that wakes up to honor `stop` — a producer blocked on a full
    queue must not outlive an abandoned consumer (it would pin the data
    source for the process lifetime)."""
    import queue as _q
    while not stop.is_set():
        try:
            q.put(item, timeout=0.1)
            return True
        except _q.Full:
            continue
    return False


@dataclasses.dataclass
class SyntheticSource:
    """Uniform [-1, 1) fp32 samples + uniform labels, generated natively."""
    shape: Tuple[int, ...]
    n_classes: int = 1000

    @property
    def sample_bytes(self) -> int:
        return int(np.prod(self.shape)) * 4


@dataclasses.dataclass
class ArraySource:
    """Gather rows of a contiguous fp32 array (e.g. ``np.memmap``).

    data: (N, *shape) float32, C-contiguous.  labels: (N,) int32.
    """
    data: np.ndarray
    labels: Optional[np.ndarray] = None

    def __post_init__(self):
        # A memmap must already be fp32 C-contiguous: converting would
        # silently materialize the whole dataset in RAM (4x on-disk for the
        # common uint8 layout), defeating the no-load contract — fail fast.
        if isinstance(self.data, np.memmap) and (
                self.data.dtype != np.float32
                or not self.data.flags["C_CONTIGUOUS"]):
            raise ValueError(
                "ArraySource memmap must be float32 and C-contiguous "
                f"(got {self.data.dtype}); re-export the dataset rather "
                "than loading it into RAM here.")
        self.data = np.ascontiguousarray(self.data, dtype=np.float32)
        if self.labels is not None:
            if isinstance(self.labels, np.memmap) and \
                    self.labels.dtype != np.int32:
                raise ValueError("ArraySource labels memmap must be int32 "
                                 f"(got {self.labels.dtype}).")
            self.labels = np.ascontiguousarray(self.labels, dtype=np.int32)
            assert self.labels.shape == (self.data.shape[0],)

    @property
    def shape(self):
        return self.data.shape[1:]

    @property
    def sample_bytes(self) -> int:
        return int(np.prod(self.shape)) * 4


class NativeLoader:
    """Iterator over prefetched (x, y) batches, device-put on dequeue.

    depth: ring size (reference data_prefetcher double-buffers; default 3
    keeps one extra batch in flight).  threads: C++ fill workers.
    device_put: set False to receive numpy copies instead of device arrays
    (e.g. when the consumer shards the batch itself).
    wait_timeout: seconds the consumer tolerates waiting for one batch
    before escalating (None = wait forever).  On the python ring an
    empty queue is retried ``stall_retries`` times with exponentially
    growing budgets (metered as ``loader.retry`` events) before the
    typed :class:`LoaderStallError` — a transient producer hiccup heals
    without killing the run, a real wedge still escalates to the same
    typed error.  The native ring's acquire is an uninterruptible C
    call, so detection there is post-hoc (the stall is reported as soon
    as the wedged acquire returns; no retry applies).
    """

    def __init__(self, source, batch_size: int, steps: int, *,
                 depth: int = 3, threads: int = 2, seed: int = 0,
                 device_put: bool = True,
                 wait_timeout: Optional[float] = None,
                 stall_retries: int = 2):
        self.source = source
        self.batch_size = int(batch_size)
        self.steps = int(steps)
        self.depth = int(depth)
        self.threads = int(threads)
        self.seed = int(seed)
        self.device_put = device_put
        self.wait_timeout = (None if wait_timeout is None
                             else float(wait_timeout))
        self.stall_retries = int(stall_retries)
        self._shape = (self.batch_size,) + tuple(source.shape)

    # -- iteration ---------------------------------------------------------
    def __iter__(self):
        lib = _load()
        if lib is None:
            yield from self._iter_python()
            return
        synthetic = isinstance(self.source, SyntheticSource)
        if synthetic:
            base, labels, n_samples, n_classes = None, None, 1, \
                self.source.n_classes
        else:
            base = self.source.data.ctypes.data_as(ctypes.c_char_p)
            labels = (self.source.labels.ctypes.data_as(ctypes.c_void_p)
                      if self.source.labels is not None else None)
            n_samples = self.source.data.shape[0]
            n_classes = 1
        h = lib.pf_create(base, labels, n_samples,
                          self.source.sample_bytes, self.batch_size,
                          n_classes, self.depth, self.threads, self.seed)
        if not h:
            yield from self._iter_python()
            return
        try:
            import jax
            xp = ctypes.c_void_p()
            yp = ctypes.c_void_p()
            tk = ctypes.c_int64()
            import time as _time
            for step in range(self.steps):
                t0 = _time.perf_counter()
                _fault_stall(step)       # injected stall counts as wait
                slot = lib.pf_acquire(h, ctypes.byref(xp), ctypes.byref(yp),
                                      ctypes.byref(tk))
                wait = _time.perf_counter() - t0
                # the C ring exposes no occupancy count: depth=None skips
                # the gauge, the wait histogram still lands
                _record_loader(None, wait)
                if slot < 0:
                    break
                if self.wait_timeout is not None and wait > self.wait_timeout:
                    lib.pf_release(h, slot)
                    raise LoaderStallError(
                        f"native loader stalled {wait:.2f}s (> "
                        f"wait_timeout={self.wait_timeout}s) acquiring "
                        f"batch {step}")
                n = int(np.prod(self._shape))
                x = np.ctypeslib.as_array(
                    ctypes.cast(xp, ctypes.POINTER(ctypes.c_float)),
                    shape=(n,)).reshape(self._shape)
                y = np.ctypeslib.as_array(
                    ctypes.cast(yp, ctypes.POINTER(ctypes.c_int32)),
                    shape=(self.batch_size,))
                # Copy out of the slot before releasing it: jax.device_put
                # may alias host memory (zero-copy on the CPU backend) or
                # read it asynchronously, and a worker refills the slot the
                # moment it is released.
                xc, yc = x.copy(), y.copy()
                lib.pf_release(h, slot)
                if self.device_put:
                    yield jax.device_put(xc), jax.device_put(yc)
                else:
                    yield xc, yc
        finally:
            lib.pf_destroy(h)

    # -- GIL-bound fallback (same ring/overlap structure) ------------------
    def _iter_python(self):
        import queue as _q
        import threading

        q: "_q.Queue" = _q.Queue(maxsize=self.depth)
        synthetic = isinstance(self.source, SyntheticSource)
        stop = threading.Event()

        def producer():
            try:
                _produce()
            except BaseException as e:  # surface to the consumer: a dead
                # producer with no sentinel would leave q.get() blocked
                # forever (training hang instead of an error)
                _put_checking_stop(q, e, stop)

        def _produce():
            rng = np.random.RandomState(self.seed & 0x7fffffff)
            n = (1 if synthetic else self.source.data.shape[0])
            order = None
            import time as _time
            for t in range(self.steps):
                if stop.is_set():
                    return
                t0 = _time.perf_counter()
                if synthetic:
                    x = rng.uniform(-1, 1, self._shape).astype(np.float32)
                    y = rng.randint(0, self.source.n_classes,
                                    self.batch_size).astype(np.int32)
                else:
                    bpe = max(1, n // self.batch_size)
                    if t % bpe == 0:
                        order = rng.permutation(n)
                    i0 = (t % bpe) * self.batch_size
                    idx = order[[(i0 + i) % n
                                 for i in range(self.batch_size)]]
                    x = self.source.data[idx]
                    y = (self.source.labels[idx]
                         if self.source.labels is not None
                         else np.zeros(self.batch_size, np.int32))
                _note_fill_span(t, _time.perf_counter() - t0)
                if not _put_checking_stop(q, (x, y), stop):
                    return
            _put_checking_stop(q, None, stop)

        th = threading.Thread(target=producer, daemon=True)
        th.start()
        try:
            import jax
            step = 0
            while True:
                item, wait = _timed_get(q, step, self.wait_timeout,
                                        self.stall_retries)
                step += 1
                _record_loader(q.qsize(), wait)
                if item is None:
                    return
                if isinstance(item, BaseException):
                    raise item
                x, y = item
                if self.device_put:
                    yield jax.device_put(x), jax.device_put(y)
                else:
                    yield x, y
        finally:
            stop.set()
