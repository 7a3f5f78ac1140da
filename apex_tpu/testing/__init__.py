"""Public test harness — the ``apex.testing`` analog.

The reference exposes ``apex.testing.common_utils`` (``TEST_WITH_ROCM`` env
gate + ``skipIfRocm`` decorator, `common_utils.py:12-22`) so downstream test
suites can gate on the platform.  The TPU-side equivalents:

    from apex_tpu import testing

    testing.force_cpu(8)          # 8-device virtual CPU cluster (conftest)
    with testing.cpu_platform(4): # scoped: env/config restored on exit
        ...

    @testing.skip_if_no_tpu       # pytest-style decorators
    def test_kernel_on_chip(): ...

    @testing.skip_if_cpu
    def test_needs_accelerator(): ...

``force_cpu`` is how this repo's own ``tests/conftest.py`` builds the fake
cluster the reference could not (SURVEY §4: real multi-process GPUs there,
``xla_force_host_platform_device_count`` here).  It must run before the
first jax operation: a process keeps the backend it initialised.
"""
from __future__ import annotations

from ..utils.platform import (backends_initialized, cpu_platform,
                              force_cpu)

__all__ = ["backends_initialized", "cpu_platform", "force_cpu",
           "skip_if_no_tpu", "skip_if_cpu", "on_tpu"]


def on_tpu() -> bool:
    import jax
    try:
        return jax.default_backend() == "tpu"
    except Exception:
        return False


def _skip_unless(pred, reason):
    """Call-time skip (``unittest.skipIf`` semantics, like the reference's
    ``skipIfRocm``) — evaluates the predicate when the test RUNS, so the
    backend chosen by the harness is the one consulted."""
    import functools

    def deco(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            if not pred():
                import pytest
                pytest.skip(reason)
            return fn(*args, **kwargs)
        return wrapped
    return deco


def skip_if_no_tpu(fn):
    """Skip unless a TPU backend is live (``skipIfRocm`` flipped: the gated
    resource here is the chip, not the vendor)."""
    return _skip_unless(on_tpu, "requires a TPU backend")(fn)


def skip_if_cpu(fn):
    """Skip on the CPU backend (interpret-mode Pallas, fake collectives)."""
    import jax
    return _skip_unless(lambda: jax.default_backend() != "cpu",
                        "not meaningful on the CPU backend")(fn)
