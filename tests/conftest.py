"""Test harness: force an 8-device CPU platform so multi-chip SPMD paths are
exercised without TPU hardware (the capability called out in SURVEY §4 —
``xla_force_host_platform_device_count`` gives N-device SPMD on CPU, which the
reference's real-multiprocess test harness could not do).

All platform-forcing logic (env flags, config update) lives in
``apex_tpu.utils.platform.force_cpu`` — shared with the worker subprocesses.
Importing apex_tpu imports jax but does NOT initialize a backend, so calling
``force_cpu`` right after import is still early enough.
"""
from apex_tpu.utils.platform import force_cpu

force_cpu(8)

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    return jax.devices()


@pytest.fixture(autouse=True)
def _amp_unpatch():
    """Keep autocast patches from leaking between tests."""
    yield
    from apex_tpu.amp import amp as _amp
    if _amp.is_initialized():
        _amp.uninit()
