"""Choosing the backend a process runs on, and where it keeps compiled code.

A process uses whatever backend jax brings up — the TPU on a machine that
has one.  Nothing here probes, retries or falls back: an entry point that
needs the chip asks ``found_tpu`` and exits non-zero when the answer is no
(``chip_smoke.py``).

``force_cpu`` is for the processes that must NOT take the chip: the test
suite and its worker subprocesses pin the CPU platform (with N virtual
devices for SPMD meshes) before jax initialises a backend.
``enable_compile_cache`` points jax's persistent compilation cache at a
directory that survives the process, so a second run of the same program
does not pay its compiles again.
"""
from __future__ import annotations

import contextlib
import os
import re
import sys

import jax

#: the compile cache's home when ``JAX_COMPILATION_CACHE_DIR`` is unset: a
#: fixed path inside the checkout (git-ignored).  Fixed because the
#: directory is part of the cache key's lookup — a path derived from a
#: temp name, pid or time never hits.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_DEVICE_COUNT_FLAG = r"--xla_force_host_platform_device_count=(\d+)"


def backends_initialized() -> bool:
    from jax._src import xla_bridge
    return bool(xla_bridge.backends_are_initialized())


def found_tpu(who: str) -> bool:
    """True when the backend jax brought up is a TPU.  Otherwise says on
    stderr what ``who`` found instead — for entry points that measure or
    prove the chip and have no stand-in for it: they exit non-zero."""
    dev = jax.devices()[0]
    if dev.platform == "tpu":
        return True
    print(f"{who} needs a TPU and jax found platform {dev.platform!r} "
          f"({dev.device_kind}): refusing to run", file=sys.stderr)
    return False


def force_cpu(n_devices: int | None = None) -> None:
    """Pin the CPU platform (with ``n_devices`` virtual devices if given).

    Call it before the first jax operation: a backend, once initialised,
    stays for the life of the process.  Calling it later is fine when the
    live backend already is the CPU with enough devices, and an error
    otherwise.  Sets both the environment (``JAX_PLATFORMS``,
    ``XLA_FLAGS`` — inherited by child processes) and the jax config
    (``jax_platforms``, ``jax_num_cpu_devices`` — read when this
    process creates its client)."""
    if backends_initialized():
        have = jax.default_backend(), jax.device_count()
        if have[0] != "cpu" or (n_devices and have[1] < n_devices):
            raise RuntimeError(
                f"force_cpu({n_devices}) after jax initialised "
                f"{have[0]} with {have[1]} device(s): pin the platform "
                "before the first jax operation")
        return
    if n_devices:
        flags = os.environ.get("XLA_FLAGS", "")
        m = re.search(_DEVICE_COUNT_FLAG, flags)
        if m is None:
            flags += f" --xla_force_host_platform_device_count={n_devices}"
        elif int(m.group(1)) < n_devices:
            # raise an ambient smaller value, never lower a larger one
            flags = re.sub(
                _DEVICE_COUNT_FLAG,
                f"--xla_force_host_platform_device_count={n_devices}", flags)
        os.environ["XLA_FLAGS"] = flags.strip()
        if jax.config.jax_num_cpu_devices < n_devices:
            jax.config.update("jax_num_cpu_devices", n_devices)
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")


@contextlib.contextmanager
def cpu_platform(n_devices: int | None = None):
    """Scoped ``force_cpu``: on exit the environment variables and the
    jax config values are put back, so processes started afterwards see
    the ambient settings.  The backend this process initialised inside
    the scope stays its backend."""
    saved_env = {k: os.environ.get(k) for k in ("JAX_PLATFORMS", "XLA_FLAGS")}
    saved_platforms = jax.config.jax_platforms
    saved_num_cpu = jax.config.jax_num_cpu_devices
    force_cpu(n_devices)
    try:
        yield
    finally:
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        jax.config.update("jax_platforms", saved_platforms)
        jax.config.update("jax_num_cpu_devices", saved_num_cpu)


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it and no
    directory is set in code; otherwise the cache lives at
    :data:`DEFAULT_CACHE_DIR`.  Call once, before the first trace, from
    every entry point that compiles on the chip."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir
