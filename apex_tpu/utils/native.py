"""Build the repo's native helpers (``csrc/*.cpp``) into shared libraries.

One rule for both libraries: compile the committed source with the ambient
``g++`` into :data:`BUILD_DIR` — one fixed, git-ignored directory inside
the checkout — under a name that carries the source's hash, so an edited
source is rebuilt and an unchanged one is found again.  Nothing is built
anywhere else, and a failure says why.
"""
from __future__ import annotations

import hashlib
import os
import shutil
import subprocess

CSRC_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "csrc")
BUILD_DIR = os.path.join(CSRC_DIR, "_build")


class NativeBuildError(RuntimeError):
    """The native library could not be built: no source, no compiler, or
    a compile error (the compiler's message is included)."""


def build(source: str, lib_name: str) -> tuple[str, bool]:
    """Path of the shared library for ``csrc/<source>``, compiling it
    first when it is not in :data:`BUILD_DIR` yet.  Returns ``(path,
    built_now)``; raises :class:`NativeBuildError` otherwise."""
    src = os.path.join(CSRC_DIR, source)
    try:
        with open(src, "rb") as f:
            tag = hashlib.sha256(f.read()).hexdigest()[:16]
    except OSError as err:
        raise NativeBuildError(f"cannot read {src}: {err}") from None
    so = os.path.join(BUILD_DIR, f"{lib_name}_{tag}.so")
    if os.path.exists(so):
        return so, False
    cxx = shutil.which("g++")
    if cxx is None:
        raise NativeBuildError(f"no g++ on PATH to build {src}")
    os.makedirs(BUILD_DIR, exist_ok=True)
    tmp = f"{so}.tmp{os.getpid()}"      # same directory: atomic rename
    try:
        subprocess.run(
            [cxx, "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
             "-o", tmp, src],
            check=True, capture_output=True, text=True, timeout=300)
        os.replace(tmp, so)
    except subprocess.CalledProcessError as err:
        raise NativeBuildError(
            f"g++ failed on {src}:\n{err.stderr[-2000:]}") from None
    except (OSError, subprocess.TimeoutExpired) as err:
        raise NativeBuildError(f"building {src}: {err}") from None
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    return so, True
