"""Shared compile-and-load machinery for the C++ engines.

Both native tiers — the storage engine (libs/db_native.py over
native/nkv.cpp) and the host batch verifier (crypto/host_batch.py over
native/edbatch.cpp) — build a shared object on first use with the
baked-in g++ and load it via ctypes (no pybind11 in the image). One
implementation of the staleness check / atomic replace / failure
handling keeps the two paths from drifting.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
from . import sync as libsync

_lock = libsync.Mutex("libs.native_build._lock")


class NativeBuildError(RuntimeError):
    pass


def _stamp(src: str, extra_flags: tuple[str, ...]) -> str:
    """What a built ``.so`` must have been built FROM: the source's
    content hash plus the flags. File times say nothing on a machine
    the tree was copied to (a stale or foreign ``.so`` copied along
    with a checkout carries whatever mtime the copy gave it)."""
    h = hashlib.sha256()
    with open(src, "rb") as f:
        h.update(f.read())
    h.update(" ".join(extra_flags).encode())
    return h.hexdigest()


def _fresh(so: str, stamp: str) -> bool:
    try:
        with open(so + ".stamp") as f:
            return os.path.exists(so) and f.read().strip() == stamp
    except OSError:
        return False


def build_and_load(
    src: str,
    so: str,
    extra_flags: tuple[str, ...] = (),
    timeout: float = 120.0,
) -> ctypes.CDLL:
    """Compile ``src`` -> ``so`` (unless a ``.so`` stamped with this
    exact source already exists) and dlopen it.

    Raises NativeBuildError when the toolchain is unavailable or the
    compile fails; callers decide their own fallback policy.
    """
    with _lock:
        stamp = _stamp(src, extra_flags)
        if not _fresh(so, stamp):
            _compile(src, so, extra_flags, timeout, stamp)
        try:
            return ctypes.CDLL(so)
        except OSError:
            # A stamped .so that won't dlopen (truncated artifact,
            # wrong architecture) must not take down callers that have a
            # pure-Python fallback: rebuild once from source, and map any
            # remaining failure to NativeBuildError so the callers'
            # fallback policy applies.
            try:
                os.remove(so)
            except OSError:
                pass
            _compile(src, so, extra_flags, timeout, stamp)
            try:
                return ctypes.CDLL(so)
            except OSError as e:
                raise NativeBuildError(
                    f"{os.path.basename(so)} rebuilt but won't load: {e!r}"
                )


def _compile(
    src: str,
    so: str,
    extra_flags: tuple[str, ...],
    timeout: float,
    stamp: str,
) -> None:
    cmd = [
        "g++", "-O3", "-funroll-loops", "-shared", "-fPIC",
        "-std=c++17", *extra_flags, src, "-o", so + ".tmp",
    ]
    try:
        # cometlint: disable=CLNT009 -- one-time lazy toolchain build; the
        # resulting .so is cached on disk and re-dlopened for free after
        r = subprocess.run(
            cmd, capture_output=True, text=True, timeout=timeout
        )
    except (OSError, subprocess.TimeoutExpired) as e:
        raise NativeBuildError(f"g++ unavailable: {e!r}")
    if r.returncode != 0:
        raise NativeBuildError(
            f"{os.path.basename(src)} compile failed:\n"
            f"{r.stderr[:800]}"
        )
    os.replace(so + ".tmp", so)
    with open(so + ".stamp.tmp", "w") as f:
        f.write(stamp)
    os.replace(so + ".stamp.tmp", so + ".stamp")
