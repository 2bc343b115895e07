"""Lazy g++ build + cache for native components.

The reference ships prebuilt native binaries (bazel); we compile on first
use instead — a few hundred ms once per machine — and cache the object
next to the sources under a name that carries the hash of what it was
built from (sources + flags), so an edit rebuilds and a stale object that
rode along in a copied tree (file times mean nothing there) is never
loaded: its name simply is not the one asked for.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import logging
import os
import subprocess
import threading

logger = logging.getLogger(__name__)

_SRC_DIR = os.path.dirname(os.path.abspath(__file__))
_BUILD_DIR = os.path.join(_SRC_DIR, "_build")
_lock = threading.Lock()
_cache: dict = {}


def _compile(stem: str, suffix: str, srcs: list, flags: list,
             timeout: float) -> str:
    """Content-addressed g++ compile-and-swap shared by every build
    target: the output is _build/<stem>.<hash><suffix>.  Raises
    RuntimeError on any failure mode (missing compiler included)."""
    h = hashlib.sha256(" ".join(flags).encode())
    for path in srcs:
        with open(path, "rb") as f:
            h.update(b"\0" + os.path.basename(path).encode() + b"\0")
            h.update(f.read())
    out = os.path.join(_BUILD_DIR, f"{stem}.{h.hexdigest()[:16]}{suffix}")
    if os.path.exists(out):
        return out
    os.makedirs(_BUILD_DIR, exist_ok=True)
    for old in glob.glob(os.path.join(_BUILD_DIR, f"{stem}.*{suffix}")):
        try:
            os.unlink(old)  # built from other sources; never loadable again
        except OSError:
            pass
    tmp = out + ".tmp.%d" % os.getpid()
    cmd = ["g++", *flags, "-o", tmp, *srcs, "-lpthread"]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=timeout)
    except (OSError, subprocess.TimeoutExpired) as e:
        raise RuntimeError(f"native build failed to run: {e}") from e
    if proc.returncode != 0:
        raise RuntimeError(
            f"native build of {os.path.basename(out)} failed:\n"
            f"{proc.stderr[-4000:]}")
    os.replace(tmp, out)  # atomic: concurrent builders race benignly
    return out


def build_extension(name: str, sources: list, extra_flags: list = ()) -> str:
    """Compile sources into _build/lib<name>.<hash>.so; returns the path.

    Rebuilds when the sources (or flags) differ from what the cached
    object was built from.  Raises RuntimeError if the compiler fails.
    """
    return _compile(
        f"lib{name}", ".so",
        [os.path.join(_SRC_DIR, s) for s in sources],
        ["-O2", "-g", "-std=c++17", "-shared", "-fPIC", *extra_flags],
        timeout=120)


def build_sanitized_selftest() -> str:
    """Build the ASAN+UBSAN self-test binary (reference: the C++ tests'
    bazel asan/tsan configs in .bazelrc); returns the binary path.
    Rebuilds when any native source changed."""
    sources = ["selftest.cc", "shm_arena.cc", "shm_channel.cc", "sched.cc"]
    return _compile(
        "native_selftest_san", "",
        [os.path.join(_SRC_DIR, s) for s in sources],
        ["-std=c++17", "-g", "-O1", "-fno-omit-frame-pointer",
         "-fsanitize=address,undefined", "-fno-sanitize-recover=all"],
        timeout=300)


def load_library(name: str, sources: list) -> ctypes.CDLL:
    """Build (if needed) and dlopen a native component; cached per-process."""
    with _lock:
        if name in _cache:
            return _cache[name]
        path = build_extension(name, sources)
        lib = ctypes.CDLL(path)
        _cache[name] = lib
        return lib


if __name__ == "__main__":
    import sys

    if "--sanitize" in sys.argv:
        path = build_sanitized_selftest()
        print(path)
        rc = subprocess.run([path, "/tmp"]).returncode
        sys.exit(rc)
    print("usage: python -m ray_tpu.native.build --sanitize")
