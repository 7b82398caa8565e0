"""Build the native library on demand.

The .so is compiled into ray_tpu/native/_build/ under a name that
carries a hash of its sources' contents and the compile command, so a
binary is reused only for the exact sources it was built from: _build/
is git-ignored but travels with a copied tree, where mtimes say nothing.
Keeps the repo pip-install-free (no pybind11; plain ctypes ABI).

Build failures (g++ missing, compile error) raise NativeBuildError and
are cached: the first failure logs one warning, later calls fail fast
instead of re-running the compiler on every import/call so callers can
route onto their pure-Python fallbacks cheaply.
"""

from __future__ import annotations

import hashlib
import logging
import os
import re
import subprocess
import threading

from ray_tpu.devtools import locktrace

logger = logging.getLogger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC_DIR = os.path.join(_DIR, "src")
_BUILD_DIR = os.path.join(_DIR, "_build")
_lock = locktrace.traced_lock("native.build")
# target key -> failure detail; guarded by _lock. A key present here
# means "don't retry the compile this process".
_build_failed: dict = {}


class NativeBuildError(RuntimeError):
    """Raised when the native toolchain is unavailable or the compile
    fails; callers catch this and fall back to pure Python."""


def _sources():
    return sorted(
        os.path.join(_SRC_DIR, f)
        for f in os.listdir(_SRC_DIR)
        # *_main.cc are standalone test binaries (stress harness), not
        # part of the runtime library
        if f.endswith(".cc") and not f.endswith("_main.cc")
    )


def _build(key: str, stem: str, ext: str, cmd, srcs) -> str:
    """Path of ``_build/<stem>-<hash><ext>``, compiled if absent. The
    hash covers the compile command and every source's name and
    contents. The g++ run holds no lock (compiles take seconds; a lock
    across them would serialize unrelated callers and trip the
    blocking-under-lock lint); concurrent duplicate compiles are benign:
    each writes a unique tmp and os.replace is atomic. Outputs of other
    source versions are removed afterwards (a process that already
    mapped one keeps it)."""
    _check_cached_failure(key)
    h = hashlib.sha256(" ".join(cmd).encode())
    for src in srcs:
        h.update(os.path.basename(src).encode())
        with open(src, "rb") as f:
            h.update(f.read())
    out = os.path.join(_BUILD_DIR, f"{stem}-{h.hexdigest()[:16]}{ext}")
    if os.path.exists(out):
        return out
    os.makedirs(_BUILD_DIR, exist_ok=True)
    tmp = f"{out}.tmp.{os.getpid()}.{threading.get_ident()}"
    try:
        proc = subprocess.run(cmd + srcs + ["-o", tmp],
                              capture_output=True, text=True)
    except OSError as exc:  # g++ not installed at all
        _record_failure(key, f"toolchain unavailable: {exc}")
        raise NativeBuildError(f"native build failed ({key}): {exc}") \
            from exc
    if proc.returncode != 0:
        detail = (proc.stderr or proc.stdout or "").strip()[-2000:]
        _record_failure(key, detail)
        raise NativeBuildError(
            f"native build failed ({key}, rc={proc.returncode}):\n{detail}")
    os.replace(tmp, out)
    versioned = re.compile(
        re.escape(stem) + "-[0-9a-f]{16}" + re.escape(ext) + "$")
    for name in os.listdir(_BUILD_DIR):
        path = os.path.join(_BUILD_DIR, name)
        if versioned.match(name) and path != out:
            try:
                os.unlink(path)
            except OSError:
                pass
    return out


def _record_failure(key: str, detail: str) -> None:
    with _lock:
        first = not _build_failed
        _build_failed[key] = detail
    if first:
        logger.warning(
            "native build failed (%s); using pure-Python fallbacks for "
            "this process: %s", key, detail.splitlines()[-1] if detail
            else detail)


def _check_cached_failure(key: str) -> None:
    with _lock:
        detail = _build_failed.get(key)
    if detail is not None:
        raise NativeBuildError(
            f"native build previously failed ({key}): {detail}")


def ensure_built() -> str:
    return _build("lib", "libray_tpu_native", ".so",
                  ["g++", "-O2", "-g", "-fPIC", "-shared", "-std=c++17",
                   "-Wall", "-pthread"], _sources())


def build_stress(sanitizer: str = "",
                 main_src: str = "stress_test_main.cc") -> str:
    """Build a stress binary from ``src/<main_src>`` linked against the
    library sources, optionally under ASan/TSan — the seam the
    reference covers with its sanitizer bazel configs (SURVEY.md §5.2,
    .bazelrc:112-132). The default main is the shm-store harness; pass
    ``wire_stress_main.cc`` for the wire-codec harness. Returns the
    binary path; raises NativeBuildError with compiler output on
    failure."""
    if sanitizer not in ("", "address", "thread"):
        raise ValueError(f"unknown sanitizer {sanitizer!r}")
    stem = "shm_stress" if main_src == "stress_test_main.cc" \
        else main_src[:-len("_main.cc")]
    suffix = f"-{sanitizer}" if sanitizer else ""
    cmd = ["g++", "-O1", "-g", "-std=c++17", "-Wall", "-pthread"]
    if sanitizer:
        cmd += [f"-fsanitize={sanitizer}", "-fno-omit-frame-pointer"]
    return _build(f"{stem}{suffix}", f"{stem}{suffix}", "", cmd,
                  _sources() + [os.path.join(_SRC_DIR, main_src)])
