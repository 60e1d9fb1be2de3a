"""Native (C++) runtime components, built on demand with g++.

The reference's runtime is compiled Go; the equivalent here is a thin
C++ layer for the host-side hot paths that Python cannot make fast —
currently the block pre-parser (blockparse.cpp): one C call per block
replaces ~6 protobuf unmarshals + 3 SHA-256 calls per transaction on
the commit path.  Build artifacts cache under _build/; when no
compiler is available the callers fall back to the pure-Python paths,
so the framework never hard-requires a toolchain at run time."""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess

log = logging.getLogger("fabric_tpu.native")

_DIR = os.path.dirname(os.path.abspath(__file__))

_libs: dict = {}       # name → CDLL
_lib_failed: set = set()


_FLAGS = ["-O3", "-shared", "-fPIC", "-std=c++17"]


def _build(src: str, so: str) -> bool:
    os.makedirs(os.path.dirname(so), exist_ok=True)
    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", *_FLAGS, src, "-o", tmp],
            check=True, capture_output=True, timeout=120,
        )
        os.replace(tmp, so)  # atomic: concurrent builders can't corrupt
        with open(so + ".flags", "w") as f:
            f.write(" ".join(_FLAGS))
        return True
    except Exception as e:
        log.warning("native %s build failed (%s); using Python path", src, e)
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False


def _fresh(src: str, so: str) -> bool:
    """Artifact is current iff newer than the source AND built with the
    current flag set (a flag change must invalidate cached .so files)."""
    if not os.path.exists(so) or os.path.getmtime(so) < os.path.getmtime(src):
        return False
    try:
        with open(so + ".flags") as f:
            return f.read() == " ".join(_FLAGS)
    except OSError:
        return False


def _load(name: str):
    """Build-if-stale + dlopen fabric_tpu/native/<name>.cpp → CDLL or
    None (callers fall back to their pure-Python paths)."""
    if name in _libs:
        return _libs[name]
    if name in _lib_failed:
        return None
    src = os.path.join(_DIR, f"{name}.cpp")
    so = os.path.join(_DIR, "_build", f"lib{name}.so")
    if not _fresh(src, so) and not _build(src, so):
        _lib_failed.add(name)
        return None
    try:
        lib = ctypes.CDLL(so)
    except OSError as e:
        log.warning("native %s load failed (%s)", name, e)
        _lib_failed.add(name)
        return None
    _libs[name] = lib
    return lib


def blockparse_lib():
    """→ ctypes CDLL with parse_block, or None (Python fallback)."""
    lib = _load("blockparse")
    if lib is not None:
        lib.parse_block.restype = ctypes.c_int64
    return lib


def mvccprep_lib():
    """→ ctypes CDLL with mvcc_prep (rwset wire parse + key interning
    into flat arrays), or None (Python fallback)."""
    lib = _load("mvccprep")
    if lib is not None:
        lib.mvcc_prep.restype = ctypes.c_int64
    return lib


def ecprep_lib():
    """→ ctypes CDLL with ec_prepare (batch u1/u2 window recoding +
    admission flags) and ec_prepare_pack (strided int16 digits/limbs
    straight into the packed launch frame), or None (Python
    fallback)."""
    lib = _load("ecprep")
    if lib is not None:
        lib.ec_prepare.restype = None
        lib.ec_prepare_pack.restype = None
    return lib
