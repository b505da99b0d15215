"""Native (C++) components, loaded via ctypes.

The reference's performance-critical host code is Go with unsafe casts
(roaring/roaring.go:934-944); here it is C++ compiled on demand with the
system toolchain.  Import never fails: when no compiler is available the
callers fall back to the pure-NumPy paths, which are retained as the
differential oracles (tests/test_native_codec.py,
tests/test_native_merge.py) — one line on stderr and ``status()`` say
so.  ``scripts/build_native.sh`` compiles both libraries ahead of time
for this host (warnings as errors, with an ``--asan`` mode for
debugging).

Two libraries share the loader:
- ``roaring_codec``  — fragment-file decode/encode (PR 5);
- ``sparse_merge``   — the bulk-ingest sorted-merge + dense-apply kernels
  (docs/ingest.md).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
import sys
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))

_FLAGS = ("-O3", "-march=native", "-shared", "-fPIC", "-std=c++17")

_lock = threading.Lock()
# name -> loaded CDLL | None; presence means a load was attempted.
_libs: dict = {}


def _host_id() -> str:
    """What ``-march=native`` resolved to on this host: the first CPU's
    model and feature flags where the kernel lists them, else the
    machine type."""
    lines = [platform.machine()]
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if not line.strip():
                    break  # end of the first processor's block
                if line.startswith(("model name", "flags", "Features")):
                    lines.append(line.strip())
    except OSError:
        pass
    return "\n".join(lines)


def _lib_path(name: str, src: str) -> str:
    """``lib<name>.<key>.so`` with the key over source + flags + host: a
    library is only ever loaded on the host, and from the source, it was
    built for — a stale or foreign ``.so`` in the tree (a copied
    checkout, an edited ``.cpp``) simply has another name."""
    h = hashlib.sha256()
    with open(src, "rb") as f:
        h.update(f.read())
    h.update(" ".join(_FLAGS).encode())
    h.update(_host_id().encode())
    return os.path.join(_HERE, f"lib{name}.{h.hexdigest()[:16]}.so")


def _build(src: str, lib: str) -> bool:
    tmp = f"{lib}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", *_FLAGS, "-o", tmp, src],
            check=True, capture_output=True, cwd=_HERE, timeout=120,
        )
        os.replace(tmp, lib)  # atomic: a sibling process never maps half a file
        return True
    except (subprocess.SubprocessError, OSError) as e:
        detail = getattr(e, "stderr", b"") or b""
        print(
            f"pilosa_tpu.native: building {os.path.basename(src)} failed "
            f"({e!r}); using the NumPy path. "
            f"{detail.decode(errors='replace')[-400:]}",
            file=sys.stderr, flush=True,
        )
        return False
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def _load(name: str, configure) -> ctypes.CDLL | None:
    """Get-or-build-or-fail ``lib<name>.<key>.so``; ``configure(lib)``
    checks the ABI stamp and sets prototypes, returning False to
    reject."""
    with _lock:
        if name in _libs:
            return _libs[name]
        _libs[name] = None  # one attempt per process
        src = os.path.join(_HERE, name + ".cpp")
        libpath = _lib_path(name, src)
        if not os.path.exists(libpath) and not _build(src, libpath):
            return None
        try:
            lib = ctypes.CDLL(libpath)
        except OSError:
            return None
        if not configure(lib):
            return None
        _libs[name] = lib
        return lib


def _configure_codec(lib) -> bool:
    lib.rc_abi_version.restype = ctypes.c_int32
    if lib.rc_abi_version() != 1:
        return False
    lib.rc_deserialize.restype = ctypes.c_int64
    lib.rc_deserialize.argtypes = [
        ctypes.c_char_p,
        ctypes.c_size_t,
        ctypes.c_void_p,
        ctypes.c_size_t,
        ctypes.POINTER(ctypes.c_int64),
    ]
    lib.rc_serialize.restype = ctypes.c_int64
    lib.rc_serialize.argtypes = [
        ctypes.c_void_p,
        ctypes.c_size_t,
        ctypes.c_void_p,
        ctypes.c_size_t,
    ]
    return True


def _configure_merge(lib) -> bool:
    lib.sm_abi_version.restype = ctypes.c_int32
    if lib.sm_abi_version() != 1:
        return False
    split_args = [
        ctypes.c_void_p,  # a_rows (int64*)
        ctypes.c_void_p,  # a_ptrs (const uint32* const*)
        ctypes.c_void_p,  # a_lens (int64*)
        ctypes.c_int64,   # a_nrows
        ctypes.c_void_p,  # b (int64*)
        ctypes.c_int64,   # nb
        ctypes.c_int32,   # exp
        ctypes.c_uint32,  # mask
        ctypes.c_void_p,  # pos_out (uint32*)
        ctypes.c_void_p,  # rows_out (int64*)
        ctypes.c_void_p,  # bounds_out (int64*)
        ctypes.POINTER(ctypes.c_int64),  # n_merged
    ]
    for fn in (lib.sm_union_split, lib.sm_diff_split):
        fn.restype = ctypes.c_int64
        fn.argtypes = split_args
    lib.sm_apply_dense.restype = ctypes.c_int64
    lib.sm_apply_dense.argtypes = [
        ctypes.c_void_p,  # words (uint64*)
        ctypes.c_int64,   # n_words
        ctypes.c_void_p,  # pos (uint32*)
        ctypes.c_int64,   # n
        ctypes.c_int32,   # clear
    ]
    lib.sm_shard_split.restype = ctypes.c_int64
    lib.sm_shard_split.argtypes = [
        ctypes.c_void_p,  # cols (int64*)
        ctypes.c_void_p,  # rows (int64*)
        ctypes.c_int64,   # n
        ctypes.c_int32,   # exp
        ctypes.c_int64,   # max_shards
        ctypes.c_void_p,  # cols_out
        ctypes.c_void_p,  # rows_out
        ctypes.c_void_p,  # shard_ids_out
        ctypes.c_void_p,  # bounds_out
    ]
    return True


def load():
    """The roaring codec library, building it on first use; None if
    unavailable."""
    return _load("roaring_codec", _configure_codec)


def load_merge():
    """The sparse-merge library; None when unavailable — callers take
    the numpy path."""
    return _load("sparse_merge", _configure_merge)


def status() -> dict:
    """``{library: "built" | "unavailable"}`` after attempting both
    loads — the /debug/vars ``native`` block, so a node that dropped to
    the NumPy paths says so."""
    return {
        "roaring_codec": "built" if load() is not None else "unavailable",
        "sparse_merge": "built" if load_merge() is not None else "unavailable",
    }
