"""Python bindings for the native C++ runtime pieces (ctypes).

Reference analog: the reference's engine is C++ with Python on top; here
the compute path is jax/XLA and these native pieces cover the IO/runtime
side — recordio file handling and the async shuffling data pool
(PyDataProvider2's pool thread, DataProvider double buffering) — plus the
C inference ABI (paddle/capi) built from native/src/.

The shared library builds on demand with g++ (cached by source hash);
everything degrades gracefully when no toolchain is present
(``available()`` returns False and the pure-python paths keep working).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
from typing import Iterable, List, Optional

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "native")
_SRC = os.path.join(_NATIVE_DIR, "src")
_BUILD = os.path.join(_NATIVE_DIR, "build")

_lib = None
_load_error: Optional[str] = None


def _sources() -> List[str]:
    return [os.path.join(_SRC, f) for f in ("recordio.cpp",
                                            "shuffle_pool.cpp")]


def _deps() -> List[str]:
    import glob

    return _sources() + glob.glob(os.path.join(_SRC, "*.h"))


def _compile(out_name: str, deps: List[str], cmd_for, force: bool) -> str:
    """Compile ``cmd_for(out_path)`` into native/build/``out_name``
    unless a library built from EXACTLY these sources with this command
    is already there.  The stamp beside the library is a hash of the
    command and of every dependency's bytes — not an mtime, which a
    clone, an archive or a copy rewrites freely, so a stale library
    could otherwise stand in for newer sources."""
    os.makedirs(_BUILD, exist_ok=True)
    out = os.path.join(_BUILD, out_name)
    cmd = cmd_for(out)
    h = hashlib.sha256("\0".join(cmd).encode())
    for dep in sorted(deps):
        with open(dep, "rb") as f:
            h.update(f.read())
    want = h.hexdigest()
    stamp = out + ".srchash"
    if not force and os.path.exists(out) and os.path.exists(stamp):
        with open(stamp) as f:
            if f.read() == want:
                return out
    subprocess.run(cmd, check=True, capture_output=True)
    with open(stamp, "w") as f:
        f.write(want)
    return out


def build(force: bool = False) -> str:
    """Compile native/src → native/build/libptn.so (no python linkage —
    the capi library builds separately via build_capi)."""
    return _compile(
        "libptn.so", _deps(),
        lambda out: ["g++", "-O2", "-fPIC", "-shared", "-std=c++17",
                     "-o", out] + _sources() + ["-lpthread"], force)


def build_capi(force: bool = False) -> str:
    """Compile the C inference ABI (embeds CPython) → libptpu_capi.so."""
    import sysconfig

    src = os.path.join(_SRC, "capi.cpp")
    inc = sysconfig.get_paths()["include"]
    libdir = sysconfig.get_config_var("LIBDIR")
    ver = sysconfig.get_config_var("LDVERSION")
    return _compile(
        "libptpu_capi.so", [src],
        lambda out: ["g++", "-O2", "-fPIC", "-shared", "-std=c++17",
                     f"-I{inc}", "-o", out, src,
                     f"-L{libdir}", f"-lpython{ver}", "-lpthread"], force)


def build_aot(force: bool = False) -> str:
    """Compile the interpreter-free AOT inference runtime →
    libptpu_aot.so. PURE C++ — no Python, no jax, no XLA linked; this is
    the embedded-deployment artifact (paddle/capi Android analog)."""
    src = os.path.join(_SRC, "aot_runtime.cpp")
    return _compile(
        "libptpu_aot.so", [src],
        lambda out: ["g++", "-O2", "-fPIC", "-shared", "-std=c++17",
                     "-o", out, src], force)


def _pjrt_include_dir():
    """The PJRT C API header ships with the tensorflow wheel."""
    import sysconfig

    inc = os.path.join(sysconfig.get_paths()["purelib"], "tensorflow",
                       "include")
    if os.path.exists(os.path.join(inc, "xla", "pjrt", "c", "pjrt_c_api.h")):
        return inc
    return None


def build_pjrt(force: bool = False) -> str:
    """Compile the PJRT C-API inference runtime → libptpu_pjrt.so.
    Pure C++ + libdl; the PJRT plugin (libtpu.so on TPU hosts) is
    dlopen'd at runtime, never linked."""
    src = os.path.join(_SRC, "pjrt_capi.cpp")
    inc = _pjrt_include_dir()
    if inc is None:
        raise RuntimeError("no pjrt_c_api.h found in site-packages")
    return _compile(
        "libptpu_pjrt.so", [src],
        lambda out: ["g++", "-O2", "-fPIC", "-shared", "-std=c++17",
                     f"-I{inc}", "-o", out, src, "-ldl"], force)


def _load():
    global _lib, _load_error
    if _lib is not None or _load_error is not None:
        return _lib
    try:
        path = build()
        lib = ctypes.CDLL(path)
    except Exception as e:  # toolchain missing etc.
        _load_error = str(e)
        return None
    lib.ptn_write_open.restype = ctypes.c_void_p
    lib.ptn_write_open.argtypes = [ctypes.c_char_p]
    lib.ptn_write_record.restype = ctypes.c_int
    lib.ptn_write_record.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                     ctypes.c_uint64]
    lib.ptn_write_close.restype = ctypes.c_uint64
    lib.ptn_write_close.argtypes = [ctypes.c_void_p]
    lib.ptn_index.restype = ctypes.c_int
    lib.ptn_index.argtypes = [ctypes.c_char_p,
                              ctypes.POINTER(ctypes.POINTER(ctypes.c_uint64)),
                              ctypes.POINTER(ctypes.c_uint64)]
    lib.ptn_free_offsets.argtypes = [ctypes.POINTER(ctypes.c_uint64)]
    lib.ptn_read_chunk.restype = ctypes.c_void_p
    lib.ptn_read_chunk.argtypes = [ctypes.c_char_p, ctypes.c_uint64,
                                   ctypes.c_uint64]
    lib.ptn_buf_count.restype = ctypes.c_uint64
    lib.ptn_buf_count.argtypes = [ctypes.c_void_p]
    lib.ptn_buf_get.restype = ctypes.c_int
    lib.ptn_buf_get.argtypes = [ctypes.c_void_p, ctypes.c_uint64,
                                ctypes.POINTER(ctypes.c_char_p),
                                ctypes.POINTER(ctypes.c_uint64)]
    lib.ptn_buf_free.argtypes = [ctypes.c_void_p]
    lib.ptn_pool_create.restype = ctypes.c_void_p
    lib.ptn_pool_create.argtypes = [ctypes.POINTER(ctypes.c_char_p),
                                    ctypes.c_uint64, ctypes.c_uint64,
                                    ctypes.c_uint64]
    lib.ptn_pool_next.restype = ctypes.c_int
    lib.ptn_pool_next.argtypes = [ctypes.c_void_p,
                                  ctypes.POINTER(ctypes.c_char_p),
                                  ctypes.POINTER(ctypes.c_uint64)]
    lib.ptn_pool_destroy.argtypes = [ctypes.c_void_p]
    _lib = lib
    return _lib


def available() -> bool:
    return _load() is not None


def _require():
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native library unavailable: {_load_error}")
    return lib


# ---------------------------------------------------------------------------
# recordio
# ---------------------------------------------------------------------------


def write_records(path: str, records: Iterable[bytes]) -> int:
    lib = _require()
    h = lib.ptn_write_open(path.encode())
    if not h:
        raise OSError(f"cannot open {path}")
    n = 0
    for rec in records:
        if isinstance(rec, str):
            rec = rec.encode()
        if lib.ptn_write_record(h, rec, len(rec)) != 0:
            lib.ptn_write_close(h)
            raise OSError(f"short write to {path}")
        n += 1
    if lib.ptn_write_close(h) == 2 ** 64 - 1:  # flush failed (disk full)
        raise OSError(f"flush failed writing {path}")
    return n


def index(path: str) -> List[int]:
    lib = _require()
    arr = ctypes.POINTER(ctypes.c_uint64)()
    n = ctypes.c_uint64()
    if lib.ptn_index(path.encode(), ctypes.byref(arr),
                     ctypes.byref(n)) != 0:
        raise OSError(f"cannot index {path}")
    out = [arr[i] for i in range(n.value)]
    lib.ptn_free_offsets(arr)
    return out


def read_chunk(path: str, offset: int, count: int) -> List[bytes]:
    lib = _require()
    h = lib.ptn_read_chunk(path.encode(), offset, count)
    if not h:
        raise OSError(f"cannot read {path}")
    out = []
    data = ctypes.c_char_p()
    length = ctypes.c_uint64()
    for i in range(lib.ptn_buf_count(h)):
        lib.ptn_buf_get(h, i, ctypes.byref(data), ctypes.byref(length))
        out.append(ctypes.string_at(data, length.value))
    lib.ptn_buf_free(h)
    return out


# ---------------------------------------------------------------------------
# async shuffle pool (the native data loader)
# ---------------------------------------------------------------------------


class ShufflePool:
    """Background-thread record streamer with a shuffle window.

    Iterating yields raw record bytes in shuffled order while the native
    producer thread keeps the window full (IO overlaps compute)."""

    def __init__(self, paths: List[str], window: int = 1024, seed: int = 0):
        self._lib = _require()
        arr = (ctypes.c_char_p * len(paths))(
            *[p.encode() for p in paths])
        self._h = self._lib.ptn_pool_create(arr, len(paths), window, seed)

    def __iter__(self):
        data = ctypes.c_char_p()
        length = ctypes.c_uint64()
        while True:
            rc = self._lib.ptn_pool_next(self._h, ctypes.byref(data),
                                         ctypes.byref(length))
            if rc < 0:
                raise OSError("shuffle pool IO error (missing file or "
                              "corrupt record stream)")
            if rc == 0:
                return
            yield ctypes.string_at(data, length.value)

    def close(self):
        if self._h:
            self._lib.ptn_pool_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def recordio_reader(paths, window: int = 1024, seed: int = 0):
    """Reader-creator over native recordio files with async shuffling
    (v2 reader protocol: call → iterator of records)."""
    if isinstance(paths, str):
        paths = [paths]

    def reader():
        pool = ShufflePool(list(paths), window=window, seed=seed)
        try:
            for rec in pool:
                yield rec
        finally:
            pool.close()

    return reader
