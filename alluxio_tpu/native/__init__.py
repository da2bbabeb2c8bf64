"""Native (C++) runtime components, built on demand with the system
toolchain and loaded via ctypes.

``lib()`` returns the loaded library or ``None`` — callers keep their
pure-Python path as the fallback, so the native layer is an
accelerator, never a dependency. Which rung a process runs on is never
silent: :func:`status` names it and the reason, and a build that fails
WITH a toolchain present is logged at warning level and reported there
(``chip_smoke.py`` treats it as an error).
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import logging
import os
import shutil
import struct
import subprocess
import tempfile
import threading
from typing import Dict, List, Optional, Tuple

LOG = logging.getLogger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO_STEM = "_libatpu_native"
_CXX = "g++"
_CXXFLAGS = ["-O3", "-shared", "-fPIC", "-std=c++17", "-Wall", "-Werror"]

_lock = threading.Lock()
_lib: "ctypes.CDLL | None | bool" = None  # None=untried, False=failed
_so_path: Optional[str] = None  # the library lib() loaded
_error: Optional[str] = None    # why lib() is None, once tried

# Every ctypes prototype the Python side relies on, as the single
# source of truth: ``lib()`` attaches these, and the atpu-lint
# ``native-abi`` rule cross-checks this table against the symbols the
# compiled .so actually exports (both directions), so C++/Python
# signature drift is a lint failure, not a runtime segfault.
_PROTOTYPES: "Dict[str, Tuple[list, object]]" = {
    "atpu_crc32": (
        [ctypes.c_char_p, ctypes.c_size_t, ctypes.c_uint32],
        ctypes.c_uint32),
    "atpu_scan_frames": (
        [ctypes.c_void_p, ctypes.c_size_t, ctypes.c_size_t,
         ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint32),
         ctypes.c_size_t, ctypes.POINTER(ctypes.c_uint64)],
        ctypes.c_size_t),
    "atpu_prefault": (
        [ctypes.c_void_p, ctypes.c_size_t, ctypes.c_int],
        ctypes.c_int),
    "atpu_plan_exec": (
        [ctypes.c_void_p, ctypes.c_size_t, ctypes.c_void_p,
         ctypes.c_size_t],
        ctypes.c_int64),
}


class NativeBuildError(RuntimeError):
    """The toolchain is present and refused the tracked sources."""


def _sources() -> List[str]:
    """All translation units, sorted for a deterministic compile line."""
    return sorted(glob.glob(os.path.join(_DIR, "*.cpp")))


def _build_key(srcs: List[str]) -> str:
    """Content key of a build: the compiler's identity, the compile
    line and every ``*.cpp``/``*.h`` byte. The library carries it in
    its file name, so only a library built from exactly these inputs
    is ever loaded — a clock (mtime) says nothing about a file that
    was copied in with the checkout."""
    h = hashlib.sha256()
    ident = subprocess.run([_CXX, "-dumpfullversion", "-dumpmachine"],
                           capture_output=True, timeout=30)
    h.update(ident.stdout)
    h.update(" ".join([_CXX] + _CXXFLAGS).encode())
    for path in srcs + sorted(glob.glob(os.path.join(_DIR, "*.h"))):
        h.update(os.path.basename(path).encode() + b"\0")
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:16]


def _build() -> Optional[str]:
    """Path of the library built from the sources as they are now,
    compiling it when no library with this build key exists. ``None``
    without a toolchain; :class:`NativeBuildError` when the toolchain
    is there and the compile fails."""
    srcs = _sources()
    if not srcs or shutil.which(_CXX) is None:
        return None
    so = os.path.join(_DIR, f"{_SO_STEM}.{_build_key(srcs)}.so")
    if os.path.exists(so):
        return so
    # build into a temp file then rename: concurrent processes
    # (minicluster roles) must never dlopen a half-written .so
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=_DIR)
    os.close(fd)
    try:
        r = subprocess.run([_CXX] + _CXXFLAGS + ["-o", tmp] + srcs,
                           capture_output=True, timeout=120)
        if r.returncode != 0:
            raise NativeBuildError(
                f"{_CXX} rc={r.returncode}: {r.stderr.decode()[:500]}")
        os.replace(tmp, so)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    # libraries of other build keys are stale by definition
    for old in glob.glob(os.path.join(_DIR, _SO_STEM + "*.so")):
        if old != so:
            try:
                os.unlink(old)
            except OSError:
                pass
    return so


def lib() -> Optional[ctypes.CDLL]:
    global _lib, _so_path, _error
    if _lib is not None:
        return _lib or None
    with _lock:
        if _lib is not None:
            return _lib or None
        try:
            so = _build()
            if so is None:
                _error = f"no toolchain ({_CXX} not found)"
                LOG.info("native layer unavailable: %s; running the "
                         "pure-Python rung", _error)
                _lib = False
                return None
            handle = ctypes.CDLL(so)
            for name, (argtypes, restype) in _PROTOTYPES.items():
                fn = getattr(handle, name)
                fn.argtypes = argtypes
                fn.restype = restype
        except (NativeBuildError, OSError, AttributeError,
                subprocess.SubprocessError) as e:
            # toolchain present, library unusable: say so — the Python
            # rung is correct but this is not the deployment asked for
            _error = f"{type(e).__name__}: {e}"
            LOG.warning("native build failed, running the pure-Python "
                        "rung: %s", _error)
            _lib = False
            return None
        _lib, _so_path = handle, so
        return handle


def status() -> Dict[str, object]:
    """Which rung this process runs on, and why: ``rung`` is
    ``"native"`` or ``"python"``; ``toolchain`` says whether a compiler
    was found; ``error`` is the reason for the Python rung (a build
    failure with ``toolchain`` true is a defect, not a fallback)."""
    handle = lib()
    return {"rung": "native" if handle is not None else "python",
            "library": _so_path,
            "toolchain": shutil.which(_CXX) is not None,
            "error": _error}


def _buffer_address(view) -> "Tuple[int, int, object] | None":
    """(address, nbytes, keepalive) of a buffer WITHOUT copying,
    readonly or not — hold ``keepalive`` for the duration of the native
    call. None when no zero-copy address is obtainable."""
    # numpy arrays expose the address directly regardless of flags
    data_attr = getattr(view, "ctypes", None)
    if data_attr is not None and hasattr(data_attr, "data"):
        return data_attr.data, view.nbytes, view
    if isinstance(view, bytes):
        # ctypes.cast of a bytes object points at its internal buffer
        return (ctypes.cast(view, ctypes.c_void_p).value or 0,
                len(view), view)
    mv = memoryview(view)
    if not mv.readonly:
        buf = (ctypes.c_char * mv.nbytes).from_buffer(mv)
        return ctypes.addressof(buf), mv.nbytes, buf
    try:
        # readonly memoryview/mmap: a numpy view exposes the address
        # without requiring writability (native code only reads)
        import numpy as np

        arr = np.frombuffer(mv, dtype=np.uint8)
        return arr.ctypes.data, arr.nbytes, (arr, mv)
    except Exception:  # noqa: BLE001
        return None


_SCAN_CHUNK = 65536  # frames per native call: bounds the offset arrays


def scan_frames(view) -> "Tuple[List[Tuple[int, int]], int] | None":
    """Scan ``[u32 len][u32 crc][body]`` frames over a buffer
    (bytes/bytearray/ndarray/mmap) with NO copy of the data. Returns
    ``([(body_off, body_len), ...], end_off)`` — ``end_off`` is the
    truncation point after the last valid frame — or ``None`` when the
    native library (or a zero-copy address) is unavailable. The scan
    runs in bounded chunks so offset arrays stay small regardless of
    journal size."""
    handle = lib()
    if handle is None:
        return None
    loc = _buffer_address(view)
    if loc is None:
        return None
    addr, n, keepalive = loc
    if n == 0:
        return [], 0
    offs = (ctypes.c_uint64 * _SCAN_CHUNK)()
    lens = (ctypes.c_uint32 * _SCAN_CHUNK)()
    end = ctypes.c_uint64(0)
    frames: List[Tuple[int, int]] = []
    start = 0
    while True:
        got = handle.atpu_scan_frames(addr, n, start, offs, lens,
                                      _SCAN_CHUNK, ctypes.byref(end))
        frames.extend((offs[i], lens[i]) for i in range(got))
        start = end.value
        if got < _SCAN_CHUNK:
            break
    del keepalive
    return frames, end.value


def crc32(data: bytes, seed: int = 0) -> Optional[int]:
    handle = lib()
    if handle is None:
        return None
    return handle.atpu_crc32(data, len(data), seed)


#: ``atpu_prefault``'s rungs by their C value: what the function is
#: given to start from, and what it returns
PREFAULT_MODES = ("touch", "lock", "populate")


def prefault(view, first: str = "populate") -> Optional[str]:
    """Make the pages under ``view`` present before a consumer reads
    them, in one kernel call where the kernel has one: GIL-free,
    readonly-safe, zero-copy. Returns the rung that did it, one of
    :data:`PREFAULT_MODES` — ``populate`` (``MADV_POPULATE_READ``),
    ``lock`` (``mlock`` + ``munlock``, where the kernel refuses the
    advice with ``EINVAL``) or ``touch`` (one read a page: any other
    refusal, and an empty view). ``None`` when the native path is
    unavailable (the caller falls back). ``first`` names the rung to
    start from: callers start at the top; the tests start lower to run
    the fallbacks on a kernel that has the advice."""
    handle = lib()
    if handle is None:
        return None
    loc = _buffer_address(view)
    if loc is None:
        return None
    addr, n, keepalive = loc
    mode = handle.atpu_prefault(addr, n, PREFAULT_MODES.index(first))
    del keepalive
    return PREFAULT_MODES[mode]


# ---------------------------------------------------------------- plan exec

# Mirrors struct AtpuPlanOp in plan_exec.cpp exactly: 48 bytes,
# little-endian, naturally aligned (u32+i32 then five u64) — no
# padding, so a C-contiguous structured array IS the C op table.
OP_COPY = 0
OP_PREAD = 1
OP_DTYPE_FIELDS = [
    ("kind", "<u4"), ("fd", "<i4"), ("src", "<u8"), ("src_off", "<u8"),
    ("src_len", "<u8"), ("dst_off", "<u8"), ("len", "<u8"),
]


def op_dtype():
    import numpy as np

    dt = np.dtype(OP_DTYPE_FIELDS)
    assert dt.itemsize == 48, "op dtype drifted from plan_exec.cpp"
    return dt


def exec_plan(ops, dest) -> Optional[int]:
    """Run a packed op table (a C-contiguous structured array of
    ``op_dtype()`` records) against ``dest`` (writable buffer) in ONE
    native call — the GIL is released for the whole batch. Returns the
    executor's result (total bytes written >= 0, or ``-(i+1)`` when op
    ``i`` failed), or ``None`` when the native library is unavailable
    (caller falls back to Python)."""
    handle = lib()
    if handle is None:
        return None
    nops = len(ops)
    if nops == 0:
        return 0
    dst = _buffer_address(dest)
    if dst is None:
        return None
    dst_addr, dst_len, dst_keep = dst
    rc = handle.atpu_plan_exec(ops.ctypes.data, nops, dst_addr, dst_len)
    del dst_keep
    return rc


# ------------------------------------------------------------- ELF symbols

def exported_symbols(path: Optional[str] = None) -> Optional[List[str]]:
    """Defined ``atpu_*`` function symbols exported by the compiled
    library, read from the ELF ``.dynsym`` table directly (no ``nm``
    dependency). Returns ``None`` when the .so is missing or not a
    64-bit little-endian ELF — used by the atpu-lint ``native-abi``
    rule to diff the C++ export surface against ``_PROTOTYPES``."""
    if path is None:
        lib()  # builds once, or records why not
    so = path or _so_path
    if so is None or not os.path.exists(so):
        return None
    try:
        with open(so, "rb") as f:
            data = f.read()
        if data[:4] != b"\x7fELF" or data[4] != 2 or data[5] != 1:
            return None  # not ELF64 little-endian
        e_shoff, = struct.unpack_from("<Q", data, 0x28)
        e_shentsize, e_shnum = struct.unpack_from("<HH", data, 0x3A)
        dynsym = dynstr = None
        for i in range(e_shnum):
            base = e_shoff + i * e_shentsize
            sh_type, = struct.unpack_from("<I", data, base + 4)
            sh_offset, sh_size = struct.unpack_from("<QQ", data, base + 24)
            sh_link, = struct.unpack_from("<I", data, base + 40)
            sh_entsize, = struct.unpack_from("<Q", data, base + 56)
            if sh_type == 11:  # SHT_DYNSYM
                dynsym = (sh_offset, sh_size, sh_entsize, sh_link)
        if dynsym is None:
            return None
        str_base = e_shoff + dynsym[3] * e_shentsize
        str_off, str_size = struct.unpack_from("<QQ", data, str_base + 24)
        dynstr = data[str_off:str_off + str_size]
        out: List[str] = []
        off, size, entsize, _ = dynsym
        for pos in range(off, off + size, entsize or 24):
            st_name, st_info = struct.unpack_from("<IB", data, pos)
            st_shndx, = struct.unpack_from("<H", data, pos + 6)
            if (st_info & 0xF) != 2 or st_shndx == 0:  # STT_FUNC, defined
                continue
            end = dynstr.index(b"\0", st_name)
            name = dynstr[st_name:end].decode("ascii", "replace")
            if name.startswith("atpu_"):
                out.append(name)
        return sorted(out)
    except Exception:  # noqa: BLE001 - malformed ELF: lint rule skips
        LOG.debug("exported_symbols parse failed", exc_info=True)
        return None
