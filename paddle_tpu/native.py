"""ctypes bindings for the native runtime library (native/ptnative.cc).

Builds the shared library on first use with g++ (pybind11 is not in this
image; the C ABI + ctypes replaces the reference's pybind layer for these
components). `get_lib` returns None when the toolchain is unavailable and
the callers below then use their Python fallbacks; `require_lib` raises
the build error instead, for paths that must not degrade silently.

A library is loaded only if it was built from the present sources, with
the present flags, on the present machine: its file name carries a hash
of all three (``native/build/libptnative-<key>.so``). The build uses
``-march=native``, so a library copied in from another machine — a copy
keeps neither mtimes nor CPU — simply has another name and is never
picked up.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
import threading
from typing import List, Optional

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(_REPO_ROOT, "native", "ptnative.cc")
_SRC_PS = os.path.join(_REPO_ROOT, "native", "pt_ps.cc")
_BUILD_DIR = os.path.join(_REPO_ROOT, "native", "build")

_lib = None
_lib_lock = threading.Lock()
_build_error: Optional[str] = None


def _cpu_identity() -> str:
    """What ``-march=native`` depends on: architecture + ISA flags."""
    flags = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    flags = line.split(":", 1)[1].strip()
                    break
    except OSError:
        pass
    return f"{platform.machine()} {flags}"


def _keyed_path(stem: str, sources: List[str], flags: List[str]) -> str:
    """``native/build/<stem>-<key>.so`` with key = hash(sources' bytes,
    flags, this machine's CPU)."""
    h = hashlib.sha256()
    for src in sources:
        with open(src, "rb") as f:
            h.update(f.read())
    h.update("\0".join(flags).encode())
    h.update(_cpu_identity().encode())
    return os.path.join(_BUILD_DIR, f"{stem}-{h.hexdigest()[:16]}.so")


def _compile(cmd: List[str], out_path: str, timeout: float) -> None:
    """Run ``cmd -o <tmp>`` and move the result to ``out_path``
    atomically (several processes may build the same key at once).
    Raises RuntimeError with the compiler's message on failure."""
    os.makedirs(_BUILD_DIR, exist_ok=True)
    tmp = f"{out_path}.tmp{os.getpid()}"
    try:
        subprocess.run(cmd + ["-o", tmp], check=True, capture_output=True,
                       timeout=timeout)
        os.replace(tmp, out_path)
    except subprocess.CalledProcessError as e:
        raise RuntimeError(
            f"native build failed ({' '.join(cmd)}): "
            f"{e.stderr.decode('utf-8', 'replace')[-2000:]}") from e
    except (FileNotFoundError, subprocess.TimeoutExpired) as e:
        raise RuntimeError(f"native build failed ({cmd[0]}): {e}") from e
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


_FLAGS = ["-O3", "-march=native", "-shared", "-fPIC", "-std=c++17"]


def lib_path() -> str:
    """Where this machine's build of the present sources lives."""
    return _keyed_path("libptnative", [_SRC, _SRC_PS], _FLAGS)


def get_lib() -> Optional[ctypes.CDLL]:
    global _lib, _build_error
    with _lib_lock:
        if _lib is not None or _build_error is not None:
            return _lib
        path = lib_path()
        if not os.path.exists(path):
            try:
                _compile(["g++", *_FLAGS, _SRC, _SRC_PS, "-lpthread",
                          "-lrt"], path, timeout=120)
            except RuntimeError as e:
                _build_error = str(e)
                return None
        lib = ctypes.CDLL(path)
        lib.ptq_create.restype = ctypes.c_void_p
        lib.ptq_create.argtypes = [ctypes.c_char_p, ctypes.c_uint64,
                                   ctypes.c_uint64]
        lib.ptq_open.restype = ctypes.c_void_p
        lib.ptq_open.argtypes = [ctypes.c_char_p]
        lib.ptq_push.restype = ctypes.c_int
        lib.ptq_push.argtypes = [ctypes.c_void_p,
                                 ctypes.POINTER(ctypes.c_uint8),
                                 ctypes.c_uint64]
        lib.ptq_pop.restype = ctypes.c_int64
        lib.ptq_pop.argtypes = [ctypes.c_void_p,
                                ctypes.POINTER(ctypes.c_uint8),
                                ctypes.c_uint64]
        lib.ptq_size.restype = ctypes.c_int
        lib.ptq_size.argtypes = [ctypes.c_void_p]
        lib.ptq_close.argtypes = [ctypes.c_void_p]
        lib.ptq_destroy.argtypes = [ctypes.c_void_p]
        lib.pt_crc32c.restype = ctypes.c_uint32
        lib.pt_crc32c.argtypes = [ctypes.POINTER(ctypes.c_uint8),
                                  ctypes.c_uint64, ctypes.c_uint32]
        lib.pt_u8_to_f32_norm.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_float),
            ctypes.c_int64, ctypes.c_int64, ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_float)]
        lib.pt_aes128_ctr.restype = ctypes.c_int
        lib.pt_aes128_ctr.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_uint8),
            ctypes.c_uint64]
        # --- parameter-server transport (native/pt_ps.cc) ---
        fp = ctypes.POINTER(ctypes.c_float)
        kp = ctypes.POINTER(ctypes.c_int64)
        lib.pt_ps_server_create.restype = ctypes.c_void_p
        lib.pt_ps_server_add_dense.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64, ctypes.c_int,
            ctypes.c_float, ctypes.c_float, ctypes.c_float, ctypes.c_float]
        lib.pt_ps_server_add_sparse.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
            ctypes.c_float, ctypes.c_float, ctypes.c_uint64]
        lib.pt_ps_server_start.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                           ctypes.c_int]
        lib.pt_ps_server_start.restype = ctypes.c_int
        lib.pt_ps_server_port.argtypes = [ctypes.c_void_p]
        lib.pt_ps_server_port.restype = ctypes.c_int
        lib.pt_ps_server_stop.argtypes = [ctypes.c_void_p]
        lib.pt_ps_server_destroy.argtypes = [ctypes.c_void_p]
        lib.pt_ps_server_dense_read.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, fp, ctypes.c_uint64]
        lib.pt_ps_server_dense_read.restype = ctypes.c_int
        lib.pt_ps_server_sparse_size.argtypes = [ctypes.c_void_p,
                                                 ctypes.c_char_p]
        lib.pt_ps_server_sparse_size.restype = ctypes.c_int64
        lib.pt_ps_connect.restype = ctypes.c_void_p
        lib.pt_ps_connect.argtypes = [ctypes.c_char_p, ctypes.c_int]
        lib.pt_ps_disconnect.argtypes = [ctypes.c_void_p]
        lib.pt_ps_pull_dense.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                         fp, ctypes.c_uint64]
        lib.pt_ps_pull_dense.restype = ctypes.c_int
        lib.pt_ps_push_dense.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                         fp, ctypes.c_uint64, ctypes.c_int]
        lib.pt_ps_push_dense.restype = ctypes.c_int
        lib.pt_ps_pull_sparse.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                          kp, ctypes.c_uint64, fp,
                                          ctypes.c_int]
        lib.pt_ps_pull_sparse.restype = ctypes.c_int
        lib.pt_ps_push_sparse.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                          kp, ctypes.c_uint64, fp,
                                          ctypes.c_int, ctypes.c_int]
        lib.pt_ps_push_sparse.restype = ctypes.c_int
        lib.pt_ps_table_dim.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.pt_ps_table_dim.restype = ctypes.c_int64
        lib.pt_ps_sparse_size.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.pt_ps_sparse_size.restype = ctypes.c_int64
        lib.pt_ps_barrier.argtypes = [ctypes.c_void_p]
        lib.pt_ps_barrier.restype = ctypes.c_int
        lib.pt_ps_stop_server.argtypes = [ctypes.c_void_p]
        lib.pt_ps_stop_server.restype = ctypes.c_int
        _lib = lib
        return _lib


def available() -> bool:
    return get_lib() is not None


def require_lib() -> ctypes.CDLL:
    """`get_lib`, but a failed build raises with the compiler's message
    instead of leaving callers on their Python fallbacks."""
    lib = get_lib()
    if lib is None:
        raise RuntimeError(_build_error)
    return lib


_CAPI_SRC = os.path.join(_REPO_ROOT, "native", "pt_capi.cc")

_capi_lock = threading.Lock()


def build_capi() -> Optional[str]:
    """Build the C inference API (native/pt_capi.cc -> libpt_infer-<key>.so),
    the capi_exp-equivalent deployment library. Returns the .so path or
    None if the toolchain is unavailable. The key covers the libpython
    it links against, so a library built for another interpreter is
    rebuilt, not returned."""
    import sysconfig
    inc = sysconfig.get_paths()["include"]
    libdir = sysconfig.get_config_var("LIBDIR")
    pyver = f"python{sysconfig.get_config_var('py_version_short')}"
    flags = ["-O2", "-shared", "-fPIC", "-std=c++17", f"-I{inc}",
             f"-L{libdir}", f"-l{pyver}", f"-Wl,-rpath,{libdir}"]
    with _capi_lock:
        path = _keyed_path("libpt_infer", [_CAPI_SRC], flags)
        if not os.path.exists(path):
            try:
                _compile(["g++", _CAPI_SRC, *flags], path, timeout=180)
            except RuntimeError:
                return None
        try:
            ctypes.CDLL(path)
        except OSError:
            return None
        return path


class ShmQueue:
    """Shared-memory ring buffer for raw byte payloads (multiprocess
    DataLoader transport)."""

    def __init__(self, name: str, slot_size: int = 1 << 22,
                 n_slots: int = 8, create: bool = True):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("ptnative library unavailable")
        self._lib = lib
        self.name = name
        if create:
            self._h = lib.ptq_create(name.encode(), slot_size, n_slots)
        else:
            self._h = lib.ptq_open(name.encode())
        if not self._h:
            raise RuntimeError(f"failed to init ShmQueue {name!r}")
        self.slot_size = slot_size
        self._owner = create

    def push(self, payload: bytes) -> None:
        arr = (ctypes.c_uint8 * len(payload)).from_buffer_copy(payload)
        rc = self._lib.ptq_push(self._h, arr, len(payload))
        if rc == -1:
            raise RuntimeError("queue closed")
        if rc == -2:
            raise ValueError(f"payload {len(payload)} exceeds slot size")

    def push_array(self, arr: np.ndarray) -> None:
        self.push(arr.tobytes())

    def pop(self, cap: Optional[int] = None) -> Optional[bytes]:
        cap = cap or self.slot_size
        buf = (ctypes.c_uint8 * cap)()
        n = self._lib.ptq_pop(self._h, buf, cap)
        if n == -1:
            return None  # closed + drained
        if n == -2:
            raise ValueError("pop buffer too small")
        return bytes(bytearray(buf[:n]))

    def qsize(self) -> int:
        return self._lib.ptq_size(self._h)

    def close(self) -> None:
        if self._h:
            self._lib.ptq_close(self._h)

    def destroy(self) -> None:
        if self._h:
            self._lib.ptq_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.destroy()
        except Exception:
            pass


_CRC32C_TABLE = None


def _crc32c_py(data: bytes, seed: int) -> int:
    # Same Castagnoli polynomial as pt_crc32c — checksums must be
    # machine-portable (they're embedded in encrypted artifacts)
    global _CRC32C_TABLE
    if _CRC32C_TABLE is None:
        table = []
        for i in range(256):
            c = i
            for _ in range(8):
                c = (c >> 1) ^ (0x82F63B78 if c & 1 else 0)
            table.append(c)
        _CRC32C_TABLE = table
    c = seed ^ 0xFFFFFFFF
    for b in data:
        c = _CRC32C_TABLE[(c ^ b) & 0xFF] ^ (c >> 8)
    return c ^ 0xFFFFFFFF


def crc32c(data: bytes, seed: int = 0) -> int:
    lib = get_lib()
    if lib is None:
        return _crc32c_py(data, seed)
    arr = (ctypes.c_uint8 * len(data)).from_buffer_copy(data)
    return int(lib.pt_crc32c(arr, len(data), seed))


def u8_to_f32_norm(img: np.ndarray, mean, std) -> np.ndarray:
    """CHW uint8 image -> normalized float32 (native fused loop)."""
    lib = get_lib()
    img = np.ascontiguousarray(img, dtype=np.uint8)
    c = img.shape[0]
    hw = int(np.prod(img.shape[1:]))
    mean = np.asarray(mean, np.float32).ravel()
    std = np.asarray(std, np.float32).ravel()
    if mean.size == 1:
        mean = np.repeat(mean, c)
    if std.size == 1:
        std = np.repeat(std, c)
    if lib is None:
        return ((img.astype(np.float32) / 255.0 -
                 mean.reshape(-1, *([1] * (img.ndim - 1)))) /
                std.reshape(-1, *([1] * (img.ndim - 1))))
    out = np.empty(img.shape, np.float32)
    lib.pt_u8_to_f32_norm(
        img.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        c, hw, mean.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        std.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
    return out
