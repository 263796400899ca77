"""ctypes binding for the native runtime core (csrc/ -> libptcore.so).

The reference's runtime services are C++ (SURVEY.md §2.1); here the native
layer provides the flag registry, TCPStore rendezvous, stat gauges and the
dataloader prefetch ring.  pybind11 is not available in this image, so the
binding is a plain C ABI + ctypes.

The library is built from csrc/ by ``make`` at first load (g++ is part of
the toolchain), which also rebuilds a binary older than its sources;
`available()` reports whether the native core is loaded, and pure-Python
fallbacks exist for the flag registry (core.flags) so import never fails.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from pathlib import Path

_ROOT = Path(__file__).resolve().parent.parent
_LIB_PATH = _ROOT / "lib" / "libptcore.so"
_CSRC = _ROOT.parent / "csrc"

_lock = threading.Lock()
_lib = None
_load_failed = False

OK = 0
ERR_NOTFOUND = -1
ERR_TYPE = -2
ERR_TIMEOUT = -3
ERR_IO = -4
ERR_CLOSED = -5
ERR_ARG = -6


class NativeError(RuntimeError):
    pass


def _build() -> bool:
    """Bring the library up to date with csrc/.  ``make`` decides: its
    rule lists the sources, so a binary older than them (the file is
    git-ignored and survives on disk across commits) is rebuilt and an
    up-to-date one costs a stat per source."""
    if not (_CSRC / "Makefile").exists():
        return _LIB_PATH.exists()
    try:
        subprocess.run(["make", "-C", str(_CSRC)], check=True,
                       capture_output=True, timeout=180)
    except (subprocess.SubprocessError, OSError):
        return False
    return _LIB_PATH.exists()


def _configure(lib):
    c = ctypes
    lib.ptcore_flag_define.argtypes = [c.c_char_p, c.c_int, c.c_char_p,
                                       c.c_char_p]
    lib.ptcore_flag_set.argtypes = [c.c_char_p, c.c_char_p]
    lib.ptcore_flag_get.argtypes = [c.c_char_p, c.c_char_p, c.c_size_t]
    lib.ptcore_flag_name_at.argtypes = [c.c_int, c.c_char_p, c.c_size_t]
    lib.ptcore_flag_help.argtypes = [c.c_char_p, c.c_char_p, c.c_size_t]
    lib.ptcore_store_master_start.argtypes = [c.c_uint16,
                                              c.POINTER(c.c_uint16)]
    lib.ptcore_store_master_start.restype = c.c_int64
    lib.ptcore_store_master_stop.argtypes = [c.c_int64]
    lib.ptcore_store_connect.argtypes = [c.c_char_p, c.c_uint16, c.c_int64]
    lib.ptcore_store_connect.restype = c.c_int64
    lib.ptcore_store_close.argtypes = [c.c_int64]
    lib.ptcore_store_set.argtypes = [c.c_int64, c.c_char_p,
                                     c.POINTER(c.c_uint8), c.c_size_t]
    lib.ptcore_store_get.argtypes = [c.c_int64, c.c_char_p,
                                     c.POINTER(c.c_uint8), c.c_size_t,
                                     c.c_int64]
    lib.ptcore_store_get.restype = c.c_int64
    lib.ptcore_store_add.argtypes = [c.c_int64, c.c_char_p, c.c_int64,
                                     c.POINTER(c.c_int64)]
    lib.ptcore_store_wait.argtypes = [c.c_int64, c.c_char_p, c.c_int64]
    lib.ptcore_store_delete.argtypes = [c.c_int64, c.c_char_p]
    lib.ptcore_stat_update.argtypes = [c.c_char_p, c.c_int, c.c_int64]
    lib.ptcore_stat_update.restype = c.c_int64
    lib.ptcore_stat_current.argtypes = [c.c_char_p, c.c_int]
    lib.ptcore_stat_current.restype = c.c_int64
    lib.ptcore_stat_peak.argtypes = [c.c_char_p, c.c_int]
    lib.ptcore_stat_peak.restype = c.c_int64
    lib.ptcore_stat_reset_peak.argtypes = [c.c_char_p, c.c_int]
    lib.ptcore_ring_create.argtypes = [c.c_int]
    lib.ptcore_ring_create.restype = c.c_int64
    lib.ptcore_ring_push.argtypes = [c.c_int64, c.POINTER(c.c_uint8),
                                     c.c_size_t, c.c_int64]
    lib.ptcore_ring_pop.argtypes = [c.c_int64, c.POINTER(c.c_uint8),
                                    c.c_size_t, c.c_int64]
    lib.ptcore_ring_pop.restype = c.c_int64
    lib.ptcore_ring_size.argtypes = [c.c_int64]
    lib.ptcore_ring_close.argtypes = [c.c_int64]
    lib.ptcore_ring_destroy.argtypes = [c.c_int64]
    lib.ptcore_version.restype = c.c_char_p
    return lib


def peek():
    """The native lib if already loaded, else None — never builds."""
    return _lib


def load():
    """Load (building if needed) the native core; returns the lib or None."""
    global _lib, _load_failed
    if _lib is not None or _load_failed:
        return _lib
    loaded = None
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        if os.environ.get("PADDLE_TPU_DISABLE_NATIVE"):
            _load_failed = True
            return None
        if not _build():
            _load_failed = True
            return None
        try:
            loaded = _configure(ctypes.CDLL(str(_LIB_PATH)))
        except OSError:
            _load_failed = True
            return None
        _lib = loaded
    # first load: mirror the Python flag registry into the native store
    from . import flags as _flags
    _flags._sync_native(loaded)
    return _lib


def available() -> bool:
    return load() is not None


# ---------------------------------------------------------------------------
# Pythonic wrappers
# ---------------------------------------------------------------------------

def _buf(n):
    return (ctypes.c_uint8 * n)()


class TCPStore:
    """Rendezvous KV store (reference: tcp_store.h:121).

    Rank 0 (is_master=True) hosts the master daemon in-process; every rank
    (including 0) connects a client to it.
    """

    def __init__(self, host: str, port: int, is_master: bool = False,
                 world_size: int | None = None, timeout: float = 90.0):
        lib = load()
        if lib is None:
            raise NativeError(
                "native core unavailable (libptcore.so failed to build/load)")
        self._lib = lib
        self._master_handle = None
        self.host = host
        self.port = port
        if is_master:
            actual = ctypes.c_uint16(0)
            h = lib.ptcore_store_master_start(port, ctypes.byref(actual))
            if h < 0:
                raise NativeError(f"TCPStore master failed to bind :{port}")
            self._master_handle = h
            self.port = int(actual.value)
        self._client = lib.ptcore_store_connect(
            host.encode(), self.port, int(timeout * 1000))
        if self._client < 0:
            if self._master_handle is not None:
                lib.ptcore_store_master_stop(self._master_handle)
            raise NativeError(
                f"TCPStore could not connect to {host}:{self.port}")
        self.timeout = timeout

    def set(self, key: str, value):
        if isinstance(value, str):
            value = value.encode()
        data = (ctypes.c_uint8 * len(value)).from_buffer_copy(value) \
            if value else None
        rc = self._lib.ptcore_store_set(self._client, key.encode(), data,
                                        len(value))
        if rc != OK:
            raise NativeError(f"store set({key}) failed: {rc}")

    def get(self, key: str, timeout: float | None = None) -> bytes:
        ms = int((timeout if timeout is not None else self.timeout) * 1000)
        n = 4096
        while True:
            buf = _buf(n)
            r = self._lib.ptcore_store_get(self._client, key.encode(), buf, n,
                                           ms)
            if r == ERR_TIMEOUT:
                raise TimeoutError(f"store get({key}) timed out")
            if r < 0:
                raise NativeError(f"store get({key}) failed: {r}")
            if r <= n:
                return bytes(buf[:r])
            n = int(r)

    def add(self, key: str, amount: int = 1) -> int:
        out = ctypes.c_int64(0)
        rc = self._lib.ptcore_store_add(self._client, key.encode(), amount,
                                        ctypes.byref(out))
        if rc != OK:
            raise NativeError(f"store add({key}) failed: {rc}")
        return int(out.value)

    def wait(self, keys, timeout: float | None = None):
        if isinstance(keys, str):
            keys = [keys]
        ms = int((timeout if timeout is not None else self.timeout) * 1000)
        for key in keys:
            rc = self._lib.ptcore_store_wait(self._client, key.encode(), ms)
            if rc == ERR_TIMEOUT:
                raise TimeoutError(f"store wait({key}) timed out")
            if rc != OK:
                raise NativeError(f"store wait({key}) failed: {rc}")

    def delete_key(self, key: str):
        self._lib.ptcore_store_delete(self._client, key.encode())

    def close(self):
        if getattr(self, "_client", None) is not None and self._client >= 0:
            self._lib.ptcore_store_close(self._client)
            self._client = -1
        if self._master_handle is not None:
            self._lib.ptcore_store_master_stop(self._master_handle)
            self._master_handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class PrefetchRing:
    """Bounded blocking queue of byte payloads (native MPMC ring)."""

    def __init__(self, capacity: int = 8):
        lib = load()
        if lib is None:
            raise NativeError("native core unavailable")
        self._lib = lib
        self._h = lib.ptcore_ring_create(capacity)
        if self._h < 0:
            raise NativeError("ring create failed")

    def push(self, data: bytes, timeout: float = -1.0):
        buf = (ctypes.c_uint8 * len(data)).from_buffer_copy(data) \
            if data else None
        rc = self._lib.ptcore_ring_push(self._h, buf, len(data),
                                        int(timeout * 1000))
        if rc == ERR_CLOSED:
            raise NativeError("ring closed")
        if rc == ERR_TIMEOUT:
            raise TimeoutError("ring push timed out")
        if rc != OK:
            raise NativeError(f"ring push failed: {rc}")

    def pop(self, timeout: float = -1.0) -> bytes | None:
        """Returns payload, or None when the ring is closed and drained."""
        n = 1 << 16
        ms = int(timeout * 1000)
        while True:
            buf = _buf(n)
            r = self._lib.ptcore_ring_pop(self._h, buf, n, ms)
            if r == ERR_CLOSED:
                return None
            if r == ERR_TIMEOUT:
                raise TimeoutError("ring pop timed out")
            if r < 0:
                raise NativeError(f"ring pop failed: {r}")
            if r <= n:
                return bytes(buf[:r])
            n = int(r)

    def qsize(self) -> int:
        return int(self._lib.ptcore_ring_size(self._h))

    def close(self):
        if self._h >= 0:
            self._lib.ptcore_ring_close(self._h)

    def destroy(self):
        if self._h >= 0:
            self._lib.ptcore_ring_destroy(self._h)
            self._h = -1

    def __del__(self):
        try:
            self.destroy()
        except Exception:
            pass


def stat_update(name: str, delta: int, dev: int = 0) -> int:
    lib = load()
    if lib is None:
        return 0
    return int(lib.ptcore_stat_update(name.encode(), dev, delta))


def stat_current(name: str, dev: int = 0) -> int:
    lib = load()
    if lib is None:
        return 0
    return int(lib.ptcore_stat_current(name.encode(), dev))


def stat_peak(name: str, dev: int = 0) -> int:
    lib = load()
    if lib is None:
        return 0
    return int(lib.ptcore_stat_peak(name.encode(), dev))


def stat_reset_peak(name: str, dev: int = 0):
    lib = load()
    if lib is not None:
        lib.ptcore_stat_reset_peak(name.encode(), dev)
