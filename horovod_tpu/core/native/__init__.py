"""ctypes bindings for the native control-plane core (hvd_core.cc).

The reference loads its compiled library twice — as a TF op library and as a
ctypes DLL (mpi_ops.py:68-77). Here there are no framework kernels to
register (XLA provides the data plane), so a single ctypes binding carries
the whole native surface: request table + validation, fusion planning, stall
detection, and the timeline writer.

The library is compiled lazily with g++ on first use and cached next to
the source under a name that carries the source's hash (``_so_path``); if
no toolchain is available the callers fall back to the pure Python
implementations (core/negotiate.py, ops/fusion.py), which implement
identical semantics and produce byte-identical error messages.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "hvd_core.cc")
_CXX = ["g++", "-std=c++17", "-O2", "-fPIC", "-shared"]

_build_lock = threading.Lock()
_lib = None
_load_failed = False


def _so_path() -> str:
    """``_hvd_core.<hash>.so`` — the hash covers hvd_core.cc and the
    compile command, so the only binary this module ever opens is one
    built from the source that sits next to it. A stale or foreign
    ``_hvd_core*.so`` (copied along with a working tree, left by another
    checkout) has another name and is never loaded."""
    h = hashlib.sha256(" ".join(_CXX).encode())
    with open(_SRC, "rb") as f:
        h.update(f.read())
    return os.path.join(_HERE, f"_hvd_core.{h.hexdigest()[:16]}.so")


def _build() -> str | None:
    """Path of the library built from the current hvd_core.cc, compiling
    it if that exact build is not there yet; None when it cannot be."""
    try:
        so = _so_path()
        if os.path.exists(so):
            return so
        # Compile beside the target and rename: a concurrent process
        # (multi-host tests) never opens a half-written library.
        tmp = f"{so}.{os.getpid()}.tmp"
        from horovod_tpu.core import timeline as _timeline

        with _timeline.span("hvd/init/native_build"):  # a checkout's first
            res = subprocess.run(_CXX + ["-o", tmp, _SRC],
                                 capture_output=True, text=True, timeout=120)
        if res.returncode != 0:
            import warnings

            warnings.warn(
                f"hvd_core native build failed, using pure-Python control "
                f"plane: {res.stderr[-500:]}")
            return None
        os.replace(tmp, so)
        return so
    except (OSError, subprocess.SubprocessError) as e:
        import warnings

        warnings.warn(f"hvd_core native build unavailable ({e}); using "
                      f"pure-Python control plane.")
        return None


def _load():
    global _lib, _load_failed
    with _build_lock:
        if _lib is not None or _load_failed:
            return _lib
        so = _build()
        if so is None:
            _load_failed = True
            return None
        try:
            lib = ctypes.CDLL(so)
        except OSError as e:
            import warnings

            warnings.warn(f"hvd_core load failed ({e}); using pure-Python "
                          f"control plane.")
            _load_failed = True
            return None
        lib.hvd_core_create.restype = ctypes.c_void_p
        lib.hvd_core_create.argtypes = [
            ctypes.c_int, ctypes.POINTER(ctypes.c_int), ctypes.c_double]
        lib.hvd_core_destroy.argtypes = [ctypes.c_void_p]
        lib.hvd_core_submit.restype = ctypes.c_int
        lib.hvd_core_submit.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_char_p, ctypes.c_int,
            ctypes.c_char_p, ctypes.c_int, ctypes.POINTER(ctypes.c_longlong),
            ctypes.c_int, ctypes.c_int, ctypes.c_char_p, ctypes.c_int]
        lib.hvd_core_response_sizes.restype = ctypes.c_int
        lib.hvd_core_response_sizes.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_longlong), ctypes.c_int]
        lib.hvd_core_response_root.restype = ctypes.c_int
        lib.hvd_core_response_root.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_char_p]
        lib.hvd_core_response_done.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_char_p]
        lib.hvd_core_stalled.restype = ctypes.c_int
        lib.hvd_core_stalled.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_char_p, ctypes.c_int]
        lib.hvd_core_plan_fusion.restype = ctypes.c_int
        lib.hvd_core_plan_fusion.argtypes = [
            ctypes.c_longlong, ctypes.c_int,
            ctypes.POINTER(ctypes.c_longlong), ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_int)]
        lib.hvd_core_timeline_start.restype = ctypes.c_int
        lib.hvd_core_timeline_start.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p]
        lib.hvd_core_timeline_stop.argtypes = [ctypes.c_void_p]
        lib.hvd_core_timeline_event.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char]
        lib.hvd_core_abi_version.restype = ctypes.c_int
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


class NativeCore:
    """One native control-plane instance (per hvd.init)."""

    ERR_LEN = 2048

    def __init__(self, group_sizes: list[int], stall_seconds: float = 60.0):
        lib = _load()
        if lib is None:
            raise RuntimeError("native core unavailable")
        self._lib = lib
        arr = (ctypes.c_int * len(group_sizes))(*group_sizes)
        self._handle = lib.hvd_core_create(
            len(group_sizes), arr, ctypes.c_double(stall_seconds))
        if not self._handle:
            raise RuntimeError("hvd_core_create failed")
        self._group_sizes = list(group_sizes)

    def close(self) -> None:
        if getattr(self, "_handle", None):
            self._lib.hvd_core_destroy(self._handle)
            self._handle = None

    def __del__(self):  # best-effort; explicit close preferred
        try:
            self.close()
        except Exception:
            pass

    def submit(self, group: int, name: str, op: int, dtype: str,
               shape: tuple[int, ...], root_rank: int, rank: int
               ) -> tuple[int, str]:
        """Returns (status, error): status 0 pending, 1 ready, -1 error."""
        dims = (ctypes.c_longlong * max(1, len(shape)))(*(shape or (0,)))
        err = ctypes.create_string_buffer(self.ERR_LEN)
        status = self._lib.hvd_core_submit(
            self._handle, group, name.encode(), op, dtype.encode(),
            len(shape), dims, root_rank, rank, err, self.ERR_LEN)
        return status, err.value.decode()

    def response_sizes(self, group: int, name: str) -> list[int] | None:
        n = self._group_sizes[group]
        out = (ctypes.c_longlong * n)()
        got = self._lib.hvd_core_response_sizes(
            self._handle, group, name.encode(), out, n)
        if got < 0:
            return None
        return [int(out[i]) for i in range(got)]

    def response_root(self, group: int, name: str) -> int:
        return self._lib.hvd_core_response_root(
            self._handle, group, name.encode())

    def response_done(self, group: int, name: str) -> None:
        self._lib.hvd_core_response_done(self._handle, group, name.encode())

    def stalled(self, group: int) -> list[str]:
        buf = ctypes.create_string_buffer(1 << 16)
        n = self._lib.hvd_core_stalled(self._handle, group, buf, 1 << 16)
        if n <= 0:
            return []
        return buf.value.decode().split("\n")

    def plan_fusion(self, threshold: int, nbytes: list[int],
                    dtype_codes: list[int]) -> list[int]:
        n = len(nbytes)
        if n == 0:
            return []
        nb = (ctypes.c_longlong * n)(*nbytes)
        dc = (ctypes.c_int * n)(*dtype_codes)
        out = (ctypes.c_int * n)()
        got = self._lib.hvd_core_plan_fusion(threshold, n, nb, dc, out)
        if got < 0:
            raise RuntimeError("hvd_core_plan_fusion failed")
        return [int(out[i]) for i in range(n)]

    def timeline_start(self, path: str) -> bool:
        return self._lib.hvd_core_timeline_start(
            self._handle, path.encode()) == 0

    def timeline_stop(self) -> None:
        self._lib.hvd_core_timeline_stop(self._handle)

    def timeline_event(self, tensor: str, activity: str, phase: str) -> None:
        self._lib.hvd_core_timeline_event(
            self._handle, tensor.encode(), activity.encode(),
            phase.encode()[0:1] or b"i")
