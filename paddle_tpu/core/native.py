"""Loader for the native C++ runtime library (csrc/).

The reference framework's runtime substrate (store, allocators, tracer)
is C++ (paddle/phi/core/...); ours is too — csrc/ builds
libpaddle_tpu_native.so, bound here via ctypes (no pybind11 in the
image). The library is built lazily on first use and cached; every
consumer has a pure-Python fallback so the framework still works where
no C++ toolchain exists.
"""

from __future__ import annotations

import ctypes
import logging
import os
import shutil
import subprocess
import threading

logger = logging.getLogger("paddle_tpu.native")

_lock = threading.Lock()
_lib = None
_tried = False
# how the current process got (or did not get) the library; see
# native_status()
_status = "not loaded yet"

_CSRC = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), "csrc")
_SO = os.path.join(_CSRC, "build", "libpaddle_tpu_native.so")

# callback signature for the native job scheduler: (job_id, user_tag, ctx)
JSCHED_CALLBACK = ctypes.CFUNCTYPE(None, ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p)


def _stale() -> bool:
    if not os.path.exists(_SO):
        return True
    so_mtime = os.path.getmtime(_SO)
    return any(
        f.endswith(".cc") and os.path.getmtime(os.path.join(_CSRC, f)) > so_mtime
        for f in os.listdir(_CSRC))


def _build():
    """Run the csrc/ Makefile; returns None on success, else why not."""
    if not os.path.isdir(_CSRC):
        return f"no source directory {_CSRC}"
    if shutil.which("make") is None:
        return "no `make` on PATH"
    try:
        subprocess.run(
            ["make", "-C", _CSRC, f"-j{os.cpu_count() or 2}"],
            check=True, capture_output=True, timeout=300)
    except subprocess.CalledProcessError as e:
        tail = e.stderr.decode(errors="replace").strip().splitlines()[-1:]
        return f"make failed (rc {e.returncode}): {' '.join(tail)}"
    except (subprocess.SubprocessError, OSError) as e:
        return f"make failed: {e}"
    return None if os.path.exists(_SO) else f"make produced no {_SO}"


def _declare(lib: ctypes.CDLL) -> None:
    c = ctypes
    lib.pts_server_start.restype = c.c_void_p
    lib.pts_server_start.argtypes = [c.c_int]
    lib.pts_server_port.restype = c.c_int
    lib.pts_server_port.argtypes = [c.c_void_p]
    lib.pts_server_stop.argtypes = [c.c_void_p]
    lib.pts_client_new.restype = c.c_void_p
    lib.pts_client_new.argtypes = [c.c_char_p, c.c_int, c.c_long]
    lib.pts_client_free.argtypes = [c.c_void_p]
    lib.pts_set.restype = c.c_int
    lib.pts_set.argtypes = [c.c_void_p, c.c_char_p, c.c_char_p, c.c_int]
    lib.pts_get.restype = c.c_int
    lib.pts_get.argtypes = [c.c_void_p, c.c_char_p, c.c_long,
                            c.POINTER(c.c_void_p), c.POINTER(c.c_int)]
    lib.pts_buf_free.argtypes = [c.c_void_p]
    lib.pts_add.restype = c.c_longlong
    lib.pts_add.argtypes = [c.c_void_p, c.c_char_p, c.c_longlong]
    lib.pts_wait.restype = c.c_int
    lib.pts_wait.argtypes = [c.c_void_p, c.c_char_p, c.c_long]
    lib.pts_check.restype = c.c_int
    lib.pts_check.argtypes = [c.c_void_p, c.c_char_p]
    lib.pts_delete_key.restype = c.c_int
    lib.pts_delete_key.argtypes = [c.c_void_p, c.c_char_p]
    lib.pts_num_keys.restype = c.c_longlong
    lib.pts_num_keys.argtypes = [c.c_void_p]
    # arena allocator (csrc/arena.cc)
    lib.pta_create.restype = c.c_void_p
    lib.pta_create.argtypes = [c.c_uint64]
    lib.pta_destroy.argtypes = [c.c_void_p]
    lib.pta_alloc.restype = c.c_void_p
    lib.pta_alloc.argtypes = [c.c_void_p, c.c_uint64]
    lib.pta_free.restype = c.c_int
    lib.pta_free.argtypes = [c.c_void_p, c.c_void_p]
    for fn in ("pta_allocated", "pta_peak", "pta_capacity", "pta_largest_free"):
        getattr(lib, fn).restype = c.c_uint64
        getattr(lib, fn).argtypes = [c.c_void_p]
    lib.pta_reset_peak.argtypes = [c.c_void_p]
    # host tracer (csrc/host_tracer.cc)
    lib.pth_tracer_init.restype = c.c_int
    lib.pth_tracer_init.argtypes = [c.c_uint64]
    lib.pth_tracer_enable.argtypes = [c.c_int]
    lib.pth_tracer_enabled.restype = c.c_int
    lib.pth_record_begin.restype = c.c_int64
    lib.pth_record_begin.argtypes = [c.c_char_p, c.c_uint32]
    lib.pth_record_end.argtypes = [c.c_int64]
    lib.pth_record_instant.argtypes = [c.c_char_p, c.c_uint32]
    lib.pth_tracer_count.restype = c.c_uint64
    lib.pth_tracer_dropped.restype = c.c_uint64
    lib.pth_tracer_drain.restype = c.c_uint64
    lib.pth_tracer_drain.argtypes = [c.c_void_p, c.c_uint64]
    # job scheduler (csrc/job_scheduler.cc)
    lib.jsched_new.restype = c.c_void_p
    lib.jsched_new.argtypes = [c.c_int]
    lib.jsched_free.argtypes = [c.c_void_p]
    lib.jsched_add_job.restype = c.c_int64
    lib.jsched_add_job.argtypes = [c.c_void_p, c.c_int64]
    lib.jsched_add_dep.restype = c.c_int
    lib.jsched_add_dep.argtypes = [c.c_void_p, c.c_int64, c.c_int64]
    lib.jsched_run.restype = c.c_int
    lib.jsched_run.argtypes = [c.c_void_p, JSCHED_CALLBACK, c.c_void_p]
    lib.jsched_n_jobs.restype = c.c_int
    lib.jsched_n_jobs.argtypes = [c.c_void_p]


def get_native():
    """Return the loaded CDLL, building it if needed; None if unavailable.

    Disable with PADDLE_TPU_DISABLE_NATIVE=1 (forces Python fallbacks)."""
    global _lib, _tried, _status
    if _lib is not None:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get("PADDLE_TPU_DISABLE_NATIVE", "0") == "1":
            _status = "python fallback: PADDLE_TPU_DISABLE_NATIVE=1"
            return None
        how = "loaded csrc/build (newer than every source)"
        if _stale():
            err = _build()
            if err is None:
                how = "built from csrc/ by this process"
            elif os.path.exists(_SO):
                how = f"loaded STALE csrc/build (rebuild failed: {err})"
            else:
                _status = f"python fallback: {err}"
                logger.warning("native runtime unavailable, %s", _status)
                return None
        try:
            lib = ctypes.CDLL(_SO)
            _declare(lib)
            _lib = lib
            _status = how
        except (OSError, AttributeError) as e:
            # AttributeError: stale .so missing newer symbols and the
            # rebuild failed — use the pure-Python fallbacks instead
            _status = f"python fallback: cannot load {_SO}: {e}"
            logger.warning("native runtime unavailable, %s", _status)
    return _lib


def native_available() -> bool:
    return get_native() is not None


def native_status() -> str:
    """Which runtime substrate this process runs on, and why: the
    loaded csrc/build library, one built just now from csrc/, or the
    pure-Python fallbacks with the reason the library is missing."""
    get_native()
    return _status
