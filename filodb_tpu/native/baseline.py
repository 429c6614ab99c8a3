"""ctypes binding for the C++ CPU baseline (src/baseline.cpp).

A multithreaded -O3 C++ implementation of the reference's per-series /
per-window query iterator (a stand-in for the JVM's).  No serving path
calls it: ``benchmark/run.py`` imports it to report whether the native
sources build (``native_build_errors``), and ``tests/test_baseline.py``
holds its semantics.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "src", "baseline.cpp")
_SO = os.path.join(_HERE, "_baseline.so")

_lock = threading.Lock()
_lib = None
_build_error: str | None = None


def _build() -> str | None:
    try:
        if os.path.exists(_SO) and os.path.getmtime(_SO) >= os.path.getmtime(_SRC):
            return None
        tmp = f"{_SO}.{os.getpid()}.tmp"
        cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
               "-o", tmp, _SRC]
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
        if proc.returncode != 0:
            if os.path.exists(tmp):
                os.remove(tmp)
            return proc.stderr.strip() or "g++ failed"
        os.replace(tmp, _SO)
        return None
    except Exception as e:
        return str(e)


def _load():
    global _lib, _build_error
    with _lock:
        if _lib is not None or _build_error is not None:
            return _lib
        err = _build()  # filolint: disable=blocking-under-lock — single-flight native build: the first caller compiles once per process; contenders must wait for the artifact, not race the compiler
        if err is not None:
            _build_error = err
            return None
        lib = ctypes.CDLL(_SO)
        lib.baseline_hw_threads.restype = ctypes.c_int
        sig = [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t,
               ctypes.c_size_t, ctypes.c_void_p, ctypes.c_size_t,
               ctypes.c_void_p, ctypes.c_size_t, ctypes.c_longlong,
               ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int]
        for name in ("baseline_rate_sum", "baseline_sum_over_time"):
            fn = getattr(lib, name)
            fn.restype = ctypes.c_int
            fn.argtypes = sig
        _lib = lib
        return _lib


def build_error() -> str | None:
    _load()
    return _build_error


def available() -> bool:
    return _load() is not None


def hw_threads() -> int:
    lib = _load()
    return int(lib.baseline_hw_threads()) if lib is not None else 1


def _run(name: str, ts: np.ndarray, vals: np.ndarray, ids: np.ndarray,
         n_groups: int, steps: np.ndarray, window_ms: int,
         nthreads: int = 0) -> tuple[np.ndarray, np.ndarray]:
    lib = _load()
    if lib is None:
        raise RuntimeError(f"baseline lib unavailable: {_build_error}")
    ts = np.ascontiguousarray(ts, dtype=np.int64)
    vals = np.ascontiguousarray(vals, dtype=np.float64)
    ids = np.ascontiguousarray(ids, dtype=np.int32)
    steps = np.ascontiguousarray(steps, dtype=np.int64)
    S, R = ts.shape
    assert vals.shape == (S, R) and ids.shape == (S,)
    T = len(steps)
    out = np.zeros((n_groups, T), dtype=np.float64)
    cnt = np.zeros((n_groups, T), dtype=np.float64)
    rc = getattr(lib, name)(
        ts.ctypes.data, vals.ctypes.data, S, R, ids.ctypes.data, n_groups,
        steps.ctypes.data, T, window_ms, out.ctypes.data, cnt.ctypes.data,
        nthreads)
    if rc != 0:
        raise ValueError(f"{name} failed (bad group ids?)")
    return out, cnt


def rate_sum(ts, vals, ids, n_groups, steps, window_ms, nthreads=0):
    """sum by (group)(rate(metric[window])) — NaN where a group had no
    contributing series in a window."""
    out, cnt = _run("baseline_rate_sum", ts, vals, ids, n_groups, steps,
                    window_ms, nthreads)
    return np.where(cnt > 0, out, np.nan)


def sum_over_time_sum(ts, vals, ids, n_groups, steps, window_ms, nthreads=0):
    out, cnt = _run("baseline_sum_over_time", ts, vals, ids, n_groups,
                    steps, window_ms, nthreads)
    return np.where(cnt > 0, out, np.nan)
