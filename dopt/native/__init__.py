"""Native (C++) host-runtime components, bound via ctypes.

The reference is pure Python (SURVEY §2: no native components anywhere
in the tree), so nothing here is owed for parity — this is the
framework's own host runtime: per-round batch-plan generation in C++
(``plan.cpp``) so the host side never throttles the TPU at large worker
counts.

Build model: compiled lazily with ``g++ -O3 -shared -fPIC`` into the
package directory on first use and cached (mtime-checked against the
source).  The native planner draws a DIFFERENT batch order than the
numpy one, so a run that asked for it (``plan_impl="native"``) and
cannot have it fails with the compiler's stderr (``NativeUnavailable``)
instead of quietly training on other batches.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "plan.cpp")
_ABI_VERSION = 2
# ABI version in the filename: a cached .so from a different source
# generation gets a different name, so a rebuild can never collide with
# an already-dlopened stale handle (glibc returns the existing handle
# for a known pathname).
_LIB = os.path.join(_DIR, f"libdopt_host_v{_ABI_VERSION}.so")



class NativeUnavailable(RuntimeError):
    """The native host library could not be built or loaded."""


_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_error: NativeUnavailable | None = None


def _build() -> None:
    """Compile plan.cpp → libdopt_host_v<ABI>.so (written under a
    per-process name and renamed, so concurrent first uses never load a
    half-written binary)."""
    tmp = f"{_LIB}.{os.getpid()}"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", _SRC, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True,
                       timeout=120)
        os.replace(tmp, _LIB)
    except subprocess.CalledProcessError as e:
        raise NativeUnavailable(
            f"{' '.join(cmd)} exited {e.returncode}:\n{e.stderr}") from e
    except (OSError, subprocess.TimeoutExpired) as e:
        raise NativeUnavailable(f"{' '.join(cmd)} did not run: {e}") from e
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def _load() -> ctypes.CDLL:
    fresh = os.path.exists(_LIB) and (
        not os.path.exists(_SRC)
        or os.path.getmtime(_LIB) >= os.path.getmtime(_SRC)
    )
    if not fresh:
        _build()
    try:
        # Single dlopen, then validate; never re-dlopen the same
        # pathname in-process (it would return the stale handle).
        lib = ctypes.CDLL(_LIB)
        lib.dopt_native_abi_version.restype = ctypes.c_int
        abi = lib.dopt_native_abi_version()
        fill = lib.dopt_fill_batch_plan
    except (OSError, AttributeError) as e:
        raise NativeUnavailable(f"cannot load {_LIB}: {e}") from e
    if abi != _ABI_VERSION:
        raise NativeUnavailable(
            f"{_LIB} reports ABI {abi}, expected {_ABI_VERSION}")
    fill.restype = ctypes.c_int
    fill.argtypes = [
        ctypes.POINTER(ctypes.c_int32),  # index_matrix
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,  # W, L, B
        ctypes.c_int64, ctypes.c_int64,  # local_ep, steps_per_epoch
        ctypes.c_int32,                  # drop_last
        ctypes.c_int64, ctypes.c_int64,  # seed, round_idx
        ctypes.POINTER(ctypes.c_int64),  # worker_ids (nullable)
        ctypes.POINTER(ctypes.c_int32),  # idx_out
        ctypes.POINTER(ctypes.c_float),  # w_out
    ]
    return lib


def load_native() -> ctypes.CDLL:
    """Load (building if needed) the native library, once per process;
    raises ``NativeUnavailable`` (every call) when that failed."""
    global _lib, _error
    with _lock:
        if _lib is None and _error is None:
            try:
                _lib = _load()
            except NativeUnavailable as e:
                _error = e
        if _error is not None:
            raise _error
        return _lib


def native_available() -> bool:
    """Probe for tests and tools that pick the planner themselves."""
    try:
        load_native()
    except NativeUnavailable:
        return False
    return True


def fill_batch_plan_native(
    index_matrix: np.ndarray,
    *,
    batch_size: int,
    local_ep: int,
    seed: int,
    round_idx: int,
    drop_last: bool = False,
    worker_ids: np.ndarray | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Native batch-plan fill; returns (idx, weight) arrays shaped like
    ``dopt.data.pipeline.make_batch_plan``'s.  Raises
    ``NativeUnavailable`` when the library cannot be built or loaded.

    ``worker_ids`` maps each row of ``index_matrix`` to its true worker
    id for RNG keying (compact-sampling: pass the m sampled rows plus
    their ids and get plans bit-identical to those rows of the full
    plan).  None means row i is worker i.

    Deterministic in (seed, round_idx, epoch, worker) via a seeded
    xoshiro256** stream — NOT bit-identical to the numpy PCG64 plans
    (use the numpy path for torch-oracle parity runs).
    """
    lib = load_native()
    im = np.ascontiguousarray(index_matrix, dtype=np.int32)
    w, l = im.shape
    bs = min(batch_size, l)
    steps_per_epoch = (l // bs) if drop_last else -(-l // bs)
    s = local_ep * steps_per_epoch
    idx = np.empty((w, s, bs), dtype=np.int32)
    weight = np.empty((w, s, bs), dtype=np.float32)
    if worker_ids is not None:
        wid = np.ascontiguousarray(worker_ids, dtype=np.int64)
        if wid.shape != (w,):
            raise ValueError(f"worker_ids shape {wid.shape} != ({w},)")
        wid_ptr = wid.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))
    else:
        wid_ptr = None
    rc = lib.dopt_fill_batch_plan(
        im.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        w, l, bs, local_ep, steps_per_epoch, int(drop_last),
        seed, round_idx, wid_ptr,
        idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        weight.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
    )
    if rc != 0:
        raise ValueError(f"dopt_fill_batch_plan rejected its arguments "
                         f"(rc={rc}; W={w}, L={l}, B={bs})")
    return idx, weight


__all__ = ["NativeUnavailable", "load_native", "native_available",
           "fill_batch_plan_native"]
