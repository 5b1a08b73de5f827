"""Native (C++) runtime core: builds native.cc on first import and exposes
the hot host-side paths via ctypes (which releases the GIL for the call —
batch assembly overlaps the training step in the prefetch thread).

`_native.so` is a build product (git-ignored), compiled from the committed
native.cc on the machine that runs. Every caller treats
`batch_gather(...) -> None` as "use the numpy path", so a helper that
cannot be built is reported ONCE, loudly (a warning naming the compiler's
error) — never dropped in silence."""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "native.cc")
_SO = os.path.join(_HERE, "_native.so")
_lock = threading.Lock()
_lib = None
_failed = False  # one build attempt per process; don't re-spawn c++ on failure
_log = logging.getLogger(__name__)


def _give_up(why: str) -> None:
    global _failed
    _failed = True
    _log.warning("flexflow_tpu native helper unavailable, using the numpy/"
                 "python paths (batch assembly, topo order): %s", why)


def _build() -> Optional[ctypes.CDLL]:
    global _lib
    with _lock:
        if _lib is not None:
            return _lib
        if _failed:
            return None
        if not os.path.exists(_SO) or \
                os.path.getmtime(_SO) < os.path.getmtime(_SRC):
            # pid-unique temp so concurrent processes can't corrupt the .so
            # mid-write; os.replace is atomic
            tmp = f"{_SO}.{os.getpid()}.tmp"
            cmd = ["c++", "-O3", "-shared", "-fPIC", "-std=c++17",
                   "-o", tmp, _SRC]
            try:
                subprocess.run(cmd, check=True, capture_output=True,
                               text=True, timeout=120)
                os.replace(tmp, _SO)
            except (OSError, subprocess.SubprocessError) as e:
                _give_up(f"`{' '.join(cmd)}` failed: {e}\n"
                         f"{getattr(e, 'stderr', '') or ''}")
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                return None
        try:
            lib = ctypes.CDLL(_SO)
        except OSError as e:
            _give_up(f"cannot load {_SO}: {e}")
            return None
        lib.ff_batch_gather.restype = ctypes.c_int
        lib.ff_batch_gather.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int64, ctypes.c_int64]
        lib.ff_topo_order.restype = ctypes.c_int
        lib.ff_topo_order.argtypes = [
            ctypes.c_int64, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64)]
        _lib = lib
        return lib


def available() -> bool:
    return _build() is not None


def batch_gather(arr: np.ndarray, idx: np.ndarray) -> Optional[np.ndarray]:
    """dst[i] = arr[idx[i]] over the leading dim (dataloader batch assembly,
    reference src/dataloader/dataloader.cc next_batch scatter). Returns None
    when the native path doesn't apply (caller falls back to numpy)."""
    lib = _build()
    if lib is None or arr.ndim < 1 or arr.dtype == object:
        return None
    if not arr.flags.c_contiguous:
        # copying the whole dataset per batch would be slower than numpy's
        # fancy indexing; fall back
        return None
    idx64 = np.ascontiguousarray(np.asarray(idx, dtype=np.int64))
    if idx64.ndim != 1:
        return None
    out = np.empty((idx64.shape[0],) + arr.shape[1:], dtype=arr.dtype)
    row_bytes = int(arr.dtype.itemsize * np.prod(arr.shape[1:], dtype=np.int64))
    if row_bytes == 0 or arr.shape[0] == 0:
        # match numpy semantics: any index into an empty dim is an error
        if idx64.size and (arr.shape[0] == 0 or
                           (idx64 >= arr.shape[0]).any() or (idx64 < 0).any()):
            raise IndexError("batch_gather index out of range")
        return out
    rc = lib.ff_batch_gather(
        arr.ctypes.data_as(ctypes.c_char_p), arr.shape[0],
        out.ctypes.data_as(ctypes.c_char_p),
        idx64.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        idx64.shape[0], row_bytes)
    if rc != 0:
        raise IndexError("batch_gather index out of range")
    return out


def topo_order_indices(n_nodes: int, edges) -> Optional[np.ndarray]:
    """Stable Kahn topological order over (src, dst) index pairs
    (reference basic_graph.h traversals). Returns node indices, or None
    when the native library is unavailable. Raises ValueError on a cycle."""
    lib = _build()
    if lib is None:
        return None
    edges = np.ascontiguousarray(np.asarray(list(edges), dtype=np.int64))
    if edges.size == 0:
        edges = np.zeros((0, 2), np.int64)
    src = np.ascontiguousarray(edges[:, 0])
    dst = np.ascontiguousarray(edges[:, 1])
    out = np.empty((n_nodes,), np.int64)
    rc = lib.ff_topo_order(
        n_nodes, src.shape[0],
        src.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        dst.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
    if rc != 0:
        raise ValueError("cycle detected in layer graph")
    return out
