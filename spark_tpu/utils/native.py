"""ctypes bindings to the native (C++) host runtime in native/.

Role of the reference's [NATIVE-ROLE] Java off-heap layer
(common/unsafe/.../Platform.java, Murmur3_x86_32.java, RadixSort.java):
host-side hot loops — string hashing at dictionary build, counting-sort
partitioning, dictionary merge — implemented in C++ and loaded via ctypes
(no pybind11 in the image). Builds with g++ on first use, and again
whenever the library is older than its source (the library is git-ignored,
so a copied tree may carry a stale one); every entry point has a numpy
fallback so callers catch ImportError/OSError. `status()` says which of
the three happened.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from functools import lru_cache

import numpy as np

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native")
_SRC_PATH = os.path.join(_NATIVE_DIR, "sparktpu_native.cpp")
_SO_PATH = os.path.join(_NATIVE_DIR, "build", "libsparktpu_native.so")


def _try_build() -> None:
    if not os.path.exists(_SRC_PATH):
        raise ImportError("native source missing")
    os.makedirs(os.path.dirname(_SO_PATH), exist_ok=True)
    # build beside the target and rename: a concurrent process (cluster
    # workers start together) must never dlopen a half-written library
    tmp = f"{_SO_PATH}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O3", "-fPIC", "-shared", "-std=c++17", "-o", tmp,
             _SRC_PATH],
            check=True, capture_output=True, timeout=120)
        os.replace(tmp, _SO_PATH)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def _stale() -> bool:
    try:
        return os.path.getmtime(_SO_PATH) < os.path.getmtime(_SRC_PATH)
    except OSError:
        return True  # no library yet (a missing source fails the build)


@lru_cache(maxsize=1)
def _load_with_origin():
    """(library, "built" | "loaded"): built when this process compiled
    it, loaded when an up-to-date one was already there."""
    origin = "loaded"
    if _stale():
        _try_build()
        origin = "built"
    lib = ctypes.CDLL(_SO_PATH)
    lib.spark_tpu_hash_strings.restype = None
    lib.spark_tpu_hash_strings.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p]
    lib.spark_tpu_radix_partition.restype = None
    lib.spark_tpu_radix_partition.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32,
        ctypes.c_void_p, ctypes.c_void_p]
    lib.spark_tpu_merge_dicts.restype = ctypes.c_int64
    lib.spark_tpu_merge_dicts.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
        ctypes.c_void_p, ctypes.c_void_p]
    return lib, origin


def _load():
    return _load_with_origin()[0]


def available() -> bool:
    try:
        _load()
        return True
    except Exception:
        return False


def status() -> str:
    """`built` (compiled by this process), `loaded` (an up-to-date
    library was already there) or `numpy-fallback (reason)`."""
    try:
        return _load_with_origin()[1]
    except Exception as e:
        return f"numpy-fallback ({type(e).__name__}: {str(e)[:120]})"


def _pack(values: list[str]) -> tuple[bytes, np.ndarray]:
    encoded = [v.encode("utf-8") for v in values]
    blob = b"".join(encoded)
    offsets = np.zeros(len(values) + 1, dtype=np.int64)
    np.cumsum([len(e) for e in encoded], out=offsets[1:])
    return blob, offsets


def hash_strings(values: list[str]) -> np.ndarray:
    """64-bit hashes for a list of strings via the C++ xxhash64 kernel."""
    lib = _load()
    if not values:
        return np.zeros(0, dtype=np.int64)
    blob, offsets = _pack(values)
    out = np.empty(len(values), dtype=np.int64)
    buf = ctypes.create_string_buffer(blob, max(len(blob), 1))
    lib.spark_tpu_hash_strings(
        buf, offsets.ctypes.data_as(ctypes.c_void_p), len(values),
        out.ctypes.data_as(ctypes.c_void_p))
    return out


def radix_partition(pids: np.ndarray, num_partitions: int):
    """Counting-sort row indices by partition id.

    Returns (order int64[n] — row indices grouped by pid, counts int64[p])."""
    lib = _load()
    pids = np.ascontiguousarray(pids, dtype=np.int32)
    order = np.empty(len(pids), dtype=np.int64)
    counts = np.zeros(num_partitions, dtype=np.int64)
    lib.spark_tpu_radix_partition(
        pids.ctypes.data_as(ctypes.c_void_p), len(pids), num_partitions,
        order.ctypes.data_as(ctypes.c_void_p),
        counts.ctypes.data_as(ctypes.c_void_p))
    return order, counts


def merge_dicts(value_lists: list[list[str]]):
    """Union several string dictionaries.

    Returns (merged values list, [recode int32 array per input dict])."""
    lib = _load()
    all_values = [v for vals in value_lists for v in vals]
    if not all_values:
        return [], [np.zeros(0, np.int32) for _ in value_lists]
    blob, offsets = _pack(all_values)
    recode = np.empty(len(all_values), dtype=np.int32)
    morder = np.empty(len(all_values), dtype=np.int64)
    buf = ctypes.create_string_buffer(blob, max(len(blob), 1))
    n = lib.spark_tpu_merge_dicts(
        buf, offsets.ctypes.data_as(ctypes.c_void_p), len(all_values),
        recode.ctypes.data_as(ctypes.c_void_p),
        morder.ctypes.data_as(ctypes.c_void_p))
    merged = [all_values[morder[i]] for i in range(n)]
    out = []
    pos = 0
    for vals in value_lists:
        out.append(recode[pos:pos + len(vals)].copy())
        pos += len(vals)
    return merged, out
