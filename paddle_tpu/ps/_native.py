"""ctypes loader for the native ps_core library; builds on first use.

The reference's pybind bridge role (`paddle/fluid/pybind/`) is played by a
plain C ABI + ctypes (pybind11 is not in this image); numpy arrays pass
zero-copy via ctypes pointers.
"""
from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "csrc", "ps_core.cpp")

_lib = None


def lib_path(src=_SRC):
    """`libps_core.<hash of the source>.so`, beside the source. The
    hash is in the NAME so that a binary built from another version of
    `ps_core.cpp` — copied along with the tree, whatever its mtime —
    can never be the one that loads."""
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(os.path.dirname(src), f"libps_core.{digest}.so")


def _build(out):
    tmp = f"{out}.tmp{os.getpid()}"
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", _SRC,
           "-o", tmp, "-lpthread"]
    proc = subprocess.run(cmd, capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(
            f"native ps_core build failed ({' '.join(cmd)}):\n"
            f"{proc.stderr[-4000:]}")
    os.replace(tmp, out)        # atomic: a concurrent loader sees all or nothing
    for old in glob.glob(os.path.join(os.path.dirname(out),
                                      "libps_core*.so")):
        if old != out:
            os.remove(old)


def get_lib():
    global _lib
    if _lib is not None:
        return _lib
    path = lib_path()
    if not os.path.exists(path):
        _build(path)
    try:
        lib = ctypes.CDLL(path)
    except OSError:
        # foreign binary (e.g. different arch): rebuild from source
        _build(path)
        lib = ctypes.CDLL(path)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    f32p = ctypes.POINTER(ctypes.c_float)
    i32p = ctypes.POINTER(ctypes.c_int)

    lib.pscore_sparse_create.argtypes = [ctypes.c_int, ctypes.c_int,
                                         ctypes.c_float, ctypes.c_float]
    lib.pscore_sparse_create.restype = ctypes.c_int
    lib.pscore_sparse_pull.argtypes = [ctypes.c_int, u64p, ctypes.c_int,
                                       f32p]
    lib.pscore_sparse_push.argtypes = [ctypes.c_int, u64p, f32p,
                                       ctypes.c_int, f32p, f32p]
    lib.pscore_sparse_size.argtypes = [ctypes.c_int]
    lib.pscore_sparse_size.restype = ctypes.c_int64
    lib.pscore_sparse_enable_spill.argtypes = [ctypes.c_int,
                                               ctypes.c_char_p,
                                               ctypes.c_int64]
    lib.pscore_sparse_enable_spill.restype = ctypes.c_int
    lib.pscore_sparse_mem_size.argtypes = [ctypes.c_int]
    lib.pscore_sparse_mem_size.restype = ctypes.c_int64
    lib.pscore_sparse_spill_size.argtypes = [ctypes.c_int]
    lib.pscore_sparse_spill_size.restype = ctypes.c_int64
    lib.pscore_sparse_shrink.argtypes = [ctypes.c_int, ctypes.c_float,
                                         ctypes.c_int]
    lib.pscore_sparse_shrink.restype = ctypes.c_int64
    lib.pscore_sparse_save.argtypes = [ctypes.c_int, ctypes.c_char_p]
    lib.pscore_sparse_save.restype = ctypes.c_int
    lib.pscore_sparse_load.argtypes = [ctypes.c_int, ctypes.c_char_p]
    lib.pscore_sparse_load.restype = ctypes.c_int
    # accessor-family API (CtrCommon/CtrDouble/CtrDymf)
    lib.pscore_sparse_create2.argtypes = [
        ctypes.c_int, ctypes.c_int, ctypes.c_float, ctypes.c_float,
        ctypes.c_int, ctypes.c_float]
    lib.pscore_sparse_create2.restype = ctypes.c_int
    lib.pscore_sparse_accessor.argtypes = [ctypes.c_int]
    lib.pscore_sparse_accessor.restype = ctypes.c_int
    lib.pscore_sparse_pull_dymf.argtypes = [
        ctypes.c_int, u64p, ctypes.c_int, f32p, ctypes.c_int]
    lib.pscore_sparse_push_dymf.argtypes = [
        ctypes.c_int, u64p, i32p, f32p, ctypes.c_int, ctypes.c_int,
        f32p, f32p, f32p]
    lib.pscore_sparse_key_stats.argtypes = [
        ctypes.c_int, ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_double),
        i32p]
    lib.pscore_sparse_key_stats.restype = ctypes.c_int

    lib.pscore_dense_create.argtypes = [ctypes.c_int64, ctypes.c_int,
                                        ctypes.c_float]
    lib.pscore_dense_create.restype = ctypes.c_int
    lib.pscore_dense_set.argtypes = [ctypes.c_int, f32p, ctypes.c_int64]
    lib.pscore_dense_pull.argtypes = [ctypes.c_int, f32p, ctypes.c_int64]
    lib.pscore_dense_push.argtypes = [ctypes.c_int, f32p, ctypes.c_int64]
    lib.pscore_dense_add.argtypes = [ctypes.c_int, f32p, ctypes.c_int64]

    lib.pscore_dataset_create.restype = ctypes.c_int
    lib.pscore_dataset_load_file.argtypes = [ctypes.c_int, ctypes.c_char_p]
    lib.pscore_dataset_load_file.restype = ctypes.c_int
    lib.pscore_dataset_shuffle.argtypes = [ctypes.c_int, ctypes.c_uint64]
    lib.pscore_dataset_size.argtypes = [ctypes.c_int]
    lib.pscore_dataset_size.restype = ctypes.c_int64
    lib.pscore_dataset_rewind.argtypes = [ctypes.c_int]
    lib.pscore_dataset_next_batch.argtypes = [
        ctypes.c_int, ctypes.c_int, i32p, ctypes.c_int, ctypes.c_int,
        u64p, f32p]
    lib.pscore_dataset_next_batch.restype = ctypes.c_int
    lib.pscore_dataset_extract_size.argtypes = [
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_uint64]
    lib.pscore_dataset_extract_size.restype = ctypes.c_int64
    lib.pscore_dataset_extract.argtypes = [
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_uint64,
        ctypes.c_char_p]
    lib.pscore_dataset_extract.restype = ctypes.c_int64
    lib.pscore_dataset_retain.argtypes = [
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_uint64]
    lib.pscore_dataset_ingest.argtypes = [
        ctypes.c_int, ctypes.c_char_p, ctypes.c_int64]
    lib.pscore_dataset_ingest.restype = ctypes.c_int64
    _lib = lib
    return lib


def u64_ptr(arr):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64))


def f32_ptr(arr):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


def i32_ptr(arr):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_int))
