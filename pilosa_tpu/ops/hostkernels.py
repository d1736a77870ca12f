"""Native host popcount kernels — the CPU half of the execution engine.

When the framework runs without an accelerator (CPU-only host, CI, laptop)
the fused query pipeline keeps operand stacks host-resident as numpy
arrays and counts them here: single-pass AND+popcount in C++
(native/bitcount.cpp, compiled -march=native → AVX-512 VPOPCNTDQ on
capable hosts), no intermediates.  The role the reference's per-container
fast paths play on CPU (roaring/roaring.go:570 intersectionCount*).

Every function falls back to vectorized numpy (np.bitwise_count) when
the native library is unavailable, so behavior is identical everywhere —
only speed differs.
"""

from __future__ import annotations

import ctypes
import os

import numpy as np

from pilosa_tpu.native_loader import NativeLib

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "native")


def _isa_tag() -> str:
    """Short hash of the host's CPU feature flags, embedded in the .so
    name.  -march=native binaries are host-specific; a checkout reused
    on a different CPU (NFS, baked image) must rebuild rather than
    SIGILL on the first AVX-512 instruction — dlopen alone can't catch
    an ISA mismatch."""
    import hashlib

    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return hashlib.sha1(line.encode()).hexdigest()[:8]
    except OSError:
        pass
    import platform

    return hashlib.sha1(platform.processor().encode()).hexdigest()[:8]


def _setup(lib) -> None:
    LL, VP, IP = ctypes.c_longlong, ctypes.c_void_p, ctypes.c_void_p
    lib.pt_set_threads.restype = None
    lib.pt_set_threads.argtypes = [ctypes.c_int]
    lib.pt_effective_threads.restype = ctypes.c_int
    lib.pt_effective_threads.argtypes = [LL]
    lib.pt_count.restype = LL
    lib.pt_count.argtypes = [VP, LL]
    lib.pt_count_and.restype = LL
    lib.pt_count_and.argtypes = [VP, VP, LL]
    lib.pt_row_counts.restype = None
    lib.pt_row_counts.argtypes = [VP, LL, LL, IP]
    lib.pt_row_counts_and.restype = None
    lib.pt_row_counts_and.argtypes = [VP, VP, LL, LL, IP]
    lib.pt_row_counts_masked.restype = None
    lib.pt_row_counts_masked.argtypes = [VP, VP, LL, LL, IP]
    lib.pt_row_counts_gathered.restype = None
    lib.pt_row_counts_gathered.argtypes = [VP, VP, IP, LL, LL, IP]
    lib.pt_masked_matrix_counts.restype = None
    lib.pt_masked_matrix_counts.argtypes = [VP, VP, LL, LL, LL, IP]
    lib.pt_merge_positions.restype = LL
    lib.pt_merge_positions.argtypes = [VP, VP, VP, LL, VP,
                                       ctypes.c_uint64, ctypes.c_int]
    # 0 (default) = auto: hardware_concurrency capped at >=4 MiB of
    # operand per thread; ctypes releases the GIL for the call, so the
    # kernel threads own the cores (the reference's per-shard worker
    # pool, executor.go:2561, collapsed into the kernel).
    lib.pt_set_threads(int(os.environ.get("PILOSA_TPU_HOST_THREADS", "0")))


_NATIVE = NativeLib(
    src=os.path.join(_NATIVE_DIR, "bitcount.cpp"),
    so=os.path.join(_NATIVE_DIR, "build",
                    f"libpilosa_bitcount.{_isa_tag()}.so"),
    setup=_setup,
    # -march=native: built lazily on the host that runs it; the ISA tag
    # in the filename forces a rebuild on any other CPU
    extra_flags=("-march=native", "-funroll-loops", "-pthread"),
)


def set_threads(n: int) -> bool:
    """Override the kernel thread count (0 = auto).  Returns False when
    the native library is unavailable (numpy fallback is serial)."""
    lib = _NATIVE.load()
    if lib is None:
        return False
    lib.pt_set_threads(int(n))
    return True


def effective_threads(words: int) -> int:
    """Thread count a kernel touching `words` uint32s would use under
    the current setting (test/diagnostic hook; 1 when the native
    library is unavailable — the numpy fallback is serial)."""
    lib = _NATIVE.load()
    if lib is None:
        return 1
    return int(lib.pt_effective_threads(int(words)))


def native_available() -> bool:
    return _NATIVE.available()


def _c(a: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(a)


def count(a: np.ndarray) -> int:
    """Total set bits of a uint32 array (any shape)."""
    lib = _NATIVE.load()
    if lib is None:
        return int(np.bitwise_count(a).sum(dtype=np.uint64))
    a = _c(a)
    return int(lib.pt_count(a.ctypes.data, a.size))


def count_and(a: np.ndarray, b: np.ndarray) -> int:
    """|a & b| without materializing the intersection."""
    if a.shape != b.shape:
        raise ValueError(f"shape mismatch: {a.shape} vs {b.shape}")
    lib = _NATIVE.load()
    if lib is None:
        return int(np.bitwise_count(a & b).sum(dtype=np.uint64))
    a, b = _c(a), _c(b)
    return int(lib.pt_count_and(a.ctypes.data, b.ctypes.data, a.size))


def row_counts(mat: np.ndarray) -> np.ndarray:
    """int32[rows] popcounts of a [rows, words] matrix (stacks flatten
    leading dims: a [shards, rows, words] input counts per (shard,row))."""
    lead = mat.shape[:-1]
    rows = int(np.prod(lead)) if lead else 1
    words = mat.shape[-1]
    lib = _NATIVE.load()
    if lib is None:
        return np.bitwise_count(mat).sum(axis=-1).astype(np.int32)
    mat = _c(mat)
    out = np.empty(lead, dtype=np.int32)
    lib.pt_row_counts(mat.ctypes.data, rows, words, out.ctypes.data)
    return out


def row_counts_and(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """int32[rows] of |a[r] & b[r]| — no materialized intersection."""
    if a.shape != b.shape:
        raise ValueError(f"shape mismatch: {a.shape} vs {b.shape}")
    lib = _NATIVE.load()
    if lib is None:
        return np.bitwise_count(a & b).sum(axis=-1).astype(np.int32)
    a, b = _c(a), _c(b)
    rows, words = a.shape
    out = np.empty(rows, dtype=np.int32)
    lib.pt_row_counts_and(a.ctypes.data, b.ctypes.data,
                          rows, words, out.ctypes.data)
    return out


def row_counts_masked(mat: np.ndarray, filt: np.ndarray) -> np.ndarray:
    """int32[rows] of |mat[r] & filt|."""
    if mat.shape[-1] != filt.shape[-1]:
        raise ValueError(f"word-count mismatch: {mat.shape} vs {filt.shape}")
    lib = _NATIVE.load()
    if lib is None:
        return np.bitwise_count(mat & filt[None, :]).sum(axis=-1).astype(np.int32)
    mat, filt = _c(mat), _c(filt)
    rows, words = mat.shape
    out = np.empty(rows, dtype=np.int32)
    lib.pt_row_counts_masked(mat.ctypes.data, filt.ctypes.data,
                             rows, words, out.ctypes.data)
    return out


def row_counts_gathered(mat: np.ndarray, filt_stack: np.ndarray,
                        shard_pos: np.ndarray) -> np.ndarray:
    """int32[rows] of |mat[r] & filt_stack[shard_pos[r]]|."""
    pos = np.ascontiguousarray(shard_pos, dtype=np.int32)
    if mat.shape[-1] != filt_stack.shape[-1]:
        raise ValueError(
            f"word-count mismatch: {mat.shape} vs {filt_stack.shape}")
    if pos.size and (pos.min() < 0 or pos.max() >= len(filt_stack)):
        raise IndexError("shard_pos out of range")
    lib = _NATIVE.load()
    if lib is None:
        filt = filt_stack[pos]
        return np.bitwise_count(mat & filt).sum(axis=-1).astype(np.int32)
    mat, filt_stack = _c(mat), _c(filt_stack)
    rows, words = mat.shape
    out = np.empty(rows, dtype=np.int32)
    lib.pt_row_counts_gathered(mat.ctypes.data, filt_stack.ctypes.data,
                               pos.ctypes.data, rows, words, out.ctypes.data)
    return out


def masked_matrix_counts(mat: np.ndarray, masks: np.ndarray) -> np.ndarray:
    """int32[groups, rows] of |mat[r] & masks[g]|."""
    if mat.shape[-1] != masks.shape[-1]:
        raise ValueError(f"word-count mismatch: {mat.shape} vs {masks.shape}")
    lib = _NATIVE.load()
    if lib is None:
        # per-mask loop bounds memory at O(rows*words), like the native
        # kernel and the jit lax.map — a broadcast would materialize a
        # [groups, rows, words] intermediate
        return np.stack([
            np.bitwise_count(mat & m).sum(axis=-1).astype(np.int32)
            for m in masks]) if len(masks) else np.empty(
                (0, mat.shape[0]), dtype=np.int32)
    mat, masks = _c(mat), _c(masks)
    rows, words = mat.shape
    groups = masks.shape[0]
    out = np.empty((groups, rows), dtype=np.int32)
    lib.pt_masked_matrix_counts(mat.ctypes.data, masks.ctypes.data,
                                groups, rows, words, out.ctypes.data)
    return out


def merge_positions(row_arrays: list, seg_start: np.ndarray,
                    seg_end: np.ndarray, pos: np.ndarray,
                    width_mask: int, clear: bool) -> int | None:
    """Sparse position-space merge into per-row bitmap buffers: for row
    r, OR (or ANDN when clear) the sorted absolute positions
    pos[seg_start[r]:seg_end[r]] (in-row offset = pos & width_mask)
    into row_arrays[r], in place.  Returns flipped-bit count, or None
    when the native library is unavailable (caller runs its numpy
    fallback).  One C call replaces the whole numpy aggregation
    pipeline — the import-roaring sparse hot path
    (fragment._merge_positions)."""
    lib = _NATIVE.load()
    if lib is None:
        return None
    # __array_interface__ is ~10x cheaper per array than .ctypes.data
    ptrs = np.array([a.__array_interface__["data"][0]
                     for a in row_arrays], dtype=np.uint64)
    seg_start = np.ascontiguousarray(seg_start, dtype=np.int64)
    seg_end = np.ascontiguousarray(seg_end, dtype=np.int64)
    pos = np.ascontiguousarray(pos, dtype=np.uint64)
    return int(lib.pt_merge_positions(
        ptrs.ctypes.data, seg_start.ctypes.data, seg_end.ctypes.data,
        len(row_arrays), pos.ctypes.data, width_mask,
        1 if clear else 0))
