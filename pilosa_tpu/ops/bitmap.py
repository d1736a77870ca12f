"""Packed-bitmap kernel: the TPU-native core set-algebra engine.

Replaces the reference's hand-written roaring container algebra
(roaring/roaring.go:595-1023 Intersect/Union/Difference/Xor/Shift/Flip and
the per-container-type fast paths at roaring/roaring.go:2069-2749) with
dense bitwise ops the XLA compiler fuses and tiles onto TPU vector units.

Layout
------
A bitmap covering ``nbits`` columns is a ``uint32[nbits // 32]`` tensor.
Bit for column ``c`` lives in word ``c // 32`` at bit position ``c % 32``
(LSB-first).  Because the byte order is little-endian, viewing a host copy
as uint64 reproduces the reference's 64-bit word layout bit-for-bit
(roaring containers hold 1024 x uint64 = 2^16 bits), which keeps the
roaring file codec (storage/roaring.py) a pure reinterpret-cast away.

Counts are returned as int32: a single shard holds at most 2^20 bits per
row, far below 2^31, and cross-shard / cross-row totals are accumulated in
Python ints by the executor — exact arithmetic without enabling jax x64.

uint32 (not uint64) words are used on device because JAX's default dtype
regime is 32-bit and TPU has no native 64-bit integer path.
"""

from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from pilosa_tpu import faultinject as _fi
from pilosa_tpu import observe as _observe

WORD_BITS = 32
_WORD_DTYPE = np.uint32


# ---------------------------------------------------------------------------
# Dispatch accounting — every public op below ticks once per kernel launch
# (jit dispatch on device, native/numpy kernel pass on host), so tests can
# assert how many launches a query actually cost.  The fused expression
# compiler (ops/expr.py) ticks ONCE for a whole tree, which is the point:
# launch count is what a CPU run can say about a query's device cost,
# and it must be regression-testable.
# ---------------------------------------------------------------------------

_dispatch = threading.local()  # .log: list[str] while a counter is active


def note_dispatch(name: str) -> None:
    """Record one kernel launch on this thread (no-op unless a
    dispatch_counter — or a query flight record, pilosa_tpu.observe —
    is active on it).  The flight recorder reuses THIS hook so a
    query's profiled device-launch count is the dispatch-count the
    regression tests pin, by construction."""
    if _fi.armed:
        # failpoint: every device kernel launch funnels through here —
        # error(oom) exercises the executor's RESOURCE_EXHAUSTED
        # evict-and-retry without a real allocation failure.  Gated on
        # the module bool so the disarmed hot path pays one attribute
        # read (tests/test_observer_cost.py).
        _fi.hit("device.dispatch")
    log = getattr(_dispatch, "log", None)
    if log is not None:
        log.append(name)
    rec = _observe.current()
    if rec is not None:
        rec.note_launch(name)


class dispatch_counter:
    """Context manager counting kernel launches on the CURRENT thread.
    Nested counters stack (the inner one shadows).  Thread-local by
    design: the executor's fused paths run on the calling thread, which
    is exactly the scope a dispatch-count regression test needs."""

    def __enter__(self):
        self._prev = getattr(_dispatch, "log", None)
        self.launches: list[str] = []
        _dispatch.log = self.launches
        return self

    def __exit__(self, *exc):
        _dispatch.log = self._prev
        return False

    @property
    def n(self) -> int:
        return len(self.launches)


def n_words(nbits: int) -> int:
    """Number of uint32 words for a bitmap of ``nbits`` columns."""
    if nbits % WORD_BITS != 0:
        raise ValueError(f"nbits must be a multiple of {WORD_BITS}, got {nbits}")
    return nbits // WORD_BITS


def host_mode() -> bool:
    """True when compute should stay host-resident: a single CPU device
    means XLA buys no parallelism here, and the native popcount kernels
    (ops/hostkernels.py) beat XLA:CPU codegen by ~8x at query shapes.
    Placement (Field._place_on_devices, Fragment.device_*) consults this
    once per stack build; every op below then dispatches on operand
    type, so host stacks flow through numpy + native C++ and device
    stacks through the jit kernels.  Asked of JAX once a backend: its
    devices cannot change while it lives (``forget_backend``)."""
    global _host_mode
    mode = _host_mode
    if mode is None:
        devs = jax.devices()
        mode = _host_mode = len(devs) == 1 and devs[0].platform == "cpu"
    return mode


#: ``host_mode``'s answer for the live backend; None = not asked yet
_host_mode: bool | None = None


def forget_backend() -> None:
    """The backend is being replaced (``meshexec.backend_reset``)."""
    global _host_mode
    _host_mode = None


def _host(*xs) -> bool:
    """Dispatch predicate: all array operands are host numpy arrays."""
    return all(isinstance(x, np.ndarray) for x in xs)


# ---------------------------------------------------------------------------
# Host-side packing (numpy) — the boundary between sparse positions arriving
# over the wire and dense device tensors.
# ---------------------------------------------------------------------------


def pack_positions(positions, nbits: int) -> np.ndarray:
    """Pack sorted-or-not bit positions into a uint32 word array (host)."""
    words = np.zeros(n_words(nbits), dtype=_WORD_DTYPE)
    if len(positions) == 0:
        return words
    pos = np.asarray(positions, dtype=np.int64)
    if pos.size and (pos.min() < 0 or pos.max() >= nbits):
        raise ValueError(f"position out of range [0, {nbits})")
    np.bitwise_or.at(
        words,
        pos // WORD_BITS,
        (np.uint32(1) << (pos % WORD_BITS).astype(np.uint32)),
    )
    return words


def group_indices(keys: np.ndarray) -> dict:
    """Group index positions 0..n-1 by ``keys[i]`` -> {int(key):
    ndarray of indices}, via one stable argsort + split.  The shared
    host-side bulk-import grouping primitive (field.import_bits and
    api._group_by_shard) — a per-element Python loop costs ~1 us/key
    at millions of keys; this is ~30x faster and must exist exactly
    once."""
    if not len(keys):
        return {}
    order = np.argsort(keys, kind="stable")
    ks = keys[order]
    bounds = np.flatnonzero(np.diff(ks)) + 1
    firsts = ks[np.concatenate(([0], bounds))]
    return {int(k): chunk
            for k, chunk in zip(firsts, np.split(order, bounds))}


def unpack_positions(words: np.ndarray) -> np.ndarray:
    """Inverse of pack_positions: word array -> sorted int64 positions (host)."""
    bits = np.unpackbits(np.ascontiguousarray(words).view(np.uint8), bitorder="little")
    return np.nonzero(bits)[0].astype(np.int64)


def device_put(stack: np.ndarray, device=None, label: str = "other"):
    """``jax.device_put`` with the transfer metered under ``label``
    (devobs transfer section) — the one staging call every
    host->device operand upload goes through."""
    from pilosa_tpu import devobs as _devobs

    _devobs.note_transfer(stack.nbytes, 1, label)
    if device is not None:
        return jax.device_put(stack, device)
    return jax.device_put(stack)


def pack_positions_matrix(rows_cols, row_ids, nbits: int) -> np.ndarray:
    """Pack (row, col) pairs into a dense [len(row_ids), nbits/32] matrix.

    ``row_ids`` maps matrix slots to logical row ids; pairs whose row is not
    present raise.  Host-side bulk-import helper (analog of the sorted-run
    import at fragment.go:2053).
    """
    slot = {r: i for i, r in enumerate(row_ids)}
    mat = np.zeros((len(row_ids), n_words(nbits)), dtype=_WORD_DTYPE)
    for r, c in rows_cols:
        if c < 0 or c >= nbits:
            raise ValueError(f"column {c} out of range [0, {nbits})")
        mat[slot[r], c // WORD_BITS] |= _WORD_DTYPE(1) << _WORD_DTYPE(c % WORD_BITS)
    return mat


# ---------------------------------------------------------------------------
# Elementwise set algebra — jitted; XLA fuses chains of these into one kernel.
# ---------------------------------------------------------------------------


@jax.jit
def _jit_and(a, b):
    return jnp.bitwise_and(a, b)


def b_and(a, b):
    """Intersect (roaring.Intersect, roaring/roaring.go:595)."""
    note_dispatch("b_and")
    if _host(a, b):
        return np.bitwise_and(a, b)
    return _jit_and(a, b)


@jax.jit
def _jit_or(a, b):
    return jnp.bitwise_or(a, b)


def b_or(a, b):
    """Union (roaring.Union, roaring/roaring.go:620)."""
    note_dispatch("b_or")
    if _host(a, b):
        return np.bitwise_or(a, b)
    return _jit_or(a, b)


@jax.jit
def _jit_xor(a, b):
    return jnp.bitwise_xor(a, b)


def b_xor(a, b):
    """Symmetric difference (roaring.Xor, roaring/roaring.go:918)."""
    note_dispatch("b_xor")
    if _host(a, b):
        return np.bitwise_xor(a, b)
    return _jit_xor(a, b)


@jax.jit
def _jit_andnot(a, b):
    return jnp.bitwise_and(a, jnp.bitwise_not(b))


def b_andnot(a, b):
    """Difference a \\ b (roaring.Difference, roaring/roaring.go:891)."""
    note_dispatch("b_andnot")
    if _host(a, b):
        return np.bitwise_and(a, np.bitwise_not(b))
    return _jit_andnot(a, b)


@jax.jit
def _jit_not(a, existence):
    return jnp.bitwise_and(jnp.bitwise_not(a), existence)


def b_not(a, existence):
    """Complement within an existence mask (executor Not uses the index's
    existence row as the universe, executor.go:1708)."""
    note_dispatch("b_not")
    if _host(a, existence):
        return np.bitwise_and(np.bitwise_not(a), existence)
    return _jit_not(a, existence)


@functools.lru_cache(maxsize=256)
def _range_mask_np(nwords: int, start: int, end: int) -> np.ndarray:
    """Host-built mask with bits [start, end) set, cached per (shape, range)."""
    mask = np.zeros(nwords, dtype=_WORD_DTYPE)
    if end > start:
        first, last = start // WORD_BITS, (end - 1) // WORD_BITS
        mask[first : last + 1] = np.uint32(0xFFFFFFFF)
        mask[first] &= np.uint32(0xFFFFFFFF) << np.uint32(start % WORD_BITS)
        keep = (end - 1) % WORD_BITS
        mask[last] &= np.uint32(0xFFFFFFFF) >> np.uint32(WORD_BITS - 1 - keep)
    return mask


def b_flip_range(a, start: int, end: int):
    """Flip bits in [start, end) (roaring.Flip, roaring/roaring.go:1683)."""
    mask = _range_mask_np(a.shape[-1], start, end)
    if _host(a):
        note_dispatch("b_flip_range")
        return np.bitwise_xor(a, mask)
    return b_xor(a, jnp.asarray(mask))  # b_xor ticks the dispatch


def shift_words(xp, a, n: int):
    """The ONE shift body, over either array namespace (``xp`` = numpy
    or jax.numpy; jax-traceable with static ``n``): bits move toward
    higher columns and drop at the shard edge (roaring.Shift semantics
    per shard, executor.go:1730).  Shared by the host/jit wrappers here
    and the fused expression compiler (ops/expr.py) so the four shift
    call sites cannot drift bit-for-bit."""
    if n == 0:
        return a
    w, s = n // WORD_BITS, n % WORD_BITS
    nw = a.shape[-1]
    if w >= nw:
        # every bit shifts past the shard edge; computing it would pad
        # an O(n)-word intermediate and compile per distinct n
        return xp.zeros_like(a)
    pad = [(0, 0)] * (a.ndim - 1)
    # words move up by w: out_word[i] = a[i - w]
    shifted = xp.pad(a, pad + [(w, 0)])[..., :nw]
    if s == 0:
        return shifted
    prev = xp.pad(shifted, pad + [(1, 0)])[..., :nw]
    return (shifted << np.uint32(s)) | (prev >> np.uint32(WORD_BITS - s))


def b_shift(a, n: int = 1):
    """Shift all bits toward higher columns by ``n`` (roaring.Shift,
    roaring/roaring.go:946).  Bits shifted past the shard width are dropped,
    matching per-shard Shift execution (executor.go:1730)."""
    if n < 0:
        raise ValueError("shift distance must be non-negative")
    note_dispatch("b_shift")
    if _host(a):
        return shift_words(np, a, n)
    return _jit_shift(a, n)


@functools.partial(jax.jit, static_argnums=(1,))
def _jit_shift(a, n: int = 1):
    if n < 0:
        # a clean error instead of a cryptic negative-pad failure from
        # inside jit tracing; surfaces as a 400 at the query layer
        raise ValueError("shift distance must be non-negative")
    return shift_words(jnp, a, n)


# ---------------------------------------------------------------------------
# Counting — popcount is the workhorse of Count/TopN/Sum.
# ---------------------------------------------------------------------------


@jax.jit
def _jit_popcount(a):
    return jnp.sum(lax.population_count(a), dtype=jnp.int32)


def popcount(a):
    """Total set bits (roaring.Count, roaring/roaring.go:478) — int32
    scalar on device, Python int on host stacks (native kernel)."""
    note_dispatch("popcount")
    if _host(a):
        from pilosa_tpu.ops import hostkernels as hk

        return hk.count(a)
    return _jit_popcount(a)


@jax.jit
def _jit_popcount_and(a, b):
    return jnp.sum(lax.population_count(jnp.bitwise_and(a, b)), dtype=jnp.int32)


def popcount_and(a, b):
    """Fused |a & b| — the north-star IntersectionCount fast path
    (roaring.IntersectionCount, roaring/roaring.go:570): one XLA kernel
    on device (AND + popcount + reduce, no intermediate materialized),
    one C++ pass on host stacks."""
    note_dispatch("popcount_and")
    if _host(a, b):
        from pilosa_tpu.ops import hostkernels as hk

        return hk.count_and(a, b)
    return _jit_popcount_and(a, b)


@jax.jit
def _jit_row_counts(mat):
    return jnp.sum(lax.population_count(mat), axis=-1, dtype=jnp.int32)


def row_counts(mat):
    """Per-row popcounts of a [rows, words] matrix -> int32[rows].

    The batched scan under TopN (fragment.top, fragment.go:1570) — one
    device-wide reduction instead of a per-row heap walk."""
    note_dispatch("row_counts")
    if _host(mat):
        from pilosa_tpu.ops import hostkernels as hk

        return hk.row_counts(mat)
    return _jit_row_counts(mat)


@jax.jit
def _jit_row_counts_and(a, b):
    return jnp.sum(lax.population_count(jnp.bitwise_and(a, b)),
                   axis=-1, dtype=jnp.int32)


def row_counts_and(a, b):
    """Per-row |a[r] & b[r]| -> int32[rows], no materialized
    intersection: one fused XLA kernel on device, one C++ pass on host
    stacks — the Count(Intersect(x, y)) fast path over stacked shard
    operands (vs b_and + row_counts, which allocates the full
    intersection stack first)."""
    note_dispatch("row_counts_and")
    if _host(a, b):
        from pilosa_tpu.ops import hostkernels as hk

        return hk.row_counts_and(a, b)
    return _jit_row_counts_and(a, b)


@jax.jit
def _jit_row_counts_masked(mat, filt):
    return jnp.sum(
        lax.population_count(jnp.bitwise_and(mat, filt[None, :])),
        axis=-1,
        dtype=jnp.int32,
    )


def row_counts_masked(mat, filt):
    """Per-row |row & filter| -> int32[rows]; TopN-with-filter / GroupBy
    inner loop (fragment.go:1600, groupByIterator executor.go:3058)."""
    note_dispatch("row_counts_masked")
    if _host(mat, filt):
        from pilosa_tpu.ops import hostkernels as hk

        return hk.row_counts_masked(mat, filt)
    return _jit_row_counts_masked(mat, filt)


def row_counts_gathered(mat, filt_stack, shard_pos):
    """Per-row |mat[r] & filt_stack[shard_pos[r]]| -> int32[rows]; see
    _jit_row_counts_gathered for the device story."""
    note_dispatch("row_counts_gathered")
    if _host(mat, filt_stack):
        from pilosa_tpu.ops import hostkernels as hk

        return hk.row_counts_gathered(mat, filt_stack, np.asarray(shard_pos))
    return _jit_row_counts_gathered(mat, filt_stack, shard_pos)


@jax.jit
def _jit_row_counts_gathered(mat, filt_stack, shard_pos):
    """Per-row |mat[r] & filt_stack[shard_pos[r]]| -> int32[rows].

    The fused cross-shard TopN scan: row matrices from many fragments
    concatenate along axis 0 (each row tagged with its shard's position
    in the query's shard tuple) and the whole filtered scan runs as one
    dispatch instead of one per shard (fragment.top over shards,
    fragment.go:1570 × executor.go:2561)."""
    filt = jnp.take(filt_stack, shard_pos, axis=0)
    return jnp.sum(
        lax.population_count(jnp.bitwise_and(mat, filt)),
        axis=-1,
        dtype=jnp.int32,
    )


def gathered_pair_counts(a_pool, ai, b_pool, bi):
    """Per-pair |a_pool[ai[p]] & b_pool[bi[p]]| -> int32[P] — the
    compressed-container IntersectionCount core (ops/containers.py):
    both gathers, the AND, the popcount and the per-container reduce
    fuse into one kernel, and only directory-matched container blocks
    are ever read (the dense layout's zero words are never streamed).
    Pool rows past the directory's count are zeros, so an absent-
    container index contributes 0 — the roaring co-present-container
    walk (roaring/roaring.go:570) as a gather."""
    note_dispatch("gathered_pair_counts")
    if _host(a_pool, b_pool):
        from pilosa_tpu.ops import hostkernels as hk

        return hk.row_counts_and(a_pool[np.asarray(ai)],
                                 b_pool[np.asarray(bi)])
    return _jit_gathered_pair_counts(a_pool, ai, b_pool, bi)


@jax.jit
def _jit_gathered_pair_counts(a_pool, ai, b_pool, bi):
    a = jnp.take(a_pool, ai, axis=0, mode="clip")
    b = jnp.take(b_pool, bi, axis=0, mode="clip")
    return jnp.sum(lax.population_count(jnp.bitwise_and(a, b)),
                   axis=-1, dtype=jnp.int32)


def masked_matrix_counts(mat, masks):
    """counts[g, r] = |mat[r] & masks[g]| -> int32[G, rows]; see
    _jit_masked_matrix_counts for the device story."""
    note_dispatch("masked_matrix_counts")
    if _host(mat, masks):
        from pilosa_tpu.ops import hostkernels as hk

        return hk.masked_matrix_counts(mat, masks)
    return _jit_masked_matrix_counts(mat, masks)


@jax.jit
def _jit_masked_matrix_counts(mat, masks):
    """counts[g, r] = |mat[r] & masks[g]| -> int32[G, rows].

    The GroupBy inner product (groupByIterator, executor.go:3058): every
    group mask against every child row in ONE dispatch.  lax.map keeps
    the [G, rows, words] intermediate out of memory — each step is a
    fused row_counts_masked."""
    return lax.map(lambda m: _jit_row_counts_masked(mat, m), masks)


def and_pairs(mat, masks, slots, group_idx):
    """out[p] = mat[slots[p]] & masks[group_idx[p]]; see _jit_and_pairs."""
    note_dispatch("and_pairs")
    if _host(mat, masks):
        return np.bitwise_and(np.take(mat, np.asarray(slots), axis=0),
                              np.take(masks, np.asarray(group_idx), axis=0))
    return _jit_and_pairs(mat, masks, slots, group_idx)


@jax.jit
def _jit_and_pairs(mat, masks, slots, group_idx):
    """out[p] = mat[slots[p]] & masks[group_idx[p]] -> uint32[P, words].

    Builds the next GroupBy level's group masks for every surviving
    (group, row) pair in one dispatch."""
    return jnp.bitwise_and(
        jnp.take(mat, slots, axis=0), jnp.take(masks, group_idx, axis=0))


# ---------------------------------------------------------------------------
# Point mutations — delta application from the host write path.  The host
# pre-ORs colliding bits into unique (word index, value) pairs; on device
# this is gather -> combine -> scatter with a donated buffer.
# ---------------------------------------------------------------------------


@jax.jit
def _jit_set_bits(words, idx, or_vals):
    return words.at[idx].set(words[idx] | or_vals)


def set_bits(words, idx, or_vals):
    """OR ``or_vals`` into ``words`` at unique ``idx`` (fragment setBit batch
    apply; mirrors the opN batch design of fragment.go:84,2296)."""
    note_dispatch("set_bits")
    if _host(words):
        out = words.copy()
        out[np.asarray(idx)] |= np.asarray(or_vals)
        return out
    return _jit_set_bits(words, idx, or_vals)


@jax.jit
def _jit_clear_bits(words, idx, andnot_vals):
    return words.at[idx].set(words[idx] & ~andnot_vals)


def clear_bits(words, idx, andnot_vals):
    """Clear bits given per-word masks of bits to remove."""
    note_dispatch("clear_bits")
    if _host(words):
        out = words.copy()
        out[np.asarray(idx)] &= ~np.asarray(andnot_vals)
        return out
    return _jit_clear_bits(words, idx, andnot_vals)


@jax.jit
def _jit_get_bits(words, positions):
    w = words[positions // WORD_BITS]
    return ((w >> (positions % WORD_BITS).astype(jnp.uint32)) & 1).astype(jnp.int32)


def get_bits(words, positions):
    """Read individual bits -> int32[len(positions)] of 0/1."""
    note_dispatch("get_bits")
    if _host(words):
        pos = np.asarray(positions)
        w = words[pos // WORD_BITS]
        return ((w >> (pos % WORD_BITS).astype(np.uint32)) & 1).astype(np.int32)
    return _jit_get_bits(words, positions)


# ---------------------------------------------------------------------------
# Row-axis reductions — union/intersection of many rows in one call
# (executor Union/Intersect over >2 children collapse to these).
# ---------------------------------------------------------------------------


@jax.jit
def _jit_reduce_or_rows(mat):
    return lax.reduce(mat, np.uint32(0), lax.bitwise_or, (0,))


def reduce_or_rows(mat):
    """OR-reduce a [rows, words] matrix -> [words]."""
    note_dispatch("reduce_or_rows")
    if _host(mat):
        return np.bitwise_or.reduce(mat, axis=0)
    return _jit_reduce_or_rows(mat)


@jax.jit
def _jit_reduce_and_rows(mat):
    return lax.reduce(mat, np.uint32(0xFFFFFFFF), lax.bitwise_and, (0,))


def reduce_and_rows(mat):
    """AND-reduce a [rows, words] matrix -> [words]."""
    note_dispatch("reduce_and_rows")
    if _host(mat):
        return np.bitwise_and.reduce(mat, axis=0)
    return _jit_reduce_and_rows(mat)


# ---------------------------------------------------------------------------
# Compile telemetry — every _jit_* kernel above routes through the
# device-runtime observer (pilosa_tpu.devobs), which detects and times
# jit cache-miss first lowerings per canonical operand shape.  One loop,
# so a new kernel added above is instrumented by adding its name here.
# ---------------------------------------------------------------------------

from pilosa_tpu import devobs as _devobs  # noqa: E402

for _n in ("_jit_and", "_jit_or", "_jit_xor", "_jit_andnot", "_jit_not",
           "_jit_shift", "_jit_popcount", "_jit_popcount_and",
           "_jit_row_counts", "_jit_row_counts_and",
           "_jit_row_counts_masked", "_jit_row_counts_gathered",
           "_jit_masked_matrix_counts", "_jit_and_pairs",
           "_jit_gathered_pair_counts",
           "_jit_set_bits", "_jit_clear_bits", "_jit_get_bits",
           "_jit_reduce_or_rows", "_jit_reduce_and_rows"):
    globals()[_n] = _devobs.instrument(f"bitmap.{_n[5:]}", globals()[_n])
del _n
