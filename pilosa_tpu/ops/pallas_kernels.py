"""Pallas TPU kernels for the non-trivially-XLA hot ops.

SURVEY.md §7 names three ops worth hand-scheduling below XLA: the TopN
rank scan (segmented popcount), the BSI range compare (bit-sliced ripple
compare), and the fused intersection count.  XLA already fuses the
elementwise chains well; what Pallas buys is (a) a single pass over HBM
for AND+popcount+row-reduce with explicit VMEM blocking, and (b) keeping
the D-plane ripple compare's intermediates entirely in VMEM.

Every kernel has a jnp reference implementation in pilosa_tpu.ops used
as the differential oracle (the roaring/naive.go pattern) and as the
dispatch fallback off-TPU or for small inputs where kernel launch
overhead dominates.  `interpret=True` runs the same kernels on CPU for
tests.

Reference analogs: roaring.IntersectionCount (roaring/roaring.go:570),
fragment.top scan (fragment.go:1570), BSI rangeLT/GT
(fragment.go:1111-1537).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Row-block of 128 keeps the int32 output a native (8,128)-tileable
# [1, 128] block; 2048 uint32 words = 8KB lanes per row block.
ROW_BLOCK = 128
WORD_BLOCK = 2048


def on_tpu() -> bool:
    """True on a TPU backend.  A backend that fails to initialise
    raises: a swallowed error here would quietly reroute every Pallas
    call site to its XLA twin."""
    return jax.devices()[0].platform == "tpu"


def pallas_enabled() -> bool:
    """Operator gate for the on-TPU Pallas routing:
    PILOSA_TPU_PALLAS=0/off disables it (the escape hatch for a Mosaic
    regression in a new toolchain); any other value (or unset) leaves
    it enabled.  The knob only matters ON a TPU — off-chip the XLA
    path always runs, because Mosaic kernels need a TPU (tests reach
    them via interpret=True).  benchmarks/validate_tpu.py records
    per-kernel pallas-vs-XLA chip timings so the default tracks
    evidence, not hope."""
    import os

    v = os.environ.get("PILOSA_TPU_PALLAS", "auto").lower()
    return v not in ("0", "off", "false", "no")


@functools.cache
def _kernel_winners() -> dict:
    """Per-kernel chip A/B winners ('pallas' | 'xla') from the
    committed validation artifact (PALLAS_TPU_VALIDATION.json, written
    unedited by a benchmarks/validate_tpu.py run on the chip).  Empty
    when the artifact is absent or was not taken on a TPU — routing
    then takes the documented default, Pallas on TPU."""
    import json
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "PALLAS_TPU_VALIDATION.json")
    try:
        with open(path) as f:
            doc = json.load(f)
    except FileNotFoundError:
        return {}
    if doc.get("platform") != "tpu":
        return {}
    return {name: k["perf"]["winner"]
            for name, k in doc.get("kernels", {}).items()
            if isinstance(k, dict) and k.get("ok")
            and isinstance(k.get("perf"), dict)
            and k["perf"].get("winner") in ("pallas", "xla")
            # timings the validator itself flagged as beating the
            # HBM roof (memoized dispatches) must not decide
            # routing — treat them as no evidence
            and not k["perf"].get("suspect_memoized_dispatch")}


def _use_pallas(interpret: bool, elems: int, floor: int = 1 << 16,
                kernel: str | None = None) -> bool:
    """The single routing gate every dispatcher shares: interpret mode
    always exercises the kernel (how CPU tests reach it); below
    ``floor`` elements launch overhead dominates so XLA always runs;
    otherwise Pallas runs on a TPU with the operator knob enabled —
    UNLESS the committed chip validation timed this kernel slower than
    XLA's fusion (per-kernel evidence beats the blanket default;
    PILOSA_TPU_PALLAS=force overrides the evidence for A/B work)."""
    if interpret:
        return True
    if elems < floor:
        return False
    if not (on_tpu() and pallas_enabled()):
        return False
    import os

    if os.environ.get("PILOSA_TPU_PALLAS", "").lower() == "force":
        return True
    return _kernel_winners().get(kernel) != "xla"


def _pad_to(x: jnp.ndarray, axis: int, multiple: int):
    size = x.shape[axis]
    rem = (-size) % multiple
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad)


# ---------------------------------------------------------------------------
# masked row counts: out[r] = sum(popcount(mat[r] & filt)) — the TopN scan
# ---------------------------------------------------------------------------


def _row_counts_kernel(mat_ref, filt_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    blk = lax.population_count(mat_ref[:] & filt_ref[0, :])
    # counts broadcast across the 128 lanes — the lane dim only exists
    # to satisfy TPU tiling; the wrapper reads lane 0
    out_ref[:] += jnp.sum(blk, axis=1, dtype=jnp.int32)[:, None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _row_counts_masked_pallas(mat, filt, interpret: bool = False):
    R, W = mat.shape
    mat = _pad_to(_pad_to(mat, 1, WORD_BLOCK), 0, ROW_BLOCK)
    filt = _pad_to(filt.reshape(1, -1), 1, WORD_BLOCK)
    Rp, Wp = mat.shape
    grid = (Rp // ROW_BLOCK, Wp // WORD_BLOCK)
    out = pl.pallas_call(
        _row_counts_kernel,
        out_shape=jax.ShapeDtypeStruct((Rp, 128), jnp.int32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((ROW_BLOCK, WORD_BLOCK), lambda i, j: (i, j)),
            pl.BlockSpec((1, WORD_BLOCK), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((ROW_BLOCK, 128), lambda i, j: (i, 0)),
        interpret=interpret,
    )(mat, filt)
    return out[:R, 0]


def row_counts_masked(mat, filt, interpret: bool = False):
    """Dispatching wrapper: Pallas on TPU for big matrices, fused jnp
    otherwise (the two produce identical int32 counts)."""
    from pilosa_tpu.ops import bitmap as bm

    R, W = mat.shape
    if _use_pallas(interpret, R * W, kernel="row_counts_masked"):
        return _row_counts_masked_pallas(mat, jnp.asarray(filt),
                                         interpret=interpret)
    return bm.row_counts_masked(mat, filt)


# ---------------------------------------------------------------------------
# fused intersection count: |a & b| — the north-star op
# ---------------------------------------------------------------------------


def _count_and_kernel(a_ref, b_ref, out_ref):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        out_ref[0, 0] = 0

    out_ref[0, 0] += jnp.sum(
        lax.population_count(a_ref[:] & b_ref[:]), dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _count_and_pallas(a, b, interpret: bool = False):
    a = _pad_to(a.reshape(1, -1), 1, WORD_BLOCK)
    b = _pad_to(b.reshape(1, -1), 1, WORD_BLOCK)
    Wp = a.shape[1]
    out = pl.pallas_call(
        _count_and_kernel,
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.int32),
        grid=(Wp // WORD_BLOCK,),
        in_specs=[
            pl.BlockSpec((1, WORD_BLOCK), lambda j: (0, j)),
            pl.BlockSpec((1, WORD_BLOCK), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1), lambda j: (0, 0), memory_space=pltpu.SMEM),
        interpret=interpret,
    )(a, b)
    return out[0, 0]


def count_and(a, b, interpret: bool = False):
    """|a & b| with Pallas on TPU (single pass; no intermediate), jnp
    fusion elsewhere (roaring.IntersectionCount, roaring/roaring.go:570)."""
    from pilosa_tpu.ops import bitmap as bm

    if _use_pallas(interpret, a.size, kernel="count_and"):
        return _count_and_pallas(jnp.asarray(a), jnp.asarray(b),
                                 interpret=interpret)
    return bm.popcount_and(a, b)


# ---------------------------------------------------------------------------
# compressed-container intersection count: the directory walk on TPU.
# Scalar-prefetched gather indices drive the BlockSpec index maps, so the
# DMA engine fetches exactly the directory-matched container blocks from
# the two word pools — absent containers (index = the pool's zero row)
# cost one zero block, and the dense layout's zero words never stream
# (ops/containers.py; roaring.IntersectionCount's co-present-container
# walk, roaring/roaring.go:570, as hardware-prefetched gathers).
# ---------------------------------------------------------------------------

CONTAINER_WORDS = 2048  # uint32 words per 2^16-bit container

# The TPU lowering wants a block's last two dims divisible by (8, 128)
# (or equal to the array's): a pool row cannot be a block of its own.
# So a gather step DMAs the aligned 8-row group that holds its row and
# the kernel picks the row with a dynamic sublane slice.  The pool
# keeps its resident [R, 2048] layout — a [R, 1, 2048] view would give
# exact-row blocks but XLA relayouts (copies) the whole pool per call.
POOL_ROW_GROUP = 8
COUNT_LANES = 128  # per-step counts land in lanes of a [1, 128] block


def _pool_row_spec(index_of):
    """BlockSpec gathering the 8-row group of the pool row that
    ``index_of(*grid_and_prefetch_args)`` names."""
    return pl.BlockSpec(
        (POOL_ROW_GROUP, CONTAINER_WORDS),
        lambda *a: (index_of(*a) // POOL_ROW_GROUP, 0))


def _pool_row(ref, index):
    """The [1, 2048] pool row ``index`` out of its gathered group."""
    return ref[pl.ds(index % POOL_ROW_GROUP, 1), :]


def _store_count(out_ref, step, count):
    """Write one int32 into lane ``step % 128`` of the resident
    [1, 128] output block (zeroed when a new block starts) — a scalar
    per grid step cannot be an output block of its own on TPU."""
    lane = step % COUNT_LANES

    @pl.when(lane == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    lanes = lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    out_ref[:] = jnp.where(lanes == lane, count, out_ref[:])


def _gathered_count_and_kernel(ai_ref, bi_ref, a_ref, b_ref, out_ref):
    p = pl.program_id(0)
    xa = _pool_row(a_ref, ai_ref[p])
    xb = _pool_row(b_ref, bi_ref[p])
    _store_count(out_ref, p, jnp.sum(
        lax.population_count(xa & xb).astype(jnp.int32)))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gathered_count_and_pallas(a_pool, ai, b_pool, bi,
                               interpret: bool = False):
    P = ai.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(P,),
        in_specs=[
            _pool_row_spec(lambda p, ai, bi: ai[p]),
            _pool_row_spec(lambda p, ai, bi: bi[p]),
        ],
        out_specs=pl.BlockSpec((1, COUNT_LANES),
                               lambda p, ai, bi: (0, p // COUNT_LANES)),
    )
    out = pl.pallas_call(
        _gathered_count_and_kernel,
        out_shape=jax.ShapeDtypeStruct(
            (1, pl.cdiv(P, COUNT_LANES) * COUNT_LANES), jnp.int32),
        grid_spec=grid_spec,
        interpret=interpret,
    )(ai, bi, a_pool, b_pool)
    return out[0, :P]


def gathered_count_and(a_pool, ai, b_pool, bi, interpret: bool = False):
    """Per-pair |a_pool[ai[p]] & b_pool[bi[p]]| -> int32[P]: Pallas
    directory-walk on TPU, the fused jnp gather kernel elsewhere
    (bm.gathered_pair_counts) — identical counts.  Exactly one
    dispatch tick on either route, like every bm op."""
    from pilosa_tpu.ops import bitmap as bm

    ai = jnp.asarray(ai, dtype=jnp.int32)
    bi = jnp.asarray(bi, dtype=jnp.int32)
    if (a_pool.shape[-1] == CONTAINER_WORDS
            and _use_pallas(interpret, ai.shape[0] * CONTAINER_WORDS,
                            kernel="gathered_count_and")):
        bm.note_dispatch("gathered_count_and")
        return _gathered_count_and_pallas(jnp.asarray(a_pool), ai,
                                          jnp.asarray(b_pool), bi,
                                          interpret=interpret)
    return bm.gathered_pair_counts(a_pool, ai, b_pool, bi)


# ---------------------------------------------------------------------------
# kind-specialized pair counts (roaring pair-matrix arms, ops/kindpools.py
# layouts).  array∩array runs a vectorized binary-search membership test
# (the galloping/binary-search hybrid of roaring's array-array intersect,
# roaring/arraycontainer.go) over the compact uint16 pools; array∩bitmap
# gather-tests each value's word/bit.  Both touch ONLY compact rows —
# no dense 2048-word block exists anywhere on these arms — and both have
# numpy twins that are bit-exact by construction (same integer algebra).
# The caller (containers.Plan._gathered_kinds) owns the dispatch tick.
# ---------------------------------------------------------------------------


def _count_aa_one(v0, c0, v1, c1):
    import jax.numpy as jnp  # shadows module alias inside vmap trace

    pos = jnp.searchsorted(v1, v0)
    probe = jnp.take(v1, jnp.minimum(pos, v1.shape[0] - 1))
    # pos < c1 rejects pad hits: padding is 0xFFFF, so a REAL 65535 in
    # v1 sits at pos c1-1 and still passes
    hit = (pos < c1) & (probe == v0)
    valid = jnp.arange(v0.shape[0], dtype=jnp.int32) < c0
    return jnp.sum((hit & valid).astype(jnp.int32), dtype=jnp.int32)


@jax.jit
def _count_aa_jnp(apool0, acard0, ia0, apool1, acard1, ia1):
    v0 = jnp.take(apool0, ia0, axis=0, mode="clip")
    c0 = jnp.take(acard0, ia0, mode="clip")
    v1 = jnp.take(apool1, ia1, axis=0, mode="clip")
    c1 = jnp.take(acard1, ia1, mode="clip")
    return jax.vmap(_count_aa_one)(v0, c0, v1, c1)


def _count_aa_np(apool0, acard0, ia0, apool1, acard1, ia1):
    # sort-and-count-duplicates, vectorized over all pairs: each side's
    # values are unique within a row, so after sorting the two rows
    # together every intersection element appears as exactly one
    # adjacent equal pair.  ~4x faster than per-element binary search
    # on host (row-local sorts are cache-resident; searchsorted pays a
    # cache miss per probe).  Pad slots get side- AND slot-distinct
    # sentinels above the uint16 range so they never pair up
    ia0 = np.asarray(ia0)
    ia1 = np.asarray(ia1)
    v0 = apool0[ia0].astype(np.int32)
    v1 = apool1[ia1].astype(np.int32)
    c0 = acard0[ia0].astype(np.int32)[:, None]
    c1 = acard1[ia1].astype(np.int32)[:, None]
    slot0 = np.arange(v0.shape[1], dtype=np.int32)[None, :]
    slot1 = np.arange(v1.shape[1], dtype=np.int32)[None, :]
    v0 = np.where(slot0 < c0, v0, 0x10000 + slot0)
    v1 = np.where(slot1 < c1, v1, 0x20000 + slot1)
    m = np.sort(np.concatenate([v0, v1], axis=1), axis=1)
    return (m[:, 1:] == m[:, :-1]).sum(axis=1, dtype=np.int32)


def gathered_count_array_array(apool0, acard0, ia0, apool1, acard1, ia1):
    """Per-pair |A0[ia0[p]] ∩ A1[ia1[p]]| -> int32[P] over two array
    pools: binary-search membership of the smaller-capacity side's
    values in the other's sorted row.  Pad lanes point at the pools'
    zero rows (card 0) and count 0."""
    if isinstance(apool0, np.ndarray) and isinstance(apool1, np.ndarray):
        return _count_aa_np(apool0, acard0, ia0, apool1, acard1, ia1)
    return _count_aa_jnp(
        jnp.asarray(apool0), jnp.asarray(acard0),
        jnp.asarray(ia0, dtype=jnp.int32),
        jnp.asarray(apool1), jnp.asarray(acard1),
        jnp.asarray(ia1, dtype=jnp.int32))


def _count_ab_one(v, c, brow):
    import jax.numpy as jnp

    word = jnp.take(brow, (v >> 5).astype(jnp.int32), mode="clip")
    bit = (word >> (v & 31).astype(jnp.uint32)) & jnp.uint32(1)
    valid = jnp.arange(v.shape[0], dtype=jnp.int32) < c
    return jnp.sum(jnp.where(valid, bit, 0).astype(jnp.int32),
                   dtype=jnp.int32)


@jax.jit
def _count_ab_jnp(apool, acard, ia, bpool, ib):
    v = jnp.take(apool, ia, axis=0, mode="clip")
    c = jnp.take(acard, ia, mode="clip")
    b = jnp.take(bpool, ib, axis=0, mode="clip")
    return jax.vmap(_count_ab_one)(v, c, b)


def _count_ab_np(apool, acard, ia, bpool, ib):
    # vectorized over all pairs (the aa twin's discipline): one fancy
    # word gather per batch; pad values (0xFFFF -> word 2047) stay in
    # range and the validity mask zeroes them
    ia = np.asarray(ia)
    ib = np.asarray(ib)
    v = apool[ia].astype(np.int64)
    c = acard[ia].astype(np.int64)[:, None]
    b = bpool[ib]
    rows = np.arange(v.shape[0], dtype=np.int64)[:, None]
    bits = (b[rows, v >> 5] >> (v & 31).astype(np.uint32)) & 1
    valid = np.arange(v.shape[1], dtype=np.int64)[None, :] < c
    return np.where(valid, bits, 0).sum(axis=1).astype(np.int32)


def gathered_count_array_bitmap(apool, acard, ia, bpool, ib):
    """Per-pair |A[ia[p]] ∩ B[ib[p]]| -> int32[P], array values
    gather-tested against the bitmap row's words (roaring's
    array-bitmap intersect).  Only the array side's compact rows and
    the bitmap rows the directory matched are touched."""
    if isinstance(apool, np.ndarray) and isinstance(bpool, np.ndarray):
        return _count_ab_np(apool, acard, ia, bpool, ib)
    return _count_ab_jnp(
        jnp.asarray(apool), jnp.asarray(acard),
        jnp.asarray(ia, dtype=jnp.int32),
        jnp.asarray(bpool), jnp.asarray(ib, dtype=jnp.int32))


# ---------------------------------------------------------------------------
# bitmap VM: ONE scalar-prefetch kernel for a megabatch of ragged op-tapes
# over compressed container pools.  Each grid step (q, d) interprets query
# q's flat register program (ops/tape.py grammar: AND/OR/XOR/ANDNOT/COPY
# over leaf slots + instruction outputs) on domain slot d's container
# blocks, which the BlockSpec index maps gather straight from the pooled
# word storage via the host-computed directory (ops/containers.py) — the
# Ragged Paged Attention recipe (heterogeneous work items driven by
# scalar-prefetched indirection in one kernel) applied to expression
# trees over roaring containers.  No dense register file and no dense
# row word ever materializes: absent containers cost one canonical zero
# block, and the fused popcount root reduces each (q, d) cell to a
# single int32 in SMEM.
# ---------------------------------------------------------------------------


def _vm_counts_kernel(prog_ref, gidx_ref, *refs, slots: int,
                      tape_len: int):
    """One (query, domain-slot) cell: interpret the tape over the
    gathered leaf blocks.  ``prog_ref`` is the scalar-prefetched
    int32[B, T, 3] program (absolute register operands — ops/tape.py's
    ``_abs_operand`` encoding, COPY-chain padded so the LAST register
    holds the result); ``gidx_ref`` the int32[L, B, D] directory that
    also drove the index maps.  The register file is a VMEM scratch:
    ``slots`` gathered leaf rows + ``tape_len`` instruction outputs,
    one [1, 2048] container each, addressed by dynamic sublane slices
    (the operands are data, not trace-time constants)."""
    leaf_refs = refs[:slots]
    out_ref, regs_ref = refs[slots], refs[slots + 1]
    q = pl.program_id(0)
    d = pl.program_id(1)
    for l, leaf_ref in enumerate(leaf_refs):
        regs_ref[pl.ds(l, 1), :] = _pool_row(leaf_ref, gidx_ref[l, q, d])
    for t in range(tape_len):
        # opcode constants are ops/tape.py's OP_AND..OP_COPY = range(5)
        # (literal here so the kernel module stays import-light)
        op = prog_ref[q, t, 0]
        xa = regs_ref[pl.ds(prog_ref[q, t, 1], 1), :]
        xb = regs_ref[pl.ds(prog_ref[q, t, 2], 1), :]
        regs_ref[pl.ds(slots + t, 1), :] = jnp.where(
            op == 0, xa & xb,
            jnp.where(op == 1, xa | xb,
                      jnp.where(op == 2, xa ^ xb,
                                jnp.where(op == 3, xa & ~xb, xa))))
    result = regs_ref[pl.ds(slots + tape_len - 1, 1), :]
    _store_count(out_ref, d, jnp.sum(
        lax.population_count(result).astype(jnp.int32)))


def _vm_counts_pallas_body(pool, prog, gidx, interpret: bool):
    """grid (B, D): every query x domain-slot cell is one step whose
    ``slots`` leaf rows DMA from the ONE megapool through per-slot
    index maps over the scalar-prefetched directory — the same buffer
    is passed once per leaf slot, so no operand copy exists.  Output
    is per-cell int32 popcounts (each <= 2^16, overflow-free); the
    host sums them in int64."""
    B, T, _ = prog.shape
    L, _, D = gidx.shape
    kernel = functools.partial(_vm_counts_kernel, slots=L, tape_len=T)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, D),
        in_specs=[
            _pool_row_spec(
                lambda q, d, prog, gidx, _l=l: gidx[_l, q, d])
            for l in range(L)
        ],
        # [B, 1, Dp]: the unit middle dim makes a (1, 128) lane block
        # legal for any B; the domain axis is the innermost grid dim,
        # so a block stays resident for its 128 consecutive steps
        out_specs=pl.BlockSpec(
            (None, 1, COUNT_LANES),
            lambda q, d, prog, gidx: (q, 0, d // COUNT_LANES)),
        scratch_shapes=[pltpu.VMEM((L + T, CONTAINER_WORDS),
                                   jnp.uint32)],
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(
            (B, 1, pl.cdiv(D, COUNT_LANES) * COUNT_LANES), jnp.int32),
        grid_spec=grid_spec,
        interpret=interpret,
    )(prog, gidx, *([pool] * L))
    return out[:, 0, :D]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _vm_counts_pallas(pool, prog, gidx, interpret: bool = False):
    return _vm_counts_pallas_body(pool, prog, gidx, interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _vm_counts_kinds_pallas(bpool, apool, acard, rpool, prog, gidx,
                            interpret: bool = False):
    """Kind-split megapool variant: decode the compact array/run pools
    to dense blocks and concatenate behind the bitmap rows INSIDE the
    same launch, reproducing the virtual dense row space the
    coalescer's global indices address ([0, Rb) bitmap, [Rb, Rb+Ra)
    array, the rest run — ops/containers.MegaPools), then run the
    UNCHANGED VM kernel over it.  Resident and transferred bytes stay
    compact; only this launch's VMEM/HBM scratch is dense."""
    from pilosa_tpu.ops import kindpools as kp

    pool = jnp.concatenate(
        [bpool, kp.decode_array_jnp(apool, acard),
         kp.decode_runs_jnp(rpool)], axis=0)
    return _vm_counts_pallas_body(pool, prog, gidx, interpret)


def _vm_counts_host(pool, prog, gidx):
    """Eager numpy twin of the VM kernel (host-mode engine and the
    differential oracle for interpret-mode tests) — same register
    grammar, per-cell int32 counts."""
    from pilosa_tpu.ops import hostkernels as hk

    B, T, _ = prog.shape
    L, _, D = gidx.shape
    pool = np.asarray(pool)
    out = np.zeros((B, D), dtype=np.int32)
    for q in range(B):
        # vectorized over the domain axis: each register is [D, W], so a
        # query costs T whole-array ops instead of D x T per-cell ops
        regs = [pool[gidx[l, q]] for l in range(L)]
        for t in range(T):
            op, a, b = (int(v) for v in prog[q, t])
            xa = regs[a]
            if op == 4:
                regs.append(xa)
                continue
            xb = regs[b]
            if op == 0:
                regs.append(xa & xb)
            elif op == 1:
                regs.append(xa | xb)
            elif op == 2:
                regs.append(xa ^ xb)
            else:
                regs.append(xa & ~xb)
        out[q] = hk.row_counts(regs[-1])
    return out


def _vm_counts_jnp_body(pool, prog, gidx):
    from pilosa_tpu.ops import tape as _tape_mod

    leaves = jnp.take(pool, gidx, axis=0)   # [L, B, D, W]
    leaves = jnp.moveaxis(leaves, 1, 0)     # [B, L, D, W]
    one = _tape_mod._one_query(True)
    return jax.vmap(one)(prog, leaves)      # [B, D] int32


@jax.jit
def _vm_counts_jnp(pool, prog, gidx):
    """Jitted XLA twin: gather every leaf block from the pool, then
    run the EXACT tape-interpreter closure (ops/tape._one_query) per
    query over [slots, D, W] leaf stacks — the two engines cannot
    drift because they trace the same scan/switch body.  Re-lowers
    per (B, T, L, D) bucket shape, which pow2 bucketing bounds."""
    return _vm_counts_jnp_body(pool, prog, gidx)


@jax.jit
def _vm_counts_kinds_jnp(bpool, apool, acard, rpool, prog, gidx):
    """XLA twin of the kind-split VM: same decode + concatenate as the
    Pallas wrapper, same interpreter body — one launch either way."""
    from pilosa_tpu.ops import kindpools as kp

    pool = jnp.concatenate(
        [bpool, kp.decode_array_jnp(apool, acard),
         kp.decode_runs_jnp(rpool)], axis=0)
    return _vm_counts_jnp_body(pool, prog, gidx)


def _vm_counts_kinds(bundle, prog, gidx, interpret: bool):
    """Dispatch the kind-split megapool bundle (containers.MegaPools):
    host pools decode eagerly in numpy and reuse the eager twin; on
    device the decode happens inside the single jitted launch."""
    B, T, _ = prog.shape
    _L, _, D = gidx.shape
    if isinstance(bundle.bpool, np.ndarray):
        from pilosa_tpu.ops import kindpools as kp

        pool = np.concatenate(
            [np.asarray(bundle.bpool),
             kp.decode_array_np(np.asarray(bundle.apool),
                                np.asarray(bundle.acard)),
             kp.decode_runs_np(np.asarray(bundle.rpool))], axis=0)
        return _vm_counts_host(pool, prog, gidx)
    progj = jnp.asarray(prog)
    gidxj = jnp.asarray(gidx)
    if _use_pallas(interpret, B * D * CONTAINER_WORDS,
                   kernel="vm_counts"):
        return _vm_counts_kinds_pallas(bundle.bpool, bundle.apool,
                                       bundle.acard, bundle.rpool,
                                       progj, gidxj,
                                       interpret=interpret)
    return _vm_counts_kinds_jnp(bundle.bpool, bundle.apool,
                                bundle.acard, bundle.rpool,
                                progj, gidxj)


def vm_counts(pool, prog, gidx, interpret: bool = False):
    """Per-cell popcounts int32[B, D] of a batch of op-tapes over one
    pooled compressed operand: the Pallas VM on TPU, the jitted
    gather+interpret twin elsewhere, eager numpy for host pools —
    bit-identical counts on every route.  ``pool`` may also be a
    kind-split ``containers.MegaPools`` bundle, which decodes inside
    the launch.  The caller (ops/tape.execute_vm) owns the single
    dispatch tick."""
    prog = np.ascontiguousarray(prog, dtype=np.int32)
    gidx = np.ascontiguousarray(gidx, dtype=np.int32)
    B, T, _ = prog.shape
    _L, _, D = gidx.shape
    from pilosa_tpu.ops import containers as _containers

    if isinstance(pool, _containers.MegaPools):
        return _vm_counts_kinds(pool, prog, gidx, interpret)
    if isinstance(pool, np.ndarray):
        return _vm_counts_host(pool, prog, gidx)
    progj = jnp.asarray(prog)
    gidxj = jnp.asarray(gidx)
    if (pool.shape[-1] == CONTAINER_WORDS
            and _use_pallas(interpret, B * D * CONTAINER_WORDS,
                            kernel="vm_counts")):
        return _vm_counts_pallas(jnp.asarray(pool), progj, gidxj,
                                 interpret=interpret)
    return _vm_counts_jnp(jnp.asarray(pool), progj, gidxj)


# ---------------------------------------------------------------------------
# GroupBy cartesian counts: out[g, r] = |mat[r] & masks[g]| — one pass
# over the row matrix per mask block, [GB, RB, WB] intermediate in VMEM
# (SURVEY §7's third Pallas target; groupByIterator, executor.go:3058)
# ---------------------------------------------------------------------------

MMC_GROUP_BLOCK = 8
MMC_ROW_BLOCK = 128
MMC_WORD_BLOCK = 256


def _mmc_kernel(mat_ref, masks_ref, out_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    m = mat_ref[:]        # [RB, WB]
    g = masks_ref[:]      # [GB, WB]
    cnt = lax.population_count(g[:, None, :] & m[None, :, :])  # [GB,RB,WB]
    out_ref[:] += jnp.sum(cnt, axis=2, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _mmc_pallas(mat, masks, interpret: bool = False):
    R, W = mat.shape
    G = masks.shape[0]
    mat = _pad_to(_pad_to(mat, 1, MMC_WORD_BLOCK), 0, MMC_ROW_BLOCK)
    masks = _pad_to(_pad_to(masks, 1, MMC_WORD_BLOCK), 0, MMC_GROUP_BLOCK)
    Rp, Wp = mat.shape
    Gp = masks.shape[0]
    grid = (Gp // MMC_GROUP_BLOCK, Rp // MMC_ROW_BLOCK,
            Wp // MMC_WORD_BLOCK)
    out = pl.pallas_call(
        _mmc_kernel,
        out_shape=jax.ShapeDtypeStruct((Gp, Rp), jnp.int32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((MMC_ROW_BLOCK, MMC_WORD_BLOCK),
                         lambda i, j, k: (j, k)),
            pl.BlockSpec((MMC_GROUP_BLOCK, MMC_WORD_BLOCK),
                         lambda i, j, k: (i, k)),
        ],
        out_specs=pl.BlockSpec((MMC_GROUP_BLOCK, MMC_ROW_BLOCK),
                               lambda i, j, k: (i, j)),
        interpret=interpret,
    )(mat, masks)
    return out[:G, :R]


def masked_matrix_counts(mat, masks, interpret: bool = False):
    """counts[g, r] = |mat[r] & masks[g]| — the GroupBy inner product.
    Pallas on TPU for big products (single HBM pass per block, VMEM
    accumulation); the bm dispatcher elsewhere (native C++ on host
    stacks, lax.map of fused row counts on other devices)."""
    from pilosa_tpu.ops import bitmap as bm

    R, W = mat.shape
    G = masks.shape[0]
    if (_use_pallas(interpret, G * R * W, floor=1 << 18,
                    kernel="masked_matrix_counts")
            and not isinstance(mat, np.ndarray)):
        return _mmc_pallas(jnp.asarray(mat), jnp.asarray(masks),
                           interpret=interpret)
    return bm.masked_matrix_counts(mat, masks)


# ---------------------------------------------------------------------------
# BSI ripple compare: keep/lt/gt masks across bit planes, all in VMEM
# ---------------------------------------------------------------------------


def _bsi_compare_kernel(planes_ref, filt_ref, pred_ref, out_lt_ref,
                        out_gt_ref, *, depth: int):
    """One word-block: ripple from the MSB plane down, computing
    columns strictly-below / strictly-above the predicate among
    non-null, non-negative, filtered columns (the unsigned core of
    fragment.rangeLTUnsigned/rangeGTUnsigned, fragment.go:1277-1343).
    pred is pre-split into per-plane broadcast masks by the host."""
    exists = planes_ref[0, :]
    sign = planes_ref[1, :]
    consider = exists & ~sign & filt_ref[0, :]
    lt = jnp.zeros_like(consider)
    gt = jnp.zeros_like(consider)
    eq = consider
    for i in range(depth - 1, -1, -1):
        plane = planes_ref[2 + i, :]
        pred_bit = pred_ref[i, 0]  # 0 or 0xFFFFFFFF broadcast mask
        # predicate bit 1: plane-0 columns fall below; bit 0: plane-1
        # columns rise above
        lt = lt | (eq & pred_bit & ~plane)
        gt = gt | (eq & ~pred_bit & plane)
        eq = eq & ~(plane ^ pred_bit)
    out_lt_ref[0, :] = lt
    out_gt_ref[0, :] = gt


@functools.partial(jax.jit, static_argnames=("depth", "interpret"))
def _bsi_compare_pallas(planes, filt, pred_masks, depth: int,
                        interpret: bool = False):
    W = planes.shape[1]
    # pad the PLANE axis to the uint32 sublane tile (8): a block whose
    # second-minor dim is the raw depth+2 (e.g. 19) risks a Mosaic
    # lowering rejection; padded planes are zeros the kernel never
    # indexes (it reads exactly [0], [1], [2..2+depth))
    planes = _pad_to(_pad_to(planes, 1, WORD_BLOCK), 0, 8)
    P = planes.shape[0]
    filt = _pad_to(filt.reshape(1, -1), 1, WORD_BLOCK)
    Wp = planes.shape[1]
    kernel = functools.partial(_bsi_compare_kernel, depth=depth)
    out_lt, out_gt = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((1, Wp), jnp.uint32),
            jax.ShapeDtypeStruct((1, Wp), jnp.uint32),
        ),
        grid=(Wp // WORD_BLOCK,),
        in_specs=[
            pl.BlockSpec((P, WORD_BLOCK), lambda j: (0, j)),
            pl.BlockSpec((1, WORD_BLOCK), lambda j: (0, j)),
            pl.BlockSpec((depth, 1), lambda j: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=(
            pl.BlockSpec((1, WORD_BLOCK), lambda j: (0, j)),
            pl.BlockSpec((1, WORD_BLOCK), lambda j: (0, j)),
        ),
        interpret=interpret,
    )(planes, filt, pred_masks)
    return out_lt[0, :W], out_gt[0, :W]


def bsi_compare_unsigned(planes, filt, upred: int, depth: int,
                         interpret: bool = False):
    """(strictly_lt, strictly_gt) word masks among filtered non-negative
    columns vs an unsigned predicate.  Pallas on TPU, the shared jnp
    ripple (pilosa_tpu.ops.bsi.compare) elsewhere — bit-identical."""
    if upred < 0:
        raise ValueError("predicate magnitude must be non-negative")
    if upred >= 1 << depth:
        # every depth-bit value is strictly below the predicate; the
        # kernels only ripple `depth` planes, so handle this here rather
        # than silently truncating predicate bits
        consider = jnp.asarray(planes[0]) & ~jnp.asarray(planes[1]) \
            & jnp.asarray(filt)
        return consider, jnp.zeros_like(consider)
    if _use_pallas(interpret, planes.shape[1], floor=1 << 12,
                   kernel="bsi_compare_unsigned"):
        pred_masks = np.array(
            [[0xFFFFFFFF if (upred >> i) & 1 else 0]
             for i in range(depth)],
            dtype=np.uint32,
        )
        return _bsi_compare_pallas(jnp.asarray(planes), jnp.asarray(filt),
                                   jnp.asarray(pred_masks), depth,
                                   interpret=interpret)
    return _bsi_compare_jnp(planes, filt, upred, depth)


def _bsi_compare_jnp(planes, filt, upred: int, depth: int):
    """Fallback via the canonical jitted ripple (bsi.compare takes the
    predicate as traced uint32 limbs — no per-predicate recompilation)."""
    from pilosa_tpu.ops import bsi

    planes = jnp.asarray(planes)
    consider = planes[0] & ~planes[1] & jnp.asarray(filt)
    lo, hi = bsi.split_predicate(upred)
    lt, eq = bsi.compare(planes, consider, lo, hi)
    return lt, consider & ~lt & ~eq


# Compile telemetry (pilosa_tpu.devobs): Mosaic lowerings are the most
# expensive compiles in the process, so the Pallas entry points carry
# the same cache-miss detection as the XLA kernels (ops/bitmap.py).
from pilosa_tpu import devobs as _devobs  # noqa: E402

for _n in ("_row_counts_masked_pallas", "_count_and_pallas",
           "_gathered_count_and_pallas", "_vm_counts_pallas",
           "_vm_counts_jnp", "_vm_counts_kinds_pallas",
           "_vm_counts_kinds_jnp", "_count_aa_jnp", "_count_ab_jnp",
           "_mmc_pallas", "_bsi_compare_pallas"):
    globals()[_n] = _devobs.instrument(f"pallas.{_n.strip('_')}",
                                       globals()[_n])
del _n
