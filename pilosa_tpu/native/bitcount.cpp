// Host-side fused popcount kernels for the CPU execution engine.
//
// On TPU the set-algebra hot path is XLA (ops/bitmap.py jit kernels);
// when the framework runs on a plain CPU host (CPU-only server, laptop
// dev, CI) the same ops dispatch here instead: single-pass AND+popcount with
// no materialized intermediates, compiled -march=native so gcc lowers
// __builtin_popcountll to POPCNT / AVX-512 VPOPCNTDQ where available.
// This is the moral analog of the reference's hand-tuned container
// fast paths (roaring/roaring.go:570 intersectionCount*) — the exact
// counting loop a CPU should run, where XLA:CPU's generic codegen loses
// to vectorized popcount.
//
// Large inputs fan out over std::thread (the analog of the reference's
// per-shard worker pool, executor.go:2561, collapsed to one kernel):
// the ctypes caller has already released the GIL, so the threads own
// the cores.  Auto mode (pt_set_threads(0), the default) uses
// hardware_concurrency capped so each thread gets >= 4 MiB of operand —
// below that, spawn cost and memory-bandwidth saturation make threading
// a wash and the loops stay serial.  An explicit pt_set_threads(n>0)
// is honored exactly (tests force threading on tiny inputs with it).
//
// Buffers arrive as raw bytes from numpy uint32 arrays (C-contiguous,
// little-endian), processed as uint64 lanes with a uint32 tail — the
// same reinterpret-cast equivalence the file codec relies on
// (storage/roaring.py layout note).

#include <algorithm>
#include <cstdint>
#include <mutex>
#include <thread>
#include <vector>

namespace {

// Alias- and alignment-safe 8-byte load: row pointers into a [rows, n32]
// uint32 matrix are only 4-byte aligned for odd n32 x odd row, and a
// uint32->uint64 pointer pun is UB regardless; __builtin_memcpy folds to
// a single unaligned vector load under -O3.
inline uint64_t load64(const uint32_t* p) {
    uint64_t v;
    __builtin_memcpy(&v, p, 8);
    return v;
}

int g_threads = 0;  // 0 = auto; >0 = exact count (1 = always serial)

// 4 MiB of uint32 operand per extra thread before auto mode fans out.
constexpr long long kMinWordsPerThread = 1LL << 20;

// Thread count for a kernel touching `words` uint32s of operand total.
int effective_threads(long long words) {
    if (g_threads > 0) return g_threads;
    int t = (int)std::thread::hardware_concurrency();
    if (t < 2) return 1;
    long long cap = words / kMinWordsPerThread;
    if (cap < (long long)t) t = (int)(cap < 1 ? 1 : cap);
    return t;
}

// Split `total` items into contiguous chunks, each a multiple of
// `align` items (except the final chunk, which absorbs the tail).
std::vector<std::pair<long long, long long>> make_chunks(long long total,
                                                         long long align,
                                                         int t) {
    long long chunk = ((total / t) / align) * align;
    if (chunk < align) chunk = align;
    std::vector<std::pair<long long, long long>> chunks;
    for (long long lo = 0; lo < total; lo += chunk) {
        long long hi = std::min(total, lo + chunk);
        if (hi + chunk > total) hi = total;  // fold the tail into the last
        chunks.emplace_back(lo, hi);
        if (hi == total) break;
    }
    return chunks;
}

template <class F>
void run_chunks(const std::vector<std::pair<long long, long long>>& chunks,
                F fn) {
    std::vector<std::thread> ths;
    ths.reserve(chunks.size());
    for (size_t i = 0; i < chunks.size(); i++)
        ths.emplace_back(
            [&chunks, &fn, i] { fn(chunks[i].first, chunks[i].second, (int)i); });
    for (auto& th : ths) th.join();
}

// Run fn(lo, hi, slot) over `total` items; serial fast path when one
// thread suffices for `total * work_per_item` uint32s of operand.
template <class F>
void parallel_chunks(long long total, long long align, long long work_per_item,
                     F fn) {
    int t = effective_threads(total * work_per_item);
    if (t <= 1 || total < 2) {
        fn(0, total, 0);
        return;
    }
    run_chunks(make_chunks(total, align, t), fn);
}

long long count_serial(const uint32_t* a, long long n32) {
    long long n64 = n32 / 2, t = 0;
    for (long long i = 0; i < n64; i++)
        t += __builtin_popcountll(load64(a + 2 * i));
    if (n32 & 1) t += __builtin_popcount(a[n32 - 1]);
    return t;
}

long long count_and_serial(const uint32_t* a, const uint32_t* b,
                           long long n32) {
    long long n64 = n32 / 2, t = 0;
    for (long long i = 0; i < n64; i++)
        t += __builtin_popcountll(load64(a + 2 * i) & load64(b + 2 * i));
    if (n32 & 1) t += __builtin_popcount(a[n32 - 1] & b[n32 - 1]);
    return t;
}

// Scatter-reduce over word-range chunks: each thread counts its slice
// into a private slot (no false sharing at this granularity — one write
// per thread), summed after the join.  align=2 keeps every non-tail
// chunk on a uint64 lane boundary.
template <class Body>
long long chunked_count(long long n32, Body body) {
    int t = effective_threads(n32);
    if (t <= 1 || n32 < 2) return body(0, n32);
    auto chunks = make_chunks(n32, /*align=*/2, t);
    std::vector<long long> part(chunks.size(), 0);
    run_chunks(chunks, [&](long long lo, long long hi, int slot) {
        part[slot] = body(lo, hi);
    });
    long long total = 0;
    for (long long v : part) total += v;
    return total;
}

}  // namespace

extern "C" {

// 0 = auto (hardware_concurrency, >=4 MiB/thread); n>0 = exactly n.
void pt_set_threads(int n) { g_threads = n < 0 ? 0 : n; }

// The thread count a kernel touching `words` uint32s would use —
// exported so tests can pin the auto-mode cap arithmetic on any box.
int pt_effective_threads(long long words) { return effective_threads(words); }

// Popcount of one buffer of n32 uint32 words.
long long pt_count(const uint32_t* a, long long n32) {
    return chunked_count(n32, [a](long long lo, long long hi) {
        return count_serial(a + lo, hi - lo);
    });
}

// |a & b| fused: the north-star IntersectionCount.
long long pt_count_and(const uint32_t* a, const uint32_t* b, long long n32) {
    return chunked_count(n32, [a, b](long long lo, long long hi) {
        return count_and_serial(a + lo, b + lo, hi - lo);
    });
}

// out[r] = popcount(mat[r]) over a [rows, n32] matrix.
void pt_row_counts(const uint32_t* mat, long long rows, long long n32,
                   int32_t* out) {
    parallel_chunks(rows, 1, n32, [=](long long lo, long long hi, int) {
        for (long long r = lo; r < hi; r++)
            out[r] = (int32_t)count_serial(mat + r * n32, n32);
    });
}

// out[r] = |a[r] & b[r]| — pairwise per-row intersection counts with no
// materialized intermediate (the Count(Intersect(Row,Row)) hot path on
// stacked shard operands).
void pt_row_counts_and(const uint32_t* a, const uint32_t* b,
                       long long rows, long long n32, int32_t* out) {
    parallel_chunks(rows, 1, n32, [=](long long lo, long long hi, int) {
        for (long long r = lo; r < hi; r++)
            out[r] = (int32_t)count_and_serial(a + r * n32, b + r * n32, n32);
    });
}

// out[r] = |mat[r] & filt| (TopN/GroupBy inner loop).
void pt_row_counts_masked(const uint32_t* mat, const uint32_t* filt,
                          long long rows, long long n32, int32_t* out) {
    parallel_chunks(rows, 1, n32, [=](long long lo, long long hi, int) {
        for (long long r = lo; r < hi; r++)
            out[r] = (int32_t)count_and_serial(mat + r * n32, filt, n32);
    });
}

// out[r] = |mat[r] & filt_stack[pos[r]]| (fused cross-shard TopN scan).
void pt_row_counts_gathered(const uint32_t* mat, const uint32_t* filt_stack,
                            const int32_t* pos, long long rows, long long n32,
                            int32_t* out) {
    parallel_chunks(rows, 1, n32, [=](long long lo, long long hi, int) {
        for (long long r = lo; r < hi; r++)
            out[r] = (int32_t)count_and_serial(
                mat + r * n32, filt_stack + (long long)pos[r] * n32, n32);
    });
}

// out[g*rows + r] = |mat[r] & masks[g]| (GroupBy cartesian product).
// Parallel over rows (not groups): every thread streams the same
// mat rows for all masks, so the split stays balanced when groups
// is small and rows is large (the common GroupBy shape).
void pt_masked_matrix_counts(const uint32_t* mat, const uint32_t* masks,
                             long long groups, long long rows, long long n32,
                             int32_t* out) {
    parallel_chunks(rows, 1, groups * n32,
                    [=](long long lo, long long hi, int) {
                        for (long long g = 0; g < groups; g++)
                            for (long long r = lo; r < hi; r++)
                                out[g * rows + r] = (int32_t)count_and_serial(
                                    mat + r * n32, masks + g * n32, n32);
                    });
}

// Sparse position-space merge: OR (clear=0) or ANDN (clear=1) sorted
// absolute bit positions into per-row bitmap buffers, returning the
// number of bits actually flipped.  One call per payload: row r's
// positions are pos[seg_start[r]..seg_end[r]) (absolute fragment
// positions; the in-row offset is pos & width_mask), applied to
// row_ptrs[r] (a u64 view of the row's packed words).  Start/end are
// separate so a clear that skips absent rows passes a sparse subset
// of segments.  Same-word positions are consecutive (pos sorted), so
// the inner loop accumulates a register mask per word run — one pass,
// no materialized per-word aggregates.  Parallel over rows (each
// row's buffer is touched by exactly one thread); the changed-bit
// total folds under a mutex at join.
long long pt_merge_positions(uint64_t* const* row_ptrs,
                             const long long* seg_start,
                             const long long* seg_end, long long n_rows,
                             const uint64_t* pos, uint64_t width_mask,
                             int clear) {
    long long total_pos = 0;
    for (long long r = 0; r < n_rows; r++)
        total_pos += seg_end[r] - seg_start[r];
    // Parallel over rows: each row's words live in exactly one
    // segment, so threads never touch the same buffer.  Fresh rows are
    // fault-bound (zero-fill-on-demand on first touch), which
    // parallelizes well — weight the thread gate by ~8 words touched
    // per position to reflect that.
    long long changed = 0;
    std::mutex mu;
    parallel_chunks(n_rows, 1, (total_pos / (n_rows ? n_rows : 1)) * 8 + 1,
                    [&](long long rlo, long long rhi, int) {
        long long local = 0;
        for (long long r = rlo; r < rhi; r++) {
            uint64_t* w = row_ptrs[r];
            long long i = seg_start[r];
            const long long end = seg_end[r];
            while (i < end) {
                // sparse payloads touch ~1 word per cache line; the
                // scattered read-modify-write is miss-bound, so pull
                // lines ~16 positions ahead while this one resolves
                if (i + 16 < end)
                    __builtin_prefetch(w + ((pos[i + 16] & width_mask) >> 6), 1);
                uint64_t off = pos[i] & width_mask;
                uint64_t widx = off >> 6;
                uint64_t mask = 1ULL << (off & 63);
                i++;
                while (i < end && ((pos[i] & width_mask) >> 6) == widx) {
                    mask |= 1ULL << (pos[i] & width_mask & 63);
                    i++;
                }
                uint64_t cur = w[widx];
                uint64_t delta = clear ? (cur & mask) : (mask & ~cur);
                if (delta) {
                    local += __builtin_popcountll(delta);
                    w[widx] = clear ? (cur & ~mask) : (cur | mask);
                }
            }
        }
        std::lock_guard<std::mutex> g(mu);
        changed += local;
    });
    return changed;
}

}  // extern "C"
