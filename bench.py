#!/usr/bin/env python
"""North-star benchmark: PQL Count(Intersect(Row, Row)) QPS.

Measures the fused AND+popcount+reduce kernel (the hot path of every
Count/Intersect PQL query, reference executor.go:1790 → roaring.go:595)
over a multi-shard packed-bitmap index on the available accelerator, and
compares against an in-process NumPy CPU baseline evaluating the same
query the way the reference's Go engine does (per-shard AND + popcount,
serial map-reduce).

The measured path is the PRODUCT kernel: ``bm.popcount_and`` — one fused
XLA program on TPU, the native C++ AVX popcount kernel
(ops/hostkernels.py) on a CPU host — exactly what the executor's fused
pipeline dispatches.  Since the op is memory-bound, the JSON line also
reports achieved memory bandwidth and, on TPU, utilization of the chip's
peak HBM bandwidth (the MFU-equivalent for set algebra).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"platform", "engine", "achieved_gbps", "peak_gbps", "bw_util",
"engines"}.  On TPU, "engines" carries an XLA-vs-Pallas A/B of the
same exact count (per-engine QPS, or a loud skip/WRONG-COUNT marker),
"engine"/"value" take the winner, and two context keys are added:
"dispatch_floor_us" (per-dispatch overhead of a trivial kernel — when
it approaches the per-query time, the run was dispatch-bound)
and "batch32" (B=32 queries per executable launch, the product's
fused-dispatch shape; see _bench_batched_and_floor).
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

# Benchmark shape: 256 shards x 2^20 columns = 268M columns per operand.
# Each operand is a [shards, 2^15] uint32 tensor (32 MiB) resident in HBM.
N_SHARDS = 256
WORDS = (1 << 20) // 32
DENSITY = 0.08  # fraction of bits set; typical set-field fragment occupancy

#: platforms that count as a real chip for the peak-bw lookup
_CHIP_PLATFORMS = ("tpu",)

# Peak HBM bandwidth by TPU generation, GB/s (public figures; used only
# for the utilization ratio on real chips).
_PEAK_GBPS = {
    # order matters: first match wins, most specific first.  JAX reports
    # v5e as "TPU v5 lite" and v6e as "TPU v6 lite" (normalized below to
    # "tpuv5lite"/"tpuv6lite"), hence the *lite aliases.
    "v5lite": 819.0,
    "v6lite": 1640.0,
    "v5e": 819.0,
    "v6e": 1640.0,
    "v5p": 2765.0,
    "v5": 2765.0,   # bare "TPU v5" = v5p
    "v4": 1228.0,
}


def make_operands(seed: int):
    rng = np.random.default_rng(seed)
    # Bernoulli bits packed into uint32 words, identical data for both runs.
    bits_a = rng.random((N_SHARDS, WORDS, 32)) < DENSITY
    bits_b = rng.random((N_SHARDS, WORDS, 32)) < DENSITY
    weights = (1 << np.arange(32, dtype=np.uint64)).astype(np.uint32)
    a = (bits_a * weights).sum(axis=2, dtype=np.uint32)
    b = (bits_b * weights).sum(axis=2, dtype=np.uint32)
    return a, b


def _timed_median(dispatch, verify_sample, start_iters: int,
                  max_iters: int, rng) -> float:
    """Median-of-3 pipelined dispatch rate.  Each repeat grows the
    pipelined batch until it spans >=0.3 s (one scheduler hiccup can't
    swing a shorter window), blocks once, then verifies a random
    sample of the window's results via ``verify_sample(i, out)``.
    Shared by the single-dispatch engines and the batched engine so
    the memoization-defeat/verification logic cannot drift between
    them.  Returns dispatches/second (callers scale by queries per
    dispatch)."""
    import jax

    reps = []
    for _ in range(3):
        iters = start_iters
        while True:
            outs = []
            t0 = time.perf_counter()
            for i in range(iters):
                outs.append(dispatch(i))
            jax.block_until_ready(outs)
            dt = time.perf_counter() - t0
            if dt >= 0.3 or iters >= max_iters:
                break
            iters *= 4
        for i in rng.choice(iters, size=min(32, iters), replace=False):
            verify_sample(int(i), outs[int(i)])
        reps.append(iters / dt)
    reps.sort()
    return reps[1]


def bench_device(a_np: np.ndarray, b_np: np.ndarray):
    """Throughput of the product fused kernel — ``bm.popcount_and``, the
    exact computation the executor's fused all-shard path dispatches for
    `Count(Intersect(Row, Row))`.

    On an accelerator, queries pipeline (block once at the end), as a
    serving process overlaps independent queries; a sync-per-query loop
    would measure host<->device round-trip latency, not chip throughput.
    On a CPU host the kernel is the synchronous native C++ popcount —
    each call IS a full query.

    Returns (qps, count, platform, engine, qps_by_engine, extras)
    where extras carries the chip-only context measurements
    (dispatch_floor_us, batch32) or is empty."""
    import jax

    from pilosa_tpu.ops import bitmap as bm

    platform = jax.devices()[0].platform

    if bm.host_mode():
        from pilosa_tpu.ops import hostkernels as hk

        engine = "native-host" if hk.native_available() else "numpy-host"
        expect = int(bm.popcount_and(a_np, b_np))
        # run for >= 2s so one scheduler hiccup on the single core
        # cannot swing the figure
        iters = 0
        t0 = time.perf_counter()
        while iters < 100 or time.perf_counter() - t0 < 2.0:
            bm.popcount_and(a_np, b_np)
            iters += 1
        dt = time.perf_counter() - t0
        qps = iters / dt
        # context fields on the CPU fallback too (VERDICT #1: the
        # committed artifact must not drop the fields the capture
        # instrumentation computes just because the chip was away)
        extras = _bench_batched_and_floor_host(a_np, b_np)
        return qps, expect, platform, engine, {engine: qps}, extras

    a = jax.device_put(a_np)
    b = jax.device_put(b_np)

    # Pre-stage N_VARIANTS distinct left operands (low bits XOR'd with the
    # variant id — same byte volume, near-identical density, different
    # count) and precompute each expected count on the host.  Timing with
    # DISTINCT inputs matters twice over: (1) a serving process never
    # re-answers one literal query back-to-back, and (2) the execution
    # path may memoize an identical (executable, args) dispatch.  Rotating
    # variants keeps every iteration a real HBM-streaming execution.
    N_VARIANTS = 16
    expects = [int(np.bitwise_count((a_np ^ np.uint32(i)) & b_np)
                   .sum(dtype=np.uint64))
               for i in range(N_VARIANTS)]
    # Derive the variants ON DEVICE from the one staged operand (a
    # jitted XOR each) instead of staging 16x32 MiB from the host.
    import jax.numpy as jnp

    xor_const = jax.jit(lambda x, c: x ^ c)
    a_vars = [a] + [xor_const(a, jnp.uint32(i))
                    for i in range(1, N_VARIANTS)]
    jax.block_until_ready(a_vars)

    check_rng = np.random.default_rng(7)

    def timed_qps(fn) -> float:
        # Closed-loop QPS over rotating distinct queries: dispatches
        # pipeline (block once at the end) as a serving process overlaps
        # independent queries.  Correctness is checked two ways — each
        # variant individually before timing, and a random sample of
        # the timed window after it (checking every one of thousands
        # of results would dwarf the measurement; any systematic
        # work-dropping still hits the sample with certainty) — so a
        # run that got
        # fast by skipping work fails loudly instead of recording a
        # fantasy number.
        for i in range(N_VARIANTS):
            got = int(np.asarray(fn(a_vars[i], b)))
            if got != expects[i]:
                raise AssertionError(
                    f"variant {i} returned {got}, expected {expects[i]}")

        def verify(i, out):
            got = int(np.asarray(out))
            if got != expects[i % N_VARIANTS]:
                raise AssertionError(
                    f"query {i} returned {got}, "
                    f"expected {expects[i % N_VARIANTS]}")

        return _timed_median(
            lambda i: fn(a_vars[i % N_VARIANTS], b), verify,
            start_iters=200, max_iters=3200, rng=check_rng)

    # Warm-up: compile + one execution.
    expect = int(np.asarray(bm.popcount_and(a, b)))
    qps_by_engine = {"xla": timed_qps(bm.popcount_and)}

    if platform in _CHIP_PLATFORMS:
        # A/B the Pallas single-pass kernel against XLA's fused
        # AND+popcount on the real chip — both are exact; the headline
        # takes the winner and the artifact records both.  The PRIVATE kernel
        # entry point, deliberately: the public wrapper routes by the
        # committed per-kernel winners, so going through it would time
        # XLA against itself once evidence says XLA wins.
        from pilosa_tpu.ops import pallas_kernels as pk

        pallas_count = pk._count_and_pallas

        try:
            got = int(np.asarray(pallas_count(a, b)))
        except Exception as e:  # noqa: BLE001 — a Mosaic lowering bug
            # must not kill the bench; the xla number stands, and the
            # artifact records WHY the pallas leg is absent
            print(f"bench: pallas engine skipped: {e!r}", file=sys.stderr)
            qps_by_engine["pallas"] = f"error: {type(e).__name__}"
        else:
            if got != expect:
                # a wrong COUNT is a correctness bug, not a benign
                # skip — it must be loud in the artifact
                qps_by_engine["pallas"] = f"WRONG COUNT {got} != {expect}"
            else:
                qps_by_engine["pallas"] = timed_qps(pallas_count)

    extras: dict = {}
    if platform in _CHIP_PLATFORMS:
        extras = _bench_batched_and_floor(a, b, a_np, b_np)

    numeric = {k: v for k, v in qps_by_engine.items()
               if isinstance(v, float)}
    engine = max(numeric, key=numeric.get)
    return numeric[engine], expect, platform, engine, qps_by_engine, extras


def _bench_batched_and_floor(a, b, a_np: np.ndarray,
                             b_np: np.ndarray) -> dict:
    """Two context measurements for chip captures:

    ``dispatch_floor_us`` — per-dispatch overhead of a trivial kernel
    through the same pipelined loop shape.  When this approaches the
    measured per-query time, the single-dispatch QPS figures above are
    dispatch-bound and the kernel time is hidden under dispatch
    overhead — the artifact then proves WHERE the bottleneck was
    instead of leaving a low bw_util unexplained.

    ``batch32`` — B=32 intersect-counts per executable launch: 32
    DISTINCT device-resident row variants against one filter, the
    dispatch shape of the product's fused all-shard paths
    (`masked_matrix_counts`, TopN/GroupBy row scans) and of any server
    batching concurrent queries.  The row stack is MATERIALIZED in HBM
    so every dispatch must stream all B rows (no cross-query read
    fusion can fake throughput), and a rotating scalar salt makes each
    dispatch's args distinct (see timed_qps).  Bandwidth accounting uses the row-stack bytes
    only (the shared filter's re-reads are not credited), so the
    figure is a LOWER bound and the >roof memoization flag stays
    valid."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from pilosa_tpu.ops import hostkernels as hk

    extras: dict = {}

    # ---- per-dispatch floor: trivial kernel, same loop shape
    tiny = jax.device_put(np.arange(8, dtype=np.uint32))
    tiny_fn = jax.jit(lambda x, c: jnp.sum(x ^ c, dtype=jnp.uint32))
    consts = [jnp.uint32(i) for i in range(16)]
    jax.block_until_ready([tiny_fn(tiny, c) for c in consts])
    t0 = time.perf_counter()
    iters = 2048
    outs = [tiny_fn(tiny, consts[i % 16]) for i in range(iters)]
    jax.block_until_ready(outs)
    extras["dispatch_floor_us"] = round(
        (time.perf_counter() - t0) / iters * 1e6, 1)

    # ---- batched engine
    B = 32
    N_ROT = 8
    row_salts = (np.arange(1, B + 1, dtype=np.uint64)
                 * np.uint64(0x9E3779B9)).astype(np.uint32)
    rot_salts = (np.arange(N_ROT, dtype=np.uint64)
                 * np.uint64(0x85EBCA6B)).astype(np.uint32)
    # 32 distinct rows derived ON DEVICE, then materialized:
    # [B, shards, words]
    stack = jax.jit(jax.vmap(lambda r: a ^ r))(
        jax.device_put(row_salts))
    jax.block_until_ready(stack)

    if hk.native_available():
        def host_count(x):
            return int(hk.count_and(x, b_np))
    else:
        def host_count(x):
            return int(np.bitwise_count(x & b_np).sum(dtype=np.uint64))

    expects = [[host_count(a_np ^ np.uint32(int(r) ^ int(s)))
                for r in row_salts] for s in rot_salts]

    @jax.jit
    def batched(stack, b, s):
        return jax.vmap(
            lambda ai: jnp.sum(lax.population_count((ai ^ s) & b),
                               dtype=jnp.uint32))(stack)

    dev_salts = [jnp.uint32(int(s)) for s in rot_salts]
    for j in range(N_ROT):  # warm + verify every rotation
        got = np.asarray(batched(stack, b, dev_salts[j]))
        if got.tolist() != expects[j]:
            extras["batch32"] = "WRONG COUNTS"
            return extras

    def verify(i, out):
        if np.asarray(out).tolist() != expects[i % N_ROT]:
            raise AssertionError(
                f"batched dispatch {i} returned wrong counts")

    try:
        qps_b = _timed_median(
            lambda i: batched(stack, b, dev_salts[i % N_ROT]), verify,
            start_iters=64, max_iters=1024,
            rng=np.random.default_rng(11)) * B
    except AssertionError as e:
        # a wrong batched count must not kill the single-dispatch
        # artifact — record it loudly instead
        extras["batch32"] = f"WRONG COUNTS (timed window): {e}"
        return extras
    extras["batch32"] = {
        "qps": round(qps_b, 2),
        "queries_per_dispatch": B,
        # row-stack bytes only — lower bound, see docstring
        "achieved_gbps_lower": round(
            qps_b * (stack.nbytes / B) / 1e9, 1),
    }
    return extras


def _bench_batched_and_floor_host(a_np: np.ndarray,
                                  b_np: np.ndarray) -> dict:
    """CPU-fallback analogs of the chip context measurements, same
    field names and shapes so artifact consumers never branch:

    ``dispatch_floor_us`` — per-call floor of the host kernel entry
    point (a trivial 8-word count through the same native/numpy path
    every query pays; the host's analog of launch overhead).

    ``batch32`` — B=32 distinct intersect-counts back-to-back; the
    host has no executable-launch batching to amortize, so this is the
    honest per-query cost at the batched shape, bandwidth-credited
    like the chip version (each query's own operand bytes only)."""
    from pilosa_tpu.ops import hostkernels as hk

    extras: dict = {}
    tiny = np.arange(8, dtype=np.uint32)
    if hk.native_available():
        def tiny_fn():
            return hk.count_and(tiny, tiny)

        def count(x):
            return int(hk.count_and(x, b_np))
    else:
        def tiny_fn():
            return int(np.bitwise_count(tiny).sum())

        def count(x):
            return int(np.bitwise_count(x & b_np).sum(dtype=np.uint64))

    for _ in range(256):
        tiny_fn()
    iters = 20000
    t0 = time.perf_counter()
    for _ in range(iters):
        tiny_fn()
    extras["dispatch_floor_us"] = round(
        (time.perf_counter() - t0) / iters * 1e6, 1)

    B = 32
    salts = (np.arange(1, B + 1, dtype=np.uint64)
             * np.uint64(0x9E3779B9)).astype(np.uint32)
    expects = [count(a_np ^ s) for s in salts]
    reps = []
    for _ in range(3):
        t0 = time.perf_counter()
        got = [count(a_np ^ s) for s in salts]
        dt = time.perf_counter() - t0
        if got != expects:
            extras["batch32"] = "WRONG COUNTS"
            return extras
        reps.append(B / dt)
    reps.sort()
    qps_b = reps[1]
    extras["batch32"] = {
        "qps": round(qps_b, 2),
        "queries_per_dispatch": B,
        # each query's own operand bytes only — lower bound, matching
        # the chip accounting
        "achieved_gbps_lower": round(qps_b * a_np.nbytes / 1e9, 1),
    }
    return extras


def bench_coalescer(a_np: np.ndarray,
                    b_np: np.ndarray) -> tuple[dict, dict, dict] | None:
    """Serving-path benchmark of the PRODUCT batching layer: concurrent
    `Count(Intersect(Row, Row))` PQL queries through the executor with
    the cross-query coalescer (parallel/coalescer.py) enabled — the
    `batch32` context measurement made product code.  Row-id variants
    rotate across queries (distinct leaf stacks per query, one compiled
    shape), so no dispatch can be satisfied by memoization, and
    every result is verified against a host-computed expected count.

    Bandwidth accounting credits only each query's own row stack (the
    shared filter's re-reads are not credited), so ``achieved_gbps_lower``
    is a LOWER bound and the >roof memoization flag stays valid.

    The load runs TWICE — query flight recorder enabled (the product
    default) and disabled — so the artifact carries the recorder's
    overhead on this exact coalesced Count path (the <1% budget of the
    observe layer).  The headline coalescer numbers come from the
    recorder-ENABLED run, the shipping configuration.

    Returns (coalescer_extras, observe_extras, devobs_extras,
    perfobs_extras), or None under a non-default shard width (the
    index rows are built for 2^20-column shards)."""
    import tempfile
    import threading

    from pilosa_tpu import stats as _stats
    from pilosa_tpu.models.holder import Holder
    from pilosa_tpu.ops import bitmap as bm
    from pilosa_tpu.parallel.coalescer import Coalescer
    from pilosa_tpu.parallel.executor import Executor
    from pilosa_tpu.shardwidth import SHARD_WIDTH

    if bm.n_words(SHARD_WIDTH) != WORDS:
        return None

    N_VAR = 8
    salts = (np.arange(1, N_VAR + 1, dtype=np.uint64)
             * np.uint64(0x9E3779B9)).astype(np.uint32)
    holder = Holder(tempfile.mkdtemp() + "/bench-co")
    idx = holder.create_index("i")
    f = idx.create_field("f")
    view = f.create_view_if_not_exists("standard")
    for s in range(N_SHARDS):
        frag = view.create_fragment_if_not_exists(s)
        with frag._lock:
            frag._rows[2] = b_np[s].copy()
            for v in range(N_VAR):
                frag._rows[100 + v] = a_np[s] ^ salts[v]
            frag._bump_gen()
        f._note_shard(s)
    expects = [int(np.bitwise_count((a_np ^ salts[v]) & b_np)
                   .sum(dtype=np.uint64)) for v in range(N_VAR)]

    ex = Executor(holder)
    stats = _stats.MemStatsClient()
    ex.coalescer = Coalescer(window_s=0.002, max_batch=32,
                             enabled=True, stats=stats)
    # this benchmark measures the coalesced DISPATCH path; with the
    # result cache on, the 8-variant rotation would turn into pure
    # cache hits after one window (bench_resultcache measures that
    # side separately)
    from pilosa_tpu.runtime import resultcache as _resultcache

    _resultcache.cache().enabled = False
    qs = [f"Count(Intersect(Row(f={100 + v}), Row(f=2)))"
          for v in range(N_VAR)]
    for v, q in enumerate(qs):  # warm (stacks + jit) and verify each
        got = int(ex.execute("i", q)[0])
        if got != expects[v]:
            raise AssertionError(
                f"coalescer variant {v} returned {got}, "
                f"expected {expects[v]}")

    THREADS = 16

    def run_load(seconds: float) -> float:
        done = [0] * THREADS
        errs: list = []
        t0 = time.perf_counter()
        stop = t0 + seconds

        def worker(t: int) -> None:
            i = t
            try:
                while time.perf_counter() < stop:
                    v = i % N_VAR
                    got = int(ex.execute("i", qs[v])[0])
                    if got != expects[v]:
                        raise AssertionError(
                            f"coalesced query returned {got}, "
                            f"expected {expects[v]}")
                    i += THREADS
                    done[t] += 1
            except BaseException as e:  # noqa: BLE001 — fail loudly
                errs.append(e)

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(THREADS)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        elapsed = time.perf_counter() - t0
        if errs:
            raise errs[0]
        return sum(done) / elapsed

    # Recorder on/off A/B as INTERLEAVED median windows: a sequential
    # off-then-on pair confounds the delta with load drift on a busy
    # host (observed swings of tens of percent between identical runs,
    # far above any real recorder cost), while medians of alternating
    # short windows see the same ambient load on both sides.  Both
    # phases are PRE-WARMED by a throwaway window first and the side
    # that goes first alternates per iteration — before this, the
    # off-phase always ran first and ate the serving-path warmup
    # (thread ramp, allocator), which made recorder-ON measure FASTER
    # than off (overhead_pct -26.2 in BENCH_r06, a nonsense number).
    ex.recorder.stats = stats
    run_load(0.6)  # warmup window: not recorded on either side
    offs, ons = [], []
    for i in range(3):
        order = ((False, True) if i % 2 == 0 else (True, False))
        for rec_on in order:
            ex.recorder.enabled = rec_on
            (ons if rec_on else offs).append(run_load(0.6))
    ex.recorder.enabled = True
    qps_off = sorted(offs)[1]
    qps_on = sorted(ons)[1]
    # The noise-free overhead figure: the recorder's own begin+publish
    # cost per query (histogram observation included), measured
    # directly — the note_* calls on the hot path are list appends and
    # perf_counter reads, dwarfed by this pair.
    from pilosa_tpu import observe as _observe

    r = _observe.FlightRecorder(stats=_stats.MemStatsClient())
    n_rec = 20000
    t0 = time.perf_counter()
    for _ in range(n_rec):
        r.publish(r.begin("i", "Count(Row(f=1))"))
    record_cost_us = (time.perf_counter() - t0) / n_rec * 1e6

    # Device-runtime telemetry A/B on the same coalesced path (the
    # [observe] devobs budget): interleaved median windows with the
    # observer on (shipping default) vs off, plus the noise-free
    # per-dispatch probe cost measured directly — two _cache_size C
    # calls and a perf_counter pair around a cached jit dispatch.
    from pilosa_tpu import devobs as _devobs

    dv_obs = _devobs.observer()
    dv_offs, dv_ons = [], []
    for _ in range(3):
        dv_obs.enabled = False
        dv_offs.append(run_load(0.6))
        dv_obs.enabled = True
        dv_ons.append(run_load(0.6))
    dv_qps_off = sorted(dv_offs)[1]
    dv_qps_on = sorted(dv_ons)[1]
    import jax.numpy as jnp

    probe_a = jnp.zeros(256, dtype=jnp.uint32)
    wrapped = bm._jit_popcount_and      # devobs-instrumented
    raw = getattr(wrapped, "fn", wrapped)  # the underlying jit
    n_probe = 20000
    wrapped(probe_a, probe_a)  # warm
    t0 = time.perf_counter()
    for _ in range(n_probe):
        wrapped(probe_a, probe_a)
    t_wrapped = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(n_probe):
        raw(probe_a, probe_a)
    t_raw = time.perf_counter() - t0
    probe_cost_us = max(0.0, (t_wrapped - t_raw) / n_probe * 1e6)

    # Engine-observatory A/B on the same coalesced path (the perfobs
    # <1% budget): interleaved median windows with the observatory on
    # (shipping default) vs off, plus the noise-free per-launch cost
    # measured directly — a t0()+sample() bracket over an
    # already-materialized host array (block_until_ready is a no-op,
    # isolating the observatory's own bookkeeping).
    from pilosa_tpu import perfobs as _perfobs

    po_offs, po_ons = [], []
    for _ in range(3):
        _perfobs.configure(enabled_=False)
        po_offs.append(run_load(0.6))
        _perfobs.configure(enabled_=True)
        po_ons.append(run_load(0.6))
    po_qps_off = sorted(po_offs)[1]
    po_qps_on = sorted(po_ons)[1]
    probe_out = np.zeros(64, dtype=np.uint32)
    n_s = 20000
    t0 = time.perf_counter()
    for _ in range(n_s):
        s0 = _perfobs.t0()
        _perfobs.sample("dense", probe_out, s0, nbytes=256)
    sample_cost_us = (time.perf_counter() - t0) / n_s * 1e6
    # drop the probe's synthetic samples so the headline window below
    # owns the measured per-engine summary
    _perfobs.reset_counters()

    # headline run, shipping configuration (recorder on); occupancy
    # must describe the SAME window as the headline qps, so delta the
    # histogram across this run only
    occ0 = dict(stats.snapshot().get("coalescer.batch_occupancy") or {})
    qps = run_load(1.5)
    occ = stats.snapshot().get("coalescer.batch_occupancy") or {}
    occ_sum = occ.get("sum", 0) - occ0.get("sum", 0)
    occ_n = occ.get("count", 0) - occ0.get("count", 0)
    out = {
        "qps": round(qps, 2),
        "threads": THREADS,
        "window_ms": 2.0,
        "queries_per_dispatch_mean": round(occ_sum / max(1, occ_n), 2),
        # each query's own 32 MiB row stack only — lower bound
        "achieved_gbps_lower": round(qps * a_np.nbytes / 1e9, 1),
    }
    obs = {
        "qps_recorder_on": round(qps_on, 2),
        "qps_recorder_off": round(qps_off, 2),
        # the qps A/B is EVIDENCE, not the budget pin: even
        # order-alternated median windows swing by double digits on a
        # busy host (23.58% in BENCH_r10 with a 9us direct cost —
        # three orders of magnitude apart), so the delta mostly
        # measures ambient load, and it reports unclamped under a
        # name that says so
        "ab_overhead_pct_noisy": round(
            (qps_off - qps_on) / qps_off * 100.0, 2),
        # per-query recorder cost measured directly (begin+publish
        # bracket), as a share of the measured per-query service time
        # — THE number the <1% budget is judged on
        "record_cost_us": round(record_cost_us, 2),
        "record_cost_pct_of_query": round(
            record_cost_us / (THREADS / qps * 1e6) * 100.0, 3),
        "budget_pct": 1.0,
        "within_budget": bool(
            record_cost_us / (THREADS / qps * 1e6) * 100.0 < 1.0),
    }
    dv = {
        "qps_devobs_on": round(dv_qps_on, 2),
        "qps_devobs_off": round(dv_qps_off, 2),
        # medians of interleaved windows; negative = within noise
        "overhead_pct": round(
            (dv_qps_off - dv_qps_on) / dv_qps_off * 100.0, 2),
        # per-dispatch probe cost as a share of the measured per-query
        # service time — the number the <1% budget is judged on (one
        # coalesced dispatch serves a whole batch, so the per-QUERY
        # share is smaller still)
        "probe_cost_us": round(probe_cost_us, 3),
        "probe_cost_pct_of_query": round(
            probe_cost_us / (THREADS / qps * 1e6) * 100.0, 3),
        "budget_pct": 1.0,
    }
    po = {
        "qps_perfobs_on": round(po_qps_on, 2),
        "qps_perfobs_off": round(po_qps_off, 2),
        # medians of interleaved windows; negative = within noise
        "overhead_pct": round(
            (po_qps_off - po_qps_on) / po_qps_off * 100.0, 2),
        # per-launch bracket cost as a share of the measured per-query
        # service time — the number the <1% budget is judged on (one
        # coalesced launch serves a whole batch, so the per-QUERY
        # share is smaller still)
        "sample_cost_us": round(sample_cost_us, 3),
        "sample_cost_pct_of_query": round(
            sample_cost_us / (THREADS / qps * 1e6) * 100.0, 3),
        "budget_pct": 1.0,
        # MEASURED per-engine achieved bandwidth over the headline
        # window — the bw_util slice tools/chipcapture.py stamps
        "engines": _perfobs.engine_summary(),
    }
    holder.close()
    _resultcache.cache().enabled = True
    return out, obs, dv, po


def bench_ragged(a_np: np.ndarray, b_np: np.ndarray) -> dict | None:
    """Homogeneous-vs-heterogeneous A/B on the coalesced serving path
    (the ragged-megabatch round): closed-loop concurrent Count
    traffic through the executor, first 8 same-shape variants (the
    pre-ragged best case — every query merges into one fused-program
    launch), then 16 structurally DISTINCT shapes (realistic mixed
    dashboard traffic — pre-ragged this coalesced almost never and
    paid per-query dispatch; with the op-tape interpreter the whole
    mix shares size-class buckets).

    Every completed query is verified against a host-computed expected
    count, and each phase reports p50 latency plus
    ``dispatches_per_query`` (coalescer launches over completed
    queries — the number the engine exists to push toward the batch
    dispatch floor).  Artifact pins: ``pin_2x_ok`` — the mixed-shape
    open-loop p50 stays within 2x of the homogeneous p50 — and
    ``pin_dpq_ok`` — mixed dispatches/query <= 0.25 (>= 4 queries per
    launch on heterogeneous traffic)."""
    import statistics
    import tempfile
    import threading

    from pilosa_tpu import stats as _stats
    from pilosa_tpu.models.holder import Holder
    from pilosa_tpu.ops import bitmap as bm
    from pilosa_tpu.parallel.coalescer import Coalescer
    from pilosa_tpu.parallel.executor import Executor
    from pilosa_tpu.runtime import resultcache as _resultcache
    from pilosa_tpu.shardwidth import SHARD_WIDTH
    from tools.loadgen import shape_mix_queries

    if bm.n_words(SHARD_WIDTH) != WORDS:
        return None

    SH = 64  # shards: real fan-out, bounded host A/B time
    N_VAR = 8
    salts = (np.arange(1, N_VAR + 1, dtype=np.uint64)
             * np.uint64(0x9E3779B9)).astype(np.uint32)
    holder = Holder(tempfile.mkdtemp() + "/bench-rg")
    idx = holder.create_index("i")
    f = idx.create_field("f")
    view = f.create_view_if_not_exists("standard")
    for s in range(SH):
        frag = view.create_fragment_if_not_exists(s)
        with frag._lock:
            # rows 0..5 feed the shape-mix trees; row 2 doubles as the
            # homogeneous filter; 100+v are the same-shape variants
            for r in range(6):
                frag._rows[r] = (
                    a_np[s] ^ np.uint32((r * 0x85EBCA6B) & 0xFFFFFFFF)
                    if r != 2 else b_np[s].copy())
            for v in range(N_VAR):
                frag._rows[100 + v] = a_np[s] ^ salts[v]
            frag._bump_gen()
        f._note_shard(s)

    ex = Executor(holder)
    stats = _stats.MemStatsClient()
    # 10ms window (vs the 2ms serving default): the host A/B runs
    # closed-loop with ~100ms flushes, and a 2ms window lets the
    # post-flush re-convergence straggle into under-filled buckets —
    # the wider window costs ~10% of one flush and makes the measured
    # dispatches/query describe batching, not thread wake-up jitter
    ex.coalescer = Coalescer(window_s=0.010, max_batch=32,
                             enabled=True, stats=stats)
    _resultcache.cache().enabled = False

    homo_qs = [f"Count(Intersect(Row(f={100 + v}), Row(f=2)))"
               for v in range(N_VAR)]
    mixed_qs = shape_mix_queries(16, field="f", rows=6)

    def ground_truth(qs):
        ex.fuse_shards = False
        try:
            return [int(ex.execute("i", q)[0]) for q in qs]
        finally:
            ex.fuse_shards = True

    homo_expect = ground_truth(homo_qs)
    mixed_expect = ground_truth(mixed_qs)
    for qs, expects in ((homo_qs, homo_expect),
                        (mixed_qs, mixed_expect)):
        for q, want in zip(qs, expects):  # warm stacks + programs
            got = int(ex.execute("i", q)[0])
            if got != want:
                raise AssertionError(
                    f"ragged bench warm-up mismatch: {q} -> {got}, "
                    f"expected {want}")

    THREADS = 16

    def phase(qs, expects, seconds: float) -> dict:
        lats: list[list[int]] = [[] for _ in range(THREADS)]
        errs: list = []
        d0 = stats.snapshot().get("coalescer.dispatches", 0)
        t0 = time.perf_counter()
        stop = t0 + seconds

        def worker(t: int) -> None:
            i = t
            try:
                while time.perf_counter() < stop:
                    v = i % len(qs)
                    tq = time.perf_counter_ns()
                    got = int(ex.execute("i", qs[v])[0])
                    lats[t].append(time.perf_counter_ns() - tq)
                    if got != expects[v]:
                        raise AssertionError(
                            f"ragged bench returned {got}, expected "
                            f"{expects[v]} for {qs[v]}")
                    i += THREADS
            except BaseException as e:  # noqa: BLE001 — fail loudly
                errs.append(e)

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(THREADS)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        if errs:
            raise errs[0]
        flat = [x for per in lats for x in per]
        done = len(flat)
        dn = stats.snapshot().get("coalescer.dispatches", 0) - d0
        return {
            "p50_us": round(statistics.median(flat) / 1e3, 1),
            "queries": done,
            "qps": round(done / seconds, 1),
            "dispatches_per_query": round(dn / max(1, done), 4),
        }

    try:
        homo = phase(homo_qs, homo_expect, 1.5)
        mixed = phase(mixed_qs, mixed_expect, 1.5)
    finally:
        _resultcache.cache().enabled = True
        holder.close()
    from pilosa_tpu.ops import tape as _tape

    out = {
        "homogeneous_batch32": homo,
        "mixed_16_shapes": mixed,
        "shape_mix": 16,
        "mixed_vs_homogeneous_p50": round(
            mixed["p50_us"] / homo["p50_us"], 2),
        "tape_counters": {k: v for k, v in _tape.counters().items()
                          if v},
        "pin_2x_ok": mixed["p50_us"] <= 2.0 * homo["p50_us"],
        "pin_dpq_ok": mixed["dispatches_per_query"] <= 0.25,
    }
    if not out["pin_2x_ok"]:
        print(f"bench: ragged mixed-shape p50 {mixed['p50_us']:.0f}us "
              f"is NOT within 2x of the homogeneous p50 "
              f"{homo['p50_us']:.0f}us", file=sys.stderr)
    if not out["pin_dpq_ok"]:
        print(f"bench: ragged mixed dispatches/query "
              f"{mixed['dispatches_per_query']} exceeds the 0.25 "
              f"acceptance bound", file=sys.stderr)
    return out


def bench_resultcache(a_np: np.ndarray,
                      b_np: np.ndarray) -> dict | None:
    """Cold/warm A/B of the generation-stamped result cache on the
    coalesced Count path (the acceptance pin of the resultcache
    round): per-query p50 of the UNCACHED fused-dispatch path
    (?nocache semantics — every query stages leaves and launches) vs
    the warm-hit p50 (parse + translate + generation probe, zero
    device work), plus the cache's per-query cost on a 0%-hit-rate
    workload measured directly (probe -> miss -> fill, the exact work
    a never-repeating query stream adds).

    Artifact pins: ``speedup_p50`` must be >= 10 (``pin_10x_ok``), and
    ``miss_overhead_pct_of_query`` must stay under the 1% budget."""
    import statistics
    import tempfile

    from pilosa_tpu.models.holder import Holder
    from pilosa_tpu.ops import bitmap as bm
    from pilosa_tpu.parallel.coalescer import Coalescer
    from pilosa_tpu.parallel.executor import ExecOptions, Executor
    from pilosa_tpu.pql import parse
    from pilosa_tpu.runtime import resultcache
    from pilosa_tpu.shardwidth import SHARD_WIDTH

    if bm.n_words(SHARD_WIDTH) != WORDS:
        return None

    N_VAR = 4
    salts = (np.arange(1, N_VAR + 1, dtype=np.uint64)
             * np.uint64(0x9E3779B9)).astype(np.uint32)
    holder = Holder(tempfile.mkdtemp() + "/bench-rc")
    idx = holder.create_index("i")
    f = idx.create_field("f")
    view = f.create_view_if_not_exists("standard")
    for s in range(N_SHARDS):
        frag = view.create_fragment_if_not_exists(s)
        with frag._lock:
            frag._rows[2] = b_np[s].copy()
            for v in range(N_VAR):
                frag._rows[100 + v] = a_np[s] ^ salts[v]
            frag._bump_gen()
        f._note_shard(s)
    expects = [int(np.bitwise_count((a_np ^ salts[v]) & b_np)
                   .sum(dtype=np.uint64)) for v in range(N_VAR)]
    ex = Executor(holder)
    ex.coalescer = Coalescer(window_s=0.002, max_batch=32,
                             enabled="auto")
    resultcache.reset()
    qs = [f"Count(Intersect(Row(f={100 + v}), Row(f=2)))"
          for v in range(N_VAR)]
    nocache = ExecOptions(cache=False)
    for v, q in enumerate(qs):  # warm stacks + jit, verify, fill cache
        for opt in (nocache, None):
            got = int(ex.execute("i", q, opt=opt)[0])
            if got != expects[v]:
                raise AssertionError(
                    f"resultcache variant {v} returned {got}, "
                    f"expected {expects[v]}")

    def p50_us(n: int, run) -> float:
        lats = []
        for i in range(n):
            t0 = time.perf_counter_ns()
            run(i)
            lats.append(time.perf_counter_ns() - t0)
        return statistics.median(lats) / 1e3

    uncached_p50 = p50_us(
        40, lambda i: ex.execute("i", qs[i % N_VAR], opt=nocache))
    warm_p50 = p50_us(2000, lambda i: ex.execute("i", qs[i % N_VAR]))

    # 0%-hit-rate added cost, measured directly: canonical signature +
    # generation capture + key digest + miss lookup + fill — what a
    # never-repeating query stream pays per query on top of execution
    call = parse(qs[0]).calls[0]
    shards_t = tuple(range(N_SHARDS))
    scratch = resultcache.ResultCache()
    n_probe = 2000
    reps = []
    for _ in range(5):  # median-of-5: host timing jitter dominates
        t0 = time.perf_counter()
        for i in range(n_probe):
            rc, key, gens = ex._rc_probe(idx, "count", shards_t, None,
                                         tree=call.children[0])
            # distinct keys, like a never-repeating stream: every get
            # is a genuine miss and every put a genuine fill
            scratch.get((key, i), gens)
            scratch.put((key, i), gens, 1, 32)
        reps.append((time.perf_counter() - t0) / n_probe * 1e6)
        scratch.invalidate_all()
    miss_cost_us = statistics.median(reps)

    out = {
        "uncached_p50_us": round(uncached_p50, 1),
        "warm_hit_p50_us": round(warm_p50, 1),
        "speedup_p50": round(uncached_p50 / warm_p50, 1),
        "pin_10x_ok": uncached_p50 >= 10 * warm_p50,
        "miss_overhead_us": round(miss_cost_us, 2),
        "miss_overhead_pct_of_query": round(
            miss_cost_us / uncached_p50 * 100.0, 3),
        "budget_pct": 1.0,
    }
    if not out["pin_10x_ok"]:
        print(f"bench: resultcache warm-hit p50 {warm_p50:.0f}us is "
              f"NOT >=10x under the uncached path "
              f"{uncached_p50:.0f}us", file=sys.stderr)
    holder.close()
    return out


def bench_ingest(a_np: np.ndarray, b_np: np.ndarray) -> dict | None:
    """Read-under-ingest A/B on the coalesced Count path (the
    streaming-ingest round): p50 of a repeated
    ``Count(Intersect(Row, Row))`` measured in three phases —
    read-only baseline, reads while a writer thread sustains batched
    same-field imports with DELTA PLANES ON (writes land beside the
    base; only compaction bumps the generation, so the queried rows'
    device stacks stay resident), and the same write load with deltas
    OFF (every import bumps the generation: per-read stack rebuild +
    re-upload, the pre-ingest-subsystem behavior).

    Every sampled read is verified bit-exact (the write load touches
    rows the query never reads, so the count is invariant), background
    compactions run mid-phase to exercise the merge-vs-read race, and
    each phase reports the result-cache hit rate over its window.
    Artifact pin: ``pin_2x_ok`` — the delta-path p50 under ingest
    stays within 2x of the read-only baseline (the bench-local analog
    of the loadgen acceptance run's read-p99 bound)."""
    import statistics
    import tempfile
    import threading

    from pilosa_tpu import ingest as _ingest
    from pilosa_tpu.ingest import compactor as _compactor
    from pilosa_tpu.models.holder import Holder
    from pilosa_tpu.ops import bitmap as bm
    from pilosa_tpu.parallel.coalescer import Coalescer
    from pilosa_tpu.parallel.executor import Executor
    from pilosa_tpu.runtime import resultcache
    from pilosa_tpu.shardwidth import SHARD_WIDTH

    if bm.n_words(SHARD_WIDTH) != WORDS:
        return None

    SH = 32  # shards: enough for a real fan-out, small enough to A/B
    holder = Holder(tempfile.mkdtemp() + "/bench-ing")
    idx = holder.create_index("i")
    f = idx.create_field("f")
    view = f.create_view_if_not_exists("standard")
    for s in range(SH):
        frag = view.create_fragment_if_not_exists(s)
        with frag._lock:
            frag._rows[1] = a_np[s].copy()
            frag._rows[2] = b_np[s].copy()
            frag._bump_gen()
        f._note_shard(s)
    expect = int(np.bitwise_count(a_np[:SH] & b_np[:SH])
                 .sum(dtype=np.uint64))
    ex = Executor(holder)
    ex.coalescer = Coalescer(window_s=0.002, max_batch=32,
                             enabled="auto")
    q = "Count(Intersect(Row(f=1), Row(f=2)))"
    rng = np.random.default_rng(4242)

    def phase(delta_on: bool, write: bool, seconds: float) -> dict:
        # short compact interval: at the default 2.0s age bound (and
        # 128k-bit threshold vs ~16 bits/fragment/batch here) nothing
        # would be _due() inside a 2s phase and run_once() below would
        # be a no-op — the merge-vs-read race this phase exists to
        # exercise needs age-due fragments mid-phase (reset() in the
        # outer finally restores the defaults)
        _ingest.configure(delta_enabled=delta_on,
                          compact_interval=0.2)
        _compactor.reset()
        resultcache.reset()
        rc0 = resultcache.cache().stats_dict()
        stop = threading.Event()
        bits = [0]

        def writer():
            batch = 0
            while not stop.is_set():
                rows = rng.integers(10, 18, size=512).tolist()
                cols = rng.integers(0, SH * SHARD_WIDTH,
                                    size=512).tolist()
                f.import_bits(rows, cols)
                bits[0] += 512
                batch += 1
                if delta_on and batch % 50 == 0:
                    # background merge racing the reads (what the
                    # compactor thread does in production)
                    _compactor.compactor().run_once()
                time.sleep(0.001)

        t = threading.Thread(target=writer, daemon=True)
        if write:
            t.start()
        lats = []
        end = time.perf_counter() + seconds
        while time.perf_counter() < end:
            t0 = time.perf_counter_ns()
            got = int(ex.execute("i", q)[0])
            lats.append(time.perf_counter_ns() - t0)
            if got != expect:
                stop.set()
                raise AssertionError(
                    f"ingest A/B bit-exactness violated: {got} != "
                    f"{expect} (delta_on={delta_on})")
        stop.set()
        if write:
            t.join(timeout=10)
        merged = f.flush_deltas()
        if int(ex.execute("i", q)[0]) != expect:
            raise AssertionError("post-flush count diverged")
        rc1 = resultcache.cache().stats_dict()
        dh = rc1["hits"] - rc0["hits"]
        dm = rc1["misses"] - rc0["misses"]
        elapsed_bits = bits[0] / seconds
        return {
            "p50_us": round(statistics.median(lats) / 1e3, 1),
            "reads": len(lats),
            "ingest_bits_per_s": round(elapsed_bits, 0),
            "cache_hit_rate": round(dh / (dh + dm), 3)
            if dh + dm else None,
            "flushed_bits": merged,
            # proof the merge-vs-read race actually ran mid-phase
            "compactions": _compactor.compactor().compactions,
        }

    try:
        read_only = phase(True, write=False, seconds=1.0)
        under_delta = phase(True, write=True, seconds=2.0)
        under_base = phase(False, write=True, seconds=2.0)
    finally:
        _ingest.reset()
        _compactor.reset()
        holder.close()
    out = {
        "read_only": read_only,
        "under_ingest_delta": under_delta,
        "under_ingest_base": under_base,
        "delta_vs_readonly": round(
            under_delta["p50_us"] / read_only["p50_us"], 2),
        "base_vs_readonly": round(
            under_base["p50_us"] / read_only["p50_us"], 2),
        "pin_2x_ok": under_delta["p50_us"]
        <= 2.0 * read_only["p50_us"],
    }
    if not out["pin_2x_ok"]:
        print(f"bench: ingest read-under-write p50 "
              f"{under_delta['p50_us']:.0f}us is NOT within 2x of the "
              f"read-only baseline {read_only['p50_us']:.0f}us",
              file=sys.stderr)
    return out


def bench_containers() -> dict | None:
    """Sparse/dense A/B of the compressed container-directory engine
    (ops/containers.py — the roaring-on-TPU representation change):

    - builds a ≤1%-fill CLUSTERED synthetic index (each row's bits
      confined to 2 of the 16 containers per shard — the shape real
      sparse bitmap rows take, and exactly what roaring's container
      specialization exists for) plus a dense ~50%-fill control,
    - measures the same Count(Intersect(...)) workload with the
      engine enabled vs disabled (``[containers] enabled`` — disabled
      IS the pre-container dense fused path, byte-identical),
    - reports resident device bytes both ways (dense stacks vs pooled
      container blocks, from the residency manager's kind split) and
      the achieved streaming rates, every sample verified against a
      host-computed expected count,
    - adds an ultra-sparse (~0.1% fill) leg A/Bing the per-kind pools
      (``[containers] kinds``) against the dense-kind compressed path
      — the array-kind capacity pin (>=5x lower resident bytes) plus
      a kinds-dispatch no-regression qps pin on the 1%-fill leg.

    Returns None under a non-default shard width (the container
    geometry assumes 2^20-column shards here).  CPU-fallback numbers
    are acceptable for the artifact; the chip capture slot rides the
    main JSON line like every other extras phase."""
    import tempfile

    from pilosa_tpu.models.holder import Holder
    from pilosa_tpu.ops import bitmap as bm
    from pilosa_tpu.ops import containers as ct
    from pilosa_tpu.parallel.executor import Executor
    from pilosa_tpu.runtime import residency
    from pilosa_tpu.shardwidth import SHARD_WIDTH

    if bm.n_words(SHARD_WIDTH) != WORDS:
        return None
    CT_SHARDS = 32
    FILL = 0.01
    bits_per_row = int(FILL * SHARD_WIDTH)      # ~10.5k bits/shard-row
    rng = np.random.default_rng(12348)
    holder = Holder(tempfile.mkdtemp() + "/bench-ct")
    idx = holder.create_index("i")
    f = idx.create_field("f")
    view = f.create_view_if_not_exists("standard")
    # ~0.09% fill, sized so per-container cardinality (~460) sits
    # under the 512 pow2 size class — device array-pool rows pad to
    # powers of two, and a card just past a boundary doubles the row
    us_bits = 920
    FILL_US = us_bits / SHARD_WIDTH
    truth: dict[int, set] = {10: set(), 11: set(),
                             20: set(), 21: set()}
    for s in range(CT_SHARDS):
        frag = view.create_fragment_if_not_exists(s)
        # clustered: all bits inside containers 0-1 (128Ki bits); the
        # shared half is drawn ONCE per shard so rows 10 and 11 really
        # intersect in ~bits_per_row/2 positions (drawing it inside
        # the row loop made the sets independent and the measured
        # intersection mostly random overlap)
        shared = rng.choice(1 << 17, size=bits_per_row // 2,
                            replace=False)
        for r in (10, 11):
            own = rng.choice(1 << 17, size=bits_per_row // 2,
                             replace=False)
            pos = np.unique(np.concatenate([shared, own]))
            frag.import_positions((r * SHARD_WIDTH + pos)
                                  .astype(np.uint64))
            truth[r].update((s * SHARD_WIDTH + pos).tolist())
        # ultra-sparse rows (~0.1% fill, same clustering): each
        # non-empty container holds a few hundred bits — exactly the
        # array-kind regime the per-kind pools exist for
        us_shared = rng.choice(1 << 17, size=us_bits // 2,
                               replace=False)
        for r in (20, 21):
            own = rng.choice(1 << 17, size=us_bits // 2,
                             replace=False)
            pos = np.unique(np.concatenate([us_shared, own]))
            frag.import_positions((r * SHARD_WIDTH + pos)
                                  .astype(np.uint64))
            truth[r].update((s * SHARD_WIDTH + pos).tolist())
        f._note_shard(s)
    ex = Executor(holder)
    from pilosa_tpu.runtime import resultcache as _resultcache

    rc_was = _resultcache.cache().enabled
    ct.retain()  # baseline-snapshot the [containers] config we flip
    _resultcache.cache().enabled = False  # measure the dispatch path
    q = "Count(Intersect(Row(f=10), Row(f=11)))"
    expect = len(truth[10] & truth[11])
    q_us = "Count(Intersect(Row(f=20), Row(f=21)))"
    expect_us = len(truth[20] & truth[21])

    def timed(seconds: float, query: str = q,
              want: int | None = None) -> float:
        want = expect if want is None else want
        got = int(ex.execute("i", query)[0])  # warm + verify
        if got != want:
            raise AssertionError(f"containers bench: {got} != {want}")
        n = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            if int(ex.execute("i", query)[0]) != want:
                raise AssertionError("containers bench: drift mid-run")
            n += 1
        return n / (time.perf_counter() - t0)

    try:
        ct.configure(enabled=True, kinds=True)
        ct.reset_counters()
        qps_compressed = timed(1.0)
        gathered = ct.counters()["container.containers_gathered"]
        queries = max(1, ct.counters()["container.queries"])
        # THIS workload's pooled leaves, not the process-wide kind
        # split (earlier bench phases leave their own residency
        # behind); the /debug/devices residency.kinds gauge carries
        # the live total
        compressed_bytes = sum(
            f.device_container_leaf(r, tuple(range(CT_SHARDS))).nbytes
            for r in (10, 11))
        assert (residency.manager().stats().get("kinds") or {}).get(
            "compressed", 0) >= compressed_bytes
        # ultra-sparse leg (~0.1% fill): per-kind pools vs the
        # dense-kind compressed path (kinds=false — every non-empty
        # container a full 2048-word block).  The bytes ratio is the
        # array-kind capacity story; the 1%-leg qps pin below guards
        # against the kinds dispatch costing throughput
        qps_us_kinds = timed(1.0, q_us, expect_us)
        us_kinds_bytes = sum(
            f.device_container_leaf(r, tuple(range(CT_SHARDS))).nbytes
            for r in (20, 21))
        ct.configure(kinds=False)
        qps_nokinds = timed(1.0)           # 1%-fill leg, kinds off
        qps_us_nokinds = timed(1.0, q_us, expect_us)
        us_nokinds_bytes = sum(
            f.device_container_leaf(r, tuple(range(CT_SHARDS))).nbytes
            for r in (20, 21))
        ct.configure(kinds=True)
        ct.configure(enabled=False)
        qps_dense = timed(1.0)
    finally:
        # restore the pre-bench [containers] baseline and the result
        # cache, and close the holder, no matter which phase raised
        ct.release()
        _resultcache.cache().enabled = rc_was
        holder.close()
    # dense layout residency for the same two leaves: 2 row stacks of
    # [shards, words] uint32
    dense_bytes = 2 * CT_SHARDS * WORDS * 4
    per_query_compressed = gathered / queries * ct.CWORDS * 4
    out = {
        "fill": FILL,
        "shards": CT_SHARDS,
        "qps_compressed": round(qps_compressed, 2),
        "qps_dense": round(qps_dense, 2),
        "speedup": round(qps_compressed / qps_dense, 2),
        "resident_bytes_dense": dense_bytes,
        "resident_bytes_compressed": compressed_bytes,
        "bytes_ratio": round(dense_bytes / max(1, compressed_bytes), 1),
        # bytes the compressed launch actually streams per query vs
        # the dense layout's full-stack read
        "achieved_gbps_compressed": round(
            qps_compressed * per_query_compressed / 1e9, 2),
        "achieved_gbps_dense": round(
            qps_dense * dense_bytes / 1e9, 2),
        # acceptance pins: >=4x lower resident bytes at <=1% fill, and
        # the sparse workload at least matching the dense path
        "pin_bytes_ok": dense_bytes >= 4 * max(1, compressed_bytes),
        "pin_qps_ok": qps_compressed >= 0.95 * qps_dense,
        # ---- per-kind pools (ultra-sparse ~0.1% fill leg) ----
        "ultra_sparse": {
            "fill": FILL_US,
            "qps_kinds": round(qps_us_kinds, 2),
            "qps_nokinds": round(qps_us_nokinds, 2),
            "resident_bytes_kinds": us_kinds_bytes,
            "resident_bytes_nokinds": us_nokinds_bytes,
            "bytes_ratio": round(
                us_nokinds_bytes / max(1, us_kinds_bytes), 1),
            # acceptance pins: array/run pools >=5x smaller than the
            # dense-kind compressed path at ~0.1% fill, and kinds
            # dispatch not costing throughput on the 1%-fill leg
            "pin_bytes_ok": us_nokinds_bytes >= 5 * max(
                1, us_kinds_bytes),
            "pin_qps_ok": qps_compressed >= 0.95 * qps_nokinds,
            "qps_1pct_kinds": round(qps_compressed, 2),
            "qps_1pct_nokinds": round(qps_nokinds, 2),
        },
    }
    return out


def bench_vm() -> dict | None:
    """Bitmap-VM A/B (ops/pallas_kernels.vm_counts + the coalescer's
    "vm" buckets): the SAME heterogeneous 16-distinct-shape sparse
    Count mix served closed-loop through the coalescer twice — once
    with the VM routing eligible buckets through the one
    scalar-prefetch kernel over compressed container pools, once with
    ``?novm`` semantics (the pre-VM engines: dense gather + the XLA
    tape interpreter).  Every completed query is verified against a
    host-computed expected count.

    The reported pin is the no-regression floor ``pin_vm_qps_ok``
    (vm qps >= 0.9x the pre-VM path on this host); the chip target —
    beat the XLA route's committed 1801 qps / 0.148 bw_util capture —
    rides the chip-capture slot (tools/chipcapture.py)."""
    import statistics
    import tempfile
    import threading

    from pilosa_tpu import stats as _stats
    from pilosa_tpu.models.holder import Holder
    from pilosa_tpu.ops import bitmap as bm
    from pilosa_tpu.ops import containers as ct
    from pilosa_tpu.ops import tape as _tape
    from pilosa_tpu.parallel.coalescer import Coalescer
    from pilosa_tpu.parallel.executor import ExecOptions, Executor
    from pilosa_tpu.runtime import resultcache as _resultcache
    from pilosa_tpu.shardwidth import SHARD_WIDTH
    from tools.loadgen import shape_mix_queries

    if bm.n_words(SHARD_WIDTH) != WORDS:
        return None
    VM_SHARDS = 32
    FILL = 0.01
    bits_per_row = int(FILL * SHARD_WIDTH)
    rng = np.random.default_rng(12350)
    holder = Holder(tempfile.mkdtemp() + "/bench-vm")
    idx = holder.create_index("i")
    f = idx.create_field("f")
    view = f.create_view_if_not_exists("standard")
    exist: dict[int, set] = {}
    for s in range(VM_SHARDS):
        frag = view.create_fragment_if_not_exists(s)
        # clustered sparsity: each row's bits confined to the first
        # two containers (the roaring-shaped rows the VM gathers)
        for r in range(6):
            pos = np.unique(rng.choice(
                1 << 17, size=bits_per_row, replace=False))
            frag.import_positions(
                (r * SHARD_WIDTH + pos).astype(np.uint64))
            exist.setdefault(s, set()).update(pos.tolist())
        f._note_shard(s)
    for s, cols in exist.items():
        arr = np.fromiter(cols, dtype=np.int64) + s * SHARD_WIDTH
        idx.import_existence(arr)
    ex = Executor(holder)
    stats = _stats.MemStatsClient()
    ex.coalescer = Coalescer(window_s=0.010, max_batch=32,
                             enabled=True, stats=stats)
    rc_was = _resultcache.cache().enabled
    _resultcache.cache().enabled = False
    qs = shape_mix_queries(16, field="f", rows=6)
    # mesh off in both legs: the VM is a single-device kernel, and the
    # A/B must differ only in the ?novm bit
    vm_on = ExecOptions(mesh=False)
    vm_off = ExecOptions(mesh=False, vm=False)

    def ground_truth(q):
        ex.fuse_shards = False
        try:
            return int(ex.execute("i", q)[0])
        finally:
            ex.fuse_shards = True

    expects = [ground_truth(q) for q in qs]
    THREADS = 16

    def phase(opt, seconds: float) -> dict:
        for q, want in zip(qs, expects):  # warm + verify
            got = int(ex.execute("i", q, opt=opt)[0])
            if got != want:
                raise AssertionError(
                    f"vm bench warm-up mismatch: {q} -> {got}, "
                    f"expected {want}")
        lats: list[list[int]] = [[] for _ in range(THREADS)]
        errs: list = []
        t0 = time.perf_counter()
        stop = t0 + seconds

        def worker(t: int) -> None:
            i = t
            try:
                while time.perf_counter() < stop:
                    v = i % len(qs)
                    tq = time.perf_counter_ns()
                    got = int(ex.execute("i", qs[v], opt=opt)[0])
                    lats[t].append(time.perf_counter_ns() - tq)
                    if got != expects[v]:
                        raise AssertionError(
                            f"vm bench returned {got}, expected "
                            f"{expects[v]} for {qs[v]}")
                    i += THREADS
            except BaseException as e:  # noqa: BLE001 — fail loudly
                errs.append(e)

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(THREADS)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        if errs:
            raise errs[0]
        flat = [x for per in lats for x in per]
        return {
            "p50_us": round(statistics.median(flat) / 1e3, 1),
            "queries": len(flat),
            "qps": round(len(flat) / seconds, 1),
        }

    try:
        c0 = dict(_tape.counters())
        with_vm = phase(vm_on, 1.2)
        c1 = dict(_tape.counters())
        without = phase(vm_off, 1.2)
        c2 = dict(_tape.counters())
    finally:
        _resultcache.cache().enabled = rc_was
        holder.close()
    vm_q = c1["vm.queries"] - c0["vm.queries"]
    vm_x = c1["vm.executions"] - c0["vm.executions"]
    out = {
        "shape_mix": 16,
        "fill": FILL,
        "shards": VM_SHARDS,
        "vm": with_vm,
        "novm": without,
        "speedup": round(with_vm["qps"] / max(1.0, without["qps"]), 2),
        "vm_queries": vm_q,
        "vm_executions": vm_x,
        "vm_queries_per_launch": round(vm_q / max(1, vm_x), 2),
        # the pre-VM engines must stay off the VM leg's counters and
        # vice versa: the off leg's executions delta is the evidence
        "novm_leaked_vm_launches": c2["vm.executions"]
        - c1["vm.executions"],
        "pin_vm_qps_ok": with_vm["qps"] >= 0.9 * without["qps"],
    }
    if not out["pin_vm_qps_ok"]:
        print(f"bench: bitmap-VM qps {with_vm['qps']:.0f} fell below "
              f"0.9x the pre-VM path {without['qps']:.0f}",
              file=sys.stderr)
    return out


def bench_residency() -> dict | None:
    """Tiered-residency A/B (runtime/residency.py): the same zipfian
    Count mix measured (a) fully resident — HBM budget far above the
    working set, (b) at a 4x-over-budget working set with the
    predictive prefetcher OFF, and (c) 4x over budget with it ON.

    The pinned number is the STALL RATE: the fraction of queries whose
    flight record shows any non-HBM stack access (an async-promotion
    wait, a host-compute fallback, or a cold rebuild).  Fully resident
    it is ~0 after warmup by construction; at 4x the tier machinery
    absorbs the overflow, and the prefetcher must strictly reduce it
    on the zipfian mix (the hot head gets promoted ahead of demand) —
    ``pin_prefetch_ok``.  Every sample is verified against the
    imported truth (one bit per shard per row -> count == shards)."""
    from pilosa_tpu import observe
    from pilosa_tpu.models.holder import Holder
    from pilosa_tpu.ops import bitmap as bm
    from pilosa_tpu.parallel.executor import ExecOptions, Executor
    from pilosa_tpu.runtime import residency
    from pilosa_tpu.runtime.prefetch import Prefetcher
    from pilosa_tpu.shardwidth import SHARD_WIDTH

    SHARDS = 8
    stack_bytes = SHARDS * bm.n_words(SHARD_WIDTH) * 4
    budget = 8 * stack_bytes + (64 << 10)   # ~8 resident row stacks
    n_rows = 32                              # 4x the budget
    rng = np.random.default_rng(12349)
    holder = Holder(None)
    idx = holder.create_index("i")
    f = idx.create_field("f")
    for row in range(n_rows):
        cols = np.arange(SHARDS, dtype=np.int64) * SHARD_WIDTH + row
        f.import_bits(np.full(SHARDS, row), cols)
    ex = Executor(holder)
    # zipfian row schedule, fixed across all three legs
    weights = [1.0 / (r + 1) ** 1.2 for r in range(n_rows)]
    zrng = np.random.default_rng(4242)
    schedule = zrng.choice(n_rows, size=4096,
                           p=np.array(weights) / sum(weights))

    def leg(seconds: float) -> dict:
        n = 0
        stalled = 0
        stall_ms = 0.0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            row = int(schedule[n % len(schedule)])
            got = ex.execute(
                "i", f"Count(Row(f={row}))",
                opt=ExecOptions(cache=False, containers=False))[0]
            if got != SHARDS:
                raise AssertionError(
                    f"residency bench: row {row}: {got} != {SHARDS}")
            rec = observe.take_last()
            tier = (rec.to_dict().get("tier") or {}) if rec else {}
            if (tier.get("promoted", 0) or tier.get("fallback", 0)
                    or tier.get("cold", 0)):
                stalled += 1
                stall_ms += tier.get("stallMs", 0.0)
            n += 1
        dt = time.perf_counter() - t0
        return {"qps": round(n / dt, 2), "queries": n,
                "stall_rate": round(stalled / max(1, n), 4),
                "stall_ms_total": round(stall_ms, 1)}

    def fresh_manager(hbm: int) -> None:
        # a reset ORPHANS entries still sitting in the field's stack
        # cache (they keep hitting, untracked — hiding the budget);
        # clear the owner dicts so every leg restages under its own
        # budget from a cold start
        residency.reset(hbm)
        residency.configure(host_budget_bytes=1 << 30, prefetch=False)
        with f._lock:
            f._row_stack_cache.clear()
            f._matrix_stack_cache.clear()

    try:
        # (a) fully resident
        fresh_manager(64 * stack_bytes)
        leg(0.5)  # warm
        resident = leg(1.0)
        # (b) 4x working set, prefetch off
        fresh_manager(budget)
        leg(1.0)  # populate + demote into steady churn
        off = leg(2.0)
        # (c) 4x working set, prefetch on (same demoted steady state)
        residency.configure(prefetch=True, prefetch_interval=0.005)
        pf = Prefetcher()
        pf.start()
        try:
            leg(1.0)
            on = leg(2.0)
        finally:
            pf.stop()
    finally:
        residency.reset()
        holder.close()
    return {
        "shards": SHARDS,
        "rows": n_rows,
        "budget_bytes": budget,
        "working_set_bytes": n_rows * stack_bytes,
        "working_set_factor": round(n_rows * stack_bytes / budget, 2),
        "resident": resident,
        "overbudget_prefetch_off": off,
        "overbudget_prefetch_on": on,
        # acceptance pins: the prefetcher strictly reduces the stall
        # rate on the zipfian mix, and the fully-resident control is
        # (near-)stall-free after warmup
        "pin_prefetch_ok": on["stall_rate"] < off["stall_rate"],
        "pin_resident_ok": resident["stall_rate"] <= 0.01,
    }


def bench_admission(coalescer_extras: dict | None) -> dict:
    """Admission-layer overhead on the uncontended serving path: the
    gate's acquire+release pair is what every admitted request pays on
    top of execution, so its cost must stay under 1% of the coalesced
    Count path's per-query service time (the [admission] budget).
    Measured directly (one thread, free slots — the uncontended case);
    ``pct_of_query`` is computed against the coalescer benchmark's
    measured per-query time when that ran."""
    from pilosa_tpu import stats as _stats
    from pilosa_tpu.serve.admission import AdmissionController

    ctrl = AdmissionController(stats=_stats.MemStatsClient())
    n = 20000
    ctrl.acquire("query").release()  # warm (lock, stats path)
    t0 = time.perf_counter()
    for _ in range(n):
        ctrl.acquire("query").release()
    cost_us = (time.perf_counter() - t0) / n * 1e6
    out = {"acquire_release_us": round(cost_us, 3), "budget_pct": 1.0}
    if coalescer_extras and coalescer_extras.get("qps"):
        per_query_us = (coalescer_extras.get("threads", 16)
                        / coalescer_extras["qps"] * 1e6)
        out["pct_of_query"] = round(cost_us / per_query_us * 100.0, 3)
    return out


def bench_tenants(coalescer_extras: dict | None) -> dict:
    """[tenants] isolation cost + effect.

    Two measurements: (1) the UNCONTENDED acquire+release pair with
    isolation off vs on — the per-request tax every admitted request
    pays, held to the same <1% budget as the admission/observe gates;
    (2) an abusive-mix A/B at the controller — one tenant flooding
    from 12 threads against a 2-thread victim on a 4-slot class, with
    isolation off vs on — reporting the victim's queue-wait p99 both
    ways (the isolation contract: the victim's wait must not degrade
    with isolation ON vs OFF while the abuser floods)."""
    import threading

    from pilosa_tpu import stats as _stats
    from pilosa_tpu.serve import tenant as _tenant
    from pilosa_tpu.serve.admission import AdmissionController

    out: dict = {"budget_pct": 1.0}
    try:
        n = 20000
        _tenant.reset()
        ctrl = AdmissionController(stats=_stats.MemStatsClient())
        ctrl.acquire("query", tenant="t0").release()  # warm
        t0 = time.perf_counter()
        for _ in range(n):
            ctrl.acquire("query", tenant="t0").release()
        off_us = (time.perf_counter() - t0) / n * 1e6
        _tenant.configure(enabled=True,
                          quotas={"t0": {"share": 8, "queue": 32}})
        ctrl.acquire("query", tenant="t0").release()  # warm tenant path
        t0 = time.perf_counter()
        for _ in range(n):
            ctrl.acquire("query", tenant="t0").release()
        on_us = (time.perf_counter() - t0) / n * 1e6
        out["acquire_release_us_off"] = round(off_us, 3)
        out["acquire_release_us_on"] = round(on_us, 3)
        out["added_us"] = round(on_us - off_us, 3)
        if coalescer_extras and coalescer_extras.get("qps"):
            per_query_us = (coalescer_extras.get("threads", 16)
                            / coalescer_extras["qps"] * 1e6)
            out["pct_of_query"] = round(
                max(0.0, on_us - off_us) / per_query_us * 100.0, 3)

        def abusive(iso: bool) -> dict:
            _tenant.reset()
            if iso:
                _tenant.configure(
                    enabled=True, default_share=1, default_queue=8,
                    quotas={"victim": {"share": 3, "queue": 32},
                            "abuser": {"share": 1, "queue": 64}})
            c = AdmissionController(query_cap=4, query_queue=128,
                                    stats=_stats.MemStatsClient())
            waits: dict = {"victim": [], "abuser": []}
            shed = {"victim": 0, "abuser": 0}
            lock = threading.Lock()
            stop = time.perf_counter() + 0.75

            def client(name: str):
                from pilosa_tpu.serve.admission import ShedError

                while time.perf_counter() < stop:
                    try:
                        tk = c.acquire("query", tenant=name)
                    except ShedError:
                        with lock:
                            shed[name] += 1
                        time.sleep(0.001)
                        continue
                    with lock:
                        waits[name].append(tk.queue_wait_ns / 1e6)
                    time.sleep(0.002)  # simulated service time
                    tk.release()

            threads = ([threading.Thread(target=client,
                                         args=("abuser",))
                        for _ in range(12)]
                       + [threading.Thread(target=client,
                                           args=("victim",))
                          for _ in range(2)])
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            vw = sorted(waits["victim"])
            return {
                "victim_ok": len(vw),
                "victim_wait_p99_ms": round(
                    vw[int(0.99 * (len(vw) - 1))] if vw else 0.0, 3),
                "victim_shed": shed["victim"],
                "abuser_ok": len(waits["abuser"]),
                "abuser_shed": shed["abuser"],
            }

        iso_on = abusive(True)
        iso_off = abusive(False)
        out["abusive"] = {
            "isolation_on": iso_on,
            "isolation_off": iso_off,
            # the isolation contract (with margin for scheduler noise)
            "pin_isolation_ok": (
                iso_on["victim_wait_p99_ms"]
                <= max(1.0, 1.5 * iso_off["victim_wait_p99_ms"])),
        }
    finally:
        from pilosa_tpu.serve import tenant as _tenant2

        _tenant2.reset()
    return out


def verify_product_path(a_np: np.ndarray, b_np: np.ndarray,
                        expect: int) -> None:
    """Bit-exactness of the REAL path: the PQL string through the
    executor's fused pipeline must produce the identical count."""
    import tempfile

    from pilosa_tpu.models.holder import Holder
    from pilosa_tpu.ops import bitmap as bm
    from pilosa_tpu.parallel.executor import Executor
    from pilosa_tpu.shardwidth import SHARD_WIDTH

    if bm.n_words(SHARD_WIDTH) != WORDS:
        # benchmark rows are built for the default 2^20-column shards;
        # with a non-default PILOSA_TPU_SHARD_WIDTH_EXP the kernel
        # benchmark above is still valid, so just skip this check
        return

    holder = Holder(tempfile.mkdtemp() + "/bench")
    idx = holder.create_index("i")
    f = idx.create_field("f")
    view = f.create_view_if_not_exists("standard")
    for s in range(N_SHARDS):
        frag = view.create_fragment_if_not_exists(s)
        with frag._lock:
            frag._rows[1] = a_np[s].copy()
            frag._rows[2] = b_np[s].copy()
            frag._bump_gen()
        f._note_shard(s)
    ex = Executor(holder)
    got = int(ex.execute("i", "Count(Intersect(Row(f=1), Row(f=2)))")[0])
    assert got == expect, f"product path mismatch: {got} != {expect}"


def bench_cpu_baseline(a: np.ndarray, b: np.ndarray) -> tuple[float, int]:
    """Serial per-shard AND+popcount, mirroring the reference's single-node
    map-reduce over shards (executor.go:2561 worker loop, one shard at a
    time per worker; we grant the baseline full vectorization per shard)."""
    def query() -> int:
        total = 0
        for s in range(a.shape[0]):
            total += int(np.bitwise_count(a[s] & b[s]).sum(dtype=np.uint64))
        return total

    expect = query()  # warm-up / page-in
    # Best-of-3 minimum-duration loops: the baseline is the denominator
    # of vs_baseline, so noise here swings the headline ratio harder
    # than device noise does.  Taking the BEST repeat is deliberately
    # conservative — it credits the CPU with its least-interrupted run.
    best = 0.0
    for _ in range(3):
        iters = 0
        t0 = time.perf_counter()
        while iters < 3 or time.perf_counter() - t0 < 1.0:
            query()
            iters += 1
        best = max(best, iters / (time.perf_counter() - t0))
    return best, expect


def _peak_gbps(platform: str) -> float | None:
    if platform not in _CHIP_PLATFORMS:
        return None
    import jax

    kind = (jax.devices()[0].device_kind or "").lower().replace(" ", "")
    for gen, peak in _PEAK_GBPS.items():
        if gen in kind:
            return peak
    return None


def bench_faultinject() -> dict:
    """Disarmed-failpoint A/B (the chaos round's <1% budget, same
    discipline as extras.observe/devobs): the per-site disarmed cost
    is one module-bool read — measured directly against an empty-body
    baseline loop, and expressed against the ~20 us dispatch floor the
    serving path is built around.  Armed-pass cost is also reported
    (registry lock + dict probe) for context; it is off the shipping
    path by definition."""
    import time

    from pilosa_tpu import faultinject as fi

    n = 200000

    def loop(body) -> float:
        t0 = time.perf_counter()
        for _ in range(n):
            body()
        return (time.perf_counter() - t0) / n * 1e9  # ns/op

    def disarmed():
        if fi.armed:
            fi.hit("device.dispatch")

    fi.disarm()
    base_ns = loop(lambda: None)
    off_ns = loop(disarmed)
    fi.arm("device.dispatch=delay(0)@1000000000")  # armed, never fires
    try:
        on_ns = loop(disarmed)
    finally:
        fi.disarm()
    gate_ns = max(0.0, off_ns - base_ns)
    return {
        "disarmed_gate_ns": round(gate_ns, 2),
        "armed_pass_ns": round(max(0.0, on_ns - base_ns), 2),
        # share of the 20 us trivial-dispatch floor (VERDICT round 5)
        # — the budget the acceptance criterion pins
        "disarmed_pct_of_dispatch_floor": round(
            gate_ns / 20_000 * 100.0, 4),
        "budget_pct": 1.0,
    }


def bench_traceasm() -> dict:
    """Disarmed event-journal A/B (the autopsy round's <1% budget,
    same discipline as extras.faultinject): the per-site disarmed
    cost is one module-bool read, measured against an empty-body
    baseline loop and expressed against the ~20 us dispatch floor.
    The armed-emit cost (lock + ring append) is reported for context
    — it is paid only at state transitions (breaker flips, hedge
    fires), never per query on the coalesced Count path."""
    import time

    from pilosa_tpu import observe as obs

    n = 200000

    def loop(body) -> float:
        t0 = time.perf_counter()
        for _ in range(n):
            body()
        return (time.perf_counter() - t0) / n * 1e9  # ns/op

    def gated():
        if obs.journal_on:
            pass

    obs.retain()
    try:
        obs.configure(enabled=False)
        base_ns = loop(lambda: None)
        off_ns = loop(gated)
        obs.configure(enabled=True)
        emit_ns = loop(lambda: obs.emit("bench.tick"))
    finally:
        obs.release()  # restores the pre-bench journal baseline
        obs.reset_journal()
    gate_ns = max(0.0, off_ns - base_ns)
    return {
        "disarmed_gate_ns": round(gate_ns, 2),
        "armed_emit_ns": round(max(0.0, emit_ns - base_ns), 2),
        # share of the 20 us trivial-dispatch floor — the budget the
        # acceptance criterion pins (<1% on the coalesced Count path)
        "disarmed_pct_of_dispatch_floor": round(
            gate_ns / 20_000 * 100.0, 4),
        "budget_pct": 1.0,
    }


def main():
    import os

    # tools/chipcapture.py --profile: bracket the whole bench with a
    # device trace (the capture must come from THIS process — the
    # harness wrapping the subprocess would trace nothing)
    prof_dir = os.environ.get("PILOSA_TPU_BENCH_PROFILE")
    prof_info = None
    if prof_dir:
        from pilosa_tpu import perfobs as _perfobs

        try:
            prof_info = _perfobs.profiler_start(prof_dir, max_seconds=0)
        except Exception as e:  # noqa: BLE001 — bench over trace
            prof_info = {"error": f"{type(e).__name__}: {e}"}
    a, b = make_operands(seed=12348)
    cpu_qps, cpu_count = bench_cpu_baseline(a, b)
    (dev_qps, dev_count, platform, engine, qps_by_engine,
     extras) = bench_device(a, b)
    assert dev_count == cpu_count, f"bit-exactness violated: {dev_count} != {cpu_count}"
    verify_product_path(a, b, cpu_count)
    co_obs = bench_coalescer(a, b)
    co = None
    if co_obs is not None:
        co, obs, dv, po = co_obs
        extras["coalescer"] = co
        extras["observe"] = obs
        extras["devobs"] = dv
        extras["perfobs"] = po
    extras["admission"] = bench_admission(co)
    rg = bench_ragged(a, b)
    if rg is not None:
        extras["ragged"] = rg
    rc = bench_resultcache(a, b)
    if rc is not None:
        extras["resultcache"] = rc
    ing = bench_ingest(a, b)
    if ing is not None:
        extras["ingest"] = ing
    ctn = bench_containers()
    if ctn is not None:
        extras["containers"] = ctn
    vmab = bench_vm()
    if vmab is not None:
        extras["vm"] = vmab
    extras["faultinject"] = bench_faultinject()
    extras["traceasm"] = bench_traceasm()
    extras["tenants"] = bench_tenants(co)
    rsd = bench_residency()
    if rsd is not None:
        extras["residency"] = rsd
    bytes_per_query = a.nbytes + b.nbytes  # streamed once per query
    achieved_gbps = dev_qps * bytes_per_query / 1e9
    peak = _peak_gbps(platform)
    # Physics backstop: a memory-bound kernel cannot beat the HBM roof.
    # Variant rotation (see timed_qps) defeats back-to-back
    # memoization of identical dispatches, but a deeper
    # (executable, args) cache would inflate QPS while every sampled
    # count still verifies — so a >roof figure is flagged as a
    # measurement fault in the artifact itself, never recorded as a
    # clean number.
    b32 = extras.get("batch32")
    over_roof = []
    if peak is not None:
        if achieved_gbps > peak:
            over_roof.append(f"single-dispatch {achieved_gbps:.0f} GB/s")
        if isinstance(b32, dict) and b32["achieved_gbps_lower"] > peak:
            over_roof.append(
                f"batch32 {b32['achieved_gbps_lower']:.0f} GB/s")
        if (co is not None
                and co["achieved_gbps_lower"] > peak):
            over_roof.append(
                f"coalescer {co['achieved_gbps_lower']:.0f} GB/s")
    suspect = bool(over_roof)
    if suspect:
        print(f"bench: MEASUREMENT FAULT: {' and '.join(over_roof)} "
              f"exceeds the {peak:.0f} GB/s HBM roof — dispatches "
              "were memoized, not executed; number is NOT trustworthy",
              file=sys.stderr)
    if prof_dir and prof_info is not None and "error" not in prof_info:
        from pilosa_tpu import perfobs as _perfobs

        try:
            prof_info = _perfobs.profiler_stop()
        except Exception as e:  # noqa: BLE001
            prof_info = {"error": f"{type(e).__name__}: {e}"}
    print(json.dumps({
        "metric": "intersect_count_qps_268M_cols",
        "value": round(dev_qps, 2),
        "unit": "qps",
        "vs_baseline": round(dev_qps / cpu_qps, 2),
        "platform": platform,
        "engine": engine,
        "achieved_gbps": round(achieved_gbps, 1),
        "peak_gbps": peak,
        "bw_util": None if peak is None else round(achieved_gbps / peak, 3),
        "engines": {k: round(v, 2) if isinstance(v, float) else v
                    for k, v in qps_by_engine.items()},
        **extras,
        **({"suspect_memoized_dispatch": True} if suspect else {}),
        **({"profile": prof_info} if prof_info is not None else {}),
    }))


if __name__ == "__main__":
    main()


