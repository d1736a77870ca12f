#!/usr/bin/env python
"""On-chip Pallas validation: run every Pallas kernel NON-interpreted on
a TPU against its numpy oracle and record pass/fail.

The test suite exercises the kernels with interpret=True and lowers
them for the TPU (tests/test_pallas_kernels.py), which cannot catch a
Mosaic compile failure or a wrong count on the hardware — this script
is the on-chip complement, and `chip_smoke.py` runs it as its last
child:

    python benchmarks/validate_tpu.py

Its pass/fail is the gate: exit 0 only when all seven kernels compile
and agree with their oracles.  Without a TPU it FAILS (exit 2) and
writes nothing.  On a TPU it writes PALLAS_TPU_VALIDATION.json at the
repo root — one entry per kernel with ok/detail and a Pallas-vs-XLA
timing for context (ops/pallas_kernels._kernel_winners reads the
winners); the file is committed as the run wrote it.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
OUT = os.path.join(REPO, "PALLAS_TPU_VALIDATION.json")

def _random_programs(rng, B: int, T: int, L: int) -> np.ndarray:
    """int32[B, T, 3] valid op-tapes: opcode 0..4, operands any
    register written before step t (leaf slots + earlier outputs)."""
    prog = np.zeros((B, T, 3), dtype=np.int32)
    prog[:, :, 0] = rng.integers(0, 5, size=(B, T))
    for t in range(T):
        prog[:, t, 1] = rng.integers(0, L + t, size=B)
        prog[:, t, 2] = rng.integers(0, L + t, size=B)
    return prog


def _kind_pools(rng, n_bitmap: int, n_array: int, n_run: int):
    """(bpool, apool, acard, rpool) with every pool's last row its
    canonical empty row, built by the product splitter from random
    containers of each kind."""
    from pilosa_tpu.ops import kindpools as kp
    from pilosa_tpu.storage.roaring import (KIND_ARRAY, KIND_BITMAP,
                                            KIND_RUN)

    blocks = np.zeros((n_bitmap + n_array + n_run, kp.CWORDS),
                      dtype=np.uint32)
    blocks[:n_bitmap] = rng.integers(0, 1 << 32, size=(n_bitmap, kp.CWORDS),
                                     dtype=np.uint32)
    for i in range(n_bitmap, n_bitmap + n_array):
        vals = rng.choice(1 << 16, size=int(rng.integers(1, 300)),
                          replace=False)
        np.bitwise_or.at(blocks[i], vals >> 5,
                         np.uint32(1) << (vals & 31).astype(np.uint32))
    for i in range(n_bitmap + n_array, len(blocks)):
        bits = np.zeros(1 << 16, dtype=np.uint8)
        for s in rng.choice((1 << 16) - 4096, size=int(rng.integers(1, 6)),
                            replace=False):
            bits[s:s + int(rng.integers(1, 4096))] = 1
        blocks[i] = np.packbits(bits, bitorder="little").view(np.uint32)
    kinds = np.array([KIND_BITMAP] * n_bitmap + [KIND_ARRAY] * n_array
                     + [KIND_RUN] * n_run, dtype=np.uint8)
    _slots, bblocks, apool, acard, rpool = kp.split_pools(blocks, kinds)
    bpool = np.concatenate(
        [bblocks, np.zeros((1, kp.CWORDS), dtype=np.uint32)])
    apool = np.concatenate(
        [apool, np.full((1, apool.shape[1]), kp.ARRAY_PAD, np.uint16)])
    acard = np.concatenate([acard, np.zeros(1, acard.dtype)]).astype(
        np.int32)
    pad = np.zeros((1, rpool.shape[1]), dtype=np.uint16)
    pad[:, 0::2] = 1  # (1, 0): the canonical invalid pair
    rpool = np.concatenate([rpool, pad])
    return bpool, apool, acard, rpool


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"FAIL: no TPU (platform={dev.platform}); the Pallas "
              "kernels are only validated by Mosaic on a chip",
              file=sys.stderr)
        return 2

    import jax.numpy as jnp
    import jax.random as jr

    from pilosa_tpu.ops import bitmap as bm
    from pilosa_tpu.ops import kindpools as kp
    from pilosa_tpu.ops import pallas_kernels as pk

    rng = np.random.default_rng(12348)
    results: dict[str, dict] = {}

    def check(name, fn):
        try:
            fn()
            results[name] = {"ok": True}
            print(f"PASS {name}", flush=True)
        except Exception as e:  # noqa: BLE001 — a compile error or a
            # wrong count is this kernel's verdict, with its message
            results[name] = {"ok": False,
                             "detail": f"{type(e).__name__}: {e}"}
            print(f"FAIL {name}: {type(e).__name__}: {e}", flush=True)

    def words(*shape):
        return rng.integers(0, 1 << 32, size=shape, dtype=np.uint32)

    def _row_counts():
        mat, filt = words(300, 4096), words(4096)
        got = np.asarray(pk._row_counts_masked_pallas(mat, filt))
        want = np.bitwise_count(mat & filt).sum(axis=-1).astype(np.int32)
        np.testing.assert_array_equal(got, want)

    def _count_and():
        a, b = words(1 << 18), words(1 << 18)
        got = int(pk._count_and_pallas(a, b))
        want = int(np.bitwise_count(a & b).sum(dtype=np.uint64))
        assert got == want, (got, want)

    def _bsi_compare():
        depth = 21
        planes, filt = words(2 + depth, 8192), words(8192)
        upred = int(rng.integers(0, 1 << depth))
        # private Pallas entry, NOT the public wrapper: the wrapper
        # routes by committed winners, so after a winner='xla' capture
        # it would compare the jnp fallback against itself and record
        # a vacuous ok while a Mosaic regression hides
        pred_masks = np.array(
            [[0xFFFFFFFF if (upred >> i) & 1 else 0]
             for i in range(depth)], dtype=np.uint32)
        lt, gt = pk._bsi_compare_pallas(
            jnp.asarray(planes), jnp.asarray(filt),
            jnp.asarray(pred_masks), depth)
        wlt, wgt = pk._bsi_compare_jnp(planes, filt, upred, depth)
        np.testing.assert_array_equal(np.asarray(lt), np.asarray(wlt))
        np.testing.assert_array_equal(np.asarray(gt), np.asarray(wgt))

    def _mmc():
        mat, masks = words(200, 1024), words(17, 1024)
        got = np.asarray(pk._mmc_pallas(jnp.asarray(mat),
                                        jnp.asarray(masks)))
        want = np.bitwise_count(
            mat[None, :, :] & masks[:, None, :]).sum(axis=-1)
        np.testing.assert_array_equal(got, want.astype(np.int32))

    # The three scalar-prefetch kernels, each at a toy shape (row counts
    # that are not multiples of the 8-row DMA group, a domain narrower
    # than one 128-lane output block) and at what a 256-shard index
    # hands them (pools of 10^4-10^5 containers, hundreds to thousands
    # of domain slots, a full [vm] max-prefetch directory).

    def _gathered_count_and():
        for R, P in ((9, 5), (1031, 512), (32768, 4096)):
            a, b = words(R, pk.CONTAINER_WORDS), words(R, pk.CONTAINER_WORDS)
            ai = rng.integers(0, R, size=P).astype(np.int32)
            bi = rng.integers(0, R, size=P).astype(np.int32)
            got = np.asarray(pk._gathered_count_and_pallas(
                jnp.asarray(a), jnp.asarray(ai), jnp.asarray(b),
                jnp.asarray(bi)))
            want = np.bitwise_count(a[ai] & b[bi]).sum(axis=-1)
            np.testing.assert_array_equal(got, want.astype(np.int32),
                                          err_msg=f"R={R} P={P}")

    def _vm_counts():
        for R, B, T, L, D in ((13, 2, 4, 4, 8), (32768, 16, 8, 4, 1024),
                              (8192, 32, 32, 16, 128)):
            pool = words(R, pk.CONTAINER_WORDS)
            pool[-1] = 0  # the canonical zero row
            prog = _random_programs(rng, B, T, L)
            gidx = rng.integers(0, R, size=(L, B, D)).astype(np.int32)
            got = np.asarray(pk._vm_counts_pallas(
                jnp.asarray(pool), jnp.asarray(prog), jnp.asarray(gidx)))
            want = pk._vm_counts_host(pool, prog, gidx)
            np.testing.assert_array_equal(
                got, want, err_msg=f"R={R} B={B} T={T} L={L} D={D}")

    def _vm_counts_kinds():
        for nb, na, nr, B, T, L, D in ((3, 5, 4, 2, 4, 4, 8),
                                       (2047, 4095, 1023, 16, 8, 4, 1024)):
            bpool, apool, acard, rpool = _kind_pools(rng, nb, na, nr)
            dense = np.concatenate(
                [bpool, kp.decode_array_np(apool, acard),
                 kp.decode_runs_np(rpool)])
            prog = _random_programs(rng, B, T, L)
            gidx = rng.integers(0, len(dense),
                                size=(L, B, D)).astype(np.int32)
            got = np.asarray(pk._vm_counts_kinds_pallas(
                jnp.asarray(bpool), jnp.asarray(apool), jnp.asarray(acard),
                jnp.asarray(rpool), jnp.asarray(prog), jnp.asarray(gidx)))
            want = pk._vm_counts_host(dense, prog, gidx)
            np.testing.assert_array_equal(
                got, want, err_msg=f"pools={nb}/{na}/{nr} B={B} D={D}")

    check("row_counts_masked", _row_counts)
    check("count_and", _count_and)
    check("bsi_compare_unsigned", _bsi_compare)
    check("masked_matrix_counts", _mmc)
    check("gathered_count_and", _gathered_count_and)
    check("vm_counts", _vm_counts)
    check("vm_counts_kinds", _vm_counts_kinds)

    # --- per-kernel Pallas-vs-XLA timing at executor-realistic shapes:
    # context, and the per-kernel winners ops/pallas_kernels routes by.
    # Operands are generated on the device (jax.random.bits).  Timing
    # rotates 8 distinct variants through a pipelined loop (enqueue N,
    # block once), median of 3 repeats, so no dispatch can be answered
    # by a memoized identical one.

    def timed_us(fn, variants, min_iters=16):
        outs = [fn(*v) for v in variants]
        jax.block_until_ready(outs)  # compile + warm every variant
        meds = []
        for _ in range(3):
            iters = max(min_iters, len(variants))
            t0 = time.perf_counter()
            outs = [fn(*variants[i % len(variants)])
                    for i in range(iters)]
            jax.block_until_ready(outs)
            meds.append((time.perf_counter() - t0) / iters)
        meds.sort()
        return meds[1] * 1e6

    def dvars(key, *shape, n=8):
        ks = jr.split(jr.PRNGKey(key), n)
        return [jr.bits(k, shape, dtype=jnp.uint32) for k in ks]

    def ivars(key, hi, *shape, n=8):
        ks = jr.split(jr.PRNGKey(key), n)
        return [jr.randint(k, shape, 0, hi, dtype=jnp.int32) for k in ks]

    # physics backstop: these kernels are HBM-bound, so a per-call time
    # below streaming the operand bytes at the HBM roof means dispatches
    # were cache hits, not executions — the A/B is then recorded as
    # suspect instead of deciding routing from it
    # (the roof is perfobs' table by device kind; None = kind unknown)
    from pilosa_tpu import perfobs

    peak_gbps = perfobs.device_peak_gbps()

    def ab(name, pallas_fn, xla_fn, variants, bytes_per_call):
        if not results.get(name, {}).get("ok"):
            return
        try:
            p_us = timed_us(pallas_fn, variants)
            x_us = timed_us(xla_fn, variants)
            perf = {
                "pallas_us": round(p_us, 1),
                "xla_us": round(x_us, 1),
                "winner": "pallas" if p_us < x_us else "xla",
            }
            if peak_gbps is not None:
                floor_us = bytes_per_call / (peak_gbps * 1e9) * 1e6
                if min(p_us, x_us) < floor_us:
                    perf["suspect_memoized_dispatch"] = True
                    perf["hbm_floor_us"] = round(floor_us, 1)
            results[name]["perf"] = perf
            print(f"PERF {name}: pallas {p_us:.0f} us vs xla "
                  f"{x_us:.0f} us -> {perf['winner']}"
                  + (" [SUSPECT: beat the HBM roof]"
                     if perf.get("suspect_memoized_dispatch") else ""),
                  flush=True)
        except Exception as e:  # noqa: BLE001 — timings are context;
            # the correctness verdict above already stands
            results[name]["perf"] = {"error": f"{type(e).__name__}: {e}"}
            print(f"PERF {name} failed: {e}", flush=True)

    W = 32768  # one 2^20-column shard in uint32 words
    filt = dvars(99, W, n=1)[0]
    masks = dvars(98, 32, W, n=1)[0]
    planes_depth = 21

    ab("row_counts_masked",
       lambda m: pk._row_counts_masked_pallas(m, filt),
       lambda m: bm.row_counts_masked(m, filt),
       [(v,) for v in dvars(1, 512, W)],
       bytes_per_call=512 * W * 4)
    # count_and at the north-star shape (256 shards' worth of words)
    b_flat = dvars(97, 256 * W, n=1)[0]
    ab("count_and",
       lambda a: pk._count_and_pallas(a, b_flat),
       lambda a: bm.popcount_and(a, b_flat),
       [(v,) for v in dvars(2, 256 * W)],
       bytes_per_call=2 * 256 * W * 4)
    # the private kernels, NOT the public dispatchers — a dispatcher
    # consults pallas_enabled()/on_tpu()/the winners, so both legs
    # could silently time XLA and record a meaningless "winner"
    pred_masks = jnp.asarray(np.array(
        [[0xFFFFFFFF if (123456 >> i) & 1 else 0]
         for i in range(planes_depth)], dtype=np.uint32))
    ab("bsi_compare_unsigned",
       lambda p: pk._bsi_compare_pallas(p, filt, pred_masks,
                                        planes_depth),
       lambda p: pk._bsi_compare_jnp(p, filt, 123456, planes_depth),
       [(v,) for v in dvars(3, 2 + planes_depth, W)],
       bytes_per_call=(2 + planes_depth) * W * 4)
    # the XLA leg must be the dispatcher's REAL fallback
    # (bm.masked_matrix_counts -> lax.map of fused row counts)
    ab("masked_matrix_counts",
       lambda m: pk._mmc_pallas(m, masks),
       lambda m: bm.masked_matrix_counts(m, masks),
       [(v,) for v in dvars(4, 512, W)],
       # true lower bound: each operand streamed once with perfect
       # VMEM reuse of the mask block
       bytes_per_call=(512 + 32) * W * 4)
    # the scalar-prefetch kernels rotate their DIRECTORIES (the pools
    # stay resident, as they do in serving); bytes are the rows the
    # directory names, once each
    CW = pk.CONTAINER_WORDS
    pool_a = dvars(5, 16384, CW, n=1)[0]
    pool_b = dvars(6, 16384, CW, n=1)[0]
    P = 2048
    ab("gathered_count_and",
       lambda ai, bi: pk._gathered_count_and_pallas(pool_a, ai, pool_b, bi),
       lambda ai, bi: bm._jit_gathered_pair_counts(pool_a, ai, pool_b, bi),
       list(zip(ivars(7, 16384, P), ivars(8, 16384, P))),
       bytes_per_call=2 * P * CW * 4)
    B, T, L, D = 16, 8, 4, 512
    prog = jnp.asarray(_random_programs(rng, B, T, L))
    ab("vm_counts",
       lambda g: pk._vm_counts_pallas(pool_a, prog, g),
       lambda g: pk._vm_counts_jnp(pool_a, prog, g),
       [(g,) for g in ivars(9, 16384, L, B, D)],
       bytes_per_call=L * B * D * CW * 4)
    kpools = [jnp.asarray(p) for p in _kind_pools(rng, 2047, 4095, 1023)]
    n_virtual = sum(int(p.shape[0]) for p in
                    (kpools[0], kpools[1], kpools[3]))
    ab("vm_counts_kinds",
       lambda g: pk._vm_counts_kinds_pallas(*kpools, prog, g),
       lambda g: pk._vm_counts_kinds_jnp(*kpools, prog, g),
       [(g,) for g in ivars(10, n_virtual, L, B, D)],
       bytes_per_call=L * B * D * CW * 4)

    payload = {
        "status": "ran",
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "jax": jax.__version__,
        "kernels": results,
        "all_ok": all(r["ok"] for r in results.values()),
    }
    with open(OUT, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    print(json.dumps({"all_ok": payload["all_ok"],
                      "platform": dev.platform,
                      "device_kind": dev.device_kind}))
    return 0 if payload["all_ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
