"""Pallas kernel tests: interpret-mode runs on CPU diffed against the
jnp reference implementations (the roaring/naive.go oracle pattern)."""

from __future__ import annotations

import numpy as np
import pytest

from pilosa_tpu.ops import bitmap as bm
from pilosa_tpu.ops import pallas_kernels as pk


def _rand_words(rng, *shape):
    return rng.integers(0, 1 << 32, size=shape, dtype=np.uint32)


class TestRowCounts:
    @pytest.mark.parametrize("rows,words", [(1, 64), (7, 100),
                                            (128, 2048), (130, 2049),
                                            (300, 4096)])
    def test_matches_jnp(self, rows, words):
        rng = np.random.default_rng(rows * 1000 + words)
        mat = _rand_words(rng, rows, words)
        filt = _rand_words(rng, words)
        want = np.asarray(bm.row_counts_masked(mat, filt))
        got = np.asarray(pk._row_counts_masked_pallas(mat, filt,
                                                      interpret=True))
        np.testing.assert_array_equal(got, want)

    def test_zero_filter(self):
        mat = _rand_words(np.random.default_rng(0), 8, 256)
        filt = np.zeros(256, dtype=np.uint32)
        got = np.asarray(pk._row_counts_masked_pallas(mat, filt,
                                                      interpret=True))
        assert got.tolist() == [0] * 8

    def test_dispatch_fallback_small(self):
        # tiny inputs use the jnp path regardless of platform
        mat = _rand_words(np.random.default_rng(1), 2, 8)
        filt = _rand_words(np.random.default_rng(2), 8)
        got = np.asarray(pk.row_counts_masked(mat, filt))
        want = np.asarray(bm.row_counts_masked(mat, filt))
        np.testing.assert_array_equal(got, want)


class TestCountAnd:
    @pytest.mark.parametrize("words", [64, 2048, 4096, 5000])
    def test_matches_jnp(self, words):
        rng = np.random.default_rng(words)
        a, b = _rand_words(rng, words), _rand_words(rng, words)
        want = int(bm.popcount_and(a, b))
        got = int(pk._count_and_pallas(a, b, interpret=True))
        assert got == want

    def test_oracle_python_sets(self):
        rng = np.random.default_rng(7)
        pos_a = rng.choice(1 << 16, 500, replace=False)
        pos_b = rng.choice(1 << 16, 500, replace=False)
        a = bm.pack_positions(pos_a, 1 << 16)
        b = bm.pack_positions(pos_b, 1 << 16)
        want = len(set(pos_a) & set(pos_b))
        assert int(pk._count_and_pallas(a, b, interpret=True)) == want


class TestBsiCompare:
    def _planes(self, values, depth, words):
        """Build [2+depth, words] plane stack from {col: value>=0}."""
        P = np.zeros((2 + depth, words * 32), dtype=bool)
        for col, v in values.items():
            P[0, col] = True
            for i in range(depth):
                if (v >> i) & 1:
                    P[2 + i, col] = True
        return np.packbits(P, axis=1, bitorder="little").view(
            np.uint32).reshape(2 + depth, words)

    @pytest.mark.parametrize("depth,pred", [(4, 5), (8, 100), (12, 2048)])
    def test_matches_python_oracle(self, depth, pred):
        rng = np.random.default_rng(depth)
        words = 160
        values = {int(c): int(rng.integers(0, 1 << depth))
                  for c in rng.choice(words * 32, 300, replace=False)}
        planes = self._planes(values, depth, words)
        filt = np.full(words, 0xFFFFFFFF, dtype=np.uint32)
        lt, gt = pk.bsi_compare_unsigned(planes, filt, pred, depth,
                                         interpret=True)
        lt_cols = set(np.asarray(bm.unpack_positions(np.asarray(lt))))
        gt_cols = set(np.asarray(bm.unpack_positions(np.asarray(gt))))
        assert lt_cols == {c for c, v in values.items() if v < pred}
        assert gt_cols == {c for c, v in values.items() if v > pred}

    def test_jnp_fallback_identical(self):
        rng = np.random.default_rng(3)
        depth, words = 6, 160
        values = {int(c): int(rng.integers(0, 1 << depth))
                  for c in rng.choice(words * 32, 100, replace=False)}
        planes = self._planes(values, depth, words)
        filt = np.full(words, 0xFFFFFFFF, dtype=np.uint32)
        lt_p, gt_p = pk._bsi_compare_pallas(
            planes, filt,
            np.array([[0xFFFFFFFF if (9 >> i) & 1 else 0]
                      for i in range(depth)], dtype=np.uint32),
            depth, interpret=True)
        lt_j, gt_j = pk._bsi_compare_jnp(planes, filt, 9, depth)
        np.testing.assert_array_equal(np.asarray(lt_p), np.asarray(lt_j))
        np.testing.assert_array_equal(np.asarray(gt_p), np.asarray(gt_j))

    def test_out_of_range_predicate(self):
        # predicate above 2^depth: everything considered is strictly lt
        depth, words = 4, 160
        values = {10: 3, 50: 15}
        planes = self._planes(values, depth, words)
        filt = np.full(words, 0xFFFFFFFF, dtype=np.uint32)
        lt, gt = pk.bsi_compare_unsigned(planes, filt, 20, depth,
                                         interpret=True)
        lt_cols = set(np.asarray(bm.unpack_positions(np.asarray(lt))))
        assert lt_cols == {10, 50}
        assert int(np.asarray(gt).sum()) == 0

    def test_filter_and_sign_respected(self):
        depth, words = 4, 160
        planes = self._planes({10: 3, 50: 12}, depth, words)
        # column 50 marked negative via the sign plane
        sign = np.zeros(words * 32, dtype=bool)
        sign[50] = True
        planes[1] = np.packbits(sign, bitorder="little").view(
            np.uint32)[:words]
        filt = np.full(words, 0xFFFFFFFF, dtype=np.uint32)
        lt, _ = pk.bsi_compare_unsigned(planes, filt, 100, depth,
                                        interpret=True)
        cols = set(np.asarray(bm.unpack_positions(np.asarray(lt))))
        assert cols == {10}  # negative column excluded from unsigned path


class TestMaskedMatrixCounts:
    @pytest.mark.parametrize("groups,rows,words", [
        (1, 1, 64), (3, 7, 100), (8, 128, 256), (9, 130, 257),
        (17, 200, 512)])
    def test_matches_oracle(self, groups, rows, words):
        rng = np.random.default_rng(groups * 7 + rows)
        mat = _rand_words(rng, rows, words)
        masks = _rand_words(rng, groups, words)
        want = np.bitwise_count(
            mat[None, :, :] & masks[:, None, :]).sum(axis=-1)
        got = np.asarray(pk._mmc_pallas(mat, masks, interpret=True))
        np.testing.assert_array_equal(got, want.astype(np.int32))

    def test_dispatch_wrapper_matches(self):
        import jax.numpy as jnp

        rng = np.random.default_rng(5)
        # device (jnp) inputs above the 2^18 size gate so the wrapper
        # actually takes the Pallas branch (interpret-mode on CPU)
        mat = _rand_words(rng, 300, 512)
        masks = _rand_words(rng, 9, 512)
        got = np.asarray(pk.masked_matrix_counts(
            jnp.asarray(mat), jnp.asarray(masks), interpret=True))
        want = np.asarray(bm.masked_matrix_counts(mat, masks))
        np.testing.assert_array_equal(got, want)
        # below the gate (or host arrays): falls through to bm
        small = np.asarray(pk.masked_matrix_counts(mat[:4], masks[:2],
                                                   interpret=True))
        np.testing.assert_array_equal(
            small, np.asarray(bm.masked_matrix_counts(mat[:4], masks[:2])))

    def test_zero_masks(self):
        mat = np.full((16, 128), 0xFFFFFFFF, dtype=np.uint32)
        masks = np.zeros((4, 128), dtype=np.uint32)
        got = np.asarray(pk._mmc_pallas(mat, masks, interpret=True))
        assert got.sum() == 0


class TestRoutingGate:
    """_use_pallas is the single routing gate all four dispatchers
    share; PILOSA_TPU_PALLAS=0 is the operator escape hatch for a
    Mosaic regression."""

    def test_interpret_always_routes_to_pallas(self, monkeypatch):
        monkeypatch.setattr(pk, "on_tpu", lambda: False)
        assert pk._use_pallas(True, 1)

    def test_small_shapes_stay_on_xla(self, monkeypatch):
        monkeypatch.setattr(pk, "on_tpu", lambda: True)
        # isolate from an ambient operator escape hatch
        monkeypatch.delenv("PILOSA_TPU_PALLAS", raising=False)
        assert not pk._use_pallas(False, (1 << 16) - 1)
        assert pk._use_pallas(False, 1 << 16)

    def test_off_tpu_always_xla(self, monkeypatch):
        monkeypatch.setattr(pk, "on_tpu", lambda: False)
        assert not pk._use_pallas(False, 1 << 30)

    def test_knob_disables_on_tpu(self, monkeypatch):
        monkeypatch.setattr(pk, "on_tpu", lambda: True)
        monkeypatch.setenv("PILOSA_TPU_PALLAS", "0")
        assert not pk._use_pallas(False, 1 << 30)
        monkeypatch.setenv("PILOSA_TPU_PALLAS", "auto")
        assert pk._use_pallas(False, 1 << 30)


def test_pallas_routing_honors_chip_winners(monkeypatch):
    """The dispatch gate routes per-kernel by the committed chip A/B
    (PALLAS_TPU_VALIDATION.json winners): a kernel the chip timed
    slower than XLA's fusion routes to XLA, winners and unmeasured
    kernels route to Pallas, PILOSA_TPU_PALLAS=force/0 override both
    ways (round-5: evidence-driven routing instead of blanket
    on-TPU default)."""
    import pytest

    from pilosa_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "on_tpu", lambda: True)
    monkeypatch.delenv("PILOSA_TPU_PALLAS", raising=False)
    winners = pk._kernel_winners()
    if not winners:
        pytest.skip("no timed chip validation artifact committed")
    assert set(winners.values()) <= {"pallas", "xla"}
    for name, w in winners.items():
        assert pk._use_pallas(False, 1 << 30, kernel=name) \
            == (w != "xla"), (name, w)
    # evidence-free kernels keep the on-TPU default
    assert pk._use_pallas(False, 1 << 30, kernel="not-a-kernel")
    # force re-enables losers (the A/B escape hatch)...
    monkeypatch.setenv("PILOSA_TPU_PALLAS", "force")
    assert all(pk._use_pallas(False, 1 << 30, kernel=n) for n in winners)
    # ...and off disables winners
    monkeypatch.setenv("PILOSA_TPU_PALLAS", "0")
    assert not any(pk._use_pallas(False, 1 << 30, kernel=n)
                   for n in winners)


def _lowering_cases():
    """(id, kernel name, abstract args, static kwargs) for every Pallas
    entry point, at a small shape and at the shape a 256-shard
    (268M-column) index hands it: 32768-word shard rows, 2048-word
    containers, megapools of ~10^5 rows, a full [vm] max-prefetch
    directory."""
    import jax
    import jax.numpy as jnp

    def a(shape, dtype=jnp.uint32):
        return jax.ShapeDtypeStruct(shape, dtype)

    i32, u16 = jnp.int32, jnp.uint16
    cw = pk.CONTAINER_WORDS
    shard_w = 256 * 32768  # 256 shards x 2^20 columns, in words
    return [
        ("row_counts_masked-small", "_row_counts_masked_pallas",
         (a((7, 100)), a((100,))), {}),
        ("row_counts_masked-256shards", "_row_counts_masked_pallas",
         (a((64, shard_w)), a((shard_w,))), {}),
        ("count_and-small", "_count_and_pallas",
         (a((4, 512)), a((4, 512))), {}),
        ("count_and-256shards", "_count_and_pallas",
         (a((256, 32768)), a((256, 32768))), {}),
        ("gathered_count_and-small", "_gathered_count_and_pallas",
         (a((9, cw)), a((5,), i32), a((3, cw)), a((5,), i32)), {}),
        ("gathered_count_and-256shards", "_gathered_count_and_pallas",
         (a((1024, cw)), a((512,), i32), a((1024, cw)),
          a((512,), i32)), {}),
        ("vm_counts-small", "_vm_counts_pallas",
         (a((12, cw)), a((2, 4, 3), i32), a((4, 2, 8), i32)), {}),
        ("vm_counts-256shards", "_vm_counts_pallas",
         (a((131072, cw)), a((16, 8, 3), i32), a((4, 16, 1024), i32)),
         {}),
        ("vm_counts-max-bucket", "_vm_counts_pallas",
         (a((65536, cw)), a((32, 32, 3), i32), a((16, 32, 128), i32)),
         {}),
        ("vm_counts_kinds-small", "_vm_counts_kinds_pallas",
         (a((8, cw)), a((8, 16), u16), a((8,), i32), a((4, 8), u16),
          a((2, 4, 3), i32), a((4, 2, 8), i32)), {}),
        ("vm_counts_kinds-256shards", "_vm_counts_kinds_pallas",
         (a((16384, cw)), a((16384, 4096), u16), a((16384,), i32),
          a((4096, 512), u16), a((16, 8, 3), i32),
          a((4, 16, 1024), i32)), {}),
        ("masked_matrix_counts-small", "_mmc_pallas",
         (a((5, 300)), a((3, 300))), {}),
        ("masked_matrix_counts-256shards", "_mmc_pallas",
         (a((64, shard_w)), a((64, shard_w))), {}),
        ("bsi_compare-small", "_bsi_compare_pallas",
         (a((6, 100)), a((100,)), a((4, 1))), {"depth": 4}),
        ("bsi_compare-256shards", "_bsi_compare_pallas",
         (a((22, shard_w)), a((shard_w,)), a((20, 1))), {"depth": 20}),
    ]


@pytest.mark.parametrize(
    "kernel,args,kwargs",
    [pytest.param(k, a, kw, id=i) for i, k, a, kw in _lowering_cases()])
def test_every_pallas_entry_point_lowers_for_tpu(kernel, args, kwargs):
    """Lower each jitted Pallas kernel for the TPU from this CPU host,
    from abstract shapes (no data).  The Pallas TPU lowering rejects
    illegal block shapes here, which is how a `(1, 2048)` block over an
    `(R, 2048)` pool and a `(1, 1)` SMEM output block were found
    without a chip.  It does NOT replace the on-chip validation
    (benchmarks/validate_tpu.py, run by chip_smoke.py): Mosaic's own
    compile — VMEM/SMEM limits, unsupported vector ops — and the
    counts themselves are only checked on a TPU."""
    lowered = getattr(pk, kernel).trace(*args, **kwargs).lower(
        lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text()
