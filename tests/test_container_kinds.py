"""Full roaring parity on device: array and run containers as
first-class citizens of the compressed engine (ops/kindpools.py pools,
ops/containers.py kind-dispatched staging, ops/expr.py
evaluate_gathered_kinds, the pallas_kernels pair-matrix arms).

The acceptance surface: randomized mixed-kind bit-exactness of every
op (Intersect/Union/Xor/Difference, Count and Row roots, deltas off
and on) across the host twin, the XLA twin, the interpret-mode Pallas
VM and the naive set oracle — including all-array, all-run and
cross-kind pairs; the ?nocontainers and kind-selection-disabled routes
byte-identical; the one-launch-per-fused-query dispatch pin on every
arm (including empty domains); per-kind gather counters; the residency
array/run byte breakout; the VM per-reason fallback cells."""

from __future__ import annotations

import random

import numpy as np
import pytest

from pilosa_tpu.ops import bitmap as bm
from pilosa_tpu.ops import containers as ct
from pilosa_tpu.ops import kindpools as kp
from pilosa_tpu.ops import pallas_kernels as pk
from pilosa_tpu.ops import tape
from pilosa_tpu.parallel import meshexec
from pilosa_tpu.parallel.executor import ExecOptions
from pilosa_tpu.pql import parse
from pilosa_tpu.runtime import resultcache as _resultcache
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.storage import roaring
from tests.naive import NaiveBitmap
from tests.test_containers import HOT_BITS, _columns, _mk_holder, _naive

W = SHARD_WIDTH
CB = ct.CONTAINER_BITS
#: kind-dispatched programs are single-device: pin the mesh escape so
#: the conftest's 8-virtual-device platform doesn't route the (legacy
#: all-bitmap) mesh gather instead.
NOMESH = ExecOptions(mesh=False)
DENSE = ExecOptions(containers=False, mesh=False)


@pytest.fixture(autouse=True)
def _fresh_engine():
    ct.reset()
    ct.reset_counters()
    tape.reset_counters()
    was = _resultcache.cache().enabled
    _resultcache.cache().enabled = False
    # kind-dispatched programs are single-device: directory builds
    # keep legacy all-bitmap leaves while a mesh is active, so the
    # conftest's 8-virtual-device platform must stand down for the
    # kinds path to engage at all (tests that want the mesh route
    # re-enable it explicitly)
    mesh_was = meshexec._cfg.enabled
    meshexec.configure(enabled=False)
    yield
    meshexec.configure(enabled=mesh_was)
    _resultcache.cache().enabled = was
    ct.reset()


# ---------------------------------------------------------------------------
# Kind-styled position builders (shard offsets)
# ---------------------------------------------------------------------------


# The test build pins SHARD_WIDTH to 2^16 (conftest), so one shard IS
# one container: per-shard styles are per-container kinds, and the
# 65535/65536 container boundary is the shard boundary.


def _array_style(npr, lo=6000, card=150):
    """Scattered bits in a low window -> array kind (runs ~ card, so
    the interval list never wins)."""
    return np.unique(npr.choice(lo, size=card, replace=False))


def _run_style(span=(1000, 4000)):
    """Two long intervals -> run kind (card can exceed 4096; the
    interval count stays tiny)."""
    return np.unique(np.concatenate(
        [np.arange(span[0], span[1]),
         np.arange(span[1] + 500, span[1] + 700)]))


def _bitmap_style():
    """Alternating bits over a 12000-bit window: card 6000 > 4096 with
    6000 runs -> bitmap kind, while the row stays under the fill-ratio
    hot threshold (HOT_BITS ~ 25% of the shard)."""
    return np.arange(0, 12000, 2)


def _check_kinds(f, row, shard, want):
    quad = f.view("standard").fragment(shard).row_container_kinds(row)
    assert quad is not None
    kinds = set(int(k) for k in quad[3])
    assert kinds == set(want), (row, shard, kinds)


# ---------------------------------------------------------------------------
# kindpools unit surface
# ---------------------------------------------------------------------------


def _rand_blocks(seed, n=24):
    """Dense container blocks spanning all three kinds."""
    npr = np.random.default_rng(seed)
    blocks = np.zeros((n, ct.CWORDS), dtype=np.uint32)
    for i in range(n):
        style = i % 4
        if style == 0:      # array
            offs = npr.choice(CB, size=int(npr.integers(1, 600)),
                              replace=False)
        elif style == 1:    # run
            s = int(npr.integers(0, CB - 9000))
            offs = np.arange(s, s + int(npr.integers(100, 9000)))
        elif style == 2:    # bitmap
            offs = np.arange(0, CB, 2)
        else:               # boundary-heavy array
            offs = np.array([0, 1, 31, 32, 63, 64, CB - 2, CB - 1])
        w64 = np.zeros(1024, dtype=np.uint64)
        np.bitwise_or.at(w64, offs // 64,
                         np.uint64(1) << (offs % 64).astype(np.uint64))
        blocks[i] = w64.view(np.uint32)
    return blocks


class TestKindpools:
    def test_pick_kinds_matches_serializer(self):
        blocks = _rand_blocks(3)
        kinds = kp.pick_kinds(blocks, run_cap=1 << 20)
        for i, w in enumerate(blocks):
            card, runs = roaring.container_stats(w)
            assert int(kinds[i]) == roaring.pick_kind(card, runs), i

    def test_run_cap_demotes_interval_heavy_blocks(self):
        # 300 intervals of 15 bits: card 4500 rules the array out, so
        # the serializer picks run — but past a run_cap of 256 the
        # device demotes the block to bitmap (interval-decode cost)
        offs = np.concatenate([np.arange(s, s + 15)
                               for s in range(0, 30000, 100)])
        w64 = np.zeros(1024, dtype=np.uint64)
        np.bitwise_or.at(w64, offs // 64,
                         np.uint64(1) << (offs % 64).astype(np.uint64))
        block = w64.view(np.uint32).reshape(1, -1)
        assert roaring.pick_kind(4500, 300) == roaring.KIND_RUN
        assert int(kp.pick_kinds(block, run_cap=256)[0]) == kp.KIND_BITMAP
        assert int(kp.pick_kinds(block, run_cap=1000)[0]) == kp.KIND_RUN

    @pytest.mark.parametrize("seed", [1, 2])
    def test_split_pools_decode_twins_roundtrip(self, seed):
        blocks = _rand_blocks(seed)
        kinds = kp.pick_kinds(blocks)
        slots, bblocks, apool, acard, rpool = kp.split_pools(blocks,
                                                            kinds)
        dec_a = kp.decode_array_np(apool, acard)
        dec_r = kp.decode_runs_np(rpool)
        import jax.numpy as jnp

        np.testing.assert_array_equal(
            dec_a, np.asarray(kp.decode_array_jnp(jnp.asarray(apool),
                                                  jnp.asarray(acard))))
        np.testing.assert_array_equal(
            dec_r, np.asarray(kp.decode_runs_jnp(jnp.asarray(rpool))))
        for i in range(len(blocks)):
            k, s = int(kinds[i]), int(slots[i])
            got = {kp.KIND_BITMAP: bblocks, kp.KIND_ARRAY: dec_a,
                   kp.KIND_RUN: dec_r}[k][s]
            np.testing.assert_array_equal(got, blocks[i], err_msg=str(i))

    def test_decoders_accept_empty_pools(self):
        assert kp.decode_array_np(
            np.zeros((0, 4), dtype=np.uint16),
            np.zeros(0, dtype=np.int32)).shape == (0, ct.CWORDS)
        assert kp.decode_runs_np(
            np.zeros((0, 4), dtype=np.uint16)).shape == (0, ct.CWORDS)


class TestPairArmTwins:
    """Host/XLA twins of the pair-matrix count arms vs the set oracle."""

    @pytest.mark.parametrize("seed", range(3))
    def test_array_array(self, seed):
        npr = np.random.default_rng(seed)
        n, cap = 32, 64
        import jax.numpy as jnp

        pools, cards = [], []
        for _ in range(2):
            pool = np.full((n, cap), kp.ARRAY_PAD, dtype=np.uint16)
            card = npr.integers(0, cap + 1, size=n).astype(np.int32)
            for i in range(n):
                v = np.sort(npr.choice(CB, size=int(card[i]),
                                       replace=False)).astype(np.uint16)
                pool[i, :len(v)] = v
            pools.append(pool)
            cards.append(card)
        ia0 = npr.integers(0, n, size=48).astype(np.int32)
        ia1 = npr.integers(0, n, size=48).astype(np.int32)
        host = np.asarray(pk.gathered_count_array_array(
            pools[0], cards[0], ia0, pools[1], cards[1], ia1))
        xla = np.asarray(pk.gathered_count_array_array(
            jnp.asarray(pools[0]), jnp.asarray(cards[0]),
            jnp.asarray(ia0), jnp.asarray(pools[1]),
            jnp.asarray(cards[1]), jnp.asarray(ia1)))
        np.testing.assert_array_equal(host, xla)
        for j in range(len(ia0)):
            s0 = set(pools[0][ia0[j], :cards[0][ia0[j]]].tolist())
            s1 = set(pools[1][ia1[j], :cards[1][ia1[j]]].tolist())
            assert int(host[j]) == len(s0 & s1), j

    @pytest.mark.parametrize("seed", range(3))
    def test_array_bitmap(self, seed):
        npr = np.random.default_rng(100 + seed)
        n, cap = 16, 32
        import jax.numpy as jnp

        apool = np.full((n, cap), kp.ARRAY_PAD, dtype=np.uint16)
        acard = npr.integers(0, cap + 1, size=n).astype(np.int32)
        for i in range(n):
            v = np.sort(npr.choice(CB, size=int(acard[i]),
                                   replace=False)).astype(np.uint16)
            apool[i, :len(v)] = v
        bpool = npr.integers(0, 1 << 32, size=(n, ct.CWORDS),
                             dtype=np.uint32)
        ia = npr.integers(0, n, size=40).astype(np.int32)
        ib = npr.integers(0, n, size=40).astype(np.int32)
        host = np.asarray(pk.gathered_count_array_bitmap(
            apool, acard, ia, bpool, ib))
        xla = np.asarray(pk.gathered_count_array_bitmap(
            jnp.asarray(apool), jnp.asarray(acard), jnp.asarray(ia),
            jnp.asarray(bpool), jnp.asarray(ib)))
        np.testing.assert_array_equal(host, xla)
        for j in range(len(ia)):
            vals = apool[ia[j], :acard[ia[j]]].astype(np.int64)
            w = bpool[ib[j]]
            want = sum(int((w[v >> 5] >> (v & 31)) & 1) for v in vals)
            assert int(host[j]) == want, j


# ---------------------------------------------------------------------------
# Mixed-kind serving: every op, every engine, vs the naive oracle
# ---------------------------------------------------------------------------


def _rand_kind_rows(rng: random.Random, n_shards: int) -> dict:
    """Rows whose containers deliberately span all three kinds plus
    the boundary and full-container edge shapes."""
    npr = np.random.default_rng(rng.randrange(1 << 30))
    rows: dict[int, dict[int, np.ndarray]] = {}
    for r in range(5):
        by_shard = {}
        for s in range(n_shards):
            style = rng.choice(["empty", "array", "run", "bitmap",
                                "longrun", "boundary"])
            if style == "empty":
                continue
            if style == "array":
                pos = npr.choice(W, size=rng.randrange(1, 500),
                                 replace=False)
            elif style == "run":
                st = rng.randrange(W - 9000)
                pos = np.arange(st, st + rng.randrange(40, 9000))
            elif style == "bitmap":
                pos = np.arange(0, 12000, 2)
            elif style == "longrun":
                st = rng.randrange(W - 14000)
                pos = np.arange(st, st + 14000)
            else:  # container(=shard)-boundary bits: first/last offsets
                pos = np.array([0, 1, 77, W - 1])
            by_shard[s] = np.unique(pos)
        rows[r] = by_shard
    return rows


#: (row-root PQL, fold over per-shard naive twins)
_CASES = [
    ("Intersect(Row(f=0), Row(f=1))",
     lambda n: [a.intersect(b) for a, b in zip(n[0], n[1])]),
    ("Union(Row(f=0), Row(f=2))",
     lambda n: [a.union(b) for a, b in zip(n[0], n[2])]),
    ("Xor(Row(f=1), Row(f=3))",
     lambda n: [a.xor(b) for a, b in zip(n[1], n[3])]),
    ("Difference(Row(f=2), Row(f=0))",
     lambda n: [a.difference(b) for a, b in zip(n[2], n[0])]),
    ("Union(Intersect(Row(f=0), Row(f=1)), Row(f=4))",
     lambda n: [a.intersect(b).union(c)
                for a, b, c in zip(n[0], n[1], n[4])]),
]


class TestMixedKindBitExactness:
    @pytest.mark.parametrize("seed", [1, 2, 3])
    def test_every_op_every_engine_vs_naive(self, seed):
        rng = random.Random(seed)
        n_shards = 3
        rows = _rand_kind_rows(rng, n_shards)
        holder, ex, f = _mk_holder(rows, n_shards)
        naive = _naive(rows, n_shards)
        try:
            for q, fold in _CASES:
                want = fold(naive)
                want_cols = {s * W + p for s, b in enumerate(want)
                             for p in b.positions()}
                want_count = sum(b.count() for b in want)
                for root, check in ((q, lambda r: _columns(r)),
                                    (f"Count({q})", int)):
                    kinds_on = ex.execute("i", root, opt=NOMESH)[0]
                    # the mesh route (8 virtual devices): legacy
                    # all-bitmap leaves through the shard_map program
                    meshexec.configure(enabled="auto")
                    mesh = ex.execute("i", root)[0]
                    meshexec.configure(enabled=False)
                    dense = ex.execute("i", root, opt=DENSE)[0]
                    ct.configure(kinds=False)
                    kinds_off = ex.execute("i", root, opt=NOMESH)[0]
                    ct.configure(kinds=True)
                    want_v = (want_count if root.startswith("Count")
                              else want_cols)
                    for name, got in (("kinds", kinds_on),
                                      ("mesh", mesh), ("dense", dense),
                                      ("nokinds", kinds_off)):
                        assert check(got) == want_v, (root, name)
            snap = ct.counters()
            assert snap["container.queries"] > 0
            assert (snap["container.array_gathered"]
                    + snap["container.run_gathered"]
                    + snap["container.bitmap_gathered"]) > 0
        finally:
            holder.close()

    def test_hot_leaf_falls_back_whole_query_exact(self):
        rows = {0: {0: np.arange(HOT_BITS), 1: np.array([5])},
                1: {0: _array_style(np.random.default_rng(0)),
                    1: np.array([5, 6])}}
        holder, ex, f = _mk_holder(rows, 2)
        naive = _naive(rows, 2)
        want = sum(a.intersect(b).count()
                   for a, b in zip(naive[0], naive[1]))
        with bm.dispatch_counter() as dc:
            got = int(ex.execute(
                "i", "Count(Intersect(Row(f=0), Row(f=1)))",
                opt=NOMESH)[0])
        assert got == want
        assert "fused_gather" not in dc.launches  # dense fallback
        assert ct.counters()["container.fallbacks"] >= 1
        holder.close()

    def test_deltas_on_falls_back_then_compacts_kinds(self):
        from pilosa_tpu import ingest

        npr = np.random.default_rng(11)
        rows = {0: {0: _array_style(npr), 1: _run_style()},
                1: {0: _run_style(), 1: _array_style(npr)}}
        holder, ex, f = _mk_holder(rows, 2)
        ingest.configure(delta_enabled=True)
        try:
            frag = f.view("standard").fragment(0)
            delta_pos = np.array([7, 9], dtype=np.uint64)
            frag.import_positions(0 * W + delta_pos)
            assert frag._delta is not None
            naive = _naive(rows, 2)
            n0 = [naive[0][0].union(NaiveBitmap([7, 9], nbits=W)),
                  naive[0][1]]
            want = sum(a.intersect(b).count()
                       for a, b in zip(n0, naive[1]))
            q = "Count(Intersect(Row(f=0), Row(f=1)))"
            with bm.dispatch_counter() as dc:
                got = int(ex.execute("i", q, opt=NOMESH)[0])
            assert got == want  # base ⊕ delta, exact
            assert "fused_gather" not in dc.launches
            frag.flush_delta()
            with bm.dispatch_counter() as dc2:
                got2 = int(ex.execute("i", q, opt=NOMESH)[0])
            assert got2 == want
            assert dc2.launches == ["fused_gather"]  # compressed again
            assert ct.counters()["container.array_gathered"] > 0
        finally:
            ingest.reset()
            holder.close()


# ---------------------------------------------------------------------------
# Arm routing + dispatch pins
# ---------------------------------------------------------------------------


class TestArmRouting:
    def _holder(self, a_rows=2, styles=("array", "array")):
        npr = np.random.default_rng(42)
        mk = {"array": lambda: _array_style(npr),
              "run": _run_style, "bitmap": _bitmap_style}
        rows = {r: {s: mk[styles[r]]() for s in range(2)}
                for r in range(a_rows)}
        holder, ex, f = _mk_holder(rows, 2)
        return rows, holder, ex, f

    def _count_calls(self, monkeypatch, name):
        calls = []
        orig = getattr(pk, name)

        def wrapper(*a, **kw):
            calls.append(1)
            return orig(*a, **kw)

        monkeypatch.setattr(pk, name, wrapper)
        return calls

    def test_all_array_pair_takes_aa_arm(self, monkeypatch):
        rows, holder, ex, f = self._holder()
        for s in range(2):
            _check_kinds(f, 0, s, {kp.KIND_ARRAY})
        calls = self._count_calls(monkeypatch,
                                  "gathered_count_array_array")
        naive = _naive(rows, 2)
        want = sum(a.intersect(b).count()
                   for a, b in zip(naive[0], naive[1]))
        with bm.dispatch_counter() as dc:
            got = int(ex.execute(
                "i", "Count(Intersect(Row(f=0), Row(f=1)))",
                opt=NOMESH)[0])
        assert got == want
        assert calls, "aa arm never dispatched"
        assert dc.n == 1, dc.launches  # ONE launch, pin holds
        assert ct.counters()["container.array_gathered"] > 0
        holder.close()

    def test_cross_kind_pair_takes_ab_arm(self, monkeypatch):
        rows, holder, ex, f = self._holder(styles=("array", "bitmap"))
        _check_kinds(f, 1, 0, {kp.KIND_BITMAP})
        calls = self._count_calls(monkeypatch,
                                  "gathered_count_array_bitmap")
        naive = _naive(rows, 2)
        want = sum(a.intersect(b).count()
                   for a, b in zip(naive[0], naive[1]))
        with bm.dispatch_counter() as dc:
            got = int(ex.execute(
                "i", "Count(Intersect(Row(f=0), Row(f=1)))",
                opt=NOMESH)[0])
        assert got == want
        assert calls, "ab arm never dispatched"
        assert dc.n == 1, dc.launches
        snap = ct.counters()
        assert snap["container.array_gathered"] > 0
        assert snap["container.bitmap_gathered"] > 0
        holder.close()

    def test_run_pair_takes_generic_kinds_launch(self):
        rows, holder, ex, f = self._holder(styles=("run", "run"))
        for s in range(2):
            _check_kinds(f, 0, s, {kp.KIND_RUN})
        naive = _naive(rows, 2)
        want = sum(a.intersect(b).count()
                   for a, b in zip(naive[0], naive[1]))
        with bm.dispatch_counter() as dc:
            got = int(ex.execute(
                "i", "Count(Intersect(Row(f=0), Row(f=1)))",
                opt=NOMESH)[0])
        assert got == want
        assert dc.n == 1, dc.launches
        assert ct.counters()["container.run_gathered"] > 0
        holder.close()

    def test_empty_domain_still_one_dispatch_on_kinds(self):
        npr = np.random.default_rng(5)
        # disjoint shard footprints: every per-shard keyset
        # intersection is empty
        rows = {0: {0: _array_style(npr)},
                1: {1: _run_style()}}
        holder, ex, f = _mk_holder(rows, 2)
        with bm.dispatch_counter() as dc:
            got = int(ex.execute(
                "i", "Count(Intersect(Row(f=0), Row(f=1)))",
                opt=NOMESH)[0])
        assert got == 0
        assert dc.n == 1, dc.launches
        assert ct.counters()["container.empty_domains"] == 1
        holder.close()

    def test_nocontainers_and_nokinds_byte_identical_rows(self):
        npr = np.random.default_rng(6)
        rows = {0: {0: _array_style(npr), 1: _run_style()},
                1: {0: _run_style(span=(500, 2500)),
                    1: _array_style(npr)}}
        holder, ex, f = _mk_holder(rows, 2)
        q = "Union(Row(f=0), Row(f=1))"
        on = ex.execute("i", q, opt=NOMESH)[0]
        off = ex.execute("i", q, opt=DENSE)[0]
        ct.configure(kinds=False)
        legacy = ex.execute("i", q, opt=NOMESH)[0]
        ct.configure(kinds=True)
        for other, name in ((off, "nocontainers"), (legacy, "nokinds")):
            assert set(on.segments) == set(other.segments), name
            for s in on.segments:
                assert np.array_equal(np.asarray(on.segments[s]),
                                      np.asarray(other.segments[s])), \
                    (name, s)
        holder.close()


# ---------------------------------------------------------------------------
# Residency breakout + VM kinds + fallback reasons
# ---------------------------------------------------------------------------


class TestResidencyKinds:
    def test_array_run_bytes_break_out_and_survive_eviction(self):
        from pilosa_tpu.runtime import residency

        npr = np.random.default_rng(8)
        rows = {0: {0: _array_style(npr), 1: _array_style(npr)},
                1: {0: _run_style(), 1: _run_style()}}
        holder, ex, f = _mk_holder(rows, 2)
        res = residency.manager()
        ex.execute("i", "Count(Intersect(Row(f=0), Row(f=1)))",
                   opt=NOMESH)
        kinds = res.stats()["kinds"]
        assert kinds.get("array", 0) > 0, kinds
        assert kinds.get("run", 0) > 0, kinds
        # the sub-pool bytes are an additive breakout of the pool total
        assert kinds["compressed"] >= kinds["array"] + kinds["run"]
        res.evict_all()
        kinds = res.stats()["kinds"]
        assert kinds.get("array", 0) == 0, kinds
        assert kinds.get("run", 0) == 0, kinds
        # re-promotion restores the breakout (the admit path re-charges)
        ex.execute("i", "Count(Intersect(Row(f=0), Row(f=1)))",
                   opt=NOMESH)
        kinds = res.stats()["kinds"]
        assert kinds.get("array", 0) > 0 and kinds.get("run", 0) > 0
        holder.close()


class TestVMKinds:
    def test_vm_serves_kind_leaves_bit_exact(self):
        from pilosa_tpu import perfobs
        from tests.test_vm import _attach

        npr = np.random.default_rng(9)
        rows = {0: {0: _array_style(npr), 1: _run_style()},
                1: {0: _run_style(span=(2000, 5000)),
                    1: _array_style(npr)}}
        holder, ex, f = _mk_holder(rows, 2)
        _attach(ex)
        naive = _naive(rows, 2)
        try:
            for q, want in [
                ("Count(Intersect(Row(f=0), Row(f=1)))",
                 sum(a.intersect(b).count()
                     for a, b in zip(naive[0], naive[1]))),
                ("Count(Xor(Row(f=0), Row(f=1)))",
                 sum(a.xor(b).count()
                     for a, b in zip(naive[0], naive[1]))),
            ]:
                with bm.dispatch_counter() as dc:
                    got = int(ex.execute("i", q, opt=NOMESH)[0])
                assert got == want, q
                assert dc.launches == ["vm"], (q, dc.launches)
            # the kind-split megapool samples as its own engine cell
            engines = {r["engine"] for r in perfobs.debug()["table"]}
            assert "vm_kinds" in engines, engines
            assert ct.counters()["container.array_gathered"] > 0
            assert ct.counters()["container.run_gathered"] > 0
        finally:
            holder.close()

    def test_fallback_reason_cells(self):
        npr = np.random.default_rng(10)
        rows = {0: {0: _array_style(npr), 1: _array_style(npr)},
                1: {0: _array_style(npr), 1: np.array([3, 4])}}
        holder, ex, f = _mk_holder(rows, 2)
        idx = holder.index("i")
        call = parse("Count(Intersect(Row(f=0), Row(f=1)))").calls[0]
        inner = ex._prepare(idx, call.children[0])
        shards = (0, 1)
        try:
            snap0 = dict(tape.counters())
            ct.configure(enabled=False)
            assert ct.stage_vm(inner, shards) is None
            ct.configure(enabled=True)
            assert ct.stage_vm(inner, shards, max_leaves=1) is None
            assert ct.stage_vm(inner, shards,
                               max_prefetch=1) is None
            # min-domain floor alone blows the budget: its own cell
            assert ct.stage_vm(inner, shards, min_domain=1 << 14,
                               max_prefetch=1 << 12) is None
            # a kind byte with no decode arm (forward compatibility)
            leaf = f.device_container_leaf(0, shards)
            assert leaf.has_kinds
            for k in leaf.kinds:
                if k is not None and len(k):
                    k[0] = 7
                    break
            assert ct.stage_vm(inner, shards) is None
            snap = tape.counters()
            for reason in ("disabled", "oversize", "max_prefetch",
                           "min_domain", "kind_unsupported"):
                key = f"vm.fallbacks.{reason}"
                assert snap[key] > snap0.get(key, 0), key
            reasons = tape.debug()["vm"]["fallbackReasons"]
            for reason in ("disabled", "ineligible_leaf",
                           "kind_unsupported", "oversize",
                           "max_prefetch", "min_domain", "mesh_active"):
                assert reason in reasons, reason
        finally:
            holder.close()
