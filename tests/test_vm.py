"""Pallas bitmap VM: one scalar-prefetch kernel for ragged tapes over
compressed containers (ops/pallas_kernels.vm_counts + ops/tape.execute_vm
+ ops/containers.stage_vm + the parallel/coalescer.py "vm" buckets).

The acceptance surface: randomized bit-exactness of the interpret-mode
Pallas kernel against the host/jnp twins and the naive set oracle
(tests/naive.py), container boundary bits 65535/65536, the serving-path
pins — a heterogeneous 16-distinct-shape sparse megabatch executes as
ONE ``vm`` device launch (deltas off) and at most two (deltas on), the
``?novm=1`` escape routes byte-identical through the pre-VM engines,
the scalar-prefetch budget splits oversized batches into at most one
extra launch — plus the /debug/ragged VM inventory and the ``vm_``
metric-family declaration.

The VM is a single-device kernel: queries here pin ``mesh=False`` (the
conftest's 8-virtual-device platform would otherwise route the mesh
interpreter, which keeps its own launch accounting).
"""

from __future__ import annotations

import json
import random
import tempfile
import urllib.request

import numpy as np
import pytest

from pilosa_tpu import ingest
from pilosa_tpu import stats as _stats
from pilosa_tpu.ingest import compactor
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.ops import bitmap as bm
from pilosa_tpu.ops import containers as ct
from pilosa_tpu.ops import pallas_kernels as pk
from pilosa_tpu.ops import tape
from pilosa_tpu.parallel.coalescer import Coalescer
from pilosa_tpu.parallel.executor import ExecOptions, Executor
from pilosa_tpu.runtime import resultcache
from pilosa_tpu.shardwidth import SHARD_WIDTH
from tests.coalesce_batch import map_behind_launch
from tests.naive import NaiveBitmap

W = SHARD_WIDTH
N_SHARDS = 4

#: ?nomesh + defaults: the VM route under the multi-device test platform.
VMOPT = ExecOptions(mesh=False)
#: the ?novm=1 escape on the same route.
NOVM = ExecOptions(mesh=False, vm=False)


@pytest.fixture(autouse=True)
def _fresh():
    ct.reset()
    ct.reset_counters()
    tape.reset_counters()
    rc = resultcache.cache()
    was = rc.enabled
    rc.enabled = False  # exactness tests must reach the coalescer
    yield
    rc.enabled = was
    ct.reset()


# ---------------------------------------------------------------------------
# Kernel twins: pallas (interpret) vs host vs jnp vs naive
# ---------------------------------------------------------------------------


def _rand_program(rng: random.Random, slots: int, tape_len: int):
    """A random VALID (SSA-ordered) op-tape program row: instruction t
    may reference any leaf slot or any earlier instruction's register."""
    prog = np.zeros((tape_len, 3), dtype=np.int32)
    for t in range(tape_len):
        prog[t, 0] = rng.randrange(5)
        prog[t, 1] = rng.randrange(slots + t)
        prog[t, 2] = rng.randrange(slots + t)
    return prog


def _host_oracle(pool, prog, gidx, q, d):
    """Naive set-algebra twin of one (query, domain-slot) cell."""
    slots, tape_len = gidx.shape[0], prog.shape[1]
    nbits = ct.CWORDS * 32

    def as_naive(words):
        bits = np.unpackbits(
            words.view(np.uint8), bitorder="little")
        return NaiveBitmap(np.flatnonzero(bits), nbits=nbits)

    regs = [as_naive(pool[gidx[s, q, d]]) for s in range(slots)]
    for t in range(tape_len):
        op, a, b = (int(x) for x in prog[q, t])
        xa, xb = regs[a], regs[b]
        regs.append([xa.intersect, xa.union, xa.xor, xa.difference,
                     lambda _b: xa][op](xb))
    return regs[-1].count()


class TestKernelTwins:
    @pytest.mark.parametrize("seed", [1, 2, 3])
    def test_randomized_bit_exact(self, seed):
        rng = random.Random(seed)
        nprng = np.random.default_rng(seed)
        rows = rng.choice([9, 16, 32])
        pool = nprng.integers(0, 1 << 32, size=(rows, pk.CONTAINER_WORDS),
                              dtype=np.uint32)
        pool[rows - 1] = 0  # a canonical zero row
        slots = rng.choice([2, 4])
        tape_len = rng.choice([2, 4])
        B, D = rng.choice([3, 4]), rng.choice([1, 2])
        gidx = nprng.integers(0, rows, size=(slots, B, D)).astype(np.int32)
        prog = np.stack([_rand_program(rng, slots, tape_len)
                         for _ in range(B)])
        host = pk._vm_counts_host(pool, prog, gidx)
        jnpv = np.asarray(pk._vm_counts_jnp(pool, prog, gidx))
        import jax.numpy as jnp

        pal = np.asarray(pk._vm_counts_pallas(
            jnp.asarray(pool), prog, gidx, interpret=True))
        assert np.array_equal(host, jnpv)
        assert np.array_equal(host, pal)
        # spot-check cells against the naive set oracle
        for q, d in [(0, 0), (B - 1, D - 1)]:
            assert host[q, d] == _host_oracle(pool, prog, gidx, q, d)

    def test_dispatcher_routes(self):
        """numpy pool -> host twin; device pool + interpret -> the
        Pallas kernel; both bit-exact."""
        import jax.numpy as jnp

        nprng = np.random.default_rng(7)
        pool = nprng.integers(0, 1 << 32, size=(8, pk.CONTAINER_WORDS),
                              dtype=np.uint32)
        gidx = nprng.integers(0, 8, size=(2, 2, 2)).astype(np.int32)
        prog = np.zeros((2, 4, 3), dtype=np.int32)
        prog[:, :, 0] = tape.OP_COPY
        prog[0, 0] = (tape.OP_AND, 0, 1)
        prog[0, 1:, 1] = 2
        prog[1, 0] = (tape.OP_XOR, 0, 1)
        prog[1, 1:, 1] = 2
        want = pk._vm_counts_host(pool, prog, gidx)
        assert np.array_equal(np.asarray(pk.vm_counts(pool, prog, gidx)),
                              want)
        assert np.array_equal(
            np.asarray(pk.vm_counts(jnp.asarray(pool), prog, gidx,
                                    interpret=True)), want)


# ---------------------------------------------------------------------------
# Serving path
# ---------------------------------------------------------------------------


@pytest.fixture
def ex(tmp_path):
    holder = Holder(str(tmp_path / "h"))
    idx = holder.create_index("i")
    rng = random.Random(424)
    for fi in range(3):
        f = idx.create_field(f"f{fi}")
        rows, cols = [], []
        for row in range(6):
            for _ in range(200):
                rows.append(row)
                cols.append(rng.randrange(N_SHARDS * SHARD_WIDTH))
        f.import_bits(rows, cols)
        idx.import_existence(cols)
    yield Executor(holder)
    holder.close()


def _attach(ex, window_s=2.0, max_batch=16, **kw):
    stats = _stats.MemStatsClient()
    ex.coalescer = Coalescer(window_s=window_s, max_batch=max_batch,
                             enabled=True, stats=stats, **kw)
    return stats


def _unbatched(ex, q):
    """Ground truth: the per-shard path (fusion off, no coalescer)."""
    ex.fuse_shards = False
    try:
        return ex.execute("i", q)[0]
    finally:
        ex.fuse_shards = True


def _run_concurrent(ex, queries, opt=VMOPT):
    """Fire the queries behind a held launch (so they meet in one
    bucket); returns (results, flattened launch
    kinds across all workers — the batch's shared launch ticks the
    leader's thread-local counter only)."""
    def run(i):
        with bm.dispatch_counter() as dc:
            got = ex.execute("i", queries[i], opt=opt)[0]
        return got, dc.launches

    res = map_behind_launch(ex.coalescer, run, len(queries),
                            timeout=120)
    return [r[0] for r in res], [k for r in res for k in r[1]]


#: 16 structurally DISTINCT fused-eligible trees over <= 3 leaves, all
#: landing in the (4, 4) tape size class with deltas off — so the whole
#: mix meets in ONE ("vm", 4, 4) bucket.
SHAPES_16 = (
    ["{0}(Row(f0=1), Row(f1=2))".format(op)
     for op in ("Intersect", "Union", "Difference", "Xor")]
    + ["{0}(Row(f0=3), Row(f1=4), Row(f2=5))".format(op)
       for op in ("Intersect", "Union", "Difference", "Xor")]
    + ["{0}({1}(Row(f0=0), Row(f2=1)), Row(f1=3))".format(o1, o2)
       for o1, o2 in (("Intersect", "Union"), ("Intersect", "Xor"),
                      ("Union", "Intersect"), ("Union", "Difference"),
                      ("Difference", "Union"), ("Difference", "Xor"),
                      ("Xor", "Intersect"), ("Xor", "Union"))]
)


class TestVMServing:
    def test_16_distinct_shapes_one_vm_launch(self, ex):
        """THE acceptance bar: 16 concurrent queries over 16 distinct
        sparse shapes execute as exactly ONE bitmap-VM kernel launch,
        every result bit-exact against per-query host evaluation."""
        qs = [f"Count({t})" for t in SHAPES_16]
        assert len(set(SHAPES_16)) == 16
        expected = [_unbatched(ex, q) for q in qs]
        for q in qs:  # warm directories so staging is cache hits
            ex.execute("i", q, opt=VMOPT)
        tape.reset_counters()
        _attach(ex, window_s=2.0, max_batch=16)
        got, launches = _run_concurrent(ex, qs)
        assert got == expected
        assert launches == ["vm"], launches
        snap = tape.counters()
        assert snap["vm.executions"] == 1
        assert snap["vm.queries"] == 16
        assert snap["vm.fallbacks"] == 0
        recs = [r for r in ex.recorder.recent_records()
                if r.coalesce is not None]
        assert recs and any(r.coalesce.get("vm") for r in recs)

    def test_deltas_on_stays_compressed_bit_exact(self, ex):
        """Pending ingest deltas ride the VM as dfuse leaves (never a
        dense fallback): results bit-exact, <= 2 launches (the delta
        overlays push some tapes into the next size class), all of
        them VM launches."""
        compactor.reset()
        ingest.configure(delta_enabled=True)
        rng = random.Random(99)
        for fi in range(3):
            f = ex.holder.index("i").field(f"f{fi}")
            rows = [rng.randrange(6) for _ in range(64)]
            cols = [rng.randrange(N_SHARDS * SHARD_WIDTH)
                    for _ in range(64)]
            f.import_bits(rows, cols)  # lands in the delta planes
        qs = [f"Count({t})" for t in SHAPES_16]
        expected = [_unbatched(ex, q) for q in qs]
        for q in qs:
            ex.execute("i", q, opt=VMOPT)
        tape.reset_counters()
        _attach(ex, window_s=2.0, max_batch=16)
        got, launches = _run_concurrent(ex, qs)
        assert got == expected
        assert launches and set(launches) == {"vm"}, launches
        assert len(launches) <= 2
        assert tape.counters()["vm.fallbacks"] == 0

    def test_boundary_bits_vs_naive(self, tmp_path):
        """Container boundary bits 65535/65536: bit-exact against the
        naive set oracle through the serving VM path."""
        holder = Holder(str(tmp_path / "b"))
        idx = holder.create_index("i")
        f = idx.create_field("f")
        boundary = [ct.CONTAINER_BITS - 1, ct.CONTAINER_BITS,
                    0, 1, ct.CONTAINER_BITS + 1]
        rows = {1: boundary, 2: [ct.CONTAINER_BITS - 1, 5,
                                 2 * ct.CONTAINER_BITS % (N_SHARDS * W)]}
        naive = {}
        for rid, cols in rows.items():
            cols = [c % (N_SHARDS * W) for c in cols]
            f.import_bits([rid] * len(cols), cols)
            idx.import_existence(cols)
            per = [NaiveBitmap((), nbits=W) for _ in range(N_SHARDS)]
            for c in cols:
                per[c // W] = per[c // W].union(
                    NaiveBitmap([c % W], nbits=W))
            naive[rid] = per
        ex = Executor(holder)
        _attach(ex)
        try:
            for q, want in [
                ("Count(Intersect(Row(f=1), Row(f=2)))",
                 sum(a.intersect(b).count()
                     for a, b in zip(naive[1], naive[2]))),
                ("Count(Union(Row(f=1), Row(f=2)))",
                 sum(a.union(b).count()
                     for a, b in zip(naive[1], naive[2]))),
                ("Count(Difference(Row(f=1), Row(f=2)))",
                 sum(a.difference(b).count()
                     for a, b in zip(naive[1], naive[2]))),
                ("Count(Xor(Row(f=1), Row(f=2)))",
                 sum(a.xor(b).count()
                     for a, b in zip(naive[1], naive[2]))),
            ]:
                with bm.dispatch_counter() as dc:
                    got = int(ex.execute("i", q, opt=VMOPT)[0])
                assert got == want, q
                assert dc.launches == ["vm"], (q, dc.launches)
        finally:
            holder.close()

    def test_novm_routes_pre_vm_engines_byte_identical(self, ex):
        """?novm=1: identical totals, the VM never entered — the
        query routes the pre-existing ragged/fused engines."""
        _attach(ex)
        q = "Count(Intersect(Row(f0=1), Row(f1=2)))"
        base = _unbatched(ex, q)
        tape.reset_counters()
        with bm.dispatch_counter() as dc_off:
            off = ex.execute("i", q, opt=NOVM)[0]
        assert "vm" not in dc_off.launches
        assert tape.counters()["vm.executions"] == 0
        with bm.dispatch_counter() as dc_on:
            on = ex.execute("i", q, opt=VMOPT)[0]
        assert dc_on.launches == ["vm"]
        assert tape.counters()["vm.executions"] == 1
        assert int(on) == int(off) == int(base)

    def test_nocontainers_disables_vm_too(self, ex):
        """?nocontainers=1 implies ?novm=1: the VM executes over
        compressed pools, so disabling the container engine must not
        leave the VM running."""
        _attach(ex)
        tape.reset_counters()
        q = "Count(Union(Row(f0=1), Row(f1=2)))"
        got = ex.execute("i", q,
                         opt=ExecOptions(mesh=False,
                                         containers=False))[0]
        assert tape.counters()["vm.executions"] == 0
        assert int(got) == int(_unbatched(ex, q))

    def test_vm_disabled_coalescer_keeps_tape_routing(self, ex):
        """[vm] enabled=false: the heterogeneous bucket routes the
        pre-VM tape interpreter exactly as before — the production
        off-switch regression pin."""
        qs = [f"Count({t})" for t in SHAPES_16[:6]]
        expected = [_unbatched(ex, q) for q in qs]
        tape.reset_counters()
        _attach(ex, window_s=2.0, max_batch=6, vm=False)
        got, launches = _run_concurrent(ex, qs)
        assert got == expected
        assert "vm" not in launches
        assert tape.counters()["vm.executions"] == 0
        assert tape.counters()["tape.executions"] >= 1

    def test_prefetch_budget_splits_at_most_one_extra_launch(self, ex):
        """A batch whose scalar directory would overflow the SMEM
        prefetch budget recursively halves — the acceptance bar allows
        the one extra launch, and every half stays VM + bit-exact."""
        qs = [f"Count({t})" for t in SHAPES_16[:8]]
        expected = [_unbatched(ex, q) for q in qs]
        for q in qs:
            ex.execute("i", q, opt=VMOPT)
        # each staged query here pads its domain to >= 8 slots over 4
        # leaf slots: 4 slots * 8 queries * 8 domain > 128 forces one
        # recursive split (and only one: each half fits)
        _attach(ex, window_s=2.0, max_batch=8, vm_max_prefetch=128)
        got, launches = _run_concurrent(ex, qs)
        assert got == expected
        assert set(launches) == {"vm"} and len(launches) == 2, launches

    def test_empty_domain_rides_the_batch(self, ex):
        """Disjoint sparse rows: zero work, still ONE VM launch, the
        empty-domain evidence counted — no dispatch-accounting fork."""
        holder = ex.holder
        f = holder.index("i").create_field("lone")
        f.import_bits([1], [3])  # row 1 only in shard 0
        f.import_bits([2], [W + 5])  # row 2 only in shard 1
        _attach(ex)
        tape.reset_counters()
        with bm.dispatch_counter() as dc:
            got = int(ex.execute(
                "i", "Count(Intersect(Row(lone=1), Row(lone=2)))",
                opt=VMOPT)[0])
        assert got == 0
        assert dc.launches == ["vm"], dc.launches
        assert ct.counters()["container.empty_domains"] >= 1

    def test_debug_inventory_and_counters(self, ex):
        _attach(ex)
        tape.reset_counters()
        ex.execute("i", "Count(Intersect(Row(f0=1), Row(f1=2)))",
                   opt=VMOPT)
        d = tape.debug()
        assert d["vm"]["programs"], d
        prog = d["vm"]["programs"][0]
        assert set(prog) == {"batch", "tapeLen", "slots", "domain"}
        # scrape surface: the vm.* counters render as gauges under the
        # declared vm_ family
        gauges = _stats.MemStatsClient()
        tape.publish_gauges(gauges)
        snap = gauges.snapshot()
        assert snap["vm.executions"] == 1
        assert snap["vm.queries"] == 1

    def test_vm_family_declared(self):
        from pilosa_tpu import metricfamilies
        from tools import check_metrics

        fam = {f.name: f for f in metricfamilies.FAMILIES}["vm"]
        assert fam.rendered == "vm_"
        assert "vm_" in check_metrics.TAPE_FAMILIES
        assert "vm_" in check_metrics.ALL_FAMILIES


# ---------------------------------------------------------------------------
# HTTP surface
# ---------------------------------------------------------------------------


class TestHTTP:
    def test_debug_ragged_vm_fields_and_novm_escape(self, tmp_path):
        from pilosa_tpu.server.server import Server

        srv = Server(str(tmp_path / "srv"), port=0,
                     coalescer_enabled=True, ragged_prewarm=False,
                     vm_min_domain=16, vm_max_prefetch=1 << 14)
        srv.open()
        try:
            with urllib.request.urlopen(f"{srv.uri}/debug/ragged",
                                        timeout=10) as resp:
                d = json.loads(resp.read())
            assert d["coalescer"]["vm"] is True
            assert d["coalescer"]["vmMinDomain"] == 16
            assert d["coalescer"]["vmMaxPrefetch"] == 1 << 14
            assert "vm.executions" in d["counters"]
            assert isinstance(d["vm"]["programs"], list)

            srv.api.create_index("i")
            srv.api.create_field("i", "f")
            srv.api.import_bits("i", "f", [1, 1, 2], [3, 70, 70])

            def post(flags):
                req = urllib.request.Request(
                    f"{srv.uri}/index/i/query?nocache=1{flags}",
                    data=b"Count(Intersect(Row(f=1), Row(f=2)))",
                    method="POST")
                with urllib.request.urlopen(req, timeout=30) as resp:
                    return resp.read()

            assert post("&novm=1") == post("")  # byte-identical body
        finally:
            srv.close()

    def test_config_toml_roundtrip(self, tmp_path):
        from pilosa_tpu.config import Config

        cfg = Config()
        cfg.vm.min_domain = 32
        text = cfg.to_toml()
        assert "[vm]" in text and "min-domain = 32" in text
        p = tmp_path / "cfg.toml"
        p.write_text(text)
        cfg2 = Config.load(str(p), env={})
        assert cfg2.vm.enabled is True
        assert cfg2.vm.min_domain == 32
        assert cfg2.vm.max_prefetch == 65536
