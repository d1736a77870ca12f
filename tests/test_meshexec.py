"""Mesh-native fused execution (parallel/meshexec.py).

The acceptance configuration is a 4-device CPU mesh: the suite's
virtual 8-CPU-device platform (conftest.py) runs the mesh with
``[mesh] axis-size=4``, and one subprocess leg forces a literal
4-device process (``jax_num_cpu_devices`` equivalent via XLA_FLAGS —
the only way to change a device count, which is fixed at backend
init).  Pins:

- a fused Count over >= 4 shard groups executes as ONE launch
  (dispatch_counter) with operands sharded over the 4 mesh devices
  and a collective reduction (the counts output comes back fully
  replicated across the mesh — only a shard-axis collective can
  produce that from sharded blocks), bit-exact vs host recomputation,
  deltas off AND on;
- ``?nomesh=1`` and ``[mesh] enabled=false`` reproduce the pre-mesh
  single-device path byte-identically, and never share a coalescer
  launch with mesh-routed batchmates;
- the ragged tape interpreter and the compressed container gather
  route the same mesh, bit-exact, one launch each;
- tape.prewarm keys its lowered programs on the actual device layout
  (mesh-shaped variants only under an active mesh).
"""

from __future__ import annotations

import json
import os
import random
import subprocess
import sys
import tempfile
import urllib.request

import numpy as np
import pytest

import jax

from pilosa_tpu.models.holder import Holder
from pilosa_tpu.ops import bitmap as bm
from pilosa_tpu.ops import containers as ct
from pilosa_tpu.ops import expr, tape
from pilosa_tpu.parallel import meshexec
from pilosa_tpu.parallel.executor import ExecOptions, Executor
from pilosa_tpu.runtime import resultcache as _resultcache
from pilosa_tpu.shardwidth import SHARD_WIDTH

W = SHARD_WIDTH
N_SHARDS = 6  # >= 4 shard groups, deliberately NOT an axis multiple


@pytest.fixture(autouse=True)
def _mesh4():
    """Pin the acceptance configuration: a 4-device mesh on the
    8-device test platform.  The result cache is disabled so every
    engine comparison actually executes both engines."""
    meshexec.reset()
    meshexec.reset_counters()
    meshexec.configure(axis_size=4)
    enabled = _resultcache.cache().enabled
    _resultcache.cache().enabled = False
    yield
    _resultcache.cache().enabled = enabled
    meshexec.reset()


def _mk(seed: int = 0, n_bits: int = 1500):
    holder = Holder(tempfile.mkdtemp() + "/mesh")
    idx = holder.create_index("i")
    f = idx.create_field("f")
    rng = random.Random(seed)
    oracle: dict[int, set] = {1: set(), 2: set(), 3: set()}
    rows, cols = [], []
    for r in oracle:
        for _ in range(n_bits):
            c = rng.randrange(N_SHARDS * W)
            rows.append(r)
            cols.append(c)
            oracle[r].add(c)
    # force overlap so Intersect is non-trivial
    both = rng.sample(sorted(oracle[1]),
                      min(200, len(oracle[1]) // 2))
    rows += [2] * len(both)
    cols += both
    oracle[2].update(both)
    f.import_bits(rows, cols)
    return holder, Executor(holder), f, oracle


class TestConfig:
    def test_resolve_enabled(self):
        assert meshexec.resolve_enabled(True) is True
        assert meshexec.resolve_enabled("false") is False
        assert meshexec.resolve_enabled("auto") is True  # 8 devices
        with pytest.raises(ValueError):
            meshexec.resolve_enabled("ture")

    def test_axis_clamp_and_tokens(self):
        assert meshexec.axis_size() == 4
        assert meshexec.placement_token() == ("mesh", 4)
        assert meshexec.placement_token(use_mesh=False) == "dev"
        meshexec.configure(axis_size=64)  # clamped to local devices
        assert meshexec.axis_size() == len(jax.local_devices())
        meshexec.configure(enabled=False)
        assert meshexec.axis_size() == 1
        assert meshexec.active_mesh() is None
        assert meshexec.placement_token() == "dev"

    def test_retain_release_baseline(self):
        meshexec.retain()
        meshexec.configure(enabled=False, axis_size=2)
        meshexec.retain()
        meshexec.release()
        assert meshexec.config().axis_size == 2  # sibling still holds
        meshexec.release()
        assert meshexec.config().axis_size == 4  # baseline restored
        assert meshexec.config().enabled == "auto"

    def test_pad_domain_axis_multiple(self):
        assert meshexec.pad_domain(1) == 4
        assert meshexec.pad_domain(5) == 8
        assert meshexec.pad_domain(8) == 8
        meshexec.configure(enabled=False)
        assert meshexec.pad_domain(5) == 8  # plain pow2 with mesh off

    def test_shard_plan_blocks(self):
        plan = meshexec.shard_plan(N_SHARDS)
        assert len(plan) == 4
        # 6 shards pad to 8 rows -> 2 rows per device, contiguous
        assert [p["rows"] for p in plan] == [[0, 2], [2, 4],
                                             [4, 6], [6, 8]]
        assert plan[2]["shards"] == [4, 6]
        assert plan[3]["shards"] == []  # pure padding rows


class TestFusedMesh:
    """THE acceptance pin: one launch, sharded operands, collective
    reduction, bit-exact, escapes byte-identical."""

    Q = "Count(Union(Intersect(Row(f=1), Row(f=2)), Row(f=3)))"

    def _want(self, oracle):
        return len((oracle[1] & oracle[2]) | oracle[3])

    def test_one_launch_sharded_collective_bit_exact(self):
        holder, ex, f, oracle = _mk()
        try:
            with bm.dispatch_counter() as dc:
                got = ex.execute("i", self.Q)[0]
            assert dc.n == 1, dc.launches
            assert got == self._want(oracle)
            # operands sharded over exactly the 4 mesh devices
            stack = f.device_row_stack(1, tuple(range(N_SHARDS)))
            assert len(stack.sharding.device_set) == 4
            assert stack.shape[0] == 8  # 6 shards pad to the axis
            # the launch routed the mesh program
            c = meshexec.counters()
            assert c["mesh.launches"] >= 1
            # collective-reduction pin: the counts output of the mesh
            # program is FULLY REPLICATED across the mesh — from
            # sharded blocks only a shard-axis collective (the tiled
            # all_gather) can produce that
            from pilosa_tpu.pql import parse as pql_parse

            call = pql_parse(self.Q).calls[0].children[0]
            idx = holder.index("i")
            shape, leaves = ex._fused_expr(idx, ex._prepare(idx, call),
                                           tuple(range(N_SHARDS)))
            m = meshexec.active_mesh()
            out = expr.evaluate(shape, leaves, counts=True, mesh=m)
            assert len(out.sharding.device_set) == 4
            assert out.sharding.is_fully_replicated
            assert int(np.asarray(out, dtype=np.int64).sum()) == \
                self._want(oracle)
        finally:
            holder.close()

    def test_deltas_on_bit_exact_one_launch(self):
        from pilosa_tpu import ingest

        holder, ex, f, oracle = _mk(seed=3)
        try:
            ingest.configure(delta_enabled=True)
            # pending delta writes on a queried row: mesh route must
            # fuse the overlay (dfuse leaves) in the same one launch
            f.set_bit(1, 5 * W + 17)
            oracle[1].add(5 * W + 17)
            some = sorted(oracle[2])[0]
            f.clear_bit(2, some)
            oracle[2].discard(some)
            frag = f.view("standard").fragment(5)
            assert frag is not None and frag._delta is not None
            with bm.dispatch_counter() as dc:
                got = ex.execute("i", self.Q)[0]
            assert dc.n == 1, dc.launches
            assert got == self._want(oracle)
            # and identical with deltas compacted up front (?nodelta)
            got_nd = ex.execute("i", self.Q,
                                opt=ExecOptions(delta=False))[0]
            assert got_nd == got
        finally:
            ingest.reset()
            holder.close()

    def test_nomesh_and_disabled_byte_identical(self):
        holder, ex, f, oracle = _mk(seed=4)
        try:
            want = self._want(oracle)
            got_mesh = ex.execute("i", self.Q)[0]
            fb0 = meshexec.counters()["mesh.fallbacks"]
            l0 = meshexec.counters()["mesh.launches"]
            with bm.dispatch_counter() as dc:
                got_nm = ex.execute("i", self.Q,
                                    opt=ExecOptions(mesh=False))[0]
            assert dc.n == 1  # same single launch, pre-mesh program
            assert got_nm == got_mesh == want
            c = meshexec.counters()
            assert c["mesh.fallbacks"] == fb0 + 1
            assert c["mesh.launches"] == l0  # never routed the mesh
            # process-wide disable: single-device placement + the
            # same byte-identical result
            meshexec.configure(enabled=False)
            got_off = ex.execute("i", self.Q)[0]
            assert got_off == want
            stack = f.device_row_stack(1, tuple(range(N_SHARDS)))
            assert len(stack.sharding.device_set) == 1
            assert stack.shape[0] == N_SHARDS  # no axis padding
        finally:
            holder.close()

    def test_row_result_matches_oracle(self):
        holder, ex, f, oracle = _mk(seed=5)
        try:
            with bm.dispatch_counter() as dc:
                row = ex.execute(
                    "i", "Intersect(Row(f=1), Row(f=2))")[0]
            assert dc.n == 1
            assert sorted(row.columns()) == sorted(oracle[1] & oracle[2])
            row_nm = ex.execute("i", "Intersect(Row(f=1), Row(f=2))",
                                opt=ExecOptions(mesh=False))[0]
            assert sorted(row_nm.columns()) == sorted(row.columns())
        finally:
            holder.close()


class TestTapeMesh:
    def test_tape_batch_one_launch_bit_exact(self):
        """A heterogeneous tape batch over mesh-sharded stacks: one
        launch, results bit-exact vs the host interpreter."""
        m = meshexec.active_mesh()
        rng = np.random.default_rng(9)
        S = 8  # axis multiple
        host_leaves = [rng.integers(0, 1 << 32, size=(S, 64),
                                    dtype=np.uint32) for _ in range(3)]
        dev_leaves = [meshexec.ensure_placed(
            jax.numpy.asarray(lv), m, 0) for lv in host_leaves]
        shapes = [
            ("and", ("leaf", 0), ("leaf", 1)),
            ("or", ("leaf", 0), ("leaf", 1), ("leaf", 2)),
            ("andnot", ("leaf", 0), ("leaf", 2)),
        ]
        batch, host_batch = [], []
        for sh in shapes:
            tp = tape.compile_shape(sh, 3, None)
            batch.append((tp, tuple(dev_leaves)))
            host_batch.append((tp, tuple(host_leaves)))
        with bm.dispatch_counter() as dc:
            got = tape.execute(batch, counts=True, mesh=m)
        assert dc.n == 1
        want = tape.execute(host_batch, counts=True)
        for g, w in zip(got, want):
            assert np.array_equal(np.asarray(g), np.asarray(w))
        # and bitmap roots stay sharded on the mesh
        got_rows = tape.execute(batch, counts=False, mesh=m)
        want_rows = tape.execute(host_batch, counts=False)
        for g, w in zip(got_rows, want_rows):
            assert len(g.sharding.device_set) == 4
            assert np.array_equal(np.asarray(g), np.asarray(w))

    def test_prewarm_keys_on_device_layout(self, monkeypatch):
        """The prewarm satellite: lowered interpreter programs key on
        the ACTUAL device layout — no mesh => no mesh-shaped
        programs; an active mesh => shard_map variants."""
        monkeypatch.setattr(tape, "_prewarm_worthwhile", lambda: True)
        tape._programs.clear()
        tape.reset_counters()
        n = tape.prewarm((8, 64), max_batch=4, max_tape=4,
                         max_leaves=4, mesh=None)
        assert n > 0
        assert all(isinstance(k, bool) for k in tape._programs), (
            "a no-mesh process lowered mesh-shaped programs",
            list(tape._programs))
        tape._programs.clear()
        m = meshexec.active_mesh()
        n = tape.prewarm((8, 64), max_batch=4, max_tape=4,
                         max_leaves=4, mesh=m)
        assert n > 0
        assert all(isinstance(k, tuple) and k[1] is m
                   for k in tape._programs), list(tape._programs)
        tape._programs.clear()
        # a stack that cannot shard over the axis falls back to the
        # single-device programs rather than erroring
        n = tape.prewarm((5, 64), max_batch=2, max_tape=4,
                         max_leaves=4, mesh=m)
        assert n > 0
        assert all(isinstance(k, bool) for k in tape._programs)
        tape._programs.clear()

    def test_prewarm_skips_jobs_over_the_memory_budget(self, monkeypatch):
        """Warming RUNS each program on zero stacks: a job whose
        operands + register file exceed the device memory the caller
        leaves free is skipped and listed, not attempted (at 256
        shards the b=32 job alone is tens of GiB)."""
        monkeypatch.setattr(tape, "_prewarm_worthwhile", lambda: True)
        tape._programs.clear()
        tape.reset_counters()
        stack = (8, 64)
        stack_bytes = 8 * 64 * 4
        # room for the b=2 small-class job only: 2 x (4 + 2 x 8) stacks
        budget = 2 * (4 + 2 * (4 + 4)) * stack_bytes
        n = tape.prewarm(stack, max_batch=8, max_tape=4, max_leaves=4,
                         mesh=None, budget_bytes=budget)
        assert n == 1
        pw = tape.debug()["prewarm"]
        assert pw["warmed"] == 1
        assert [j["batch"] for j in pw["skipped"]] == [4, 8]
        assert all(j["needBytes"] > j["budgetBytes"] == budget
                   for j in pw["skipped"])
        tape._programs.clear()
        tape.reset_counters()
        assert tape.debug()["prewarm"] == {
            "state": "idle", "warmed": 0, "skipped": [], "error": None}

    def test_coalesced_distinct_shapes_share_mesh_launch(self):
        """16 structurally distinct concurrent Counts through the
        ragged coalescer on the mesh: <= 2 launches, bit-exact, and a
        concurrent ?nomesh query NEVER shares their launch."""
        from pilosa_tpu.parallel.coalescer import Coalescer
        from tests.coalesce_batch import map_behind_launch

        holder, ex, f, oracle = _mk(seed=6)
        try:
            ex.coalescer = Coalescer(window_s=0.25, max_batch=32,
                                     enabled=True, ragged=True)
            qs = [f"Count(Union(Row(f=1), Row(f={1 + (i % 2)})))"
                  if i % 3 == 0 else
                  f"Count(Intersect(Row(f={1 + (i % 2)}), Row(f=3)))"
                  for i in range(8)]
            expected = [ex.execute("i", q, opt=ExecOptions(
                coalesce=False))[0] for q in qs]
            def run(i):
                with bm.dispatch_counter() as dc:
                    got = ex.execute("i", qs[i])[0]
                return got, dc.n

            res = map_behind_launch(ex.coalescer, run, len(qs))
            assert [r[0] for r in res] == expected
            launch_counts = [r[1] for r in res]
            assert sum(launch_counts) <= 2, launch_counts
        finally:
            holder.close()


class TestContainerMesh:
    def test_sparse_gather_on_mesh_bit_exact(self):
        """Sparse rows route the compressed gather under the mesh:
        one launch, domain sharded over the axis, bit-exact, and the
        dense/?nomesh routes agree byte-identically."""
        holder, ex, f, oracle = _mk(seed=7, n_bits=40)  # ultra-sparse
        try:
            assert meshexec.active()
            ct.reset_counters()
            q = "Count(Intersect(Row(f=1), Row(f=2)))"
            with bm.dispatch_counter() as dc:
                got = ex.execute("i", q)[0]
            assert dc.n == 1, dc.launches
            assert got == len(oracle[1] & oracle[2])
            assert ct.counters()["container.queries"] == 1
            got_dense = ex.execute(
                "i", q, opt=ExecOptions(containers=False))[0]
            got_nm = ex.execute("i", q, opt=ExecOptions(mesh=False))[0]
            assert got_dense == got_nm == got
        finally:
            ct.reset_counters()
            holder.close()


class TestHTTP:
    def test_debug_mesh_and_escape(self, tmp_path):
        """GET /debug/mesh serves the axis layout + plan + counters;
        ?nomesh=1 on the query route is accepted and byte-identical;
        mesh_* gauges render on /metrics (check_metrics validates the
        full family list live in test_http)."""
        from pilosa_tpu.server.server import Server

        s = Server(str(tmp_path / "m"), port=0, mesh_axis_size=4)
        s.open()
        try:
            uri = s.uri

            def post(path, obj):
                req = urllib.request.Request(
                    uri + path, data=json.dumps(obj).encode(),
                    method="POST")
                req.add_header("Content-Type", "application/json")
                with urllib.request.urlopen(req, timeout=10) as r:
                    return json.loads(r.read())

            post("/index/i", {})
            post("/index/i/field/f", {})
            post("/index/i/query",
                 {"query": "".join(f"Set({s_ * W + 3}, f={r})"
                                   for s_ in range(5) for r in (1, 2))})
            q = {"query": "Count(Intersect(Row(f=1), Row(f=2)))"}
            got = post("/index/i/query", q)["results"][0]
            got_nm = post("/index/i/query?nomesh=1&nocache=1",
                          q)["results"][0]
            assert got == got_nm == 5
            with urllib.request.urlopen(uri + "/debug/mesh",
                                        timeout=10) as r:
                doc = json.loads(r.read())
            assert doc["active"] is True
            assert doc["axisSize"] == 4
            assert len(doc["devices"]) == 4
            assert doc["counters"]["mesh.fallbacks"] >= 1
            assert doc["residency"]["perDevice"] <= \
                doc["residency"]["total"]
            assert [p["device"] for p in doc["plan"]] == \
                [d["id"] for d in doc["devices"]]
            with urllib.request.urlopen(uri + "/metrics",
                                        timeout=10) as r:
                text = r.read().decode()
            assert "mesh_launches" in text
            assert "mesh_devices" in text
        finally:
            s.close()


class TestSubprocessFourDevices:
    def test_literal_four_device_process(self):
        """The acceptance environment verbatim: a process whose jax
        backend has exactly 4 CPU devices (device counts are fixed at
        backend init, so this MUST be a subprocess) runs a fused
        Count over >= 4 shard groups as ONE mesh launch, bit-exact,
        with ?nomesh byte-identical."""
        code = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["PILOSA_TPU_SHARD_WIDTH_EXP"] = "16"
import sys, tempfile, random
sys.path.insert(0, %(repo)r)
import jax
assert len(jax.devices()) == 4, jax.devices()
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.ops import bitmap as bm
from pilosa_tpu.parallel import meshexec
from pilosa_tpu.parallel.executor import ExecOptions, Executor
from pilosa_tpu.shardwidth import SHARD_WIDTH
assert meshexec.axis_size() == 4
h = Holder(tempfile.mkdtemp() + "/h")
idx = h.create_index("i")
f = idx.create_field("f")
rng = random.Random(1)
oracle = {1: set(), 2: set()}
rows, cols = [], []
for r in (1, 2):
    for _ in range(800):
        c = rng.randrange(5 * SHARD_WIDTH)
        rows.append(r); cols.append(c); oracle[r].add(c)
f.import_bits(rows, cols)
ex = Executor(h)
with bm.dispatch_counter() as dc:
    got = ex.execute("i", "Count(Intersect(Row(f=1), Row(f=2)))",
                     opt=ExecOptions(cache=False))[0]
assert dc.n == 1, dc.launches
assert got == len(oracle[1] & oracle[2]), got
assert meshexec.counters()["mesh.launches"] == 1
st = f.device_row_stack(1, tuple(range(5)))
assert len(st.sharding.device_set) == 4
got_nm = ex.execute("i", "Count(Intersect(Row(f=1), Row(f=2)))",
                    opt=ExecOptions(cache=False, mesh=False))[0]
assert got_nm == got
print("SUBPROC_OK", got)
""" % {"repo": os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))}
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True,
                             timeout=300, env=env)
        assert out.returncode == 0, (out.stdout[-2000:],
                                     out.stderr[-2000:])
        assert "SUBPROC_OK" in out.stdout
