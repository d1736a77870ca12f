"""Engine observatory (pilosa_tpu.perfobs): per-launch wall/bytes
accounting, the EWMA cost table under a fake clock, on-demand
profiler capture (roundtrip, busy/idle 409 discipline), the canonical
``engine`` enum on flight records per routing escape, and the
/debug/cost + engine_/cost_ metric-family HTTP surface.

The serving-path pins ride the same 16-distinct-shape sparse workload
as tests/test_vm.py: the one batch that exercises vm, tape, dense and
host routing under explicit escapes, so ≥3 engines land cost-table
samples in a single test run (the ISSUE acceptance bar).
"""

from __future__ import annotations

import json
import os
import urllib.error
import urllib.request

import numpy as np
import pytest

from pilosa_tpu import perfobs
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.ops import containers as ct
from pilosa_tpu.ops import tape
from pilosa_tpu.parallel.executor import ExecOptions, Executor
from pilosa_tpu.runtime import resultcache
from tests.test_vm import (N_SHARDS, NOVM, SHAPES_16, VMOPT, _attach,
                           _run_concurrent, ex)  # noqa: F401

#: dense fused route: containers AND vm off, single-device.
DENSE = ExecOptions(mesh=False, containers=False)


@pytest.fixture(autouse=True)
def _fresh():
    perfobs.reset()
    ct.reset()
    ct.reset_counters()
    tape.reset_counters()
    rc = resultcache.cache()
    was = rc.enabled
    rc.enabled = False  # pins must reach the engines, not the cache
    yield
    rc.enabled = was
    perfobs.reset()
    ct.reset()


class _FakeClock:
    """Deterministic perf_counter_ns: each read advances ``step_ns``,
    so a t0()/sample() bracket measures exactly one step."""

    def __init__(self, step_ns: int):
        self.now = 0
        self.step = step_ns

    def __call__(self) -> int:
        self.now += self.step
        return self.now


# ---------------------------------------------------------------------------
# Cost-table math (fake clock — no device, no timing jitter)
# ---------------------------------------------------------------------------


class TestCostMath:
    def test_size_class_pow2_labels(self):
        assert perfobs.size_class(0) == "2^0"
        assert perfobs.size_class(1) == "2^0"
        assert perfobs.size_class(2) == "2^1"
        assert perfobs.size_class(1024) == "2^10"
        assert perfobs.size_class(1025) == "2^11"

    def test_sparsity_buckets(self):
        assert perfobs.sparsity_bucket(0.0) == "0"
        assert perfobs.sparsity_bucket(0.005) == "<1%"
        assert perfobs.sparsity_bucket(0.05) == "<10%"
        assert perfobs.sparsity_bucket(0.3) == "<50%"
        assert perfobs.sparsity_bucket(0.7) == ">=50%"
        assert perfobs.sparsity_bucket(1.0) == ">=50%"

    def test_first_sample_seeds_second_blends(self):
        # 1ms over 1MB -> exactly 1.0 GB/s
        perfobs.record_sample("dense", 1_000_000, 1_000_000, work=1024)
        perfobs.record_sample("dense", 2_000_000, 1_000_000, work=1024)
        [row] = perfobs.cost_debug()["table"]
        assert (row["engine"], row["size"], row["sparsity"]) == \
            ("dense", "2^10", ">=50%")
        assert row["samples"] == 2
        # seed 1000us, then EWMA: 1000 + 0.2 * (2000 - 1000)
        assert row["wallUs"] == pytest.approx(1200.0)
        assert row["devUs"] == pytest.approx(200.0)
        assert row["lastUs"] == pytest.approx(2000.0)
        # gbps samples 1.0 then 0.5 -> 1.0 + 0.2 * (0.5 - 1.0)
        assert row["gbps"] == pytest.approx(0.9)
        snap = perfobs.counters()
        assert snap["engine.launches"] == 2
        assert snap["cost.samples"] == 2
        assert snap["engine.bytes"] == 2_000_000

    def test_fake_clock_drives_sample_bracket(self, monkeypatch):
        monkeypatch.setattr(perfobs, "_clock", _FakeClock(5_000_000))
        s0 = perfobs.t0()
        assert s0 == 5_000_000
        perfobs.sample("tape", np.zeros(4, dtype=np.uint32), s0,
                       nbytes=4096, work=4096, sparsity=0.25)
        [row] = perfobs.cost_debug()["table"]
        assert (row["engine"], row["size"], row["sparsity"]) == \
            ("tape", "2^12", "<50%")
        assert row["wallUs"] == pytest.approx(5000.0)  # one clock step

    def test_disabled_gate_is_free(self):
        perfobs.configure(enabled_=False)
        assert perfobs.t0() == 0
        perfobs.sample("dense", None, 0, nbytes=8)
        assert perfobs.counters()["engine.launches"] == 0
        assert perfobs.cost_debug()["enabled"] is False

    def test_context_overrides_ops_layer(self, monkeypatch):
        monkeypatch.setattr(perfobs, "_clock", _FakeClock(1_000_000))
        with perfobs.context(engine="vm", sparsity=0.001, work=2):
            perfobs.sample("dense", np.zeros(1, dtype=np.uint32),
                           perfobs.t0(), nbytes=64)
        [row] = perfobs.cost_debug()["table"]
        assert (row["engine"], row["size"], row["sparsity"]) == \
            ("vm", "2^1", "<1%")

    def test_engine_summary_and_bw_util_roof(self):
        perfobs.configure(peak_gbps=10.0)
        perfobs.record_sample("gather", 1_000_000, 1_000_000)  # 1 GB/s
        s = perfobs.engine_summary()["gather"]
        assert s["launches"] == 1
        assert s["gbps"] == pytest.approx(1.0)
        assert s["bwUtil"] == pytest.approx(0.1)
        assert perfobs.device_peak_gbps() == 10.0


# ---------------------------------------------------------------------------
# Profiler capture
# ---------------------------------------------------------------------------


class TestProfiler:
    def test_roundtrip_writes_dated_artifact_dir(self, tmp_path):
        info = perfobs.profiler_start(str(tmp_path), max_seconds=0)
        assert os.path.isdir(info["dir"])
        assert os.sep + "profiles" + os.sep in info["dir"]
        assert os.path.basename(info["dir"]).startswith("trace_")
        st = perfobs.profiler_status()
        assert st["active"] is True and st["dir"] == info["dir"]
        out = perfobs.profiler_stop()
        assert out["dir"] == info["dir"]
        assert out["seconds"] >= 0
        assert perfobs.counters()["cost.profiles"] == 1
        st = perfobs.profiler_status()
        assert st["active"] is False and st["lastDir"] == info["dir"]

    def test_concurrent_start_is_busy(self, tmp_path):
        perfobs.profiler_start(str(tmp_path), max_seconds=0)
        try:
            with pytest.raises(perfobs.ProfilerBusy):
                perfobs.profiler_start(str(tmp_path), max_seconds=0)
        finally:
            perfobs.profiler_stop()

    def test_stop_when_idle_raises(self):
        with pytest.raises(perfobs.ProfilerIdle):
            perfobs.profiler_stop()


# ---------------------------------------------------------------------------
# Serving path: the canonical engine enum, per escape
# ---------------------------------------------------------------------------


def _engines_of(ex, n):
    return [r.engine for r in ex.recorder.recent_records()[-n:]]


class TestEngineAttribution:
    def test_vm_batch_stamps_vm(self, ex):
        qs = [f"Count({t})" for t in SHAPES_16]
        _attach(ex, window_s=2.0, max_batch=16)
        _, launches = _run_concurrent(ex, qs)
        assert launches == ["vm"], launches
        assert _engines_of(ex, len(qs)) == ["vm"] * len(qs)

    def test_novm_batch_stamps_tape(self, ex):
        qs = [f"Count({t})" for t in SHAPES_16]
        _attach(ex, window_s=2.0, max_batch=16)
        _, _ = _run_concurrent(ex, qs, opt=NOVM)
        assert _engines_of(ex, len(qs)) == ["tape"] * len(qs)

    def test_nocontainers_stamps_dense(self, ex):
        ex.execute("i", f"Count({SHAPES_16[0]})", opt=DENSE)
        assert _engines_of(ex, 1) == ["dense"]

    def test_default_mesh_route_stamps_mesh(self, ex):
        # no ?nomesh escape: the conftest's 8-virtual-device platform
        # routes the fused dispatch through the mesh shard_map programs
        ex.execute("i", f"Count({SHAPES_16[0]})")
        assert _engines_of(ex, 1) == ["mesh"]

    def test_per_shard_path_stamps_host(self, ex):
        ex.fuse_shards = False
        try:
            ex.execute("i", f"Count({SHAPES_16[0]})", opt=VMOPT)
        finally:
            ex.fuse_shards = True
        assert _engines_of(ex, 1) == ["host"]

    def test_three_engines_populate_debug_cost(self, ex):
        """THE acceptance bar: the 16-distinct-shape sparse workload,
        run under the vm / novm / nocontainers escapes, leaves
        /debug/cost holding per-launch samples for >= 3 engines."""
        qs = [f"Count({t})" for t in SHAPES_16]
        _attach(ex, window_s=2.0, max_batch=16)
        _run_concurrent(ex, qs)
        _run_concurrent(ex, qs, opt=NOVM)
        ex.coalescer = None
        for q in qs[:4]:
            ex.execute("i", q, opt=DENSE)
        d = perfobs.cost_debug()
        assert len(d["engines"]) >= 3, d["engines"]
        assert {"vm", "tape", "dense"} <= set(d["engines"])
        for s in d["engines"].values():
            assert s["launches"] >= 1
            assert set(s) == {"launches", "wallUs", "bytes", "gbps",
                              "bwUtil"}
        for row in d["table"]:
            assert row["engine"] in perfobs.ENGINES
            assert row["samples"] >= 1 and row["wallUs"] >= 0
        assert d["counters"]["cost.samples"] == \
            d["counters"]["engine.launches"]


# ---------------------------------------------------------------------------
# HTTP surface + metric families + config knobs
# ---------------------------------------------------------------------------


def _get(uri, path):
    with urllib.request.urlopen(f"{uri}{path}", timeout=10) as resp:
        return json.loads(resp.read())


def _post(uri, path, expect=200):
    req = urllib.request.Request(f"{uri}{path}", data=b"",
                                 method="POST")
    try:
        with urllib.request.urlopen(req, timeout=30) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


class TestHTTP:
    @pytest.fixture
    def srv(self, tmp_path):
        from pilosa_tpu.server.server import Server

        srv = Server(str(tmp_path / "srv"), port=0,
                     coalescer_enabled=True)
        srv.open()
        srv.api.create_index("i")
        srv.api.create_field("i", "f")
        # two shards: the fused all-shard path (and its launch
        # samples) needs a real multi-shard batch
        from pilosa_tpu.shardwidth import SHARD_WIDTH as W

        srv.api.import_bits("i", "f", [1, 1, 1, 2, 2],
                            [3, 70, W + 3, 70, W + 3])
        yield srv
        srv.close()

    def _query(self, srv, flags=""):
        req = urllib.request.Request(
            f"{srv.uri}/index/i/query?nocache=1{flags}",
            data=b"Count(Intersect(Row(f=1), Row(f=2)))",
            method="POST")
        with urllib.request.urlopen(req, timeout=30) as resp:
            return resp.read()

    def test_debug_cost_document_and_engine_field(self, srv):
        self._query(srv)
        d = _get(srv.uri, "/debug/cost")
        assert set(d) == {"enabled", "peakGbps", "counters",
                          "engines", "table", "profiler"}
        assert d["enabled"] is True
        # a CPU host has no roof in KIND_PEAKS: no assumed peak, and
        # so no utilization figure
        assert d["peakGbps"] is None
        assert all(e["bwUtil"] is None for e in d["engines"].values())
        assert d["counters"]["engine.launches"] >= 1
        assert d["engines"], d
        # the canonical enum renders on the flight record
        recs = _get(srv.uri, "/debug/queries")["recent"]
        assert recs and recs[-1]["engine"] in perfobs.ENGINES

    def test_profiler_routes_roundtrip_and_409(self, srv):
        code, out = _post(srv.uri, "/debug/profiler/start?seconds=0")
        assert code == 200 and os.path.isdir(out["dir"])
        assert out["dir"].startswith(srv.api.holder.path)
        code, _ = _post(srv.uri, "/debug/profiler/start?seconds=0")
        assert code == 409
        code, out = _post(srv.uri, "/debug/profiler/stop")
        assert code == 200 and "seconds" in out
        code, _ = _post(srv.uri, "/debug/profiler/stop")
        assert code == 409
        assert _get(srv.uri, "/debug/cost")["profiler"]["active"] \
            is False

    def test_metrics_render_engine_and_cost_families(self, srv):
        self._query(srv)
        with urllib.request.urlopen(f"{srv.uri}/metrics",
                                    timeout=10) as resp:
            text = resp.read().decode()
        for name in ("engine_launches", "engine_bytes",
                     "engine_peak_gbps", "cost_samples",
                     "cost_cells", "cost_profiles"):
            assert name in text, name

    def test_families_declared(self):
        from pilosa_tpu import metricfamilies
        from tools import check_metrics

        fams = metricfamilies.by_name()
        assert fams["engine"].rendered == "engine_"
        assert fams["cost"].rendered == "cost_"
        assert "engine_" in check_metrics.ALL_FAMILIES
        assert "cost_" in check_metrics.ALL_FAMILIES

    @pytest.mark.parametrize("old_cost_section", [False, True],
                             ids=["rendered", "old-cost-section"])
    def test_config_toml_roundtrip(self, tmp_path, old_cost_section):
        """The rendered TOML loads back; so does a file written by a
        build that still had ``[cost] shadow`` (an unknown section is
        ignored), and the CLI's server path starts from it."""
        from pilosa_tpu.config import Config
        from tests.test_config_cli import cli_server

        cfg = Config()
        cfg.data_dir = str(tmp_path / "data")
        cfg.bind = "127.0.0.1:0"
        cfg.anti_entropy.interval = 0
        cfg.observe.device_peak_gbps = 1228.0
        cfg.observe.profiler_max_seconds = 5.0
        text = cfg.to_toml()
        assert "device-peak-gbps = 1228.0" in text
        assert "[cost]" not in text
        if old_cost_section:
            text += "\n[cost]\nshadow = true\n"
        p = tmp_path / "cfg.toml"
        p.write_text(text)
        cfg2 = Config.load(str(p), env={})
        assert cfg2.observe.device_peak_gbps == 1228.0
        assert cfg2.observe.profiler_max_seconds == 5.0
        assert not hasattr(cfg2, "cost")
        if not old_cost_section:
            return
        with cli_server(cfg2) as srv:
            d = _get(srv.uri, "/debug/cost")
        assert set(d) == {"enabled", "peakGbps", "counters",
                          "engines", "table", "profiler"}
        assert d["peakGbps"] == 1228.0


# ---------------------------------------------------------------------------
# One clock with the device trace (ISSUE 24)
# ---------------------------------------------------------------------------


class TestCaptureClocks:
    @pytest.fixture
    def fake_trace(self, monkeypatch):
        import jax

        calls = {}

        def start_trace(log_dir, **kw):
            calls["start"] = (log_dir, kw)

        monkeypatch.setattr(jax.profiler, "start_trace", start_trace)
        monkeypatch.setattr(jax.profiler, "stop_trace",
                            lambda: calls.setdefault("stop", True))
        return calls

    def test_start_and_stop_return_both_clocks(self, tmp_path, fake_trace):
        import time

        lo_perf, lo_unix = time.perf_counter_ns(), time.time_ns()
        info = perfobs.profiler_start(str(tmp_path), max_seconds=0)
        out = perfobs.profiler_stop()
        hi_perf, hi_unix = time.perf_counter_ns(), time.time_ns()
        assert lo_perf <= info["perfCounterNs"] <= out["perfCounterNs"] \
            <= hi_perf
        assert lo_unix <= info["unixNs"] <= out["unixNs"] <= hi_unix
        assert fake_trace["stop"] is True

    def test_python_tracer_off_host_tracer_on(self, tmp_path, fake_trace):
        perfobs.profiler_start(str(tmp_path), max_seconds=0)
        perfobs.profiler_stop()
        log_dir, kw = fake_trace["start"]
        assert log_dir.startswith(str(tmp_path))
        opts = kw["profiler_options"]
        assert opts.python_tracer_level == 0
        assert opts.host_tracer_level >= 1

    def test_spans_annotate_only_while_a_capture_runs(self, tmp_path,
                                                      fake_trace,
                                                      monkeypatch):
        from pilosa_tpu import observe

        seen = []

        class Ann:
            def __init__(self, name, **kw):
                seen.append((name, kw))

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        monkeypatch.setattr(observe, "_annotation", Ann)
        rec = observe.FlightRecorder().begin("i", "Count(Row(f=1))")
        with observe.attach(rec):
            with observe.span("stage"):
                pass
            assert not seen and observe.capturing is False
            perfobs.profiler_start(str(tmp_path), max_seconds=0)
            assert observe.capturing is True
            with observe.span("stage"):
                s0 = perfobs.t0()
                perfobs.sample("dense", np.zeros(1, dtype=np.uint32),
                               s0, nbytes=4)
            perfobs.profiler_stop()
            with observe.span("stage"):
                pass
        assert observe.capturing is False
        assert [n for n, _ in seen] == ["stage", "launch.dispatch",
                                        "launch.ready"]
        assert all(kw == {"rid": rec.trace_id} for _, kw in seen)

    def test_sample_takes_its_clock_from_the_launch_span(self, monkeypatch):
        """With a record, t0()/sample() read no clock of their own: the
        cost-table wall is launch.dispatch's start to launch.ready's
        end."""
        from pilosa_tpu import observe

        def boom():
            raise AssertionError("perfobs read its own clock")

        monkeypatch.setattr(perfobs, "_clock", boom)
        rec = observe.FlightRecorder().begin("i", "Count(Row(f=1))")
        with observe.attach(rec):
            s0 = perfobs.t0()
            perfobs.sample("dense", np.zeros(1, dtype=np.uint32), s0,
                           nbytes=4096, work=1024)
        by = {s[2]: s for s in rec.spans}
        d, r = by["launch.dispatch"], by["launch.ready"]
        assert d[4] == r[3]  # ready starts where dispatch ended
        [row] = perfobs.cost_debug()["table"]
        assert row["lastUs"] == pytest.approx((r[4] - d[3]) / 1e3)
        assert rec.engine == "dense"
