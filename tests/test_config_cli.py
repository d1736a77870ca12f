"""Config merge + CLI command tests (parity: server/config.go + viper
merge cmd/root.go:94; ctl/ subcommands)."""

from __future__ import annotations

import contextlib
import json
import threading
import urllib.request

import pytest

from pilosa_tpu.config import Config
from pilosa_tpu.cmd import main as cli_main, run_server


def _query(uri, index, pql):
    req = urllib.request.Request(
        f"{uri}/index/{index}/query",
        data=json.dumps({"query": pql}).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.loads(resp.read())["results"]


class TestConfig:
    def test_defaults(self):
        cfg = Config()
        assert cfg.port == 10101
        assert cfg.cluster.replicas == 1
        assert cfg.anti_entropy.interval == 600.0

    def test_toml_env_flag_precedence(self, tmp_path):
        toml = tmp_path / "cfg.toml"
        toml.write_text(
            'bind = "127.0.0.1:7001"\n'
            "verbose = true\n"
            "[cluster]\n"
            "replicas = 2\n"
            'seeds = ["http://a:1"]\n'
        )
        cfg = Config.load(
            str(toml),
            env={"PILOSA_TPU_BIND": "127.0.0.1:7002",
                 "PILOSA_TPU_CLUSTER_REPLICAS": "3"},
            overrides={"bind": "127.0.0.1:7003"},
        )
        assert cfg.bind == "127.0.0.1:7003"  # flag beats env beats file
        assert cfg.cluster.replicas == 3      # env beats file
        assert cfg.verbose is True            # file beats default
        assert cfg.cluster.seeds == ["http://a:1"]

    def test_env_coercion(self):
        cfg = Config.load(env={
            "PILOSA_TPU_VERBOSE": "true",
            "PILOSA_TPU_HEARTBEAT_INTERVAL": "2.5",
            "PILOSA_TPU_CLUSTER_SEEDS": "http://a:1,http://b:2",
        })
        assert cfg.verbose is True
        assert cfg.heartbeat_interval == 2.5
        assert cfg.cluster.seeds == ["http://a:1", "http://b:2"]

    def test_toml_roundtrip(self, tmp_path):
        cfg = Config()
        cfg.cluster.replicas = 4
        p = tmp_path / "out.toml"
        p.write_text(cfg.to_toml())
        cfg2 = Config.load(str(p), env={})
        assert cfg2.cluster.replicas == 4
        assert cfg2.bind == cfg.bind


@contextlib.contextmanager
def cli_server(cfg):
    """``cfg`` run through the real CLI server path -> the Server."""
    ready, stop = threading.Event(), threading.Event()
    holder = {}

    def run():
        # capture the server to learn the bound port
        from pilosa_tpu.server.server import Server as _S

        orig_open = _S.open

        def patched_open(self):
            holder["srv"] = self
            return orig_open(self)

        _S.open = patched_open
        try:
            run_server(cfg, ready_event=ready, stop_event=stop)
        finally:
            _S.open = orig_open

    t = threading.Thread(target=run, daemon=True)
    t.start()
    try:
        assert ready.wait(60)
        yield holder["srv"]
    finally:
        stop.set()
        t.join(timeout=30)
    assert not t.is_alive()


@pytest.fixture
def running_server(tmp_path):
    """A node run through the real CLI server path on a random port."""
    cfg = Config()
    cfg.data_dir = str(tmp_path / "data")
    cfg.bind = "127.0.0.1:0"
    cfg.anti_entropy.interval = 0
    with cli_server(cfg) as srv:
        yield srv


class TestCLI:
    def test_generate_config(self, capsys):
        assert cli_main(["generate-config"]) == 0
        out = capsys.readouterr().out
        assert "[cluster]" in out and "replicas = 1" in out

    def test_server_import_export_roundtrip(self, tmp_path, running_server,
                                            capsys):
        srv = running_server
        csv_file = tmp_path / "bits.csv"
        csv_file.write_text("1,10\n1,20\n2,30\n")
        rc = cli_main([
            "import", "--host", srv.uri, "-i", "i", "-f", "f",
            "--create", str(csv_file)])
        assert rc == 0
        assert _query(srv.uri, "i", "Count(Row(f=1))") == [2]

        out_file = tmp_path / "out.csv"
        rc = cli_main(["export", "--host", srv.uri, "-i", "i", "-f", "f",
                       "-o", str(out_file)])
        assert rc == 0
        lines = sorted(out_file.read_text().strip().splitlines())
        assert lines == ["1,10", "1,20", "2,30"]

    def test_import_int_values(self, tmp_path, running_server):
        srv = running_server
        csv_file = tmp_path / "vals.csv"
        csv_file.write_text("1,100\n2,200\n")
        rc = cli_main([
            "import", "--host", srv.uri, "-i", "i2", "-f", "v",
            "--create", "--field-type", "int", "--min", "0",
            "--max", "1000", str(csv_file)])
        assert rc == 0
        assert _query(srv.uri, "i2", "Sum(field=v)")[0] == {
            "value": 300, "count": 2}

    def test_check_and_inspect(self, tmp_path, capsys):
        # build a small holder offline
        from pilosa_tpu.models.holder import Holder

        holder = Holder(str(tmp_path / "d"))
        idx = holder.create_index("i")
        f = idx.create_field("f")
        f.set_bit(1, 10)
        f.set_bit(2, 20)
        holder.snapshot()
        holder.close()

        assert cli_main(["check", str(tmp_path / "d")]) == 0
        out = capsys.readouterr().out
        assert "passed" in out and "i/f/standard/0" in out

        assert cli_main(["inspect", str(tmp_path / "d"),
                         "-i", "i", "-f", "f"]) == 0
        out = capsys.readouterr().out
        assert "rows=2 bits=2" in out

    def test_import_bad_record_errors(self, tmp_path, running_server,
                                      capsys):
        srv = running_server
        csv_file = tmp_path / "bad.csv"
        csv_file.write_text("1,notanumber\n")
        rc = cli_main(["import", "--host", srv.uri, "-i", "i3",
                       "-f", "f", "--create", str(csv_file)])
        assert rc == 1


class TestWiredOptions:
    def test_max_writes_per_request(self, tmp_path):
        from pilosa_tpu.api import API, ApiError
        from tests.test_cluster import make_cluster

        _, nodes = make_cluster(tmp_path, n=1)
        nodes[0].create_index("i")
        nodes[0].create_field("i", "f")
        api = API(nodes[0])
        api.max_writes_per_request = 2
        with pytest.raises(ApiError):
            api.query("i", "Set(1, f=1)Set(2, f=1)Set(3, f=1)")
        assert api.query("i", "Set(1, f=1)Set(2, f=1)") == [True, True]

    def test_slow_query_log(self, tmp_path):
        import io

        from pilosa_tpu.logger import StandardLogger
        from pilosa_tpu.models.holder import Holder
        from pilosa_tpu.parallel.executor import Executor

        holder = Holder(str(tmp_path / "h"))
        holder.create_index("i").create_field("f")
        ex = Executor(holder)
        buf = io.StringIO()
        ex.logger = StandardLogger(buf)
        ex.long_query_time = 0.0000001  # everything is slow
        ex.execute("i", "Count(Row(f=1))")
        assert "slow query" in buf.getvalue()
        holder.close()

    def test_import_from_stdin_does_not_close_it(self, tmp_path,
                                                 running_server,
                                                 monkeypatch):
        import io

        srv = running_server
        monkeypatch.setattr("sys.stdin", io.StringIO("1,10\n"))
        rc = cli_main(["import", "--host", srv.uri, "-i", "istdin",
                       "-f", "f", "--create", "-"])
        assert rc == 0
        import sys as _sys

        assert not _sys.stdin.closed

    def test_server_explicit_zero_heartbeat_override(self, tmp_path):
        from pilosa_tpu.cmd import cmd_server  # noqa: F401  (parse check)
        import argparse

        # simulate parsed args with explicit 0.0 override over a file
        toml = tmp_path / "c.toml"
        toml.write_text("heartbeat-interval = 5.0\n")
        cfg = Config.load(str(toml), env={},
                          overrides={"heartbeat_interval": 0.0})
        assert cfg.heartbeat_interval == 0.0


class TestStatsAndTracing:
    def test_mem_stats_registry(self):
        from pilosa_tpu.stats import MemStatsClient

        s = MemStatsClient()
        s.count("queries", 2)
        s.count("queries", 3)
        s.gauge("goroutines", 7)
        tagged = s.with_tags("index:i")
        tagged.count("queries", 1)
        snap = s.snapshot()
        assert snap["queries"] == 5
        assert snap["queries[index:i]"] == 1
        assert snap["goroutines"] == 7
        text = s.prometheus_text()
        assert "# TYPE queries counter" in text
        assert 'queries{index="i"} 1' in text

    def test_query_stats_and_metrics_endpoint(self, running_server):
        srv = running_server
        # create then query so the executor emits stats
        urllib.request.urlopen(
            urllib.request.Request(srv.uri + "/index/i9", data=b"{}",
                                   method="POST")).close()
        urllib.request.urlopen(
            urllib.request.Request(srv.uri + "/index/i9/field/f",
                                   data=b"{}", method="POST")).close()
        _query(srv.uri, "i9", "Count(Row(f=1))")
        with urllib.request.urlopen(srv.uri + "/metrics") as resp:
            text = resp.read().decode()
        assert 'query{call="Count",index="i9"}' in text
        with urllib.request.urlopen(srv.uri + "/debug/vars") as resp:
            snap = json.loads(resp.read())
        assert any(k.startswith("query[") for k in snap)

    def test_diagnostics_endpoint_and_runtime_gauges(self, running_server):
        srv = running_server
        with urllib.request.urlopen(srv.uri + "/diagnostics") as resp:
            d = json.loads(resp.read())
        assert d["numNodes"] == 1 and d["clusterState"] == "NORMAL"
        assert "version" in d and d["uptime"] >= 0
        from pilosa_tpu import diagnostics
        from pilosa_tpu.stats import MemStatsClient

        s = MemStatsClient()
        diagnostics.runtime_gauges(s)
        snap = s.snapshot()
        assert snap["threads"] >= 1
        assert snap.get("memory.rss_bytes", 1) > 0
        # device residency gauges come from the global manager
        assert snap["device.cache_budget_bytes"] > 0
        assert snap["device.cache_bytes"] >= 0

    def test_mem_tracer_spans(self):
        from pilosa_tpu import tracing
        from pilosa_tpu.tracing import MemTracer

        tracer = MemTracer()
        old = tracing.global_tracer()
        tracing.set_global_tracer(tracer)
        try:
            with tracing.start_span("outer") as outer:
                outer.set_tag("k", "v")
                with tracing.start_span("inner", outer):
                    pass
            spans = tracer.finished()
            names = {s.name for s in spans}
            assert names == {"outer", "inner"}
            inner = tracer.finished("inner")[0]
            outer_s = tracer.finished("outer")[0]
            assert inner.trace_id == outer_s.trace_id
            assert inner.parent_name == "outer"
            assert outer_s.tags == {"k": "v"}
        finally:
            tracing.set_global_tracer(old)

    def test_executor_emits_spans(self, tmp_path):
        from pilosa_tpu import tracing
        from pilosa_tpu.tracing import MemTracer
        from pilosa_tpu.models.holder import Holder
        from pilosa_tpu.parallel.executor import Executor

        holder = Holder(str(tmp_path / "h"))
        holder.create_index("i").create_field("f")
        ex = Executor(holder)
        tracer = MemTracer()
        old = tracing.global_tracer()
        tracing.set_global_tracer(tracer)
        try:
            ex.execute("i", "Count(Row(f=1))")
            assert tracer.finished("executor.Execute")
            assert tracer.finished("executor.executeCount")
        finally:
            tracing.set_global_tracer(old)
        holder.close()


def test_tracing_endpoint_config_roundtrip(tmp_path):
    """[tracing] endpoint parses from TOML and survives the
    generate-config round-trip (env pinned so ambient PILOSA_TPU_*
    variables cannot leak in)."""
    from pilosa_tpu.config import Config

    cfg_path = tmp_path / "c.toml"
    cfg_path.write_text(
        '[tracing]\nenabled = true\nendpoint = "http://collector:4318"\n')
    cfg = Config.load(str(cfg_path), env={})
    assert cfg.tracing.enabled is True
    assert cfg.tracing.endpoint == "http://collector:4318"
    dumped = cfg.to_toml()
    assert 'endpoint = "http://collector:4318"' in dumped
    cfg2 = Config.load(None, env={})
    assert cfg2.tracing.endpoint == ""
