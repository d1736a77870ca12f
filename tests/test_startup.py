"""Process start-up (runtime/startup.py): where the persistent compile
cache goes, and what a server says about its backend."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import pytest

from pilosa_tpu.runtime import startup

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_config():
    keep = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update("jax_compilation_cache_dir", keep[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", keep[1])


class TestCompileCachePlacement:
    def test_operator_placed_directory_is_left_alone(
            self, monkeypatch, tmp_path, restore_cache_config):
        """With JAX_COMPILATION_CACHE_DIR set, JAX owns the directory
        (it read the variable itself) and the code sets none."""
        placed = str(tmp_path / "placed")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
        # what `import jax` does with the variable in a fresh process
        jax.config.update("jax_compilation_cache_dir", placed)
        assert startup.configure_compile_cache() == placed
        assert jax.config.jax_compilation_cache_dir == placed

    def test_default_is_one_fixed_path_in_the_checkout(
            self, monkeypatch, restore_cache_config):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        first = startup.configure_compile_cache()
        second = startup.configure_compile_cache()
        assert first == second == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
        # every executable persists (module docstring says why)
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0

    def test_two_processes_agree_on_the_path(self):
        """The path is part of the cache key, so it must not carry a
        pid, a timestamp or a temp name: two processes, one answer —
        and the operator's directory in a process that was given one."""
        code = ("import json, jax\n"
                "from pilosa_tpu.runtime.startup import "
                "configure_compile_cache as c\n"
                "print(json.dumps([c(), "
                "jax.config.jax_compilation_cache_dir]))\n")

        def run(extra_env):
            env = {k: v for k, v in os.environ.items()
                   if k != "JAX_COMPILATION_CACHE_DIR"}
            env.update(extra_env, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
            out = subprocess.run([sys.executable, "-c", code], env=env,
                                 capture_output=True, text=True,
                                 timeout=120, check=True)
            return json.loads(out.stdout.strip().splitlines()[-1])

        want = os.path.join(REPO, ".jax_cache")
        assert run({}) == run({}) == [want, want]
        assert run({"JAX_COMPILATION_CACHE_DIR": "/tmp/placed-by-op"}) \
            == ["/tmp/placed-by-op", "/tmp/placed-by-op"]

    def test_nothing_else_sets_a_cache_directory(self):
        """One start-up function places the cache; any other write of
        jax_compilation_cache_dir would fight an operator's
        JAX_COMPILATION_CACHE_DIR."""
        out = subprocess.run(
            ["grep", "-rln", "--include=*.py",
             "jax_compilation_cache_dir\\|compilation_cache_dir\"", REPO],
            capture_output=True, text=True).stdout.split()
        rel = (os.path.relpath(p, REPO) for p in out)
        # tests aside, and scratch checkouts under dot-directories
        setters = sorted(r for r in rel
                         if not r.startswith((".", "tests/")))
        assert setters == ["pilosa_tpu/runtime/startup.py"], setters


def test_backend_info_names_the_host_engine(monkeypatch):
    """A one-CPU-device process runs numpy + native C++ and never
    touches XLA: it must say so, not pass for an accelerator server."""
    from pilosa_tpu.ops import bitmap as bm

    info = startup.backend_info()
    assert info["platform"] == "cpu"
    assert info["deviceCount"] == len(jax.devices())
    assert info["hostMode"] is bm.host_mode() is False  # 8 test devices
    assert info["engine"].startswith("device")
    monkeypatch.setattr(bm, "host_mode", lambda: True)
    assert startup.backend_info()["engine"].startswith("host")


def test_status_and_debug_devices_say_which_backend(tmp_path):
    import urllib.request

    from pilosa_tpu.server.server import Server

    srv = Server(str(tmp_path / "d"))
    srv.open()
    try:
        def get(path):
            with urllib.request.urlopen(srv.uri + path, timeout=10) as r:
                return json.loads(r.read())

        want = startup.backend_info()
        assert get("/status")["backend"] == want
        dev = get("/debug/devices")
        assert dev["backend"] == want
        assert {d["kind"] for d in dev["devices"]} == {want["deviceKind"]}
        # the four C++ components, each native or carrying its error
        assert set(dev["native"]) == {"bitcount", "roaring_codec",
                                      "pql_parser", "csv_loader"}
        assert all(v["loaded"] or v["error"]
                   for v in dev["native"].values())
    finally:
        srv.close()


def test_failed_ragged_prewarm_is_on_debug_ragged(tmp_path, monkeypatch):
    """A warm-up that raises is a finding (on an accelerator: a
    program that does not compile), so the server keeps serving but
    /debug/ragged carries the error instead of a quiet "skipped"."""
    import time
    import urllib.request

    from pilosa_tpu.ops import tape
    from pilosa_tpu.server.server import Server

    def boom(*a, **kw):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(tape, "prewarm", boom)
    tape.reset_counters()
    srv = Server(str(tmp_path / "d"), coalescer_enabled=True)
    srv.open()
    try:
        deadline = time.monotonic() + 30
        while True:
            with urllib.request.urlopen(srv.uri + "/debug/ragged",
                                        timeout=10) as r:
                pw = json.loads(r.read())["prewarm"]
            if pw["state"] not in ("idle", "running"):
                break
            assert time.monotonic() < deadline, pw
            time.sleep(0.05)
        assert pw["state"] == "failed"
        assert "Mosaic failed to compile" in pw["error"]
    finally:
        srv.close()
        tape.reset_counters()


class _FakeDevice:
    platform = "tpu"
    device_kind = "TPU v5 lite"

    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_residency_budget_needs_the_accelerator_memory_limit(monkeypatch):
    """memory_stats() without a limit used to turn a TPU into a 2 GiB
    chip; now only a CPU backend gets the conservative default."""
    from pilosa_tpu.runtime import residency

    monkeypatch.delenv("PILOSA_TPU_DEVICE_BUDGET_BYTES", raising=False)
    assert residency._default_budget() == 2 << 30  # this CPU backend
    monkeypatch.setattr(jax, "devices",
                        lambda: [_FakeDevice({"bytes_limit": 1000})] * 4)
    assert residency._default_budget() == 600 * 4
    monkeypatch.setattr(jax, "devices", lambda: [_FakeDevice(None)])
    with pytest.raises(RuntimeError, match="PILOSA_TPU_DEVICE_BUDGET_BYTES"):
        residency._default_budget()
    monkeypatch.setenv("PILOSA_TPU_DEVICE_BUDGET_BYTES", "12345")
    assert residency._default_budget() == 12345


def test_native_build_failure_is_said_and_kept(tmp_path, capsys):
    """A failed g++ build still latches to the Python implementation,
    but not without a word: stderr gets one line and the error stays
    readable (the `native` section of /debug/devices)."""
    from pilosa_tpu.native_loader import NativeLib

    src = tmp_path / "broken.cpp"
    src.write_text("this is not C++\n")
    lib = NativeLib(str(src), str(tmp_path / "build" / "libbroken.so"),
                    setup=lambda lib: None)
    assert lib.load() is None and not lib.available()
    assert lib.name == "broken"
    assert lib.error and "CalledProcessError" in lib.error
    err = capsys.readouterr().err
    assert err.count("native: broken did not build/load") == 1
    lib.load()  # latched: no second build, no second line
    assert "native:" not in capsys.readouterr().err
