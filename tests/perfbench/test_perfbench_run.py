"""The harness end to end on a CPU: ``--rehearse`` drives the whole
control flow (server child, import, warm-up, window, trace, oracle)
at the configurations' rehearsal size and reports no metric and no
device; without it the harness refuses to measure on anything but a
TPU.  Each test starts one server child and has a minute."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MINUTE = 180  # a minute alone; the tier-1 run loads every core beside it


def run(*args, cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7")
    out = subprocess.run(
        [sys.executable, os.path.join(cwd, "perfbench", "run.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=MINUTE)
    lines = out.stdout.strip().splitlines()
    try:
        last = json.loads(lines[-1]) if lines else None
    except ValueError:
        last = None
    return out, last if isinstance(last, dict) else None


@pytest.mark.parametrize("cell", ["seg-dense"])
def test_rehearsal_runs_the_whole_flow_and_reports_no_device_number(cell):
    out, line = run("--workload", cell, "--seed", str(2 ** 31 + 12345),
                    "--seconds", "2", "--trace", "1", "--rehearse")
    assert out.returncode == 0, out.stderr[-2000:]
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 20
    assert "metrics" not in line and "device" not in line
    # the readers that need no device found the program's records
    assert {"front_ms", "exec_ms", "cache_hit_pct", "evictions",
            "compiles_in_window", "launches_per_read",
            "coalesce_batch"} <= set(line["read"])
    assert "limit=0" in out.stdout  # each compared number, by its limit
    # ... and as the last lines of stderr, and last in the result's line
    assert list(line)[-1] == "compared"
    assert line["compared"]["differing_answers"] == {"value": 0, "limit": 0}
    assert out.stderr.strip().splitlines()[-2:] == [
        "perfbench: compared differing_answers=0 limit=0",
        "perfbench: compared answers_compared="
        f"{line['compared']['answers_compared']['value']} at_least=1"]
    assert not os.path.exists(os.path.join(ROOT, "perfbench", ".work", cell))


def test_a_lost_import_comes_out_not_correct():
    """The control: the run with the last shard's acknowledged imports
    never sent (the timed path broken underneath the harness) must say
    ``"correct": false``."""
    out, line = run("--workload", "seg-dense", "--seed", "77", "--seconds",
                    "2", "--trace", "0", "--rehearse", "--control",
                    "lost-shard")
    assert out.returncode == 0, out.stderr[-2000:]
    assert line["correct"] is False and line["failed"] > 0
    assert line["compared"]["differing_answers"]["value"] > 0
    assert "perfbench: compared differing_answers=" in out.stderr


def test_no_tpu_no_result():
    out, line = run("--workload", "seg-dense", "--seed", "1", "--seconds",
                    "1", "--trace", "0")
    assert out.returncode != 0 and line is None
    assert "needs 1 TPU chip" in out.stderr


def test_a_checkout_with_only_the_benchmark_gives_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    out, line = run("--workload", "seg-dense", "--seed", "1", "--seconds",
                    "1", "--trace", "0", cwd=str(tmp_path))
    assert out.returncode != 0 and line is None


def test_an_unknown_cell_is_refused():
    out, line = run("--workload", "seg-nothing", "--seed", "1", "--seconds",
                    "1", "--trace", "0")
    assert out.returncode == 2 and line is None


def test_the_sweep_steps_the_load_on_one_server():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "sweep.py"),
         "--workload", "seg-dense", "--seed", "3", "--seconds", "1",
         "--rehearse", "--warmup", "20", "--steps", "10,30",
         "--also", "seg-dense=20"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=MINUTE)
    assert out.returncode == 0, out.stderr[-2000:]
    table = json.loads(out.stdout.strip().splitlines()[-1])["sweep"]
    assert [r["step"] for r in table] == [10.0, 30.0, 20.0]
    assert "warm-up: 20 requests" in out.stdout
    assert all(r["failed"] == 0 and r["attempted"] > 0 for r in table)


def steady(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "steady.py"),
         "--workload", "seg-dense", "--seed", str(2 ** 31 + 39), *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=MINUTE)
    return out, out.stdout.strip().splitlines()


def test_the_a_a_rehearsal_runs_the_cell_and_reports_no_value():
    out, lines = steady("--runs", "1", "--seconds", "2", "--rehearse")
    assert out.returncode == 0, out.stderr[-2000:]
    assert len(lines) == 1    # the runs' own lines went to stderr
    line = json.loads(lines[0])
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["failed"] == 0 and line["attempted"][0] > 20
    assert line["seeds"] == [2 ** 31 + 39]
    assert line["read"] == ["goodput_qps", "read_p50_ms", "read_p95_ms",
                            "setup_s"]
    assert "metrics" not in line and "verdict" not in line
    assert "limit=0" in out.stderr and "window: attempted=" in out.stderr
    assert not os.path.exists(os.path.join(ROOT, "perfbench", ".work",
                                           "seg-dense"))


def test_the_a_a_rehearsal_reads_nothing_without_a_tpu():
    out, lines = steady("--runs", "2", "--seconds", "1")
    assert out.returncode != 0 and not lines
    assert "needs 1 TPU chip" in out.stderr


@pytest.mark.parametrize("sig", ["SIGTERM", "SIGKILL"])
def test_a_run_stopped_from_outside_leaves_no_server(sig):
    import signal
    import time

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.Popen(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", "seg-dense", "--seed", "9", "--seconds", "60",
         "--trace", "0", "--rehearse"], cwd=ROOT, env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        deadline = time.monotonic() + MINUTE
        child = None
        while child is None and time.monotonic() < deadline:
            out = subprocess.run(["pgrep", "-P", str(p.pid), "-f",
                                  "pilosa_tpu"], capture_output=True,
                                 text=True).stdout.split()
            child = int(out[0]) if out else None
            time.sleep(0.2)
        assert child is not None
        p.send_signal(getattr(signal, sig))
        p.wait(timeout=30)
        for _ in range(50):
            try:
                os.kill(child, 0)
            except ProcessLookupError:
                break
            time.sleep(0.1)
        else:
            raise AssertionError(f"server {child} outlived its harness")
    finally:
        if p.poll() is None:
            p.kill()
            p.wait()
        shutil.rmtree(os.path.join(ROOT, "perfbench", ".work", "seg-dense"),
                      ignore_errors=True)
