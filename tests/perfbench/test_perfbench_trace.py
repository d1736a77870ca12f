"""trace_reduce: from an .xplane.pb to busy time, operations and gaps.

``data/two_programs.xplane.pb`` is a small trace with known numbers: one
TPU device plane whose "XLA Ops" line holds five operations of two
programs, and a host plane that must be ignored.  It was written by
``write_trace`` below (the XSpace protobuf by hand, so the file needs no
chip and no TensorFlow to make); the test reads it with the same
``jax.profiler.ProfileData`` the benchmark uses."""

import json
import os
import subprocess
import sys

import pytest

from perfbench import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE = os.path.join(HERE, "data", "two_programs.xplane.pb")
ROOT = os.path.dirname(os.path.dirname(HERE))

# (name, start_us, duration_us) on the device's "XLA Ops" line
OPS = [("fusion.1", 100, 50), ("vm_counts", 150, 100),  # back to back
       ("fusion.1", 400, 50),                           # after 150 us idle
       ("copy.3", 420, 80),                             # overlaps: to 500
       ("vm_counts", 1500, 500)]                        # after 1000 us idle
MODULES = [("jit_fused_counts(123)", 90, 170), ("jit_fused_counts(123)", 390, 120),
           ("jit_vm(7)", 1490, 520)]


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _plane(pid: int, name: str, lines: dict) -> bytes:
    names = sorted({n for evs in lines.values() for n, _, _ in evs})
    meta = {n: i + 1 for i, n in enumerate(names)}
    out = _field(1, pid) + _field(2, name)
    for lid, (lname, evs) in enumerate(lines.items()):
        line = _field(1, lid + 1) + _field(2, lname) + _field(3, 1_000_000)
        for n, start_us, dur_us in evs:
            line += _field(4, _field(1, meta[n])
                           + _field(2, start_us * 1_000_000)
                           + _field(3, dur_us * 1_000_000))
        out += _field(3, line)
    for n, i in meta.items():
        out += _field(4, _field(1, i) + _field(2, _field(1, i) + _field(2, n)))
    return out


def write_trace(path: str) -> None:
    space = _field(1, _plane(1, "/device:TPU:0", {"XLA Modules": MODULES,
                                                  "XLA Ops": OPS}))
    space += _field(1, _plane(2, "/host:CPU", {
        "python": [("$server.py handle", 0, 5000)]}))
    with open(path, "wb") as f:
        f.write(space)


def test_the_recorded_trace_is_what_write_trace_writes(tmp_path):
    write_trace(tmp_path / "t.xplane.pb")
    with open(TRACE, "rb") as a, open(tmp_path / "t.xplane.pb", "rb") as b:
        assert a.read() == b.read()


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce_planes(trace_reduce.read_planes(TRACE))


def test_busy_is_the_union_and_op_seconds_the_sum(reduced):
    assert reduced["devices"] == 1
    # [100,250) + [400,500) + [1500,2000) = 750 us busy
    assert reduced["busy_s"] == pytest.approx(750e-6)
    assert reduced["op_seconds"] == pytest.approx(780e-6)  # 30 us overlap


def test_top_ops_by_name(reduced):
    ops = dict(map(tuple, reduced["top_ops"]))
    assert list(ops) == ["jit_vm/vm_counts", "jit_fused_counts/fusion.1",
                         "jit_fused_counts/vm_counts",
                         "jit_fused_counts/copy.3"]
    assert ops["jit_vm/vm_counts"] == pytest.approx(500e-6)
    assert ops["jit_fused_counts/fusion.1"] == pytest.approx(100e-6)


def test_gaps_are_labelled_by_the_programs_around_them(reduced):
    gaps = dict(map(tuple, reduced["top_gaps"]))
    assert gaps == {
        "between launches: jit_fused_counts -> jit_vm":
            pytest.approx(1000e-6),
        "between launches: jit_fused_counts -> jit_fused_counts":
            pytest.approx(150e-6)}
    assert reduced["longest_gap_s"] == pytest.approx(1000e-6)


def test_a_trace_with_no_device_plane_has_no_device(tmp_path):
    planes = [{"name": "/host:CPU", "lines": [
        {"name": "python", "events": [("x", 0, 10)]}]}]
    assert trace_reduce.reduce_planes(planes) == {
        "devices": 0, "busy_s": 0.0, "op_seconds": 0.0, "top_ops": [],
        "top_gaps": [], "longest_gap_s": 0.0}


def test_two_devices_are_averaged():
    dev = lambda i, dur: {"name": f"/device:TPU:{i}", "lines": [  # noqa
        {"name": "XLA Ops", "events": [("op", 0, dur)]}]}
    r = trace_reduce.reduce_planes([dev(0, 1_000_000), dev(1, 3_000_000)])
    assert r["devices"] == 2 and r["busy_s"] == pytest.approx(2e-3)


def test_union_merges_touching_and_nested_intervals():
    assert trace_reduce.union([(5, 6), (0, 2), (1, 3), (3, 4), (10, 11),
                               (10.2, 10.5)]) == [(0, 4), (5, 6), (10, 11)]


def test_the_command_line_prints_one_json_object():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "trace_reduce.py"),
         TRACE], env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert json.loads(out.stdout.splitlines()[-1])["busy_s"] == \
        pytest.approx(750e-6)
