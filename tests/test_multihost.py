"""Multi-host bootstrap: the single-process paths every CI run can
exercise (real pods only change env vars — SURVEY.md §5 comm backend)."""

import numpy as np
import pytest

from pilosa_tpu.parallel import mesh as pmesh
from pilosa_tpu.parallel import multihost


def test_initialize_single_process_noop():
    multihost.initialize()  # no coordinator configured: local world
    info = multihost.process_info()
    assert info["process_count"] == 1
    assert info["process_index"] == 0
    assert info["global_devices"] == info["local_devices"] == 8


def test_global_mesh_runs_collectives():
    mesh = multihost.global_mesh()
    assert mesh.devices.size == 8
    rng = np.random.default_rng(3)
    a = rng.integers(0, 1 << 32, size=(16, 64), dtype=np.uint32)
    b = rng.integers(0, 1 << 32, size=(16, 64), dtype=np.uint32)
    got = pmesh.count_intersect(mesh, pmesh.shard_stack(mesh, a),
                                pmesh.shard_stack(mesh, b))
    assert got == int(np.bitwise_count(a & b).sum())


def test_local_shard_slice_partitions_cleanly():
    sl = multihost.local_shard_slice(100)
    assert sl == range(0, 100)  # single process owns everything
    # the partition math: across k processes the blocks tile the space
    import jax

    per = -(-100 // jax.process_count())
    assert per * jax.process_count() >= 100


def test_two_process_distributed_collective(tmp_path):
    """A REAL multi-process jax.distributed run over localhost: two
    OS processes join via multihost.initialize (env-var path), build
    the global mesh spanning both processes' devices, and one psum
    crosses the process boundary with an exact result — the DCN
    data-plane story in miniature (SURVEY.md §5 comm backend)."""
    import os
    import socket
    import subprocess
    import sys

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    worker = tmp_path / "worker.py"
    worker.write_text("""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)
from pilosa_tpu.parallel import multihost, mesh as pmesh

multihost.initialize()  # env-var path: coordinator/count/id from env
info = multihost.process_info()
assert info["process_count"] == 2, info
assert info["global_devices"] == 4, info

import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

mesh = multihost.global_mesh()
rng = np.random.default_rng(0)
a = rng.integers(0, 1 << 32, size=(8, 64), dtype=np.uint32)
b = rng.integers(0, 1 << 32, size=(8, 64), dtype=np.uint32)
sharding = NamedSharding(mesh, P(pmesh.SHARD_AXIS, None))
a_g = jax.make_array_from_callback((8, 64), sharding, lambda i: a[i])
b_g = jax.make_array_from_callback((8, 64), sharding, lambda i: b[i])
got = pmesh.count_intersect(mesh, a_g, b_g)
want = int(np.bitwise_count(a & b).sum())
assert got == want, (got, want)
sl = multihost.local_shard_slice(8)
assert len(sl) == 4  # half the shard space per process
print(f"OK {got}")
""")

    env = dict(os.environ)
    env.update(
        JAX_COORDINATOR_ADDRESS=f"127.0.0.1:{port}",
        JAX_NUM_PROCESSES="2",
        PYTHONPATH=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))) + os.pathsep
        + env.get("PYTHONPATH", ""),
    )
    procs = []
    for pid in (0, 1):
        e = dict(env, JAX_PROCESS_ID=str(pid))
        procs.append(subprocess.Popen(
            [sys.executable, str(worker)], env=e,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = [p.communicate(timeout=120)[0] for p in procs]
    for p, out in zip(procs, outs):
        if "Multiprocess computations aren't implemented" in out:
            # this jaxlib's CPU backend has no cross-process
            # collectives at all — an environment limitation, not a
            # product regression
            pytest.skip("jax CPU backend lacks multiprocess collectives")
        assert p.returncode == 0, out[-2000:]
    counts = {out.strip().splitlines()[-1] for out in outs}
    assert len(counts) == 1 and next(iter(counts)).startswith("OK ")


def test_peer_death_mid_collective_is_fail_stop_not_deadlock(tmp_path):
    """Measured failure semantics of the collective plane (documented
    in spmd.try_collective / docs/architecture.md): when a participant
    dies before entering a collective the survivor is TERMINATED by
    the jax.distributed coordination service after the heartbeat
    window — no exception, no hang, no wrong answer.  This test pins
    the two properties the design relies on: boundedness (the
    survivor's wait is capped by PILOSA_TPU_DIST_HEARTBEAT_S) and
    fail-stop (the survivor never completes the collective)."""
    import os
    import socket
    import subprocess
    import sys
    import time

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    worker = tmp_path / "worker.py"
    worker.write_text("""
import os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)
from pilosa_tpu.parallel import multihost

multihost.initialize()
pid = int(os.environ["JAX_PROCESS_ID"])
print(f"init {pid}", flush=True)
if pid == 1:
    os._exit(1)  # abrupt death between promise and entry
import jax.numpy as jnp
from jax.experimental import multihost_utils

out = multihost_utils.process_allgather(jnp.ones(4))
print("COMPLETED-COLLECTIVE", out, flush=True)  # must never print
""")

    env = dict(os.environ)
    env.update(
        JAX_COORDINATOR_ADDRESS=f"127.0.0.1:{port}",
        JAX_NUM_PROCESSES="2",
        PILOSA_TPU_DIST_HEARTBEAT_S="10",
        PYTHONPATH=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))) + os.pathsep
        + env.get("PYTHONPATH", ""),
    )
    procs = []
    for pid in (0, 1):
        e = dict(env, JAX_PROCESS_ID=str(pid))
        procs.append(subprocess.Popen(
            [sys.executable, str(worker)], env=e,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    t0 = time.monotonic()
    # generous bound: heartbeat 10 s + polling/teardown margin; the
    # point is "minutes, not forever" — and nowhere near the 120 s cap
    outs = [p.communicate(timeout=120)[0] for p in procs]
    elapsed = time.monotonic() - t0
    assert procs[1].returncode == 1
    # fail-stop: the survivor terminated (nonzero) without completing
    assert procs[0].returncode != 0, outs[0][-2000:]
    assert "COMPLETED-COLLECTIVE" not in outs[0], outs[0][-2000:]
    assert elapsed < 90, f"unpark took {elapsed:.0f}s"
