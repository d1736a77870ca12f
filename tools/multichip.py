"""Multichip capture harness: the MULTICHIP_r*.json body producer.

Runs the multi-device dry run (``__graft_entry__.dryrun_multichip``)
plus a mesh-on/mesh-off A/B of the fused serving engine on an
n-device mesh, and emits ONE structured JSON body carrying the device
count and topology, so the artifact stands alone.

The A/B measures the batch32 coalesced-path workload (bench.py's
batched engine: one fused Count(Intersect) program over a [32, S, W]
operand stack) three ways:

- ``mesh``   — the shard_map program over the n-device mesh
  (parallel/meshexec.py; ONE launch spans every device, per-shard
  counts return through the shard-axis all_gather);
- ``single`` — the identical program on one device (the pre-mesh
  path, what ``?nomesh=1`` runs);
- every sampled batch is verified bit-exact against a host numpy
  recomputation before its timing counts.

Usage::

    python -m tools.multichip [--devices N] [--shards S] [--batch B]
                              [--seconds T]

Prints the JSON body on stdout.  The mesh is a VIRTUAL CPU mesh: this
checks sharding, and says nothing about chips.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def _setup(n_devices: int) -> None:
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"  # before any jax import
    sys.path.insert(0, __file__.rsplit("/", 2)[0])
    import __graft_entry__ as ge

    ge._force_virtual_cpu_mesh(n_devices)


def measure(n_devices: int, shards: int = 64, batch: int = 32,
            seconds: float = 2.0, words: int = 1 << 13) -> dict:
    """The mesh-on/mesh-off A/B on the current backend.  Returns the
    ``mesh`` axis dict: devices, qps per engine, launches/query, and
    the scaling ratio."""
    import numpy as np

    import jax
    from pilosa_tpu.ops import bitmap as bm
    from pilosa_tpu.ops import expr
    from pilosa_tpu.parallel import meshexec

    meshexec.configure(enabled=True, axis_size=n_devices)
    mesh = meshexec.active_mesh()
    assert mesh is not None and mesh.size == n_devices, (
        "mesh failed to activate", n_devices)

    rng = np.random.default_rng(7)
    # shard axis padded to the mesh multiple, exactly as
    # Field.device_row_stack pads
    pad = ((shards + n_devices - 1) // n_devices) * n_devices
    a = np.zeros((batch, pad, words), dtype=np.uint32)
    b = np.zeros((batch, pad, words), dtype=np.uint32)
    a[:, :shards] = rng.integers(0, 1 << 32,
                                 size=(batch, shards, words),
                                 dtype=np.uint32)
    b[:, :shards] = rng.integers(0, 1 << 32,
                                 size=(batch, shards, words),
                                 dtype=np.uint32)
    want = np.unpackbits((a & b).view(np.uint8),
                         axis=-1).sum(axis=(1, 2)).astype(np.int64)
    shape = ("and", ("leaf", 0), ("leaf", 1))

    def run(use_mesh: bool) -> dict:
        m = mesh if use_mesh else None
        if use_mesh:
            ad = meshexec.ensure_placed(jax.numpy.asarray(a), mesh, 1)
            bd = meshexec.ensure_placed(jax.numpy.asarray(b), mesh, 1)
        else:
            ad = jax.device_put(a)
            bd = jax.device_put(b)
        # warm (compile) + verify bit-exactness vs the host truth
        with bm.dispatch_counter() as dc:
            out = expr.evaluate(shape, (ad, bd), counts=True, mesh=m)
        got = np.asarray(out, dtype=np.int64).sum(axis=-1)
        assert np.array_equal(got, want), "bit-exactness violated"
        launches_per_query = dc.n / batch
        reps = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            out = expr.evaluate(shape, (ad, bd), counts=True, mesh=m)
            jax.block_until_ready(out)
            reps += 1
        dt = time.perf_counter() - t0
        return {
            "qps": round(batch * reps / dt, 2),
            "launches_per_query": launches_per_query,
            "reps": reps,
        }

    single = run(False)
    meshed = run(True)
    return {
        "devices": n_devices,
        "shards": shards,
        "batch": batch,
        "words": words,
        "qps": meshed["qps"],
        "launches_per_query": meshed["launches_per_query"],
        "qps_single_device": single["qps"],
        "scaling_vs_single": round(meshed["qps"] / single["qps"], 3)
        if single["qps"] else None,
        "counters": meshexec.counters(),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--devices", type=int, default=4)
    ap.add_argument("--shards", type=int, default=64)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--words", type=int, default=1 << 13)
    ap.add_argument("--skip-dryrun", action="store_true",
                    help="A/B only (bench.py's extras.mesh mode)")
    args = ap.parse_args(argv)

    _setup(args.devices)
    import jax

    devs = jax.devices()
    body: dict = {
        "devices": len(devs),
        "platform": devs[0].platform,
        "topology": [{"id": d.id, "process": d.process_index,
                      "kind": getattr(d, "device_kind", "")}
                     for d in devs],
    }
    if not args.skip_dryrun:
        import __graft_entry__ as ge

        ge.dryrun_multichip(args.devices)
        body["dryrun_ok"] = True
    body["mesh"] = measure(args.devices, shards=args.shards,
                           batch=args.batch, seconds=args.seconds,
                           words=args.words)
    print(json.dumps(body))
    return 0


if __name__ == "__main__":
    sys.exit(main())
