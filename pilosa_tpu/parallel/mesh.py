"""Mesh-parallel query execution: shard fan-out as SPMD over a device mesh.

The TPU-native replacement for the reference's mapReduce HTTP
scatter-gather (executor.go:2455-2608): shards stack into dense tensors
sharded over a ``jax.sharding.Mesh`` axis, per-shard set algebra runs as
one fused XLA program on every device, and cross-shard reduction rides
ICI collectives (``psum`` for counts, bitwise-OR all-reduce for row
merges) instead of HTTP responses.  Multi-host scaling uses the same code
path: the mesh spans hosts and XLA routes collectives over ICI/DCN.

Key programs:
- count_intersect: Count(Intersect(Row, Row)) — the north-star op.
- bitmap_reduce: segment-wise OR/AND/XOR merge of per-shard bitmaps.
- topn_counts: phase-1 TopN per-row counts psum'd across shards; the
  phase-2 candidate re-count of the reference's protocol
  (executor.go:860-928) collapses into the same collective because counts
  are exact (no rank-cache approximation to reconcile).
- bsi_sum: per-plane popcounts psum'd across shards (GroupBy/Sum path).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

SHARD_AXIS = "shards"


def device_mesh(n_devices: int | None = None, axis_name: str = SHARD_AXIS) -> Mesh:
    """A 1-D mesh over the shard axis.  The shard space is the only data
    dimension of a bitmap index (SURVEY.md §2.5: sharding is the
    reference's entire parallelism strategy), so a 1-D mesh is the whole
    layout; multi-host pods extend this axis across hosts."""
    devs = jax.devices()
    if n_devices is not None:
        if n_devices > len(devs):
            raise ValueError(
                f"requested {n_devices} devices, only {len(devs)} available"
            )
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis_name,))


def local_device_mesh(axis_name: str = SHARD_AXIS) -> Mesh:
    """A 1-D mesh over THIS process's devices only — the per-node fused
    executor path in a multi-process deployment.  Per-node stacks hold
    node-local fragments, so placing them on the global mesh would both
    violate jax's same-value-everywhere rule for host arrays and imply
    collectives nobody else is entering; node-local work stays local,
    and only parallel/spmd.py plans span processes."""
    return Mesh(np.array(jax.local_devices()), (axis_name,))


def shard_stack(mesh: Mesh, stack: np.ndarray):
    """Place a [shards, ...] host array sharded over the mesh axis."""
    spec = P(SHARD_AXIS, *([None] * (stack.ndim - 1)))
    return jax.device_put(stack, NamedSharding(mesh, spec))


@functools.partial(jax.jit, static_argnums=(0,))
def _count_intersect(mesh, a, b):
    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS, None), P(SHARD_AXIS, None)),
        out_specs=P(),
    )
    def step(a_blk, b_blk):
        part = jnp.sum(lax.population_count(a_blk & b_blk), dtype=jnp.int32)
        return lax.psum(part, SHARD_AXIS)

    return step(a, b)


def count_intersect(mesh: Mesh, a, b) -> int:
    """|A ∩ B| where A, B are [shards, words] stacks sharded over the mesh.
    AND + popcount fuse on-device; the only cross-device traffic is one
    scalar psum over ICI (vs the reference's per-node HTTP responses)."""
    return int(_count_intersect(mesh, a, b))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _bitmap_reduce(mesh, op: str, stacks):
    reducer = {"or": jnp.bitwise_or, "and": jnp.bitwise_and, "xor": jnp.bitwise_xor}[op]

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS, None),) * len(stacks),
        out_specs=P(SHARD_AXIS, None),
    )
    def step(*blks):
        out = blks[0]
        for b in blks[1:]:
            out = reducer(out, b)
        return out

    return step(*stacks)


def bitmap_combine(mesh: Mesh, op: str, *stacks):
    """Elementwise combine of N sharded [shards, words] stacks, output
    stays sharded in place (no collective needed — set algebra is
    embarrassingly shard-parallel, SURVEY.md §2.5)."""
    return _bitmap_reduce(mesh, op, tuple(stacks))


@functools.partial(jax.jit, static_argnums=(0,))
def _topn_counts(mesh, matrix, filt):
    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS, None, None), P(SHARD_AXIS, None)),
        out_specs=P(),
    )
    def step(mat_blk, filt_blk):
        masked = mat_blk & filt_blk[:, None, :]
        local = jnp.sum(
            lax.population_count(masked), axis=(0, 2), dtype=jnp.int32
        )
        return lax.psum(local, SHARD_AXIS)

    return step(matrix, filt)


def topn(mesh: Mesh, matrix, filt, n: int):
    """TopN over a [shards, rows, words] stack with a [shards, words]
    filter: per-row counts reduce with one psum; top-k runs replicated.
    Returns (row_slots, counts) as numpy."""
    counts = _topn_counts(mesh, matrix, filt)
    k = min(n, counts.shape[0]) if n else counts.shape[0]
    vals, idx = lax.top_k(counts, k)
    return np.asarray(idx), np.asarray(vals)


@functools.partial(jax.jit, static_argnums=(0,))
def _bsi_plane_counts(mesh, planes, filt):
    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS, None, None), P(SHARD_AXIS, None)),
        out_specs=P(),
    )
    def step(p_blk, f_blk):
        masked = p_blk & f_blk[:, None, :]
        local = jnp.sum(lax.population_count(masked), axis=(0, 2), dtype=jnp.int32)
        return lax.psum(local, SHARD_AXIS)

    return step(planes, filt)


def bsi_sum(mesh: Mesh, planes, filt) -> int:
    """Sum of BSI values across all shards: per-plane popcounts psum'd,
    weighted host-side with exact ints (fragment.sum semantics,
    fragment.go:1111, distributed)."""
    pc = np.asarray(_bsi_plane_counts(mesh, planes, filt))
    # planes layout per shard: [exists, sign-excluded magnitudes...] — the
    # caller passes magnitude planes only, pre-masked by sign.
    return sum(int(c) << i for i, c in enumerate(pc))


@functools.partial(jax.jit, static_argnums=(0,))
def _full_query_step(mesh, row_a, row_b, topn_matrix, planes):
    """The flagship sharded query pipeline as ONE compiled program:
    Count(Intersect) + TopN phase-1 + BSI plane counts, sharing the psum
    tree.  This is what dryrun_multichip compiles and runs."""

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            P(SHARD_AXIS, None),
            P(SHARD_AXIS, None),
            P(SHARD_AXIS, None, None),
            P(SHARD_AXIS, None, None),
        ),
        out_specs=(P(), P(), P()),
    )
    def step(a_blk, b_blk, mat_blk, p_blk):
        inter = a_blk & b_blk
        count = jnp.sum(lax.population_count(inter), dtype=jnp.int32)
        count = lax.psum(count, SHARD_AXIS)

        masked = mat_blk & inter[:, None, :]
        row_counts = jnp.sum(
            lax.population_count(masked), axis=(0, 2), dtype=jnp.int32
        )
        row_counts = lax.psum(row_counts, SHARD_AXIS)

        plane_counts = jnp.sum(
            lax.population_count(p_blk & a_blk[:, None, :]),
            axis=(0, 2),
            dtype=jnp.int32,
        )
        plane_counts = lax.psum(plane_counts, SHARD_AXIS)
        return count, row_counts, plane_counts

    return step(row_a, row_b, topn_matrix, planes)


def full_query_step(mesh: Mesh, row_a, row_b, topn_matrix, planes):
    return _full_query_step(mesh, row_a, row_b, topn_matrix, planes)
