"""Op-tape interpreter: one device launch for a batch of
heterogeneous-shape expression trees.

The fused compiler (ops/expr.py) erases leaf VALUES from a tree, so
concurrent queries with the same STRUCTURE share one compiled program
and one launch (parallel/coalescer.py).  Real mixed dashboard traffic
is structurally diverse, though — many users, many distinct
Count/Row trees — and every launch pays a fixed dispatch cost, so
each distinct shape paying its own launch loses qps per chip on
diverse traffic.

This module erases the STRUCTURE too.  Each tree compiles to a flat
op-tape — an opcode stream over a register file, leaves pre-loaded
into the low registers — and a *batch* of tapes pads to a small set of
pow2 size buckets (tape length x leaf-slot count, mirroring the
coalescer's pow2 batch padding).  One jitted program per bucket then
executes the whole batch: ``lax.scan`` over tape steps, ``lax.switch``
on the per-query opcode (under ``vmap`` the switch lowers to a select
over the five bitwise ops — all cheap next to the register-file
reads), each step writing its result register with
``dynamic_update_slice``.  A Count root folds its popcount+reduce into
the same program, exactly like the fused path.  This is the
ragged-rows-in-one-kernel design of Ragged Paged Attention and
DrJAX's batched map primitives (PAPERS.md), applied to expression
trees instead of attention rows: each query's variable-depth tree is
one ragged row of a single batched launch.

Tape grammar (compiled from the ops/expr shape grammar):

    opcodes   AND OR XOR ANDNOT COPY
    operands  i >= 0  -> leaf slot i
              i <  0  -> instruction ~i's output register
    ``not``   -> ANDNOT(exist, child)
    ``dfuse`` -> OR(ANDNOT(child, clear), set)   (two instructions)
    ``shift`` is NOT tape-eligible (its distance is baked into the
    compiled program, not an operand) — shift-carrying shapes fall
    back to the per-shape fused path.

Instruction ``t`` writes register ``n_slots + t``; buckets pad short
tapes with COPYs of the final real register, so the LAST register
always holds the result after the scan.  Pad leaf slots are zero
stacks and pad batch rows are all-COPY tapes over them — never read
by a real query's operands, never scattered back.

Host stacks (single-CPU-device mode) interpret the tapes eagerly in
numpy — dispatch is free there — and still tick ONE ``note_dispatch``
for the whole batch, keeping launch accounting meaningful across
engines.  Bit-exactness against ``ops/expr._host_tree`` /
``_host_counts`` is pinned by tests/test_tape.py.
"""

from __future__ import annotations

import threading
from collections import namedtuple
from collections.abc import Callable, Sequence
from typing import Any

import numpy as np

from pilosa_tpu import observe as _observe
from pilosa_tpu import perfobs as _perfobs
from pilosa_tpu.ops import bitmap as bm

OP_AND, OP_OR, OP_XOR, OP_ANDNOT, OP_COPY = range(5)

_FOLD_OPS = {"and": OP_AND, "or": OP_OR, "xor": OP_XOR,
             "andnot": OP_ANDNOT}

#: Smallest bucket edge for both axes: rounding tiny tapes up to 4
#: wastes a few no-op COPY steps but collapses the (1, 2, 4) size
#: classes into one — fewer lowered program variants AND better batch
#: occupancy for shallow-tree traffic (a Count(Row) and a
#: Count(Intersect(Row, Row)) share a launch).
MIN_BUCKET = 4

#: Default per-query caps (the ``[ragged]`` config): a tape longer
#: than ``max-tape`` — or a tree with more leaves than ``max-leaves``
#: — falls back to the per-shape fused path for that query alone.
DEFAULT_MAX_TAPE = 32
DEFAULT_MAX_LEAVES = 16


class TapeError(ValueError):
    """The shape cannot compile to a tape (unsupported node, bad leaf
    ref, or over the configured length cap)."""


#: One compiled tape: ``instrs`` is a tuple of (opcode, a, b) with the
#: symbolic operand encoding above; ``n_leaves`` the number of leaf
#: slots the operands reference.
Tape = namedtuple("Tape", ("instrs", "n_leaves"))


# ------------------------------------------------------------- compiler


def compile_shape(shape, n_leaves: int, max_len: int | None = None) -> Tape:
    """Compile one ops/expr shape into a Tape (post-order emission).
    Raises TapeError on shift nodes (structurally ineligible), unknown
    nodes, out-of-range leaf slots, or a tape longer than ``max_len``.
    """
    instrs: list[tuple[int, int, int]] = []

    def emit(op: int, a: int, b: int) -> int:
        instrs.append((op, a, b))
        return ~(len(instrs) - 1)

    def go(node: tuple) -> int:
        kind = node[0]
        if kind == "leaf":
            slot = node[1]
            if not 0 <= slot < n_leaves:
                raise TapeError(f"leaf slot {slot} out of range")
            return slot
        if kind in _FOLD_OPS:
            if len(node) < 2:
                raise TapeError(f"{kind} needs at least one child")
            op = _FOLD_OPS[kind]
            ref = go(node[1])
            for child in node[2:]:
                ref = emit(op, ref, go(child))
            return ref
        if kind == "not":
            # exist & ~child — one ANDNOT, same algebra as the fused
            # engine (expr._build_jnp)
            return emit(OP_ANDNOT, go(node[1]), go(node[2]))
        if kind == "dfuse":
            # (child & ~clear) | set — the streaming-ingest overlay
            if len(node) != 4:
                raise TapeError("dfuse needs (child, set, clear)")
            child = go(node[1])
            dset = go(node[2])
            dclear = go(node[3])
            return emit(OP_OR, emit(OP_ANDNOT, child, dclear), dset)
        if kind == "shift":
            raise TapeError("shift is not tape-eligible")
        raise TapeError(f"unknown expression node: {kind!r}")

    root = go(shape)
    if root >= 0:
        # pure-leaf (or single-child fold) root: materialize it into a
        # register so the result always lives in the last one
        root = emit(OP_COPY, root, 0)
    if max_len is not None and len(instrs) > max_len:
        raise TapeError(
            f"tape length {len(instrs)} exceeds cap {max_len}")
    return Tape(tuple(instrs), n_leaves)


def try_compile(shape, n_leaves: int,
                max_len: int | None = None) -> Tape | None:
    """``compile_shape`` that reports ineligibility via counters
    instead of raising — the coalescer's per-query fallback gate."""
    try:
        return compile_shape(shape, n_leaves, max_len)
    except TapeError as e:
        bump("tape.oversize_fallbacks" if "exceeds cap" in str(e)
             else "tape.unsupported")
        return None


def _pow2(n: int) -> int:
    b = 1
    while b < n:
        b <<= 1
    return b


def size_class(n_instrs: int, n_leaves: int) -> tuple[int, int]:
    """The (tape_len, leaf_slots) bucket a tape pads into: pow2 on
    both axes with a MIN_BUCKET floor.  Lowered-variant count stays
    O(log(max_tape) * log(max_leaves)) while heterogeneous shapes of
    similar size share one launch."""
    return (max(MIN_BUCKET, _pow2(max(1, n_instrs))),
            max(MIN_BUCKET, _pow2(max(1, n_leaves))))


# ------------------------------------------------------------- counters

_lock = threading.Lock()
_counters = {
    "tape.executions": 0,         # interpreter launches (device or host)
    "tape.queries": 0,            # queries served through those launches
    "tape.oversize_fallbacks": 0,  # per-query cap fallbacks to fused path
    "tape.unsupported": 0,        # structurally ineligible (shift) shapes
    "tape.prewarmed": 0,          # bucket programs lowered at server start
    "coalescer.shape_misses": 0,  # eligible queries with no same-shape
                                  # partner in their flushed batch
    "coalescer.shape_flushes": 0,  # flushes carrying >1 distinct shape
    "vm.executions": 0,           # bitmap-VM launches (pallas/jnp/host)
    "vm.queries": 0,              # queries served through those launches
    "vm.fallbacks": 0,            # VM-gated queries routed to the dense
                                  # ragged/fused engines instead
    # per-reason breakout of WHY a VM-gated query fell back (the
    # central vm.fallbacks stays the authoritative total; mesh_active
    # is informational only — a mesh route is not a degradation)
    "vm.fallbacks.disabled": 0,       # containers runtime disabled
    "vm.fallbacks.ineligible_leaf": 0,  # non-container-eligible leaf /
                                        # dense-slot directory
    "vm.fallbacks.kind_unsupported": 0,  # directory carries a kind
                                         # byte with no VM decode arm
    "vm.fallbacks.oversize": 0,       # tape/leaf caps exceeded
    "vm.fallbacks.max_prefetch": 0,   # single query blows the scalar
                                      # prefetch budget
    "vm.fallbacks.min_domain": 0,     # ...and only because of the
                                      # configured min-domain floor
    "vm.fallbacks.mesh_active": 0,    # mesh routing took the query
}
#: (counts, B, tape_len, slots, *stack_shape) combos the interpreter
#: has lowered — the /debug/ragged program inventory.
_lowered: set[tuple] = set()
#: (B, tape_len, slots, domain) combos the bitmap VM has lowered —
#: the /debug/ragged "vm" program inventory.
_vm_lowered: set[tuple] = set()
#: What the open-time warm-up did — the /debug/ragged "prewarm"
#: section.  ``state`` is idle | running | done | failed; a failure
#: keeps its error here (and in the server log) instead of vanishing
#: into a "skipped" line: on an accelerator a program that does not
#: compile is a finding, not a detail.
_prewarm_state: dict[str, Any] = {"state": "idle", "warmed": 0,
                                  "skipped": [], "error": None}


def bump(name: str, value: int = 1, also: str | None = None) -> None:
    """``also``: a second counter that moves with the first (a reason
    cell and its total), under the same take of the lock."""
    with _lock:
        _counters[name] += value
        if also is not None:
            _counters[also] += value


def counters() -> dict[str, int]:
    with _lock:
        return dict(_counters)


def reset_counters() -> None:
    """Zero the module counters and the lowered-program inventory
    (tests)."""
    with _lock:
        for k in _counters:
            _counters[k] = 0
        _lowered.clear()
        _vm_lowered.clear()
        _prewarm_state.update(state="idle", warmed=0, skipped=[],
                              error=None)


def publish_gauges(stats: Any) -> None:
    """Push the tape.* / coalescer.shape_* families into a stats
    registry at scrape time — cumulative values as gauges, same rule
    as resultcache/devobs publish_gauges (re-publishing a cumulative
    total through a counter would double-count)."""
    for name, value in counters().items():
        stats.gauge(name, value)


def debug() -> dict[str, Any]:
    """The /debug/ragged document body: counters plus the interpreter
    program inventory (which bucket variants this process has
    lowered)."""
    with _lock:
        progs = [{"counts": c, "batch": b, "tapeLen": t, "slots": s,
                  "stack": list(shape)}
                 for (c, b, t, s, *shape) in sorted(_lowered)]
        vm_progs = [{"batch": b, "tapeLen": t, "slots": s, "domain": d}
                    for (b, t, s, d) in sorted(_vm_lowered)]
        reasons = {k.split(".", 2)[2]: v for k, v in _counters.items()
                   if k.startswith("vm.fallbacks.")}
        return {"counters": dict(_counters), "programs": progs,
                "prewarm": dict(_prewarm_state,
                                skipped=list(_prewarm_state["skipped"])),
                "vm": {"programs": vm_progs,
                       "fallbackReasons": reasons}}


# ------------------------------------------------------------ interpreter


def _abs_operand(ref: int, n_slots: int) -> int:
    """Symbolic operand -> absolute register index in a bucket with
    ``n_slots`` leaf registers."""
    return ref if ref >= 0 else n_slots + ~ref


_programs: dict = {}


def _one_query(counts: bool) -> Callable[..., Any]:
    """The per-query scan/switch interpreter body, shared verbatim by
    the single-device program and the shard_map mesh variant — the
    two routes cannot drift because they trace the same closure."""
    import jax.numpy as jnp
    from jax import lax

    def one(tape_q: Any, leaves_q: Any) -> Any:
        n_slots = leaves_q.shape[0]
        tape_len = tape_q.shape[0]
        regs0 = jnp.concatenate(
            [leaves_q,
             jnp.zeros((tape_len,) + leaves_q.shape[1:],
                       leaves_q.dtype)])

        def step(regs: Any, xs: Any) -> tuple[Any, None]:
            instr, t = xs
            xa = regs[instr[1]]
            xb = regs[instr[2]]
            out = lax.switch(instr[0], (
                lambda a, b: jnp.bitwise_and(a, b),
                lambda a, b: jnp.bitwise_or(a, b),
                lambda a, b: jnp.bitwise_xor(a, b),
                lambda a, b: jnp.bitwise_and(a, jnp.bitwise_not(b)),
                lambda a, b: a,
            ), xa, xb)
            regs = lax.dynamic_update_slice(
                regs, out[None], (n_slots + t,) + (0,) * out.ndim)
            return regs, None

        regs, _ = lax.scan(step, regs0,
                           (tape_q, jnp.arange(tape_len)))
        res = regs[-1]
        if counts:
            return jnp.sum(lax.population_count(res), axis=-1,
                           dtype=jnp.int32)
        return res

    return one


def _program(counts: bool) -> Callable[..., Any]:
    """The ONE vmapped scan/switch interpreter per root kind, jitted —
    jax re-lowers it per (batch, tape_len, slots, stack) input shape,
    which is exactly the bucket structure; the Python closure is
    shared.  devobs-instrumented so first lowerings surface on
    /debug/devices and ride the paying query's flight record."""
    prog = _programs.get(counts)
    if prog is not None:
        return prog
    import jax

    from pilosa_tpu import devobs

    one = _one_query(counts)
    name = "tape.interpret_counts" if counts else "tape.interpret"
    prog = devobs.jit(name, jax.vmap(one))
    _programs[counts] = prog
    return prog


def _mesh_program(counts: bool, mesh: Any) -> Callable[..., Any]:
    """The mesh-native interpreter (parallel/meshexec.py): the SAME
    vmapped scan/switch body runs per device on shard-axis blocks of
    the batched register file under ``shard_map`` — tapes replicate
    (they are tiny int32 control words), leaf stacks shard on the
    shard axis (dim 2 of the [B, slots, S, W] batch), and a Count
    root all_gathers the per-shard popcounts back so the output is
    bit-identical to the single-device interpreter.  One launch then
    executes the whole heterogeneous megabatch across every mesh
    chip.  Cached per (root kind, mesh) — the Mesh is a meshexec
    singleton."""
    key = (counts, mesh)
    prog = _programs.get(key)
    if prog is not None:
        return prog
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from pilosa_tpu import devobs
    from pilosa_tpu.parallel import meshexec
    from pilosa_tpu.parallel.mesh import shard_map

    one = _one_query(counts)
    leaf_spec = P(None, None, meshexec.SHARD_AXIS, None)

    def body(tapes_blk: Any, leaves_blk: Any) -> Any:
        out = jax.vmap(one)(tapes_blk, leaves_blk)
        if counts:
            return lax.all_gather(out, meshexec.SHARD_AXIS,
                                  axis=1, tiled=True)
        return out

    sm = shard_map(body, mesh=mesh, in_specs=(P(), leaf_spec),
                   out_specs=(P() if counts
                              else P(None, meshexec.SHARD_AXIS, None)),
                   check_vma=False)

    def run(tapes: Any, leaves: Any) -> Any:
        return sm(tapes, leaves)

    name = ("tape.mesh_interpret_counts" if counts
            else "tape.mesh_interpret")
    prog = devobs.jit(name, run)
    _programs[key] = prog
    return prog


def _host_exec(tp: Tape, leaves: tuple, counts: bool) -> np.ndarray:
    """Eager numpy interpretation of one tape (host-mode engine)."""
    outs: list[np.ndarray] = []

    def operand(ref: int) -> np.ndarray:
        return leaves[ref] if ref >= 0 else outs[~ref]

    for op, a, b in tp.instrs:
        xa = operand(a)
        if op == OP_COPY:
            outs.append(xa)
            continue
        xb = operand(b)
        if op == OP_AND:
            outs.append(np.bitwise_and(xa, xb))
        elif op == OP_OR:
            outs.append(np.bitwise_or(xa, xb))
        elif op == OP_XOR:
            outs.append(np.bitwise_xor(xa, xb))
        else:
            outs.append(np.bitwise_and(xa, np.bitwise_not(xb)))
    res = outs[-1]
    if counts:
        from pilosa_tpu.ops import hostkernels as hk

        lead = res.shape[:-1]
        return hk.row_counts(
            res.reshape(-1, res.shape[-1])).reshape(lead)
    return res


def execute(batch: Sequence[tuple[Tape, tuple]], counts: bool = False,
            tape_len: int | None = None,
            slots: int | None = None, mesh: Any = None) -> list[Any]:
    """Execute a batch of (Tape, leaves) pairs in ONE launch.

    Every query's leaf stacks must share one array shape (the
    coalescer's bucket key guarantees it).  ``tape_len``/``slots`` pin
    the bucket the batch pads into (defaults: the batch's own pow2
    size class).  Returns one result per query, in order — the bitmap
    stack, or int32 per-row popcounts with ``counts=True``.  Pad rows
    (batch pow2, slot and tape padding) are never returned.

    ``mesh`` (meshexec.query_mesh) routes the shard_map interpreter:
    the batch's register file shards on the stack's shard axis and
    the one launch spans every mesh device, bit-identically.  None
    (and host mode) keeps the existing engines.
    """
    if not batch:
        return []
    tb, lb = size_class(max(len(t.instrs) for t, _ in batch),
                        max(t.n_leaves for t, _ in batch))
    tape_len = tape_len or tb
    slots = slots or lb
    for tp, ls in batch:
        if len(tp.instrs) > tape_len or len(ls) > slots:
            raise TapeError("tape exceeds its bucket")
    n = len(batch)
    bm.note_dispatch("tape")
    bump("tape.executions")
    bump("tape.queries", n)
    if all(isinstance(lv, np.ndarray) for _, ls in batch for lv in ls):
        t0 = _perfobs.t0()
        outs = [_host_exec(tp, ls, counts) for tp, ls in batch]
        _perfobs.sample(
            "tape", outs, t0,
            nbytes=sum(lv.nbytes for _, ls in batch for lv in ls)
            + sum(getattr(o, "nbytes", 0) for o in outs))
        return outs

    import jax.numpy as jnp

    first = batch[0][1][0]
    stack_shape = tuple(first.shape)
    # batch pads to the next power of two, like the coalescer's device
    # batches: the jitted interpreter re-lowers per input shape, and
    # free-running occupancies would each pay a fresh XLA compile in
    # the serving path
    b_pad = _pow2(n)
    # the eager stacking of the batch's operands into one register
    # file: host work plus the jit_broadcast_in_dim / jit_concatenate
    # programs of the device trace, apart from the launch itself
    with _observe.span("launch.stack", batch=n, padded=b_pad):
        zero = jnp.zeros(stack_shape, first.dtype)
        tape_rows = np.zeros((b_pad, tape_len, 3), dtype=np.int32)
        tape_rows[:, :, 0] = OP_COPY  # pad rows: COPY of leaf slot 0
        leaf_rows = []
        pad_leaves = None
        for qi in range(b_pad):
            if qi >= n:
                if pad_leaves is None:
                    pad_leaves = jnp.stack([zero] * slots)
                leaf_rows.append(pad_leaves)
                continue
            tp, ls = batch[qi]
            for ti, (op, a, b) in enumerate(tp.instrs):
                tape_rows[qi, ti] = (op, _abs_operand(a, slots),
                                     _abs_operand(b, slots))
            final = slots + len(tp.instrs) - 1
            # short tapes chain COPYs of the final real register
            # forward, so the LAST register holds the result after the
            # full scan
            tape_rows[qi, len(tp.instrs):, 1] = final
            leaf_rows.append(jnp.stack(
                list(ls) + [zero] * (slots - len(ls))))
        leaves_arr = jnp.stack(leaf_rows)
    with _lock:
        _lowered.add((counts, b_pad, tape_len, slots) + stack_shape)
    t0 = _perfobs.t0()
    if mesh is not None:
        from pilosa_tpu.parallel import meshexec

        if len(stack_shape) >= 2 and meshexec.shardable(
                mesh, stack_shape[0]):
            meshexec.note_launch(n)
            tapes_dev = meshexec.ensure_replicated(
                jnp.asarray(tape_rows), mesh)
            leaves_dev = meshexec.ensure_placed(leaves_arr, mesh, 2)
            # dispatch under the process-wide mesh launch lock (see
            # meshexec.launch_lock: concurrent collective dispatches
            # can deadlock the backend)
            with meshexec.launch_lock():
                out = _mesh_program(counts, mesh)(tapes_dev,
                                                  leaves_dev)
            # the perfobs block waits OUTSIDE the launch lock
            _perfobs.sample(
                "mesh", out, t0,
                nbytes=leaves_arr.nbytes + tape_rows.nbytes
                + getattr(out, "nbytes", 0))
            return [out[i] for i in range(n)]
    out = _program(counts)(jnp.asarray(tape_rows), leaves_arr)
    _perfobs.sample("tape", out, t0,
                    nbytes=leaves_arr.nbytes + tape_rows.nbytes
                    + getattr(out, "nbytes", 0))
    return [out[i] for i in range(n)]


def execute_vm(batch: Sequence[tuple[Tape, list]], pool: Any,
               zero_index: int, tape_len: int | None = None,
               slots: int | None = None, interpret: bool = False,
               max_prefetch: int | None = None) -> list[np.ndarray]:
    """Execute a megabatch of (Tape, gather rows) queries over ONE
    pooled compressed operand as ONE bitmap-VM launch
    (ops/pallas_kernels.vm_counts).

    Each query's second element is its per-leaf-slot list of int32[D]
    GLOBAL pool row indices (the coalescer globalizes the staged
    per-leaf directories against the bucket megapool —
    ops/containers.megapool); every query in the batch shares one
    domain width D.  ``zero_index`` is the megapool's canonical
    all-zero row: pad slots, pad batch rows and absent containers all
    gather it and contribute nothing.  Returns one int64[D] per-cell
    count vector per query, in order — the query's total is the plain
    sum (there is no shard-row alignment to trim; the domain already
    concatenated the per-shard walks).

    ``max_prefetch`` bounds the scalar-prefetch directory
    (slots x batch x D int32 entries live in SMEM on chip): an
    oversized batch splits in half recursively, each half its own
    launch — the ≤2-launch degradation the acceptance pin allows."""
    if not batch:
        return []
    tb, lb = size_class(max(len(t.instrs) for t, _ in batch),
                        max(t.n_leaves for t, _ in batch))
    tape_len = tape_len or tb
    slots = slots or lb
    for tp, idxs in batch:
        if len(tp.instrs) > tape_len or len(idxs) > slots:
            raise TapeError("tape exceeds its bucket")
    n = len(batch)
    D = len(batch[0][1][0])
    b_pad = _pow2(n)
    if (max_prefetch is not None and n > 1
            and slots * b_pad * D > max_prefetch):
        mid = (n + 1) // 2
        return (execute_vm(batch[:mid], pool, zero_index, tape_len,
                           slots, interpret, max_prefetch)
                + execute_vm(batch[mid:], pool, zero_index, tape_len,
                             slots, interpret, max_prefetch))
    bm.note_dispatch("vm")
    bump("vm.executions")
    bump("vm.queries", n)
    with _observe.span("launch.stack", batch=n, padded=b_pad):
        prog = np.zeros((b_pad, tape_len, 3), dtype=np.int32)
        prog[:, :, 0] = OP_COPY  # pad rows: COPY of leaf slot 0
        gidx = np.full((slots, b_pad, D), zero_index, dtype=np.int32)
        for qi, (tp, idxs) in enumerate(batch):
            for ti, (op, a, b) in enumerate(tp.instrs):
                prog[qi, ti] = (op, _abs_operand(a, slots),
                                _abs_operand(b, slots))
            final = slots + len(tp.instrs) - 1
            # short tapes chain COPYs of the final real register
            # forward, exactly like execute() — the LAST register holds
            # the result
            prog[qi, len(tp.instrs):, 1] = final
            for li, ix in enumerate(idxs):
                gidx[li, qi, :len(ix)] = ix
    with _lock:
        _vm_lowered.add((b_pad, tape_len, slots, D))
    from pilosa_tpu.ops import pallas_kernels as pk

    t0 = _perfobs.t0()
    out = pk.vm_counts(pool, prog, gidx, interpret=interpret)
    # what the VM launch actually touches: the gathered container
    # blocks (every directory entry DMAs one pool row), the SMEM
    # directory + programs, and the count outputs — never the dense
    # register file (the engine's whole point).  A kind-split megapool
    # bundle (containers.MegaPools) samples as its own engine cell —
    # the launch's decode arms are a different cost shape than the
    # plain dense-pool gather
    from pilosa_tpu.ops import containers as _containers

    if isinstance(pool, _containers.MegaPools):
        engine = "vm_kinds"
        touched = int(pool.nbytes)
    else:
        engine = "vm"
        cwords = int(pool.shape[-1]) if getattr(pool, "ndim", 0) else 0
        touched = gidx.size * cwords * 4
    # the sample waits for the kernel (launch.ready); the int64 copy to
    # the host after it is the caller's reduce
    _perfobs.sample(engine, out, t0,
                    nbytes=touched + gidx.nbytes
                    + prog.nbytes + out.size * 8)
    cts = np.asarray(out, dtype=np.int64)
    return [cts[i] for i in range(n)]


# --------------------------------------------------------------- prewarm


def _prewarm_worthwhile() -> bool:
    """Whether lowering interpreter programs ahead of traffic pays on
    THIS process's devices.  Host mode runs the numpy engine (nothing
    to lower); CPU backends — one device or a virtual multi-device
    test mesh alike — lower these programs cheaply on first use while
    the warm-up's register file (batch x (slots + tape) x stack
    words) would transiently cost real host memory.  Accelerator
    backends pay multi-hundred-ms serving-path compiles, which is
    what prewarm exists to move off the first window."""
    import jax

    if bm.host_mode():
        return False
    return jax.devices()[0].platform != "cpu"


def note_prewarm(state: str, error: str | None = None) -> None:
    """Record the open-time warm-up's progress (server/server.py);
    ``running`` starts a fresh account."""
    with _lock:
        if state == "running":
            _prewarm_state.update(warmed=0, skipped=[])
        _prewarm_state.update(state=state, error=error)


def prewarm(stack_shape: tuple[int, ...], max_batch: int,
            max_tape: int, max_leaves: int,
            counts: bool = True, mesh: Any = None,
            budget_bytes: int | None = None) -> int:
    """Lower the bucket programs a serving process will hit first.
    Flushes pad the BATCH axis to pow2(occupancy), so a window
    sealing at 5 queries dispatches a b=8 program — warming only the
    full batch width would leave every partially-filled first window
    paying a serving-path XLA compile (the convoy the pow2 padding
    exists to kill).  So: the smallest size class (where shallow-tree
    traffic lands) warms across the whole pow2 batch ladder
    2..pow2(max_batch), and the largest class (the configured caps,
    the worst single compile) warms at full width.

    The programs warmed are keyed on the ACTUAL device layout:
    ``mesh`` (the caller's meshexec.active_mesh(), threaded from
    server open) selects the shard_map interpreter variants, and its
    absence the single-device ones — so a 1-device process never
    lowers mesh-shaped programs and an N-device mesh never wastes its
    warm-up on programs serving traffic won't run.  ``stack_shape``
    must carry the same device-count-derived padding serving stacks
    get (models/field._padded_rows).

    Warming RUNS each program on zero stacks, so a job holds its
    operands plus the interpreter's register file in device memory:
    ``b x (slots + 2 x (slots + tape_len))`` stacks (the scan carries
    the register file in and out; XLA's own memory analysis of the
    small class comes to 16 stacks per batch row, so the bound errs on
    the safe side).  At 256 shards a stack is 32 MiB and the b=32
    small-class job alone is 20 GiB, so a job larger than
    ``budget_bytes`` (the device memory the residency budget leaves
    free — server open passes it) is skipped and listed in the
    /debug/ragged prewarm section: a batch that cannot be warmed there
    could not be served there either.

    Called from server open on a background thread; a no-op where
    lowering is cheap (``_prewarm_worthwhile``).  Returns the number of
    programs warmed."""
    import jax

    if not _prewarm_worthwhile():
        return 0
    import jax.numpy as jnp

    use_mesh = mesh is not None and len(stack_shape) >= 2
    if use_mesh:
        from pilosa_tpu.parallel import meshexec

        if not meshexec.shardable(mesh, stack_shape[0]):
            use_mesh = False

    b_full = max(2, _pow2(max_batch))
    small = size_class(1, 1)
    large = size_class(max_tape, max_leaves)
    jobs: list[tuple[int, int, int]] = []
    b = 2
    while b <= b_full:
        jobs.append((b,) + small)
        b <<= 1
    if large != small:
        jobs.append((b_full,) + large)
    warmed = 0
    stack_bytes = 4 * int(np.prod(stack_shape))
    for b, tape_len, slots in jobs:
        need = b * (slots + 2 * (slots + tape_len)) * stack_bytes
        if budget_bytes is not None and need > budget_bytes:
            with _lock:
                _prewarm_state["skipped"].append(
                    {"batch": b, "tapeLen": tape_len, "slots": slots,
                     "needBytes": need, "budgetBytes": budget_bytes})
            continue
        tape_rows = np.zeros((b, tape_len, 3), dtype=np.int32)
        tape_rows[:, :, 0] = OP_COPY
        leaves = jnp.zeros((b, slots) + tuple(stack_shape),
                           dtype=jnp.uint32)
        if use_mesh:
            from pilosa_tpu.parallel import meshexec

            tapes_dev = meshexec.ensure_replicated(
                jnp.asarray(tape_rows), mesh)
            leaves_dev = meshexec.ensure_placed(leaves, mesh, 2)
            # the every-mesh-dispatch rule applies to warm-up too: a
            # prewarm thread racing a serving thread's collective
            # launch is the same enqueue-interleave deadlock
            with meshexec.launch_lock():
                out = _mesh_program(counts, mesh)(tapes_dev,
                                                  leaves_dev)
        else:
            out = _program(counts)(jnp.asarray(tape_rows), leaves)
        jax.block_until_ready(out)
        with _lock:
            _lowered.add((counts, b, tape_len, slots)
                         + tuple(stack_shape))
        warmed += 1
        with _lock:
            _prewarm_state["warmed"] = warmed
    bump("tape.prewarmed", warmed)
    return warmed
