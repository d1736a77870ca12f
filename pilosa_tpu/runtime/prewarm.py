"""Background stack prewarm: kill the cold-first-query tail.

The reference eagerly opens + mmaps every fragment at startup
(holder.go:137 -> view.go:117-177), so a restarted server answers its
first query immediately.  Here fragments also load eagerly at open, but
the fused executor path adds one more tier the reference doesn't have:
device/host row stacks assembled on first touch.  At a 10B-column
shape that first touch is ~2 x 1.25 GB of stack assembly, competing
with the background compaction of the freshly imported fragments — a
tail the warm steady state never shows.  On the chip the benchmark's
warm-up pays it inside ``setup_s``; the first query alone is not
measured there.

This module shifts that cost off the first query.  Bulk imports and
holder open enqueue the touched field+rows; one background worker
assembles exactly the (row, shards) cache entries the fused path will
look up, so the first query hits warm caches.  The worker is bounded:

  - residency budget: a stack is only built while total usage stays
    under BUDGET_FRACTION of the budget (eviction churn would defeat
    the point);
  - ROW_CAP rows per job, most-frequent first (a bulk import naming
    10k distinct rows must not LRU-thrash the cache with 10k stacks);
  - stacks build through the normal Field entry points, so placement,
    caching, and invalidation are the product path, not a parallel one.

``PILOSA_TPU_PREWARM=0`` disables enqueueing (used to measure the
documented cold floor; tests comparing cold paths can also gate it).
"""

from __future__ import annotations

import os
import queue
import threading

from pilosa_tpu import logger as _logger

ROW_CAP = 128          # stacks per prewarm job, most-frequent rows first
BUDGET_FRACTION = 0.75  # stop building while residency usage is above this
QUEUE_DEPTH = 256

_queue: queue.Queue | None = None
_lock = threading.Lock()
_inflight = 0
_idle = threading.Condition(_lock)
_pending: set[tuple] = set()  # (id(index), field_name) queued, not started

_counters = {
    "stacks_built": 0,
    "rows_skipped_budget": 0,
    "jobs_failed": 0,
}

log: _logger.Logger = _logger.StandardLogger()


def enabled() -> bool:
    return os.environ.get("PILOSA_TPU_PREWARM", "1") != "0"


def bump(name: str, value: int = 1) -> None:
    with _lock:
        _counters[name] += value


def counters() -> dict:
    with _lock:
        return dict(_counters)


def prometheus_lines() -> str:
    out = []
    for name, v in sorted(counters().items()):
        m = f"pilosa_prewarm_{name}_total"
        out.append(f"# TYPE {m} counter")
        out.append(f"{m} {v}")
    return "\n".join(out) + "\n"


def _headroom_ok(extra_bytes: int) -> bool:
    from pilosa_tpu.runtime import residency

    mgr = residency.manager()
    return mgr.total + extra_bytes <= mgr.budget * BUDGET_FRACTION


def _job_rows(field, rows) -> list[int]:
    """Resolve the rows to warm.  Explicit rows come frequency-ordered
    from the import path; ``None`` (holder open) samples row ids from
    the first few fragments — the restart analog of the reference's
    eager mmap, bounded instead of exhaustive."""
    if rows is not None:
        return list(rows)[:ROW_CAP]
    from pilosa_tpu.models.view import VIEW_STANDARD

    view = field.view(VIEW_STANDARD)
    if view is None:
        return []
    out: list[int] = []
    seen: set[int] = set()
    for shard in sorted(view.available_shards())[:4]:
        frag = view.fragment(shard)
        if frag is None:
            continue
        # hottest rows first when the fragment's TopN cache knows them,
        # plain row ids otherwise
        counts = frag.topn_cache.get(frag._gen)
        ids = ([r for r, _ in sorted(counts.items(), key=lambda kv: -kv[1])]
               if counts else frag.row_ids())
        for r in ids:
            if r not in seen:
                seen.add(r)
                out.append(r)
            if len(out) >= ROW_CAP:
                return out
    return out


def _live(index, field) -> bool:
    """A queued job must not rebuild stacks for a deleted field: the
    queue holds strong refs, so a delete landing before the worker
    drains would otherwise re-admit multi-GB buffers into a cache
    nothing ever forgets again."""
    try:
        return index.fields.get(field.name) is field
    except Exception:
        return False


def _run_job(index, field, rows) -> None:
    from pilosa_tpu.models.field import FieldType
    from pilosa_tpu.ops import bitmap as bm
    from pilosa_tpu.shardwidth import SHARD_WIDTH

    if not _live(index, field):
        return
    shards = tuple(sorted(index.available_shards()))
    if not shards:
        return
    stack_bytes = len(shards) * bm.n_words(SHARD_WIDTH) * 4
    if field.options.type == FieldType.INT:
        # BSI queries touch the whole plane stack at once
        if _headroom_ok(stack_bytes * (field.options.bit_depth + 2)):
            field.device_plane_stack(shards)
            bump("stacks_built")
        else:
            bump("rows_skipped_budget")
        return
    for row in _job_rows(field, rows):
        if not _live(index, field):  # delete landed mid-job: stop
            return
        if not _headroom_ok(stack_bytes):
            bump("rows_skipped_budget")
            return  # budget is a hard stop, not a per-row skip
        field.device_row_stack(int(row), shards)
        bump("stacks_built")


def _worker() -> None:
    global _inflight
    while True:
        index, field, rows = _queue.get()
        # release the dedup key at DEQUEUE: an import landing while
        # this job runs carries new rows and must re-queue, not be
        # silently dropped (dedup only collapses back-to-back enqueues
        # of a still-queued job)
        with _lock:
            _pending.discard((id(index), field.name))
        try:
            _run_job(index, field, rows)
        except Exception as e:  # noqa: BLE001 — prewarm must never break serving
            bump("jobs_failed")
            log.printf("prewarm: job for field %r failed (%r); first "
                       "query pays the cold build instead", field.name, e)
        finally:
            with _lock:
                _inflight -= 1
                _idle.notify_all()
            _queue.task_done()


def _ensure_worker() -> None:
    global _queue
    if _queue is not None:
        return
    with _lock:
        if _queue is not None:
            return
        _queue = queue.Queue(maxsize=QUEUE_DEPTH)
        threading.Thread(target=_worker, daemon=True,
                         name="stack-prewarm").start()


def enqueue(index, field, rows=None) -> None:
    """Queue a prewarm job; drops silently when disabled, the queue is
    full (prewarm is best-effort — the first query just pays the build),
    or the same field is already queued."""
    global _inflight
    if not enabled():
        return
    _ensure_worker()
    key = (id(index), field.name)
    with _lock:
        if key in _pending:
            return
        _pending.add(key)
        _inflight += 1
    try:
        _queue.put_nowait((index, field, rows))
    except queue.Full:
        with _lock:
            _pending.discard(key)
            _inflight -= 1
            _idle.notify_all()


def drain(timeout: float | None = 30.0) -> bool:
    """Block until queued prewarm jobs finish (test/measure barrier)."""
    if _queue is None:
        return True
    import time

    deadline = None if timeout is None else time.monotonic() + timeout
    with _idle:
        while _inflight > 0:
            if deadline is None:
                _idle.wait(timeout=1.0)
                continue
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return False
            _idle.wait(timeout=remaining)
    return True
