"""Streaming ingest subsystem: device-side delta planes with
background compaction.

Every prior round optimized reads; writes still took the fragment lock,
mutated host roaring state, and bumped ``_gen`` — which (by design)
invalidates device caches and evicts result-cache entries, so sustained
ingest held warm hit rates near zero and forced full re-upload of
mutated fragments.  This package is the LSM-flavored write path the
reference absorbs writes with (the roaring op-log appended ahead of
snapshots, PAPER.md §roaring op-log; Chambi et al., *Better bitmap
performance with Roaring bitmaps*): batched imports and
``set_bit``/``clear_bit`` land in a small, bounded per-fragment **delta
plane** (set-bits and clear-bits planes, ``deltaplane.DeltaPlane``)
WITHOUT bumping the base generation, reads fuse ``base ⊕ delta`` inside
the existing fused expression programs (``ops/expr.py`` ``dfuse``
leaves), and a background compactor (``compactor.Compactor``, under
admission's ``internal`` class) merges deltas into the base roaring
state once a delta crosses size/age thresholds — only compaction bumps
``_gen``.

Cache discipline (the point of the whole subsystem):

- ``Fragment._gen`` — BASE generation.  Bumped by direct base
  mutations and by compaction only.  Device residency (row stacks,
  matrices, BSI planes) keys on it, so deltas leave the resident base
  tensors warm.
- ``Fragment._delta_seq`` — monotone delta sequence, bumped on every
  delta-landing write, NEVER reset (compaction leaves it alone).  The
  result cache stamps extend to ``(base_gen, delta_seq)``
  (``Executor._rc_view_stamp``), so a cached entry stays valid until
  *its* fragment's delta actually changes, and a compaction refill is
  one recompute against the already-resident base — not an eviction
  storm across every read path.
- Both move only in ``Fragment._bump_gen`` / ``_bump_delta_seq``,
  which also change the owning view's write token (``stagecheck.py``):
  the cached stacks compare the per-fragment tokens above only when
  that token has moved, so a delta write costs every resident stack of
  the view one walk over the shards on its next read, and no rebuild.

Durability is unchanged: delta-landing writes append the SAME WAL
records as the base path at write time; compaction merely moves bits
from the delta plane into the base rows (no WAL append — replay is
idempotent and in order), so a crash at any point replays losslessly.

Process-wide configuration (the ``[ingest]`` config section;
``configure`` mirrors ``runtime/resultcache.configure``).  The module
default is **disabled** — bare ``Fragment``/``Holder`` embedders keep
the exact pre-delta semantics; the server assembly turns deltas on
from ``[ingest] delta-enabled`` (default true in config.py).
"""

from __future__ import annotations

import threading

#: Process-wide budget on PENDING delta bytes across all fragments;
#: past it the writing thread flushes its own fragment inline
#: (backpressure on the writer, like snapqueue's inline overflow).
DEFAULT_DELTA_BUDGET_BYTES = 64 << 20

#: Per-fragment flush threshold: a delta holding at least this many
#: pending bit positions is merged on the compactor's next scan.
DEFAULT_COMPACT_THRESHOLD_BITS = 1 << 17

#: Compactor scan period (seconds) AND the age bound: a delta older
#: than one interval is merged on the next scan even when small, so
#: trickle writes never pend unboundedly.
DEFAULT_COMPACT_INTERVAL_S = 2.0


class IngestRuntimeConfig:
    """The process-wide [ingest] knobs (one per process, like the
    residency manager's budget)."""

    __slots__ = ("delta_enabled", "delta_budget_bytes",
                 "compact_threshold_bits", "compact_interval")

    def __init__(self):
        self.delta_enabled = False
        self.delta_budget_bytes = DEFAULT_DELTA_BUDGET_BYTES
        self.compact_threshold_bits = DEFAULT_COMPACT_THRESHOLD_BITS
        self.compact_interval = DEFAULT_COMPACT_INTERVAL_S


_cfg = IngestRuntimeConfig()
_cfg_lock = threading.Lock()


def config() -> IngestRuntimeConfig:
    return _cfg


def configure(delta_enabled: bool | None = None,
              delta_budget_bytes: int | None = None,
              compact_threshold_bits: int | None = None,
              compact_interval: float | None = None) -> IngestRuntimeConfig:
    """Apply [ingest] config to the process-wide runtime in place (a
    second in-process server must not wipe the first's settings with
    defaults — only explicit values land)."""
    with _cfg_lock:
        if delta_enabled is not None:
            _cfg.delta_enabled = bool(delta_enabled)
        if delta_budget_bytes is not None:
            _cfg.delta_budget_bytes = int(delta_budget_bytes)
        if compact_threshold_bits is not None:
            _cfg.compact_threshold_bits = int(compact_threshold_bits)
        if compact_interval is not None:
            _cfg.compact_interval = float(compact_interval)
    return _cfg


def reset() -> IngestRuntimeConfig:
    """Restore defaults (tests; also Server.close, so a closed server
    cannot leave delta semantics enabled for unrelated library users
    in the same process)."""
    global _cfg, _baseline
    with _cfg_lock:
        _cfg = IngestRuntimeConfig()
        _baseline = None
    return _cfg


# Servers configure the process-wide knobs in place, but open and
# close independently (in-process clusters, embedders).  Per-server
# restore snapshots compose wrongly under create-A-create-B-close-A-
# close-B (B's snapshot contains A's override, so the last closer
# re-installs it).  Instead the FIRST server to configure captures the
# true pre-server baseline, and the LAST server to close restores it —
# correct in any close order.

_baseline: tuple | None = None


def capture_baseline() -> None:
    """Snapshot the pre-existing config once per overlapping group of
    in-process servers (no-op while a baseline is already held)."""
    global _baseline
    with _cfg_lock:
        if _baseline is None:
            _baseline = (_cfg.delta_enabled, _cfg.delta_budget_bytes,
                         _cfg.compact_threshold_bits,
                         _cfg.compact_interval)


def restore_baseline() -> None:
    """Re-install the captured baseline and release it (the last
    closing server calls this)."""
    global _baseline
    with _cfg_lock:
        if _baseline is None:
            return
        (_cfg.delta_enabled, _cfg.delta_budget_bytes,
         _cfg.compact_threshold_bits, _cfg.compact_interval) = _baseline
        _baseline = None


def delta_enabled() -> bool:
    return _cfg.delta_enabled
