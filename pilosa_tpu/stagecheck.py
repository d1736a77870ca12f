"""The O(1) validation of a staged stack: write tokens, and the tally
of how often they spare a walk over the shards.

A cached per-row device stack (``Field.device_row_stack`` and the
builders beside it) was built from the fragments of one view, and its
entry carries one token per fragment (``field._frag_base_gen`` and
friends).  Rebuilding those tokens is a walk over every shard of the
query, in Python, on the request's thread: 128 dictionary probes and a
129-tuple to learn that nothing was written.  So every ``View`` also
owns ONE **write token**, a value that is different after every event
that could change what any per-fragment token of that view would read,
and an entry is validated in two steps:

1. The builder reads the view's token FIRST (stamp before read, the
   result cache's discipline), then looks the entry up.  Stamped with
   the same token: nothing in the view was written since the entry was
   last proved good, so it is good.  No fragment is touched.
2. Token differs: the per-fragment comparison runs exactly as it did
   before there were write tokens.  If it matches, the entry is
   re-stamped with the token read in step 1; if not, it is rebuilt.

Step 1 only ever skips work.  It cannot cause a rebuild, a device copy
or an eviction that the per-fragment tokens would not: a write to one
row changes the view's token, the next read of every OTHER row's entry
pays step 2 once, finds its own tokens unchanged, and is O(1) again.

Tokens come from one process-wide ``itertools.count``: every value is
handed out once (``next`` on a count is atomic in CPython), so two
racing writers cannot put an older value back and have it match a
stamp, and two views never share a value.  A writer changes the token
AFTER the fragment's own counters and BEFORE it returns to its caller:
a reader that still sees the old token is reading a state in which the
write has not been acknowledged.
"""

from __future__ import annotations

import itertools
import threading
from typing import Any

_TOKENS = itertools.count(1)

#: the "view" half of a stamp when the field has no such view yet; a
#: view created later brings a token of its own, which differs
NO_VIEW = 0


def next_token() -> int:
    return next(_TOKENS)


def view_token(view: Any) -> int:
    return NO_VIEW if view is None else view.write_token


# -------------------------------------------------------------------
# how often step 1 engages
# -------------------------------------------------------------------

_lock = threading.Lock()
_counters = {
    "stage.fast": 0,  # leaves whose every builder validated in O(1)
    "stage.walk": 0,  # leaves that walked the shards at least once
}


class _Tally(threading.local):
    """This thread's staging: builder calls that walked the shards,
    leaves staged, and leaves none of whose builders walked."""

    walks = 0
    leaves = 0
    fast_leaves = 0


_tally = _Tally()


def note_walk() -> None:
    """One builder call fell through to the per-fragment comparison."""
    _tally.walks += 1


def mark() -> int:
    """Taken by a stager before it calls one leaf's builders."""
    return _tally.walks


def leaf_done(since: int) -> None:
    """One leaf's builders have run since ``mark()`` gave ``since``."""
    fast = _tally.walks == since
    _tally.leaves += 1
    if fast:
        _tally.fast_leaves += 1
    with _lock:
        _counters["stage.fast" if fast else "stage.walk"] += 1


def leaves_fast(n: int) -> None:
    """``n`` leaves of one read whose every builder validated in O(1)
    (``Field.stage_rows`` proves them good together): ``leaf_done``'s
    accounting for all of them, under one take of the lock."""
    _tally.leaves += n
    _tally.fast_leaves += n
    with _lock:
        _counters["stage.fast"] += n


def fast_leaves() -> int:
    """Leaves this thread has staged without a walk, ever: a ``stage``
    span notes the difference over its own extent as ``fast=``."""
    return _tally.fast_leaves


def leaves() -> int:
    """Leaves this thread has staged, ever."""
    return _tally.leaves


def counters() -> dict[str, int]:
    with _lock:
        return dict(_counters)


def reset_counters() -> None:
    with _lock:
        for k in _counters:
            _counters[k] = 0


def publish_gauges(stats: Any) -> None:
    """Cumulative values as gauges at scrape time, like the tape.* and
    container.* families."""
    for name, value in counters().items():
        stats.gauge(name, value)
