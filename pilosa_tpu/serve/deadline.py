"""End-to-end request deadlines for the serving path.

A deadline is a *remaining budget* carried on the wire as the
``X-Pilosa-Deadline`` header (float seconds) — relative rather than an
absolute timestamp, so it survives clock skew between nodes: each hop
re-derives its own monotonic expiry from the remaining budget at
receive time (the same convention gRPC uses for its timeout header).

The handler parses the header into a :class:`Deadline` and installs it
for the request's scope (:class:`scope`); the executor carries it in
``ExecOptions`` and checks it at the translate, per-shard-map, and
reduce boundaries so expired work never reaches device dispatch; the
coalescer drops expired batch entries before launch; and the internal
client re-serializes the remaining budget onto outbound RPC so remote
sub-queries inherit the originating request's budget.

Deadline expiry raises :class:`DeadlineExceededError`, which the HTTP
layer maps to 503 with an ``expired`` outcome on the query's flight
record (pilosa_tpu.observe).
"""

from __future__ import annotations

import math
import threading
import time

#: Wire header carrying the remaining budget in seconds (float).
HEADER = "X-Pilosa-Deadline"

#: Budgets above this clamp down — a 25-hour deadline is a typo, and an
#: unbounded one would defeat the queue-wait arithmetic in admission.
MAX_BUDGET_S = 86400.0

_tls = threading.local()  # .dl: the Deadline active on this thread


class DeadlineExceededError(Exception):
    """The request's deadline expired before (or during) execution.
    Deliberately NOT a ValueError/ExecutionError subclass: the HTTP
    layer must map it to 503, not the 400 client-error bucket."""


class Deadline:
    """A monotonic expiry derived from a remaining budget."""

    __slots__ = ("budget_s", "expires_mono")

    def __init__(self, budget_s: float):
        self.budget_s = budget_s
        self.expires_mono = time.monotonic() + budget_s

    def remaining(self) -> float:
        """Seconds left; negative once expired."""
        return self.expires_mono - time.monotonic()

    def expired(self) -> bool:
        return self.remaining() <= 0.0

    def __repr__(self) -> str:  # debug surfaces only
        return f"Deadline(remaining={self.remaining():.3f}s)"


def parse_header(value: str) -> Deadline:
    """``X-Pilosa-Deadline`` value -> Deadline.  Raises ValueError on a
    malformed value (the handler maps that to 400).  Zero or negative
    budgets are VALID — they mean "already expired" and shed
    immediately with an ``expired`` outcome, which lets callers whose
    budget ran out mid-retry still get an honest signal."""
    budget = float(value)  # ValueError propagates
    if not math.isfinite(budget):
        raise ValueError(f"non-finite deadline: {value!r}")
    return Deadline(min(budget, MAX_BUDGET_S))


def current() -> Deadline | None:
    """The deadline active on THIS thread, or None."""
    return getattr(_tls, "dl", None)


class tls_scope:
    """Re-entrant save/set/restore of one attribute on a
    threading.local — the shared base of every per-request scope
    (deadline.scope here, admission.rpc_class, tenant.scope).  ``__enter__`` returns the installed
    value; ``__exit__`` restores whatever was active before, so nested
    scopes shadow rather than clobber."""

    __slots__ = ("_tls_obj", "_attr", "value", "_prev")

    def __init__(self, tls_obj, attr: str, value):
        self._tls_obj = tls_obj
        self._attr = attr
        self.value = value

    def __enter__(self):
        self._prev = getattr(self._tls_obj, self._attr, None)
        setattr(self._tls_obj, self._attr, self.value)
        return self.value

    def __exit__(self, *exc):
        setattr(self._tls_obj, self._attr, self._prev)
        return False


class scope(tls_scope):
    """Install a deadline (or None) as this thread's active deadline
    for a with-block (re-entrant; see tls_scope)."""

    __slots__ = ()

    def __init__(self, dl: Deadline | None):
        super().__init__(_tls, "dl", dl)


def check(dl: Deadline | None, where: str) -> None:
    """Raise DeadlineExceededError when ``dl`` exists and has expired —
    the single check the executor sprinkles at its stage boundaries."""
    if dl is not None and dl.expired():
        raise DeadlineExceededError(f"deadline expired before {where}")
