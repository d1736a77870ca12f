"""Failpoint registry: named fault-injection points compiled into the
production code paths.

Before this module, faults could only be injected through the test
transport fake (``LocalTransport.set_down``/``set_slow``) — the real
``InternalClient``/HTTP stack, the executor's per-shard map, the
compactor, the device dispatch funnel, and the result-cache fill path
had no way to fail on demand, so the failure-handling layer (circuit
breakers, hedged reads, partial-result degradation) could not be
exercised against the code that actually ships.  The design follows
the freebsd/etcd/pingcap failpoint idiom: sites are compiled in
permanently, and are **zero-cost when disarmed** — every site is
gated on the module-level ``armed`` bool, so the disarmed hot path
pays one attribute load and a falsy test and never enters :func:`hit`
(``tests/test_observer_cost.py`` counts it over a fused Count).

Arming surfaces (all feeding :func:`arm`):

- ``[faultinject] armed = "<spec>"`` config / the
  ``PILOSA_TPU_FAULTINJECT_ARMED`` env var (via config.py), applied by
  the server assembly at construction and disarmed at close;
- ``POST /debug/failpoints`` with ``{"arm": "<spec>"}`` /
  ``{"disarm": "<name>"|true}`` (server/handler.py) — the live ops
  surface ``tools/loadgen.py --chaos`` drives on a schedule.

Spec grammar (deterministic by construction — no randomness, so a
chaos run replays exactly)::

    spec   := point (";" point)*
    point  := name "=" action
    action := kind ["*" max] ["@" every]
    kind   := "error" | "error(" cls ")" | "delay(" ms ")"
    cls    := "fail" | "transport" | "oom" | "shed"

``*max`` fires the action at most ``max`` times (then the point stays
listed with its counters but stops triggering); ``@every`` fires on
every ``every``-th call only (1st, (every+1)-th, ...).  Examples::

    client.request.send=error(transport)*3
    executor.map_shard=delay(50)@2
    device.dispatch=error(oom)*1

Known sites (``SITES``) — arming an unknown name is a ValueError so a
typo cannot silently arm nothing.
"""

from __future__ import annotations

import threading
import time

#: The compiled-in failpoint sites.  Adding a site means adding the
#: ``hit()`` call at the code path AND the name here.
SITES: dict[str, str] = {
    "client.request.send":
        "InternalClient._request, before the request goes on the wire",
    "client.request.recv":
        "InternalClient._request, after the response body is read",
    "executor.map_shard":
        "Executor local per-shard map, before each shard evaluates",
    "admission.acquire":
        "AdmissionController.acquire, before the gate decides — "
        "error(shed) injects a deterministic refusal, delay(ms) a "
        "queue-delay stall",
    "replica.write":
        "Executor._replicate_to_shard_owners, before each remote "
        "delivery",
    "compactor.merge":
        "ingest.Compactor.run_once, before each fragment's delta merge",
    "device.dispatch":
        "ops.bitmap.note_dispatch — every device kernel launch",
    "resultcache.fill":
        "runtime.ResultCache.put, before a computed result is cached",
    "residency.promote":
        "runtime.residency promotion worker, before a host-tier entry "
        "is placed back on device (error = promotion failure -> the "
        "waiting query takes the host-compute fallback; delay(ms) = a "
        "tier stall)",
    "hint.replay":
        "parallel.hints replay worker, before each queued hint is "
        "delivered to its healed peer (errors leave the hint queued "
        "for the next backoff scan; delay(ms) = a slow drain)",
}


class FailpointError(RuntimeError):
    """The default injected error (kind ``error`` / ``error(fail)``)."""


class ResourceExhaustedError(RuntimeError):
    """Injected device-OOM lookalike (kind ``error(oom)``): the message
    carries the backend's RESOURCE_EXHAUSTED marker, so the executor's
    evict-and-retry path treats it exactly like a real XLA allocation
    failure."""

    def __init__(self, name: str):
        super().__init__(
            f"RESOURCE_EXHAUSTED: injected by failpoint {name!r}")


def _error_classes():
    # lazy: faultinject must import without dragging the cluster layer
    from pilosa_tpu.parallel.cluster import ShedByPeerError, TransportError

    return {
        "fail": lambda name: FailpointError(
            f"injected by failpoint {name!r}"),
        "transport": lambda name: TransportError(
            f"node unreachable: injected by failpoint {name!r}"),
        "shed": lambda name: ShedByPeerError(
            f"shed by peer: injected by failpoint {name!r}", 503),
        "oom": ResourceExhaustedError,
    }


class _Failpoint:
    """One armed point.  Trigger bookkeeping happens under the module
    lock; the action itself (raise / sleep) runs OUTSIDE it, so an
    injected delay can never hold the registry lock."""

    __slots__ = ("name", "spec", "kind", "arg", "max_triggers", "every",
                 "calls", "triggers")

    def __init__(self, name: str, spec: str):
        self.name = name
        self.spec = spec
        self.calls = 0
        self.triggers = 0
        action = spec
        self.max_triggers = 0  # 0 = unlimited
        self.every = 1
        if "@" in action:
            action, _, every = action.partition("@")
            self.every = int(every)
            if self.every < 1:
                raise ValueError(f"failpoint {name}: @every must be >= 1")
        if "*" in action:
            action, _, mx = action.partition("*")
            self.max_triggers = int(mx)
            if self.max_triggers < 1:
                raise ValueError(f"failpoint {name}: *max must be >= 1")
        action = action.strip()
        if action.startswith("delay(") and action.endswith(")"):
            self.kind = "delay"
            self.arg = float(action[len("delay("):-1]) / 1e3  # ms -> s
            if self.arg < 0:
                raise ValueError(f"failpoint {name}: negative delay")
        elif action == "error":
            self.kind = "error"
            self.arg = "fail"
        elif action.startswith("error(") and action.endswith(")"):
            self.kind = "error"
            self.arg = action[len("error("):-1].strip()
            if self.arg not in ("fail", "transport", "shed", "oom"):
                raise ValueError(
                    f"failpoint {name}: unknown error class "
                    f"{self.arg!r} (fail|transport|shed|oom)")
        else:
            raise ValueError(
                f"failpoint {name}: unparsable action {spec!r} "
                "(error | error(cls) | delay(ms), with optional "
                "*max and @every)")

    def decide_locked(self) -> tuple[str, object] | None:
        """Caller holds the module lock.  Returns (kind, arg) when this
        call should trigger, else None."""
        self.calls += 1
        if self.max_triggers and self.triggers >= self.max_triggers:
            return None
        if (self.calls - 1) % self.every != 0:
            return None
        self.triggers += 1
        return (self.kind, self.arg)

    def snapshot_locked(self) -> dict:
        return {"spec": self.spec, "calls": self.calls,
                "triggers": self.triggers,
                "exhausted": bool(self.max_triggers
                                  and self.triggers >= self.max_triggers)}


from pilosa_tpu import lockcheck as _lockcheck

# module-level, so the dynamic checker only wraps it in env-var mode
# (PILOSA_TPU_LOCKCHECK=1 at process start); hit() never takes any
# other lock, so no ordering edge can originate here
_lock = _lockcheck.lock("faultinject")
_points: dict[str, _Failpoint] = {}

#: The one-word fast gate every site reads BEFORE calling hit():
#: ``if faultinject.armed: faultinject.hit(name)``.  Updated (under
#: the lock) whenever the registry changes; a momentarily stale read
#: costs one extra dict probe or skips one injection window — never a
#: wrong result.
armed = False


def parse_spec(spec: str) -> dict[str, _Failpoint]:
    """Parse ``name=action;name=action`` into failpoints; validates
    both names and actions before anything arms (all-or-nothing)."""
    out: dict[str, _Failpoint] = {}
    for part in spec.split(";"):
        part = part.strip()
        if not part:
            continue
        name, eq, action = part.partition("=")
        name = name.strip()
        if not eq or not action.strip():
            raise ValueError(f"bad failpoint entry {part!r} "
                             "(expected name=action)")
        if name not in SITES:
            raise ValueError(
                f"unknown failpoint {name!r}; known sites: "
                f"{', '.join(sorted(SITES))}")
        out[name] = _Failpoint(name, action.strip())
    return out


def arm(spec: str) -> list[str]:
    """Arm every point in ``spec`` (replacing any existing arming of
    the same names; other armed points stay).  Returns the armed
    names.  Raises ValueError on any unknown name or malformed action
    without arming anything."""
    global armed
    parsed = parse_spec(spec)
    with _lock:
        _points.update(parsed)
        armed = bool(_points)
    _journal("failpoint.arm", points=sorted(parsed))
    return sorted(parsed)


def disarm(name: str | None = None) -> None:
    """Disarm one point, or all of them (``name=None``)."""
    global armed
    with _lock:
        if name is None:
            _points.clear()
        else:
            _points.pop(name, None)
        armed = bool(_points)
    _journal("failpoint.disarm",
             points=[name] if name is not None else [])


def _journal(kind: str, **fields) -> None:
    """Arming/disarming chaos is exactly the state change a merged
    cluster timeline must show next to the failures it caused.  Lazy
    import (observe is a higher layer) and AFTER ``_lock`` is released
    — the journal takes its own lock."""
    from pilosa_tpu import observe as _observe

    if _observe.journal_on:
        _observe.emit(kind, **fields)


def hit(name: str) -> None:
    """One pass through the failpoint ``name``.  Call sites gate on
    the module ``armed`` bool first, so the disarmed cost never
    exceeds one attribute read; this function is only reached while
    something is armed."""
    with _lock:
        p = _points.get(name)
        action = p.decide_locked() if p is not None else None
    if action is None:
        return
    kind, arg = action
    if kind == "delay":
        time.sleep(arg)
        return
    raise _error_classes()[arg](name)


def snapshot() -> dict:
    """The /debug/failpoints document."""
    with _lock:
        points = {n: p.snapshot_locked()
                  for n, p in sorted(_points.items())}
        total = sum(p["triggers"] for p in points.values())
    return {
        "armed": bool(points),
        "points": points,
        "triggers": total,
        "sites": dict(sorted(SITES.items())),
    }


def publish_gauges(stats) -> None:
    """failpoint.* gauge family for /metrics and /debug/vars —
    published unconditionally (zeros on a clean server) so the family
    is scrape-visible before the first chaos run."""
    with _lock:
        n = len(_points)
        total = sum(p.triggers for p in _points.values())
    stats.gauge("failpoint.armed", n)
    stats.gauge("failpoint.triggers", total)
