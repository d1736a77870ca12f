"""What the observers cost a fused Count, as counts.

Seven observers sit on the Count path: failpoints (faultinject), the
event journal behind the trace assembler (traceasm), the admission
gate, per-tenant accounting, the query flight recorder (observe),
device-runtime telemetry (devobs) and the engine observatory
(perfobs).  Each promises that its disabled (or, for the journal and
devobs, idle) path is an attribute read: no call into its recording
functions, no lock of its own, no clock read.  A wall-clock A/B on a
shared CPU cannot hold such a promise; a counting fake can.  Every
case also turns its observer ON and sees the same fakes count, so a
zero is never the zero of a fake that was not wired in.

What the observers cost in time is the chip's to say: PERF.md
section 6 (the span spine, ``?profile=1``).
"""

from __future__ import annotations

import email.parser
import json
import socket
import time
import urllib.request

import numpy as np
import pytest

from pilosa_tpu import devobs
from pilosa_tpu import faultinject as fi
from pilosa_tpu import observe, perfobs
from pilosa_tpu import stats as _stats
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.ops import bitmap as bm
from pilosa_tpu.parallel.coalescer import Coalescer
from pilosa_tpu.parallel.executor import ExecOptions, Executor
from pilosa_tpu.runtime import resultcache
from pilosa_tpu.serve import tenant
from pilosa_tpu.serve.admission import AdmissionController
from pilosa_tpu.shardwidth import SHARD_WIDTH
from tests.coalesce_batch import wait_until

N_SHARDS = 4
QUERY = "Count(Intersect(Row(f=1), Row(f=2)))"
#: one device, as on a one-chip server (conftest's 8 virtual CPU
#: devices would otherwise route every read through the mesh)
ONE_DEVICE = ExecOptions(mesh=False)


class Calls:
    """A callable that counts its calls and passes them through."""

    def __init__(self, fn):
        self.fn = fn
        self.n = 0

    def __call__(self, *args, **kwargs):
        self.n += 1
        return self.fn(*args, **kwargs)


class CountingLock:
    """Stands in for a ``threading.Lock``: counts every acquisition."""

    def __init__(self, lock):
        self.lock = lock
        self.n = 0

    def acquire(self, *args, **kwargs):
        self.n += 1
        return self.lock.acquire(*args, **kwargs)

    def release(self):
        self.lock.release()

    def __enter__(self):
        self.n += 1
        return self.lock.__enter__()

    def __exit__(self, *exc):
        return self.lock.__exit__(*exc)


def _calls(monkeypatch, owner, name) -> Calls:
    c = Calls(getattr(owner, name))
    monkeypatch.setattr(owner, name, c)
    return c


def _method_calls(monkeypatch, cls, name) -> Calls:
    """Count the calls of a method of ``cls`` (a plain function in its
    place, so that it still binds)."""
    c = Calls(getattr(cls, name))

    def method(self, *args, **kwargs):
        return c(self, *args, **kwargs)

    monkeypatch.setattr(cls, name, method)
    return c


def _lock(monkeypatch, owner, name) -> CountingLock:
    c = CountingLock(getattr(owner, name))
    monkeypatch.setattr(owner, name, c)
    return c


def _dense_rows():
    """Rows 1 and 2 at 40% fill (over the 25% containers threshold:
    dense leaves) across four shards -> (rows, columns) to import."""
    rng = np.random.default_rng(30)
    n = N_SHARDS * SHARD_WIDTH
    for row in (1, 2):
        cols = np.flatnonzero(rng.random(n) < 0.4)
        yield [row] * len(cols), cols.tolist()


@pytest.fixture
def ex(tmp_path):
    """A bare executor over the dense rows, staged and compiled once
    (conftest gives every test a fresh result cache)."""
    holder = Holder(str(tmp_path / "h"))
    f = holder.create_index("i").create_field("f")
    for rows, cols in _dense_rows():
        f.import_bits(rows, cols)
    resultcache.cache().enabled = False  # every Count reaches the engine
    perfobs.reset()
    ex = Executor(holder)
    assert _count(ex) > 0
    yield ex
    perfobs.reset()
    holder.close()


def _count(ex):
    return ex.execute("i", QUERY, opt=ONE_DEVICE)[0]


def _faultinject(ex, monkeypatch):
    hit = _calls(monkeypatch, fi, "hit")
    lock = _lock(monkeypatch, fi, "_lock")
    assert fi.armed is False
    want = _count(ex)
    off = (hit.n, lock.n)
    fi.arm("device.dispatch=delay(0)")
    try:
        assert _count(ex) == want
    finally:
        fi.disarm()
    return off, (hit.n, 0), "disarmed"


def _traceasm(ex, monkeypatch):
    """The journal records state transitions, never queries: on or
    off, a warm Count emits nothing; off, an emission site stops at
    the module bool."""
    journal = observe.journal()
    emit = _calls(monkeypatch, journal, "emit")
    lock = _lock(monkeypatch, journal, "_lock")
    monkeypatch.setattr(observe, "journal_on", True)
    _count(ex)
    assert (emit.n, lock.n) == (0, 0)
    monkeypatch.setattr(observe, "journal_on", False)
    _count(ex)
    observe.emit("test.event")
    off = (emit.n, lock.n)
    monkeypatch.setattr(observe, "journal_on", True)
    observe.emit("test.event")
    return off, (emit.n, lock.n), "journal = false"


def _admission(ex, monkeypatch):
    """The gate stands in the handler, around the executor's call."""
    stats = _stats.MemStatsClient()
    emitted = _calls(monkeypatch, stats, "count_with_tags")
    ctrl = AdmissionController(enabled=False, stats=stats)
    lock = _lock(monkeypatch, ctrl, "_lock")
    policy = _calls(monkeypatch, tenant, "policy")

    def admitted_count():
        ticket = ctrl.acquire("query")
        try:
            return _count(ex)
        finally:
            ticket.release()

    admitted_count()
    off = (emitted.n, lock.n, policy.n)
    ctrl.enabled = True
    admitted_count()
    # on and uncontended: one lock to admit, one to release, one
    # counter; a third lock would be a lock on every served read
    assert lock.n - off[1] <= 2 and emitted.n - off[0] <= 1
    return off, (emitted.n, lock.n, 0), "[admission] enabled = false"


def _tenants(ex, monkeypatch):
    resolve = _calls(monkeypatch, tenant, "resolve")
    quota_for = _calls(monkeypatch, tenant.TenantsRuntimeConfig,
                       "quota_for")
    lock = _lock(monkeypatch, tenant, "_cfg_lock")
    rc = resultcache.cache()
    monkeypatch.setattr(rc, "enabled", True)  # its put/get charge tenants
    assert tenant.policy() is None
    _count(ex)
    _count(ex)  # a hit
    off = (resolve.n, quota_for.n, lock.n, len(rc._tenant_bytes))
    monkeypatch.setattr(tenant.config(), "enabled", True)
    try:
        ex.execute("i", "Count(Row(f=1))", opt=ONE_DEVICE)
    finally:
        monkeypatch.setattr(tenant.config(), "enabled", False)
    return off, (resolve.n, 0, 0, 0), "[tenants] enabled = false"


class _CountingSpan(observe._Span):
    made = 0

    def __init__(self, *args):
        type(self).made += 1
        super().__init__(*args)


def _observe(ex, monkeypatch):
    clock = _calls(monkeypatch, observe, "clock_ns")
    monkeypatch.setattr(_CountingSpan, "made", 0)
    monkeypatch.setattr(observe, "_Span", _CountingSpan)
    lock = _lock(monkeypatch, ex.recorder, "_lock")
    begin = _calls(monkeypatch, ex.recorder, "begin")
    monkeypatch.setattr(ex.recorder, "enabled", False)
    _count(ex)
    # what is left is not the recorder's: the per-call stats timing
    # (``execute.Count``) is one span with no record, two clock reads
    assert (_CountingSpan.made, clock.n) == (1, 2)
    off = (begin.n, lock.n)
    monkeypatch.setattr(ex.recorder, "enabled", True)
    _count(ex)
    return off, (begin.n, lock.n), "[observe] enabled = false"


class _Time:
    """``devobs``'s view of the ``time`` module, counting."""

    def __init__(self):
        self.time = time.time
        self.perf_counter_ns = Calls(time.perf_counter_ns)


def _devobs(ex, monkeypatch):
    obs = devobs.observer()
    clock = _Time()
    monkeypatch.setattr(devobs, "time", clock)
    lock = _lock(monkeypatch, obs, "_lock")
    compiles = _calls(monkeypatch, obs, "note_compile")
    monkeypatch.setattr(obs, "enabled", False)
    _count(ex)
    off = (clock.perf_counter_ns.n, lock.n, compiles.n)
    monkeypatch.setattr(obs, "enabled", True)
    marks = list(devobs._marks)[-8:]
    with bm.dispatch_counter() as dc:
        _count(ex)
    # on and warm: one clock read a jitted dispatch, and still no
    # lock and no event (the observer's lock is for compiles,
    # transfers and snapshots), and JAX compiled nothing, so neither
    # ``jax.monitoring`` listener fired (``tests/test_devobs.py`` sees
    # them fire on a compile)
    assert clock.perf_counter_ns.n <= dc.n
    assert (lock.n, compiles.n) == (0, 0)
    assert list(devobs._marks)[-8:] == marks
    return off, (clock.perf_counter_ns.n, 0, 0), "observer().enabled = False"


def _perfobs(ex, monkeypatch):
    sample = _calls(monkeypatch, perfobs, "record_sample")
    block = _calls(monkeypatch, perfobs, "_block")
    clock = _calls(monkeypatch, perfobs, "_clock")
    lock = _lock(monkeypatch, perfobs, "_lock")
    cfg_lock = _lock(monkeypatch, perfobs, "_cfg_lock")
    perfobs.configure(enabled_=False)
    cfg_lock.n = 0
    _count(ex)
    off = (sample.n, block.n, clock.n, lock.n, cfg_lock.n)
    perfobs.configure(enabled_=True)
    cfg_lock.n = 0
    with bm.dispatch_counter() as dc:
        _count(ex)
    # on: one sample a launch, under one take of the table's lock;
    # the config lock is for configure() alone
    assert sample.n == dc.n == 1
    assert (lock.n, cfg_lock.n) == (1, 0)
    return off, (sample.n, block.n, 0, 0, 0), "[observe] enabled = false"


OBSERVERS = {
    "faultinject": _faultinject,
    "traceasm": _traceasm,
    "admission": _admission,
    "tenants": _tenants,
    "observe": _observe,
    "devobs": _devobs,
    "perfobs": _perfobs,
}


@pytest.mark.parametrize("observer", list(OBSERVERS))
def test_observer_off_is_no_call_no_lock_no_clock(ex, monkeypatch,
                                                  observer):
    """One fused Count through the executor with ``observer`` off:
    zero calls into its recording functions, zero takes of its locks,
    zero reads of its clock; turned on, the same fakes count."""
    off, on, switch = OBSERVERS[observer](ex, monkeypatch)
    assert not any(off), (observer, switch, off)
    assert any(on), (observer, "the fakes never counted", on)


#: what a coalesced lone dense Count costs with the flight recorder
#: on and no ``?profile=1``, read on this tree at PR 30: (clock reads,
#: spans) from ``Executor.execute`` down, and for the whole served
#: request (the handler's ``http.request``, ``http.parse``,
#: ``http.read``, ``admission.wait``, ``api.open``, ``pql.parse``,
#: ``api.close``, ``serialize`` and ``http.send`` on top).  Ceilings, not targets: a PR that adds a span to the
#: served read raises them here, in the open.  PR 32's own request
#: parse and single send read the same clocks: unchanged.  PR 37: a
#: dense read declines the VM offer before its ``stage`` span is opened
#: (one span and two clock reads fewer; was (24, 13) and (35, 19)).
#: PR 43 names the glue: ``http.send`` (two clock reads), ``api.open``
#: (one: it starts where the handler's last span ended), and
#: ``exec.open``, ``route`` and ``api.close`` from clock reads their
#: neighbours had taken (``sp.before(name)``: none); and a
#: Count, which holds no key, opens no ``translateResults`` (one span
#: and two reads fewer).  Was (22, 12) and (33, 18); ISSUE 43 allows
#: (28, 15) and (41, 22).
LONE_DENSE = {"executor": (20, 13), "http": (34, 22)}


@pytest.fixture
def served(tmp_path):
    from pilosa_tpu.server.server import Server

    srv = Server(str(tmp_path / "srv"), port=0, coalescer_enabled=True,
                 cache_enabled=False)
    srv.open()
    srv.api.create_index("i")
    srv.api.create_field("i", "f")
    for rows, cols in _dense_rows():
        srv.api.import_bits("i", "f", rows, cols)
    yield srv
    srv.close()


def _post_count(srv):
    req = urllib.request.Request(
        f"{srv.uri}/index/i/query?nomesh=1", data=QUERY.encode(),
        method="POST")
    with urllib.request.urlopen(req, timeout=60) as resp:
        return json.loads(resp.read())["results"][0]


@pytest.mark.parametrize("door", list(LONE_DENSE))
def test_recorder_on_lone_dense_count_clock_reads_and_spans(
        request, monkeypatch, door):
    """The served read of ``seg-dense``: coalesced, nothing in flight
    (``why = idle``), one dense launch, the flight recorder on, no
    ``?profile=1``.  Pins its clock reads and spans, that the
    recorder's lock is taken at begin and publish only, and that the
    flush takes the cost table's lock once (the sample) and the
    observatory's config lock never."""
    if door == "http":
        srv = request.getfixturevalue("served")
        recorder = srv.node.executor.recorder
        root = "http.request"
    else:
        ex = request.getfixturevalue("ex")
        ex.coalescer = Coalescer(enabled=True,
                                 stats=_stats.MemStatsClient())
        recorder = ex.recorder
        root = "exec"

    def run():
        n = _post_count(srv) if door == "http" else _count(ex)
        # the handler closes ``http.request`` after the client has its
        # answer: wait, or that clock read lands in the next request's
        # count (no lock: a deque's [-1] is atomic)
        assert wait_until(lambda: any(
            s[2] == root for s in recorder._recent[-1].spans), timeout=10)
        return n

    assert run() > 0  # staged, and the coalesced program compiled
    clock = _calls(monkeypatch, observe, "clock_ns")
    rec_lock = _lock(monkeypatch, recorder, "_lock")
    table_lock = _lock(monkeypatch, perfobs, "_lock")
    cfg_lock = _lock(monkeypatch, perfobs, "_cfg_lock")
    run()
    locks = (rec_lock.n, table_lock.n, cfg_lock.n)
    rec = recorder.recent_records()[-1]
    assert (rec.path, rec.engine, len(rec.launches)) == (
        "coalesced", "dense", 1)
    assert rec.coalesce["batch"] == 1 and rec.coalesce["why"] == "idle"
    names = [s[2] for s in rec.spans]
    assert {"exec", "stage", "coalesce.wait", "launch",
            "launch.dispatch", "launch.ready", "reduce"} <= set(names)
    reads, spans = LONE_DENSE[door]
    assert len(names) <= spans, names
    assert clock.n <= reads, clock.n
    assert locks == (2, 1, 0)


#: what the bookkeeping of a served lone dense Count (two leaves of one
#: field) costs since ISSUE 44, as counts: walks of its tree after the
#: parser; takes of each owner's lock from ``Executor.execute`` down
#: (the parent took the field's, the residency manager's, the access
#: table's and the staging tally's once a LEAF, the registry's nine
#: times and the program caches' six; the registry's second take is the
#: recorder's latency histogram, which needs the end of ``exec``); and
#: the scopes ``execute``
#: enters with a server's defaults (the parent entered five).
#: Ceilings: they may only fall.
BOOKS = {
    "walks": 1,
    "locks": {"field": 1, "residency": 1, "access": 1, "tally": 1,
              "cost table": 1, "stats": 2, "result cache": 0,
              # the VM's offer declined, and a flush of one shape
              "tape": 2, "containers": 1},
    "scopes": {"execute": 1, "attach": 1, "no_tiers": 0, "tenant": 0,
               "tracer span": 0},
}


def test_lone_dense_count_walks_once_and_settles_once(ex, monkeypatch):
    """The read of ``seg-dense`` through the coalescer, warm: ONE walk
    of its tree, one take of each owner's lock whatever the number of
    leaves, and one scope around the execution."""
    from pilosa_tpu import stagecheck, tracing
    from pilosa_tpu.ops import containers as ct
    from pilosa_tpu.ops import tape
    from pilosa_tpu.parallel import executor as exmod
    from pilosa_tpu.parallel import prepared
    from pilosa_tpu.runtime import residency
    stats = _stats.MemStatsClient()
    ex.stats = ex.recorder.stats = stats
    ex.coalescer = Coalescer(enabled=True, stats=stats)
    assert _count(ex) > 0  # compiled through the coalescer, verdicts in
    f = ex.holder.index("i").field("f")
    before = stats.snapshot()
    locks = {
        "field": _lock(monkeypatch, f, "_lock"),
        "residency": _lock(monkeypatch, residency.manager(), "_lock"),
        "access": _lock(monkeypatch, observe.access_stats(), "_lock"),
        "tally": _lock(monkeypatch, stagecheck, "_lock"),
        "cost table": _lock(monkeypatch, perfobs, "_lock"),
        "stats": _lock(monkeypatch, stats._registry, "_lock"),
        "result cache": _lock(monkeypatch, resultcache.cache(), "_lock"),
        "tape": _lock(monkeypatch, tape, "_lock"),
        "containers": _lock(monkeypatch, ct, "_lock"),
    }
    scopes = {
        "execute": _method_calls(monkeypatch, exmod._Scope, "__enter__"),
        "attach": _method_calls(monkeypatch, observe.attach, "__enter__"),
        "no_tiers": _method_calls(monkeypatch, residency.no_tiers,
                                  "__init__"),
        "tenant": _method_calls(monkeypatch, tenant.scope, "__init__"),
        "tracer span": _method_calls(monkeypatch, tracing.Span,
                                     "__enter__"),
    }
    walks = prepared.walks()
    with bm.dispatch_counter() as dc:
        assert _count(ex) > 0
    assert dc.n == 1
    assert prepared.walks() - walks <= BOOKS["walks"]
    assert {k: v.n for k, v in locks.items()} == BOOKS["locks"]
    assert {k: v.n for k, v in scopes.items()} == BOOKS["scopes"]
    after = stats.snapshot()
    moved = {k: after[k] - before.get(k, 0) for k in after
             if isinstance(after[k], (int, float))
             and after[k] != before.get(k, 0)}
    # every book the read kept was written, once
    assert moved == {"plan.prepared": 1, "plan.walks": 1,
                     "query[call:Count,index:i]": 1,
                     "coalescer.dispatches": 1, "coalescer.flush_idle": 1}
    for hist in ("coalescer.batch_occupancy", "coalescer.shape_distinct",
                 "coalescer.query_ns", "coalescer.launch_ns",
                 "execute.Count", "pilosa_query_latency"):
        assert (after[hist]["count"] - before[hist]["count"]) == 1, hist


class _Sends:
    """Every ``send``/``sendall`` on a socket whose local port is the
    server's, i.e. on its side of a connection: the bytes objects, in
    order."""

    def __init__(self, monkeypatch, port: int):
        self.sent: list[bytes] = []
        for name in ("send", "sendall"):
            monkeypatch.setattr(socket.socket, name,
                                self._counting(name, port))

    def _counting(self, name: str, port: int):
        real = getattr(socket.socket, name)

        def method(sock, data, *args):
            if sock.getsockname()[1] == port:
                self.sent.append(data)
            return real(sock, data, *args)

        return method


def _raw_post(sock, rfile, body: bytes) -> bytes:
    """One POST of ``body`` to the query route on an open connection,
    by hand: ``http.client`` would run ``email.parser`` over the answer
    in this same process.  -> the answer's body."""
    sock.sendall(b"POST /index/i/query?nomesh=1 HTTP/1.1\r\nHost: t\r\n"
                 b"Content-Length: %d\r\n\r\n%s" % (len(body), body))
    assert rfile.readline() == b"HTTP/1.1 200 OK\r\n"
    length = 0
    for line in iter(rfile.readline, b"\r\n"):
        if line.lower().startswith(b"content-length:"):
            length = int(line.split(b":")[1])
    return rfile.read(length)


def _wire_counts(srv) -> tuple[int, int]:
    with srv.handler._wire_lock:
        return srv.handler.responses, srv.handler.sends


#: what the served connection's protocol work costs, as counts: the
#: socket sends of one answer, and calls into ``email.parser``
WIRE = ("lone dense Count", "answer over 64 KiB")


@pytest.mark.parametrize("answer", WIRE)
def test_served_answer_is_one_parse_and_one_send(served, monkeypatch,
                                                 answer):
    """A served lone dense Count leaves in exactly ONE send on its
    connection, ``http.sends`` == ``http.responses``, and
    ``email.parser`` never runs while it is served; an answer over 64
    KiB is two sends, the second of them the body object itself."""
    from pilosa_tpu.server import handler as h

    sock = socket.create_connection(("127.0.0.1", served.handler.port),
                                    timeout=60)
    rfile = sock.makefile("rb")
    try:
        assert json.loads(_raw_post(sock, rfile, QUERY.encode()))[
            "results"][0] > 0  # staged and compiled
        sends = _Sends(monkeypatch, served.handler.port)
        parsestr = _calls(monkeypatch, email.parser.Parser, "parsestr")
        bodies = []
        respond = h.Handler._respond

        def spy(self, req, status, ctype, body, headers=None):
            bodies.append(body)
            return respond(self, req, status, ctype, body, headers)

        monkeypatch.setattr(h.Handler, "_respond", spy)
        before = _wire_counts(served)
        if answer == "lone dense Count":
            got = _raw_post(sock, rfile, QUERY.encode())
            assert len(sends.sent) == 1
            assert sends.sent[0].endswith(got) and got == bodies[0]
            took = 1
        else:
            got = _raw_post(sock, rfile, b"Row(f=1)")
            assert len(got) > h.ONE_SEND_MAX
            head, body = sends.sent
            assert head.endswith(b"\r\n\r\n") and len(head) < 512
            assert body is bodies[0] and body == got  # sent uncopied
            took = 2
        assert parsestr.n == 0
        responses, sent = _wire_counts(served)
        assert (responses - before[0], sent - before[1]) == (1, took)
    finally:
        rfile.close()
        sock.close()
