"""HTTP handler: the REST surface of one node.

Parity target: the reference's gorilla/mux route table
(http/handler.go:273-322) — public ``/index/...`` + ``/schema`` +
``/status`` routes, internal ``/internal/...`` node-to-node routes, and
infra routes (``/metrics``, ``/debug/vars``, ``/version``).  The query
and import endpoints negotiate JSON vs protobuf like the reference
(http/handler.go:499 handlePostQuery, :1002 content negotiation;
wire schemas in ``pilosa_tpu.proto``); the control plane speaks JSON.

Built on the stdlib ThreadingHTTPServer — the server side of the DCN
control plane; the TPU data path never goes through HTTP.
"""

from __future__ import annotations

import base64
import email.utils
import io
import json
import re
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlparse

import numpy as np

from pilosa_tpu import observe, proto, tracing
from pilosa_tpu import stats as _stats
from pilosa_tpu.api import (
    API,
    ApiError,
    ApiMethodNotAllowedError,
    ConflictError,
    NotFoundError,
)
from pilosa_tpu.models.field import FieldOptions
from pilosa_tpu.models.index import IndexOptions
from pilosa_tpu.models.row import Row
from pilosa_tpu.parallel.cluster import ShedByPeerError
from pilosa_tpu.parallel.executor import ShardsUnavailableError
from pilosa_tpu.parallel.results import GroupCount, Pair, PairField, ValCount
from pilosa_tpu.serve import admission as _admission
from pilosa_tpu.serve import deadline as _deadline
from pilosa_tpu.serve import tenant as _tenant
from pilosa_tpu.serve.deadline import DeadlineExceededError


def serialize_result(res):
    """Query result -> JSON-able value, matching the reference's JSON
    response shapes (http/handler.go handlePostQuery; pilosa.go
    MarshalJSON impls)."""
    if isinstance(res, Row):
        out = {}
        if res.exclude_columns:
            pass  # columns never materialized (Options excludeColumns)
        elif res.keys:
            out["keys"] = list(res.keys)
        else:
            out["columns"] = [int(c) for c in res.columns()]
        if res.attrs:
            out["attrs"] = res.attrs
        return out
    if isinstance(res, Pair):
        return _pair_dict(res)
    if isinstance(res, PairField):
        return _pair_dict(res.pair)
    if isinstance(res, ValCount):
        return {"value": int(res.val), "count": int(res.count)}
    if isinstance(res, GroupCount):
        return {
            "group": [_field_row_dict(fr) for fr in res.group],
            "count": int(res.count),
        }
    if isinstance(res, list):
        return [serialize_result(r) for r in res]
    if isinstance(res, (np.integer,)):
        return int(res)
    if isinstance(res, (bool, int, str)) or res is None:
        return res
    raise TypeError(f"unserializable result type: {type(res)!r}")


def deserialize_results(raw: list) -> list:
    """JSON query results -> internal result types; the inverse of
    ``serialize_result``, used by HTTPTransport so remote partials feed
    the same reduce paths as local ones (the reference decodes protobuf
    QueryResponse into the same structs, encoding/proto/proto.go)."""
    return [deserialize_result(r) for r in raw]


def deserialize_result(r):
    if isinstance(r, dict):
        if "columns" in r or "keys" in r:
            row = Row.from_columns(r.get("columns") or [])
            row.keys = list(r.get("keys") or [])
            row.attrs = r.get("attrs") or {}
            return row
        if "group" in r:
            from pilosa_tpu.parallel.results import FieldRow

            return GroupCount(
                group=[
                    FieldRow(
                        field=g["field"],
                        row_id=int(g.get("rowID", 0)),
                        row_key=g.get("rowKey", ""),
                        value=g.get("value"),
                    )
                    for g in r["group"]
                ],
                count=int(r["count"]),
            )
        if "value" in r:
            return ValCount(val=int(r["value"]), count=int(r["count"]))
        if "count" in r:
            return Pair(id=int(r.get("id", 0)), key=r.get("key", ""),
                        count=int(r["count"]))
    if isinstance(r, list):
        return [deserialize_result(x) for x in r]
    return r


def _pair_dict(p: Pair) -> dict:
    d = {"count": int(p.count)}
    if p.key:
        d["key"] = p.key
    else:
        d["id"] = int(p.id)
    return d


def _field_row_dict(fr) -> dict:
    d = {"field": fr.field}
    if fr.row_key:
        d["rowKey"] = fr.row_key
    else:
        d["rowID"] = int(fr.row_id)
    if fr.value is not None:
        d["value"] = int(fr.value)
    return d


# Upper bound on accepted request bodies; large enough for bulk roaring
# imports, small enough that one request cannot exhaust host memory.
MAX_REQUEST_BYTES = 256 << 20

# (method, compiled path regex, handler-method name, admission class)
_ROUTES: list[tuple[str, re.Pattern, str, str | None]] = []


def route(method: str, pattern: str, klass: str | None = None):
    """Register a route; `{name}` segments capture path params
    (the gorilla/mux analog, http/handler.go:273).  ``klass`` assigns
    the route's admission class (serve/admission.py): ``query`` for
    user PQL, ``ingest`` for imports, ``internal`` for node-to-node
    RPC; None leaves the route ungated (cheap control-plane and debug
    surfaces)."""
    rx = re.compile(
        "^" + re.sub(r"\{(\w+)\}", r"(?P<\1>[^/]+)", pattern) + "$"
    )

    def deco(fn):
        _ROUTES.append((method, rx, fn.__name__, klass))
        return fn

    return deco


def _index_routes() -> dict[str, tuple[dict, list]]:
    """``_ROUTES`` by method: (literal path -> its route, the method's
    other routes in registration order)."""
    index: dict[str, tuple[dict, list]] = {}
    for method, rx, name, klass in _ROUTES:
        literals, patterns = index.setdefault(method, ({}, []))
        path = rx.pattern[1:-1]
        # a path with no ``{name}`` segment is looked up, not matched,
        # unless a pattern registered before it would have taken it
        if (not rx.groups and path not in literals
                and not any(p.match(path) for p, _, _ in patterns)):
            literals[path] = (rx.match(path), name, klass)
        else:
            patterns.append((rx, name, klass))
    return index


def find_route(method: str, path: str):
    """(match, handler-method name, admission class) of the first
    registered route that takes ``method path``, or None: one dict
    probe for a literal path, else the method's patterns in turn (the
    query route is the first POST pattern)."""
    literals, patterns = _ROUTE_INDEX.get(method, ({}, ()))
    hit = literals.get(path)
    if hit is not None:
        return hit
    for rx, name, klass in patterns:
        match = rx.match(path)
        if match is not None:
            return match, name, klass
    return None


# ---------------------------------------------------------------- the wire

#: a response body up to this size leaves with its head in ONE send;
#: a larger one follows its head uncopied
ONE_SEND_MAX = 64 << 10

#: the stdlib parser's limits (http.client._MAXLINE, _MAXHEADERS)
MAX_HEADER_LINE = 65536
MAX_HEADERS = 100

_SERVER_LINE = (f"Server: {BaseHTTPRequestHandler.server_version} "
                f"{BaseHTTPRequestHandler.sys_version}\r\n")
#: status -> status line + ``Server:``, as ``send_response`` wrote them
_STATUS_HEAD = {
    int(code): f"HTTP/1.1 {int(code)} {phrase}\r\n{_SERVER_LINE}"
    for code, (phrase, _) in BaseHTTPRequestHandler.responses.items()}

_date_line = (0, "")


def _date_header() -> str:
    """``Date: ...`` for this second, formatted once a second."""
    global _date_line
    now = int(time.time())
    stamp, line = _date_line
    if stamp != now:
        line = f"Date: {email.utils.formatdate(now, usegmt=True)}\r\n"
        _date_line = (now, line)
    return line


def response_head(status: int, ctype: str | None, length: int,
                  headers: dict | None, close: bool) -> bytes:
    """Status line through blank line."""
    head = _STATUS_HEAD.get(status)
    if head is None:
        head = f"HTTP/1.1 {status} \r\n{_SERVER_LINE}"
    head += _date_header()
    if ctype is not None:
        head += f"Content-Type: {ctype}\r\n"
    head += f"Content-Length: {length}\r\n"
    if headers:
        for k, v in headers.items():
            head += f"{k}: {v}\r\n"
    head += "Connection: close\r\n\r\n" if close else "\r\n"
    return head.encode("latin-1", "strict")


class Headers:
    """A request's header block: read-only, names in any case, the
    first value of a repeated name (what ``email.message.Message.get``
    gave), values stripped of the whitespace around them."""

    __slots__ = ("_first",)

    def __init__(self, first: dict[str, str]):
        self._first = first

    def get(self, name: str, default=None):
        return self._first.get(name.lower(), default)


class BadHead(Exception):
    """The request head cannot be served: (status, reason phrase)."""


def read_headers(rfile) -> Headers:
    """The header lines up to the blank line, from the buffered
    ``rfile``.  The stdlib parser's limits hold (431 past a 65,536-byte
    line or 100 headers; it counted the blank line too, so took 99); a
    line with no colon, whitespace in a name, or obsolete line folding
    is 400 (``email.parser`` ended the block there without a word, and
    took a folded line into the value)."""
    first: dict[str, str] = {}
    n = 0
    while True:
        raw = rfile.readline(MAX_HEADER_LINE + 1)
        if len(raw) > MAX_HEADER_LINE:
            raise BadHead(431, "Line too long")
        if raw in (b"\r\n", b"\n", b""):
            return Headers(first)
        n += 1
        if n > MAX_HEADERS:
            raise BadHead(431, "Too many headers")
        name, colon, value = raw.decode("iso-8859-1").partition(":")
        if not colon or not name or " " in name or "\t" in name:
            raise BadHead(400, "Bad header line")
        first.setdefault(name.lower(), value.strip())


def _version_number(version: str) -> tuple[int, int] | None:
    """``HTTP/<major>.<minor>`` as integers; None when malformed (the
    stdlib's rules: one dot, digits only, ten at most each)."""
    if not version.startswith("HTTP/"):
        return None
    parts = version[5:].split(".")
    if len(parts) != 2 or not all(
            p.isdigit() and len(p) <= 10 for p in parts):
        return None
    return int(parts[0]), int(parts[1])


class Handler:
    """Routes HTTP requests to an API instance and serves forever on a
    background thread (http/handler.go:46)."""

    #: accept-side headroom above the admission gate's capacity for
    #: ungated infra routes (/metrics, /debug/*, schema) and idle
    #: keep-alive connections.  NOTE the cap counts CONNECTIONS (each
    #: holds one handler thread for its lifetime — that is the
    #: resource being bounded), not active requests: a large fleet of
    #: idle keep-alive clients consumes headroom even while the
    #: admission gate is empty.  Idle connections are reaped by the
    #: per-connection 60 s read timeout, so the steady state tracks
    #: live clients; size the headroom for the expected client pool
    #: (MAX_IDLE_PER_HOST per peer node + monitoring scrapers).
    ACCEPT_HEADROOM = 64

    def __init__(self, api: API, host: str = "127.0.0.1", port: int = 0,
                 stats=None, tracer=None, tls_cert: str | None = None,
                 tls_key: str | None = None, heap_frames: int = 4,
                 admission=None, max_threads: int | None = None,
                 peer_client=None, fanin_timeout: float = 2.0):
        self.api = api
        self.stats = stats
        self.tracer = tracer
        self.heap_frames = heap_frames  # ?start=1 tracemalloc depth
        # cluster-wide debug fan-in (/debug/cluster/*): the server
        # assembly passes its pooled InternalClient; None builds one
        # lazily on first use ([observe] fanin-timeout bounds each peer)
        self.peer_client = peer_client
        self._peer_client_lock = threading.Lock()
        self._owns_peer_client = False  # lazily built -> closed here
        self.fanin_timeout = fanin_timeout
        # admission gate (serve/admission.AdmissionController) — the
        # only accept-side gate between HTTP and device dispatch
        self.admission = admission
        # cap on in-flight handler threads: a connection flood degrades
        # to fast 503s instead of thread exhaustion.  Defaults to the
        # admission gate's total capacity (sum of class caps + queue
        # depths) + headroom; None disables the cap.
        if max_threads is None and admission is not None \
                and admission.enabled:
            max_threads = admission.total_capacity() + self.ACCEPT_HEADROOM
        self.max_threads = max_threads
        self._threads_lock = threading.Lock()
        self._threads_active = 0
        # optional zero-arg callable returning the latest released
        # version string (diagnostics.check_version); None = the
        # local-only default, never phones home
        self.version_fetcher = None
        self.tls = bool(tls_cert)
        handler_self = self

        class _Req(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"
            # keep-alive responses must not sit in Nagle's buffer
            # waiting for the client's delayed ACK
            disable_nagle_algorithm = True
            timeout = 60  # per-connection read timeout

            def setup(self):
                # the TLS handshake runs HERE, in the per-request thread
                # with a timeout — never inside the accept loop, where a
                # stalled client would hang the whole node
                self.request.settimeout(self.timeout)
                if handler_self.tls:
                    self.request.do_handshake()
                super().setup()

            def log_message(self, fmt, *args):  # quiet by default
                pass

            def parse_request(self):
                """The request head, parsed here and not by
                ``email.parser``: the stdlib's answers to a head it
                cannot serve (400, 431, 505; ``send_error`` writes
                them), its keep-alive rules and ``Expect:
                100-continue``, and ``self.headers`` a ``Headers``."""
                # the request line has just been read: the request's
                # root span starts here (observe.Request)
                self.arrived_ns = observe.clock_ns()
                self.command = None  # in case of an error on this line
                self.request_version = "HTTP/0.9"
                self.close_connection = True
                line = str(self.raw_requestline, "iso-8859-1")
                self.requestline = line = line.rstrip("\r\n")
                words = line.split()
                if not words:
                    return False
                if len(words) >= 3:
                    version = words[-1]
                    if version == "HTTP/1.1":
                        self.close_connection = False
                    else:
                        number = _version_number(version)
                        if number is None:
                            self.send_error(
                                400, f"Bad request version ({version!r})")
                            return False
                        if number >= (2, 0):
                            self.send_error(
                                505, f"Invalid HTTP version ({version[5:]})")
                            return False
                        self.close_connection = number < (1, 1)
                    self.request_version = version
                if not 2 <= len(words) <= 3:
                    self.send_error(400, f"Bad request syntax ({line!r})")
                    return False
                command, path = words[:2]
                if len(words) == 2 and command != "GET":
                    self.send_error(
                        400, f"Bad HTTP/0.9 request type ({command!r})")
                    return False
                if path.startswith("//"):
                    # no scheme-less absolute URI (gh-87389)
                    path = "/" + path.lstrip("/")
                self.command, self.path = command, path
                try:
                    headers = self.headers = read_headers(self.rfile)
                except BadHead as e:
                    self.send_error(*e.args)
                    return False
                connection = headers.get("Connection", "").lower()
                if connection == "close":
                    self.close_connection = True
                elif connection == "keep-alive" and len(words) == 3:
                    self.close_connection = False
                if (headers.get("Expect", "").lower() == "100-continue"
                        and self.request_version >= "HTTP/1.1"):
                    self.wfile.write(b"HTTP/1.1 100 Continue\r\n\r\n")
                return True

            def _dispatch(self, method: str):
                handler_self._handle(self, method)

            def do_GET(self):
                self._dispatch("GET")

            def do_POST(self):
                self._dispatch("POST")

            def do_DELETE(self):
                self._dispatch("DELETE")

        class _Srv(ThreadingHTTPServer):
            # the stdlib default listen backlog of 5 drops/resets
            # connections under a burst of concurrent clients — exactly
            # the arrival pattern the query coalescer exists to serve
            request_queue_size = 128

            def process_request(self, request, client_address):
                # accept-side thread cap: past the limit, refuse with a
                # fast 503 written from the accept loop (bounded by a
                # short socket timeout) instead of spawning yet another
                # thread — a connection flood degrades to fast refusals
                # rather than thread exhaustion
                if not handler_self._thread_slot_acquire():
                    handler_self._refuse_connection(request)
                    self.shutdown_request(request)
                    return
                try:
                    super().process_request(request, client_address)
                except BaseException:
                    # the worker thread never started; its release in
                    # process_request_thread will not run
                    handler_self._thread_slot_release()
                    raise

            def process_request_thread(self, request, client_address):
                try:
                    super().process_request_thread(request,
                                                   client_address)
                finally:
                    handler_self._thread_slot_release()

        self.httpd = _Srv((host, port), _Req)
        # close() must not block on handler threads parked in idle
        # keep-alive reads (daemon threads die with the process; bounded
        # by the per-connection timeout otherwise)
        self.httpd.block_on_close = False
        if tls_cert:
            # TLS termination (reference server/tlsconfig.go; https
            # scheme config server/config.go:60)
            import ssl

            ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
            ctx.load_cert_chain(tls_cert, tls_key or tls_cert)
            self.httpd.socket = ctx.wrap_socket(
                self.httpd.socket, server_side=True,
                do_handshake_on_connect=False)
        self.port = self.httpd.server_address[1]
        self.host = host
        # reopen support: close() closes the listening socket, so a
        # reopened server must REBUILD it (on the same port — s.uri
        # stays valid) instead of serve_forever-ing a dead fd
        self._srv_cls, self._req_cls = _Srv, _Req
        self._tls_cert, self._tls_key = tls_cert, tls_key
        self._thread: threading.Thread | None = None
        # /debug/pprof/profile serialization: a second concurrent
        # sampler would double-count stacks and burn CPU for up to 30 s
        # while holding an HTTP worker thread; try-lock -> 409
        self._profile_lock = threading.Lock()
        # set by close(): surviving keep-alive worker threads refuse
        # (503) instead of serving from a closed holder
        self._draining = False
        # answers written by ``_respond`` and the socket sends they
        # took (``http.responses`` / ``http.sends``): equal while every
        # body was at most ONE_SEND_MAX bytes
        self._wire_lock = threading.Lock()
        self.responses = 0
        self.sends = 0

    @property
    def uri(self) -> str:
        scheme = "https" if self.tls else "http"
        return f"{scheme}://{self.host}:{self.port}"

    def serve_background(self) -> None:
        self._draining = False
        if self.httpd.fileno() == -1:
            # reopened after close(): rebuild the listener on the SAME
            # port (server_close() closed the old socket; serving the
            # dead fd raised in the accept thread and the reopened
            # server silently refused every connection)
            self.httpd = self._srv_cls((self.host, self.port),
                                       self._req_cls)
            self.httpd.block_on_close = False
            if self._tls_cert:
                import ssl

                ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
                ctx.load_cert_chain(self._tls_cert,
                                    self._tls_key or self._tls_cert)
                self.httpd.socket = ctx.wrap_socket(
                    self.httpd.socket, server_side=True,
                    do_handshake_on_connect=False)
        self._thread = threading.Thread(target=self.httpd.serve_forever,
                                        daemon=True)
        self._thread.start()

    def close(self) -> None:
        self._draining = True
        self.httpd.shutdown()
        self.httpd.server_close()
        if self._thread is not None:
            self._thread.join(timeout=5)
        with self._peer_client_lock:
            # only a client this handler lazily built is its to close;
            # a server-injected one is closed by the server
            if self._owns_peer_client and self.peer_client is not None:
                self.peer_client.close()
                self.peer_client = None
                self._owns_peer_client = False

    # --------------------------------------------------- accept-side cap

    def _thread_slot_acquire(self) -> bool:
        if self.max_threads is None:
            return True
        with self._threads_lock:
            if self._threads_active < self.max_threads:
                self._threads_active += 1
                return True
        # stats OUTSIDE the lock every accept contends on, and
        # exception-guarded: a slow or raising backend must neither
        # serialize the accept path nor swallow the raw 503 refusal
        if self.stats is not None:
            try:
                self.stats.count("admission.accept_503", 1)
            except Exception:  # noqa: BLE001
                pass
        return False

    def _thread_slot_release(self) -> None:
        if self.max_threads is None:
            return
        with self._threads_lock:
            self._threads_active -= 1

    _REFUSE_BODY = b'{"error":"server overloaded"}'
    _REFUSE_RESPONSE = (
        b"HTTP/1.1 503 Service Unavailable\r\n"
        b"Content-Type: application/json\r\n"
        b"Content-Length: " + str(len(_REFUSE_BODY)).encode() + b"\r\n"
        b"Retry-After: 1\r\n"
        b"Connection: close\r\n\r\n" + _REFUSE_BODY)

    def _refuse_connection(self, request) -> None:
        """Best-effort raw 503 from the accept loop (short timeout so a
        stalled client cannot hang accepts).  TLS sockets have not
        handshaken yet (do_handshake_on_connect=False), so they just
        close — a plaintext 503 would read as a protocol error."""
        if self.tls:
            return
        try:
            request.settimeout(1.0)
            request.sendall(self._REFUSE_RESPONSE)
        except OSError:
            pass

    # ------------------------------------------------------------ plumbing

    def _handle(self, req: BaseHTTPRequestHandler, method: str) -> None:
        if self._draining:
            # close() ran, but a pooled keep-alive connection's worker
            # thread outlives httpd.shutdown(): refuse instead of
            # answering from a closed holder (an empty fragment set
            # would serve WRONG results, not an error)
            req.close_connection = True
            try:
                self._respond(req, 503, None, b"",
                              headers={"Retry-After": "1"})
            except OSError:
                pass
            return
        target = req.path
        if "?" in target or "#" in target or ";" in target \
                or not target.startswith("/"):
            parsed = urlparse(target)
            path = parsed.path
            params = ({k: v[0] for k, v in parse_qs(parsed.query).items()}
                      if parsed.query else {})
        else:
            path, params = target, {}
        path = path.rstrip("/") or "/"
        hit = find_route(method, path)
        if hit is None:
            self._error(req, 404, "not found")
            return
        match, name, klass = hit
        # the request's root span opens HERE, before admission: what
        # the handler does around Executor.execute lands on the
        # flight record that adopts this Request (observe.Request)
        recorder = getattr(self.api.executor, "recorder", None)
        if recorder is not None and recorder.enabled:
            with observe.Request(getattr(req, "arrived_ns", 0)) as rq:
                self._serve(req, rq, match, name, klass, path, params)
        else:
            self._serve(req, None, match, name, klass, path, params)

    def _serve(self, req, rq, match, name: str, klass, path: str,
               params: dict) -> None:
        """One matched request: deadline, admission, body, the route's
        handler, and the error mapping.  ``rq`` is the request's
        ``observe.Request`` (None with the flight recorder off)."""
        stats = self.stats
        if stats is not None and stats is not _stats.NOP:
            stats.count_with_tags(
                "http.request", 1, 1.0,
                [f"useragent:{req.headers.get('User-Agent', '')}"])
        # deadline + admission run BEFORE the body is read: a shed
        # request must not pay a 256MB body upload first (the
        # unread body forces the connection closed, like 413)
        dl_hdr = req.headers.get(_deadline.HEADER)
        dl = None
        if dl_hdr is not None:
            try:
                dl = _deadline.parse_header(dl_hdr)
            except ValueError:
                # the body stays unread (like 413/shed): the
                # keep-alive connection must close or its bytes
                # would parse as the next request
                req.close_connection = True
                self._error(req, 400,
                            f"invalid {_deadline.HEADER} header: "
                            f"{dl_hdr!r}")
                return
        # tenant identity ([tenants] isolation): the
        # X-Pilosa-Tenant header (authenticated clients), or
        # ?tenant= (tools and node-to-node sub-query forwarding —
        # exactly like ?nocache).  A missing/empty id rides the
        # default tier; the label is an accounting key, never a
        # credential, so malformed values degrade instead of 400.
        tenant = _tenant.clean(req.headers.get("X-Pilosa-Tenant")
                               or params.get("tenant"))
        # stash the cleaned label on the request so handle_query's
        # ExecOptions reuses THIS value — parsing twice invites the
        # two sites drifting apart (quota charged to one tenant,
        # cache/residency to another)
        req._pilosa_tenant = tenant
        ticket = None
        if self.admission is not None and klass is not None:
            k = klass
            if klass == "internal":
                # node-to-node routes accept ONE class re-tag (the
                # X-Pilosa-Class stamped by serve.admission
                # rpc_class at the call site) so import replica
                # deliveries and key allocation ride the ingest
                # gate, not internal.  "query" is deliberately NOT
                # honored — a header must never let internal
                # traffic jump into the highest-priority gate.
                if req.headers.get("X-Pilosa-Class") == "ingest":
                    k = "ingest"
            if dl is None and self.admission.default_deadline > 0:
                dl = _deadline.Deadline(
                    self.admission.default_deadline)
            try:
                with observe.span("admission.wait"):
                    ticket = self.admission.acquire(k, dl,
                                                    tenant=tenant)
            except _admission.ShedError as e:
                self._record_shed(
                    match.groupdict().get("index", path), k, e,
                    headers=req.headers)
                req.close_connection = True
                # structured shed body: ``reason`` + the tenant id
                # let a client tell "I am over quota"
                # (tenant-queue-full) from "the server is
                # drowning" (queue-full / deadline-unmeetable)
                body_obj = {"error": str(e), "reason": e.reason,
                            "class": e.klass}
                if e.tenant is not None:
                    body_obj["tenant"] = e.tenant
                self._json(req, body_obj, e.status,
                           headers={"Retry-After":
                                    str(e.retry_after)})
                return
            except ShedByPeerError as e:
                # an armed admission.acquire failpoint injects
                # error(shed) here — surface it exactly like a
                # capacity refusal (503 + Retry-After), never an
                # unhandled 500
                req.close_connection = True
                self._error(req, 503, str(e),
                            headers={"Retry-After": "1"})
                return
        try:
            body = b""
            try:
                length = int(req.headers.get("Content-Length") or 0)
                if length < 0:
                    raise ValueError
            except ValueError:
                # how long the body is cannot be known: the connection
                # closes, as for a body left unread
                req.close_connection = True
                self._error(req, 400, "invalid Content-Length header")
                return
            if length > MAX_REQUEST_BYTES:
                # the body stays unread; the keep-alive connection
                # must close or its bytes would parse as the next
                # request
                req.close_connection = True
                self._error(req, 413,
                            f"request body exceeds "
                            f"{MAX_REQUEST_BYTES} bytes")
                return
            if length:
                with observe.span("http.read", bytes=length):
                    body = req.rfile.read(length)
            # trace-context extract + a server span per route (the
            # reference's tracing middleware, http/handler.go:321);
            # entering the span makes it the parent of every span
            # the handler starts (api.*, executor.*)
            parent = tracing.extract_headers(req.headers)
            if rq is not None and ticket is not None:
                # the admission stamp (class + queue wait) rides the
                # Request onto the record that adopts it
                rq.admission = ticket.info()
            with tracing.start_span(f"http.{name}",
                                    parent=parent) as span, \
                    _deadline.scope(dl):
                span.set_tag("http.path", path)
                getattr(self, name)(req, params, match.groupdict(),
                                    body)
        except NotFoundError as e:
            self._error(req, 404, str(e))
        except ConflictError as e:
            self._error(req, 409, str(e))
        except ApiMethodNotAllowedError as e:
            self._error(req, 405, str(e))
        except DeadlineExceededError as e:
            # admitted but expired mid-execution: the executor's
            # stage checks dropped it before device dispatch
            if self.admission is not None and ticket is not None:
                self.admission.count_expired(ticket.klass)
            self._error(req, 503, str(e))
        except ShardsUnavailableError as e:
            # structured replica exhaustion (chaos round): an
            # availability condition, not a client error — 503
            # with the shard list and per-replica causes so
            # operators (and retrying clients) see WHAT is gone
            # and WHY, not a flat string
            self._json(req, {
                "error": str(e),
                "unavailableShards": e.shards,
                "causes": {str(s): e.causes.get(s, {})
                           for s in e.shards},
            }, 503, headers={"Retry-After": "1"})
        except (ApiError, ValueError, KeyError, TypeError) as e:
            self._error(req, 400, str(e))
        except ShedByPeerError as e:
            # a remote sub-request was shed by a peer's admission
            # gate (and the client's retries are exhausted):
            # surface overload honestly, with a back-off signal,
            # instead of masking it as a 500
            self._error(req, 503, str(e),
                        headers={"Retry-After": "1"})
        except Exception as e:  # internal error; keep serving
            from pilosa_tpu.server.client import ClientError

            if (isinstance(e, ClientError)
                    and e.status in (429, 503)):
                # a shed that reached us as a raw ClientError
                # (non-standard transport) still reads as overload
                self._error(req, 503, str(e))
            else:
                self._error(req, 500, f"{type(e).__name__}: {e}")
        finally:
            if ticket is not None:
                ticket.release()

    def _record_shed(self, index: str, klass: str,
                     e: "_admission.ShedError", headers=None) -> None:
        """Shed requests never execute, so the flight recorder is told
        directly — /debug/queries and the slow-query log must show the
        overload story (outcome ``shed``/``expired``, with the queue
        wait the request burned before the refusal).  The shed happens
        BEFORE the handler span opens, so the caller's traceparent is
        extracted here — a shed record must still be one
        /debug/trace/{id} away."""
        recorder = getattr(self.api.executor, "recorder", None)
        if recorder is not None:
            parent = (tracing.extract_headers(headers)
                      if headers is not None else None)
            recorder.record_shed(
                index, "", klass, e.outcome, str(e),
                wait_ns=e.wait_ns, tenant=e.tenant,
                trace_id=parent.trace_id if parent is not None
                else None)

    def _respond(self, req, status: int, ctype: str | None, body: bytes,
                 headers: dict | None = None) -> None:
        """Every answer a route writes: status line, ``Server``,
        ``Date``, ``Content-Type``, ``Content-Length``, the caller's
        ``headers``, ``Connection: close`` when the connection will
        close, and the body, in ONE send up to ``ONE_SEND_MAX`` bytes
        of body; a larger body follows its head uncopied.  The write
        is the ``http.send`` span, the root's last child on the record
        the recorder's ring keeps (an inline ``?profile=1`` is
        rendered before its own send and cannot hold it)."""
        sends = 1 if len(body) <= ONE_SEND_MAX else 2
        # counted before the send: a client that has its answer finds
        # it in the counters
        with self._wire_lock:
            self.responses += 1
            self.sends += sends
        # an HTTP/0.9 answer is its body alone, as the stdlib sent it
        head = (b"" if req.request_version == "HTTP/0.9"
                else response_head(status, ctype, len(body), headers,
                                   req.close_connection))
        with observe.span("http.send"):
            if sends == 1:
                req.wfile.write(head + body)
            else:
                req.wfile.write(head)
                req.wfile.write(body)

    def _json(self, req, obj, status: int = 200,
              headers: dict | None = None) -> None:
        self._respond(req, status, "application/json",
                      json.dumps(obj).encode(), headers)

    def _bytes(self, req, data: bytes, ctype: str = "application/octet-stream",
               status: int = 200) -> None:
        self._respond(req, status, ctype, data)

    def _error(self, req, status: int, msg: str,
               headers: dict | None = None) -> None:
        try:
            self._json(req, {"error": msg}, status, headers=headers)
        except (BrokenPipeError, ConnectionResetError):
            pass

    # ------------------------------------------------------ public routes

    @route("GET", "/")
    def handle_root(self, req, params, path, body):
        self._json(req, {
            "name": "pilosa-tpu",
            "version": self.api.version(),
            "docs": "see /schema, /status, /index/{index}/query",
        })

    @route("GET", "/version")
    def handle_version(self, req, params, path, body):
        # update-check surface (reference handleGetVersion +
        # diagnostics CheckVersion); local-only by design — see
        # diagnostics.check_version
        from pilosa_tpu import diagnostics

        self._json(req, diagnostics.check_version(self.version_fetcher))

    @route("GET", "/info")
    def handle_info(self, req, params, path, body):
        self._json(req, self.api.info())

    @route("GET", "/status")
    def handle_status(self, req, params, path, body):
        from pilosa_tpu.runtime.startup import backend_info

        self._json(req, {
            "state": self.api.state(),
            "nodes": self.api.hosts(),
            "localID": self.api.cluster.local_id,
            # which engine answers here: an accelerator, or the numpy
            # host engine of a one-CPU-device process
            "backend": backend_info(),
        })

    @route("GET", "/hosts")
    def handle_hosts(self, req, params, path, body):
        self._json(req, self.api.hosts())

    @route("GET", "/internal/nodes")
    def handle_internal_nodes(self, req, params, path, body):
        # reference /internal/nodes (http/handler.go handleGetNodes)
        self._json(req, self.api.hosts())

    @route("POST", "/recalculate-caches")
    def handle_recalculate_caches(self, req, params, path, body):
        """Force TopN caches up to date cluster-wide (reference
        handleRecalculateCaches, http/handler.go)."""
        self.api.recalculate_caches(
            remote=params.get("remote") == "true")
        self._json(req, {})

    @route("POST", "/internal/translate/keys", klass="ingest")
    def handle_translate_keys(self, req, params, path, body):
        """Key -> id translation RPC (reference handlePostTranslateKeys;
        wire form TranslateKeysRequest/Response).  Accepts protobuf or
        JSON {"index", "field", "keys"}; ids are allocated via the
        single-writer path."""
        if "protobuf" in req.headers.get("Content-Type", ""):
            d = proto.decode(proto.TRANSLATE_KEYS_REQUEST, body)
        else:
            d = json.loads(body)
        ids = self.api.node.translate_keys_cluster(
            d["index"], d.get("field") or None, d.get("keys") or [],
            create=True)
        if "protobuf" in req.headers.get("Accept", ""):
            self._proto(req, proto.encode(
                proto.TRANSLATE_KEYS_RESPONSE, {"ids": [int(i) for i in ids]}))
        else:
            self._json(req, {"ids": [int(i) for i in ids]})

    @route("GET", "/index")
    def handle_get_indexes(self, req, params, path, body):
        # reference handleGetIndexes: same shape as /schema
        self._json(req, {"indexes": self.api.schema()})

    @route("GET", "/schema")
    def handle_get_schema(self, req, params, path, body):
        self._json(req, {"indexes": self.api.schema()})

    @route("POST", "/schema")
    def handle_post_schema(self, req, params, path, body):
        d = json.loads(body or b"{}")
        self.api.apply_schema(d.get("indexes", []))
        self._json(req, {})

    @route("POST", "/index/{index}/query", klass="query")
    def handle_post_query(self, req, params, path, body):
        """PQL query with content negotiation: raw-PQL or JSON bodies
        answered in JSON, ``application/x-protobuf`` QueryRequest bodies
        answered in protobuf when Accept asks for it (reference
        handlePostQuery, http/handler.go:499,1002)."""
        ctype = req.headers.get("Content-Type", "")
        proto_accept = "protobuf" in req.headers.get("Accept", "")
        shards = None
        if "protobuf" in ctype:
            preq = proto.decode(proto.QUERY_REQUEST, body)
            pql = preq["query"]
            shards = [int(s) for s in preq["shards"]] or None
            remote = preq["remote"]
            column_attrs = preq["columnAttrs"]
            exclude_row_attrs = preq["excludeRowAttrs"]
            exclude_columns = preq["excludeColumns"]
        else:
            pql = body.decode()
            if "json" in ctype:
                pql = json.loads(pql).get("query", "")
            remote = params.get("remote") == "true"
            column_attrs = params.get("columnAttrs") == "true"
            exclude_row_attrs = params.get("excludeRowAttrs") == "true"
            exclude_columns = params.get("excludeColumns") == "true"
        if params.get("shards"):
            shards = [int(s) for s in params["shards"].split(",")]
        # ?profile=1: attach this query's flight-recorder breakdown to
        # the JSON response (protobuf responses have no profile slot).
        # Clear this thread's last-published record FIRST so a bypassed
        # execution can never serve a stale profile.
        profile = params.get("profile") == "1"
        if profile:
            observe.take_last()
        # ?partial=1 (or the X-Pilosa-Partial header): degraded reads —
        # unavailable shards are accounted in the response
        # (missingShards/missingFraction) instead of failing the query.
        # JSON responses only; the protobuf wire has no meta slot, so
        # protobuf clients keep all-or-error semantics.
        partial = (params.get("partial") in ("1", "true")
                   or req.headers.get("X-Pilosa-Partial")
                   in ("1", "true"))
        partial_meta: dict | None = \
            {} if partial and not proto_accept else None
        # degraded execution is only honored where the response can
        # CARRY the accounting: JSON responses (partial_meta) and
        # remote sub-queries (the origin accounts its own failures).
        # A protobuf origin request keeps all-or-error semantics — an
        # unannotated undercount would be silently wrong data.
        partial = partial and (partial_meta is not None or remote)
        try:
            results = self.api.query(
                path["index"], pql, shards=shards, remote=remote,
                column_attrs=column_attrs,
                exclude_row_attrs=exclude_row_attrs,
                exclude_columns=exclude_columns,
                # ?nocoalesce=true: opt this request out of cross-query
                # micro-batching (debugging / latency-sensitive callers)
                coalesce=params.get("nocoalesce") != "true",
                # ?nocache=1: opt this request out of the result cache
                # (symmetric with ?nocoalesce — force a real execution)
                cache=params.get("nocache") not in ("1", "true"),
                # ?nodelta=1: compact pending ingest deltas up front
                # and answer from pure base state (debugging escape;
                # results are bit-exact either way)
                delta=params.get("nodelta") not in ("1", "true"),
                # ?nocontainers=1: route fused reads through the dense
                # pre-container path (debugging escape; results are
                # bit-identical either way)
                containers=params.get("nocontainers")
                not in ("1", "true"),
                # ?nomesh=1: run fused dispatches on the pre-mesh
                # single-device programs (debugging escape; results
                # are byte-identical either way)
                mesh=params.get("nomesh") not in ("1", "true"),
                # ?notiers=1: bypass tiered residency (host-tier
                # lookups miss, evictions drop, misses rebuild
                # inline — the pre-tier behavior; results are
                # byte-identical either way)
                tiers=params.get("notiers") not in ("1", "true"),
                # ?novm=1: route coalesced sparse reads through the
                # pre-VM ragged/fused engines instead of the Pallas
                # bitmap VM (debugging escape; results are
                # byte-identical either way)
                vm=params.get("novm") not in ("1", "true"),
                partial=partial,
                partial_meta=partial_meta,
                # tenant identity (X-Pilosa-Tenant / ?tenant=): rides
                # ExecOptions so every shared resource charges the
                # right tenant, and forwards on sub-queries — the
                # dispatch loop already parsed and cleaned it (ONE
                # parse site; a second would invite the two drifting)
                tenant=getattr(req, "_pilosa_tenant", None),
            )
        except Exception as e:
            if not proto_accept:
                raise
            # protobuf clients get errors as QueryResponse.Err with 400
            # (reference writeProtobufQueryResponse)
            self._proto(req, proto.encode(proto.QUERY_RESPONSE,
                                          {"err": str(e)}), status=400)
            return
        if exclude_columns:
            for r in results:
                if isinstance(r, Row):
                    r.exclude_columns = True
        want_attr_rows = [r for r in results
                          if isinstance(r, Row)
                          and (column_attrs or r.wants_column_attrs)]
        # attach column attribute sets for result columns when requested
        # by the URL param or a per-call Options(columnAttrs=true) —
        # present (possibly empty) whenever requested, so clients can
        # index the key unconditionally
        # (reference executor.go:206 / QueryResponse.columnAttrSets)
        attr_sets = (self._column_attr_sets(path["index"], want_attr_rows)
                     if column_attrs or want_attr_rows else None)
        if proto_accept:
            pb = {"results": [proto.result_to_proto(r) for r in results]}
            if attr_sets is not None:
                pb["columnAttrSets"] = [
                    {"id": a.get("id", 0), "key": a.get("key", ""),
                     "attrs": proto.attrs_to_proto(a["attrs"])}
                    for a in attr_sets
                ]
            self._proto(req, proto.encode(proto.QUERY_RESPONSE, pb))
            return
        with observe.span("serialize") as sp:
            # from the executor's last clock read (the end of ``exec``)
            # to here: the recorder's ring and latency histogram,
            # ``API.query``'s return, the result post-processing
            sp.before("api.close")
            resp = {"results": [serialize_result(r) for r in results]}
        if attr_sets is not None:
            resp["columnAttrs"] = attr_sets
        if partial_meta is not None:
            # always present on partial requests — [] / 0.0 when the
            # whole shard set was reachable, so clients can read the
            # keys unconditionally
            resp["missingShards"] = partial_meta.get("missingShards",
                                                     [])
            resp["missingFraction"] = partial_meta.get(
                "missingFraction", 0.0)
        if profile:
            rec = observe.take_last()
            resp["profile"] = rec.to_dict() if rec is not None else None
        self._json(req, resp)

    def _import_ok(self, req) -> None:
        """Success response for import endpoints: an empty protobuf
        ImportResponse for protobuf clients (reference handlePostImport,
        http/handler.go:1161), JSON {} otherwise."""
        if "protobuf" in req.headers.get("Accept", ""):
            self._proto(req, proto.encode(proto.IMPORT_RESPONSE, {}))
        else:
            self._json(req, {})

    def _proto(self, req, payload: bytes, status: int = 200) -> None:
        try:
            self._bytes(req, payload, ctype="application/protobuf",
                        status=status)
        except (BrokenPipeError, ConnectionResetError):
            pass

    def _column_attr_sets(self, index: str, rows: list[Row]) -> list[dict]:
        idx = self.api.index(index)
        cols: set[int] = set()
        for r in rows:
            cols.update(int(c) for c in r.columns())
        ordered = sorted(cols)
        attrs_by_id = idx.column_attrs.attrs_bulk(ordered)
        keys_by_id = {}
        if idx.options.keys:
            keys = idx.translate_store.translate_ids(ordered)
            keys_by_id = dict(zip(ordered, keys))
        out = []
        for col in ordered:
            attrs = attrs_by_id.get(col)
            if not attrs:
                continue
            entry = {"attrs": attrs}
            if idx.options.keys:
                entry["key"] = keys_by_id.get(col) or ""
            else:
                entry["id"] = col
            out.append(entry)
        return out

    @route("POST", "/index/{index}")
    def handle_create_index(self, req, params, path, body):
        d = json.loads(body or b"{}")
        opts = IndexOptions.from_dict(d.get("options", {}))
        self.api.create_index(path["index"], opts)
        self._json(req, {})

    @route("DELETE", "/index/{index}")
    def handle_delete_index(self, req, params, path, body):
        self.api.delete_index(path["index"])
        self._json(req, {})

    @route("GET", "/index/{index}")
    def handle_get_index(self, req, params, path, body):
        idx = self.api.index(path["index"])
        self._json(req, {"name": idx.name,
                         "options": idx.options.to_dict()})

    @route("POST", "/index/{index}/field/{field}")
    def handle_create_field(self, req, params, path, body):
        d = json.loads(body or b"{}")
        opts = FieldOptions.from_dict(d.get("options", {}))
        self.api.create_field(path["index"], path["field"], opts)
        self._json(req, {})

    @route("DELETE", "/index/{index}/field/{field}")
    def handle_delete_field(self, req, params, path, body):
        self.api.delete_field(path["index"], path["field"])
        self._json(req, {})

    @route("POST", "/index/{index}/field/{field}/import",
       klass="ingest")
    def handle_import(self, req, params, path, body):
        """Bit import: JSON {"rowIDs": [...], "columnIDs": [...],
        "timestamps": [...], "rowKeys": [...], "columnKeys": [...]} or a
        protobuf ImportRequest body (reference handlePostImport; wire
        form internal/public.proto ImportRequest).  Timestamps are unix
        seconds or RFC3339 in JSON, unix NANOseconds in protobuf (the
        reference encodes time.Time.UnixNano)."""
        if "protobuf" in req.headers.get("Content-Type", ""):
            # arrays=True: large packed ID fields stay ndarrays all the
            # way into field.import_bits' vectorized grouping (length
            # checks below must use len(), never truthiness)
            d = proto.decode(proto.IMPORT_REQUEST, body, arrays=True)
            ts = d.get("timestamps")
            if ts is not None and len(ts):
                # 0 = "no timestamp" in the reference's wire form
                d["timestamps"] = [int(t) or None for t in ts]
            # empty repeated fields mean "unkeyed", like absent JSON keys
            for k in ("rowKeys", "columnKeys", "timestamps"):
                v = d.get(k)
                if v is None or not len(v):
                    d[k] = None
        else:
            d = json.loads(body)
        timestamps = d.get("timestamps")
        if timestamps:
            timestamps = [None if t is None else _parse_ts(t)
                          for t in timestamps]
        rows_in = d.get("rowIDs")
        cols_in = d.get("columnIDs")
        self.api.import_bits(
            path["index"], path["field"],
            rows_in if rows_in is not None and len(rows_in) else [],
            cols_in if cols_in is not None and len(cols_in) else [],
            timestamps=timestamps,
            row_keys=d.get("rowKeys"), col_keys=d.get("columnKeys"),
            clear=params.get("clear") == "true",
        )
        self._import_ok(req)

    @route("POST", "/index/{index}/field/{field}/import-value",
       klass="ingest")
    def handle_import_value(self, req, params, path, body):
        if "protobuf" in req.headers.get("Content-Type", ""):
            d = proto.decode(proto.IMPORT_VALUE_REQUEST, body)
            if not d.get("columnKeys"):
                d["columnKeys"] = None
        else:
            d = json.loads(body)
        self.api.import_values(
            path["index"], path["field"],
            d.get("columnIDs") or [], d.get("values") or [],
            col_keys=d.get("columnKeys"),
        )
        self._import_ok(req)

    @route("POST", "/index/{index}/field/{field}/import-roaring/{shard}",
       klass="ingest")
    def handle_import_roaring(self, req, params, path, body):
        """Binary roaring import.  Body: raw roaring bytes for the
        standard view, or JSON {"views": {name: base64}}
        (reference handlePostImportRoaring, ImportRoaringRequest)."""
        ctype = req.headers.get("Content-Type", "")
        clear = params.get("clear") == "true"
        if "protobuf" in ctype:
            d = proto.decode(proto.IMPORT_ROARING_REQUEST, body)
            views = {v["name"]: v["data"] for v in d["views"]}
            clear = clear or d["clear"]
        elif "json" in ctype:
            d = json.loads(body)
            views = {k: base64.b64decode(v)
                     for k, v in (d.get("views") or {}).items()}
        else:
            views = {"": body}
        self.api.import_roaring(path["index"], path["field"],
                                int(path["shard"]), views,
                                clear=clear,
                                remote=params.get("remote") == "true")
        self._import_ok(req)

    @route("GET", "/export", klass="query")
    def handle_export(self, req, params, path, body):
        buf = io.StringIO()
        self.api.export_csv(params["index"], params["field"],
                            int(params.get("shard", 0)), buf)
        self._bytes(req, buf.getvalue().encode(), "text/csv")

    # ---------------------------------------------------- internal routes

    @route("POST", "/internal/cluster/message", klass="internal")
    def handle_cluster_message(self, req, params, path, body):
        resp = self.api.node.receive_message(json.loads(body))
        self._json(req, resp)

    @route("GET", "/internal/shards/max")
    def handle_shards_max(self, req, params, path, body):
        self._json(req, {"standard": self.api.shards_max()})

    @route("GET", "/internal/fragment/nodes")
    def handle_fragment_nodes(self, req, params, path, body):
        self._json(req, self.api.shard_nodes(params["index"],
                                             int(params["shard"])))

    @route("GET", "/internal/fragment/blocks", klass="internal")
    def handle_fragment_blocks(self, req, params, path, body):
        blocks = self.api.fragment_blocks(
            params["index"], params["field"], params["view"],
            int(params["shard"]))
        self._json(req, {"blocks": blocks})

    @route("GET", "/internal/fragment/block/data", klass="internal")
    def handle_fragment_block_data(self, req, params, path, body):
        rows, cols = self.api.fragment_block_data(
            params["index"], params["field"], params["view"],
            int(params["shard"]), int(params["block"]))
        self._json(req, {"rowIDs": rows, "columnIDs": cols})

    @route("GET", "/internal/fragment/data", klass="internal")
    def handle_fragment_data(self, req, params, path, body):
        data = self.api.fragment_data(
            params["index"], params["field"], params["view"],
            int(params["shard"]))
        self._bytes(req, data)

    @route("GET", "/internal/translate/data", klass="internal")
    def handle_translate_data(self, req, params, path, body):
        entries = self.api.translate_data(
            params["index"], params.get("field"),
            int(params.get("offset", 0)))
        self._json(req, {"entries": [
            {"offset": o, "id": i, "key": k} for o, i, k in entries
        ]})

    @route("POST", "/cluster/resize/set-coordinator")
    def handle_set_coordinator(self, req, params, path, body):
        d = json.loads(body)
        self.api.set_coordinator(d["id"])
        self._json(req, {"old": None, "new": d["id"]})

    @route("POST", "/cluster/resize/remove-node")
    def handle_remove_node(self, req, params, path, body):
        d = json.loads(body)
        removed = self.api.remove_node(d["id"])
        self._json(req, {"remove": removed})

    @route("POST", "/cluster/resize/abort")
    def handle_resize_abort(self, req, params, path, body):
        self.api.resize_abort()
        self._json(req, {})

    @route("POST", "/cluster/resize")
    def handle_cluster_resize(self, req, params, path, body):
        """Node add/remove control route: ``mode=online`` (default)
        drives the live rebalance, ``mode=offline`` the legacy
        stop-the-world resize (see API.cluster_resize)."""
        d = json.loads(body or b"{}")
        if "mode" not in d and params.get("mode"):
            d["mode"] = params["mode"]
        self._json(req, self.api.cluster_resize(d))

    # ------------------------------------------------------- infra routes

    @route("GET", "/metrics")
    def handle_metrics(self, req, params, path, body):
        """Prometheus text exposition (http/handler.go:282).

        Trace-id exemplars on histogram buckets are an OpenMetrics
        feature the legacy 0.0.4 parser rejects, so they render only on
        explicit request (``?exemplars=1`` — operators and tooling);
        the scrape default stays a clean 0.0.4 exposition a stock
        Prometheus accepts.  (Deliberately NOT keyed on the Accept
        header: modern Prometheus offers openmetrics-text by default,
        and this exposition is 0.0.4-shaped, not fully OpenMetrics.)"""
        exemplars = params.get("exemplars") == "1"
        if self.stats is not None and hasattr(self.stats, "prometheus_text"):
            # refresh every module gauge family at scrape time so the
            # exposition is never stale (push backends get the same
            # families from the [observe] device-sample-interval loop)
            self._publish_all_gauges()
            text = self.stats.prometheus_text(exemplars=exemplars)
        else:
            text = ""
        # Snapshot-queue health is process-wide (the queue is shared by
        # every holder in the process), so append it here rather than
        # routing through any one server's registry — compaction
        # starvation must be alert-able from any node's /metrics.
        from pilosa_tpu.parallel import spmd
        from pilosa_tpu.runtime import filebudget, prewarm, snapqueue

        text += snapqueue.prometheus_lines()
        text += prewarm.prometheus_lines()
        text += spmd.prometheus_lines()
        text += filebudget.prometheus_lines()
        self._bytes(req, text.encode(), "text/plain; version=0.0.4")

    @route("GET", "/diagnostics")
    def handle_diagnostics(self, req, params, path, body):
        """Local diagnostics document (the reference phones this home to
        diagnostics.pilosa.com, diagnostics.go:42; we only serve it)."""
        from pilosa_tpu import diagnostics

        self._json(req, diagnostics.payload(self.api.node))

    @route("GET", "/debug/threads")
    def handle_debug_threads(self, req, params, path, body):
        """All thread stacks — the /debug/pprof goroutine-dump analog
        (http/handler.go:280 mounts pprof unconditionally)."""
        import sys
        import traceback

        out = []
        names = {t.ident: t.name for t in threading.enumerate()}
        for ident, frame in sys._current_frames().items():
            out.append(f"--- thread {names.get(ident, ident)} ---\n"
                       + "".join(traceback.format_stack(frame)))
        self._bytes(req, "\n".join(out).encode(), "text/plain")

    @route("GET", "/debug/pprof/heap")
    def handle_debug_heap(self, req, params, path, body):
        """Heap/allocation profile — the pprof heap analog
        (http/handler.go:280-281; rates configured like
        server/config.go:151-156, here ``[profile] heap`` starting
        tracemalloc).  Reports top allocation sites (tracemalloc, which
        also tracks numpy buffers), process RSS, and the residency
        manager's device/host cache entries — the buffers that dominate
        at the 10B-column scale.

        ``?topn=N`` bounds the site list (default 25); ``?start=1``
        begins tracing at runtime when the config didn't (allocations
        before that point are invisible — restart-free but partial);
        ``?cumulative=traceback`` groups by full stack instead of
        allocation line."""
        import tracemalloc

        from pilosa_tpu.runtime import residency

        try:
            topn = int(params.get("topn", 25))
        except ValueError:
            raise ApiError("invalid topn parameter")
        if topn < 1:
            raise ApiError("topn must be >= 1")
        if params.get("start") == "1" and not tracemalloc.is_tracing():
            tracemalloc.start(self.heap_frames)
        out = {"tracing": tracemalloc.is_tracing()}
        if tracemalloc.is_tracing():
            current, peak = tracemalloc.get_traced_memory()
            out["traced_bytes"] = current
            out["traced_peak_bytes"] = peak
            group = ("traceback" if params.get("cumulative") == "traceback"
                     else "lineno")
            stats = tracemalloc.take_snapshot().statistics(group)[:topn]
            out["top_allocations"] = [
                {"site": ";".join(f"{fr.filename}:{fr.lineno}"
                                  for fr in st.traceback),
                 "bytes": st.size, "count": st.count}
                for st in stats]
        try:
            with open("/proc/self/status") as f:
                for line in f:
                    if line.startswith("VmRSS"):
                        out["rss_bytes"] = int(line.split()[1]) * 1024
                        break
        except OSError:
            pass
        mgr = residency.manager()
        out["residency"] = mgr.stats()
        out["residency_top"] = mgr.top_entries(topn)
        self._json(req, out)

    @route("GET", "/debug/pprof/profile")
    def handle_debug_profile(self, req, params, path, body):
        """Statistical wall-clock profile over ?seconds=N (default 2,
        max 30): samples every thread's stack at ~100Hz and returns
        collapsed stacks ("frame;frame;frame count" lines, flamegraph
        format) — the CPU-profile analog of /debug/pprof/profile
        (http/handler.go:280).  Wall-clock (not CPU-time) sampling also
        surfaces lock waits, covering the block/mutex profile role
        (server/config.go:151-156)."""
        import sys
        import time as _time
        from collections import Counter

        import math

        try:
            seconds = float(params.get("seconds", 2))
        except ValueError:
            raise ApiError("invalid seconds parameter")
        if not math.isfinite(seconds):  # nan/inf defeat the clamp
            raise ApiError("invalid seconds parameter")
        seconds = min(max(seconds, 0.1), 30.0)
        # one sampler at a time: concurrent samplers double-count each
        # other's stacks and pin CPU for the full window while holding
        # HTTP worker threads; a busy signal beats a corrupt profile
        if not self._profile_lock.acquire(blocking=False):
            self._error(req, 409, "a profile is already running")
            return
        try:
            interval = 0.01
            me = threading.get_ident()
            counts: Counter = Counter()
            deadline = _time.monotonic() + seconds
            while _time.monotonic() < deadline:
                for ident, frame in sys._current_frames().items():
                    if ident == me:
                        continue  # the sampler itself is noise
                    stack = []
                    f = frame
                    while f is not None:
                        code = f.f_code
                        stack.append(
                            f"{code.co_filename.rsplit('/', 1)[-1]}:"
                            f"{code.co_name}")
                        f = f.f_back
                    counts[";".join(reversed(stack))] += 1
                _time.sleep(interval)
            out = "\n".join(f"{stack} {n}"
                            for stack, n in counts.most_common())
        finally:
            self._profile_lock.release()
        self._bytes(req, out.encode(), "text/plain")

    @route("GET", "/debug/cost")
    def handle_debug_cost(self, req, params, path, body):
        """Engine observatory state (pilosa_tpu.perfobs): per-launch
        cost table keyed (engine, work size-class, sparsity bucket)
        with EWMA wall/bytes/achieved-GB/s per cell, the per-engine
        bw_util rollup against the configured bandwidth roof, and the
        device-profiler capture status."""
        from pilosa_tpu import perfobs

        self._json(req, perfobs.cost_debug())

    @route("POST", "/debug/profiler/start")
    def handle_profiler_start(self, req, params, path, body):
        """Begin an on-demand device trace (jax.profiler) into a dated
        dir under the holder's data directory.  ``?seconds=N``
        overrides the ``[observe] profiler-max-seconds`` auto-stop.
        409 while a capture is already active (the /debug/pprof/profile
        discipline: a busy signal beats a queued second capture)."""
        import tempfile

        from pilosa_tpu import perfobs

        max_seconds = None
        if "seconds" in params:
            try:
                max_seconds = float(params["seconds"])
            except ValueError:
                raise ApiError("invalid seconds parameter")
        base = self.api.holder.path or tempfile.gettempdir()
        try:
            out = perfobs.profiler_start(base, max_seconds=max_seconds)
        except perfobs.ProfilerBusy as e:
            self._error(req, 409, str(e))
            return
        self._json(req, out)

    @route("POST", "/debug/profiler/stop")
    def handle_profiler_stop(self, req, params, path, body):
        """End the active device trace and return the artifact dir +
        capture duration.  409 when no capture is active."""
        from pilosa_tpu import perfobs

        try:
            out = perfobs.profiler_stop()
        except perfobs.ProfilerIdle as e:
            self._error(req, 409, str(e))
            return
        self._json(req, out)

    def _debug_queries_payload(self, params) -> dict:
        """The /debug/queries document — factored out so the
        cluster-wide fan-in assembles the LOCAL node's section
        in-process instead of HTTP-calling itself (a self-call would
        burn a handler thread while holding one)."""
        recorder = getattr(self.api.executor, "recorder", None)
        if recorder is None:
            return {"active": [], "recent": []}
        try:
            min_ms = float(params.get("min_ms", 0))
        except ValueError:
            raise ApiError("invalid min_ms parameter")
        sort = params.get("sort", "start")
        if sort not in ("start", "elapsed"):
            raise ApiError("sort must be 'start' or 'elapsed'")

        def prepare(records):
            out = [r.to_dict() for r in records]
            if min_ms > 0:
                out = [d for d in out if d["elapsedMs"] >= min_ms]
            key = "elapsedMs" if sort == "elapsed" else "startTime"
            out.sort(key=lambda d: d[key], reverse=True)
            return out

        return {
            "active": prepare(recorder.active_records()),
            "recent": prepare(recorder.recent_records()),
        }

    @route("GET", "/debug/queries")
    def handle_debug_queries(self, req, params, path, body):
        """Query flight recorder: in-flight queries plus the ring
        buffer of recent ones (pilosa_tpu.observe).  ``?min_ms=N``
        keeps only records at least N ms long (in-flight records by
        their elapsed-so-far); ``?sort=elapsed`` orders both lists
        slowest-first (default ``start``: newest-first)."""
        self._json(req, self._debug_queries_payload(params))

    @route("GET", "/debug/resultcache")
    def handle_debug_resultcache(self, req, params, path, body):
        """Query result cache state (runtime/resultcache): budget /
        bytes / entry count, hit / miss / fill / eviction /
        invalidation totals, and the largest entries (key digest —
        matching the ``cacheKey`` on flight records — bytes, age,
        hits)."""
        from pilosa_tpu.runtime import resultcache

        self._json(req, resultcache.cache().debug())

    @route("GET", "/debug/ingest")
    def handle_debug_ingest(self, req, params, path, body):
        """Streaming-ingest state (pilosa_tpu.ingest): the [ingest]
        config in force, pending-delta totals (bits / rows / bytes /
        fragments), compaction counters (background, inline,
        admission-skipped), and the largest pending per-fragment
        deltas with their age and delta sequence."""
        from pilosa_tpu.ingest import compactor

        self._json(req, compactor.compactor().debug())

    @route("GET", "/debug/containers")
    def handle_debug_containers(self, req, params, path, body):
        """Compressed container-directory engine state
        (ops/containers.py): the [containers] config in force
        (enabled/threshold plus the kind-specialization knobs
        kinds/arrayMax/runCap) and the container.* counters (queries
        served compressed, dense fallbacks, containers gathered vs
        skipped broken out per kind — bitmap/array/run_gathered —
        and empty-domain zero-work answers).  The per-kind
        resident-byte split (compressed total plus its array/run
        sub-pools vs dense) is on /debug/devices (residency.kinds)."""
        from pilosa_tpu.ops import containers

        self._json(req, containers.debug())

    @route("GET", "/debug/ragged")
    def handle_debug_ragged(self, req, params, path, body):
        """Ragged megabatch state (ops/tape.py +
        parallel/coalescer.py): the [ragged] config in force on this
        node's coalescer, the tape.* / coalescer.shape_* counters
        (executions, queries served, per-query fallbacks, shape
        misses), and the interpreter program inventory — which
        (batch, tape-length, leaf-slot, stack-shape) bucket variants
        this process has lowered.  The ``vm`` section covers the
        Pallas bitmap VM: the [vm] knobs in force, the vm.* counters,
        the (batch, tape-length, slot, domain) program variants
        the scalar-prefetch kernel has lowered, and
        ``fallbackReasons`` — the per-reason breakdown of dense-path
        fallbacks (disabled / ineligible_leaf / kind_unsupported /
        oversize / max_prefetch / min_domain, plus the informational
        mesh_active count)."""
        from pilosa_tpu.ops import tape

        out = tape.debug()
        co = getattr(self.api.executor, "coalescer", None)
        out["coalescer"] = {"attached": co is not None}
        if co is not None:
            out["coalescer"].update({
                "enabled": co.enabled,
                "ragged": co.ragged,
                "maxTape": co.max_tape,
                "maxLeaves": co.max_leaves,
                "windowMs": co.window_s * 1e3,
                "inFlight": co.inflight,
                "flushes": dict(co.flushes),
                "maxBatch": co.max_batch,
                "vm": co.vm,
                "vmMinDomain": co.vm_min_domain,
                "vmMaxPrefetch": co.vm_max_prefetch,
            })
        self._json(req, out)

    @route("GET", "/debug/mesh")
    def handle_debug_mesh(self, req, params, path, body):
        """Mesh-native execution state (parallel/meshexec.py): the
        [mesh] config in force, whether the mesh is active, the axis
        layout (which local devices join the shard axis), the
        per-device shard plan for the widest index's shard fan-out,
        the mesh.* counters (launches, queries, ?nomesh fallbacks,
        placements/bytes), and the residency per-device split."""
        from pilosa_tpu.parallel import meshexec
        from pilosa_tpu.runtime import residency

        widest = max(
            [len(idx.available_shards())
             for idx in self.api.holder.indexes.values()] or [0])
        out = meshexec.debug(n_shards=widest or None)
        rs = residency.manager().stats()
        tiers = rs.get("tiers") or {}
        host = tiers.get("host") or {}
        out["residency"] = {"total": rs["total"],
                            "perDevice": rs["per_device"],
                            # per-device HBM is what one chip holds;
                            # the host tier backs ALL of them (demoted
                            # entries re-place under the shard plan in
                            # force at promotion time)
                            "hostTierBytes": host.get("bytes", 0),
                            "demotions": tiers.get("demotions", 0)}
        self._json(req, out)

    @route("GET", "/debug/devices")
    def handle_debug_devices(self, req, params, path, body):
        """Device-runtime telemetry (pilosa_tpu.devobs): the backend
        this process is on (platform, device kind and count, host mode
        or device), which native libraries loaded, per-kernel /
        per-canonical-shape XLA compile counts and wall times,
        host→device transfer bytes and chunk counts by owner,
        residency usage/budget/evictions/high-water, and per-device
        memory_stats (bytes_in_use vs bytes_limit where the backend
        reports them)."""
        from pilosa_tpu import devobs

        self._json(req, devobs.observer().snapshot())

    # ------------------------------------------------- cluster-wide fan-in

    def _fan_in(self, path: str) -> tuple[dict, dict, dict]:
        """Fan ``GET path`` out to every peer over the internal client
        (tagged ``rpc_class("internal")``, deadline-propagated) and
        return (local_id, sections, errors) — sections keyed by node
        id, the local node's section assembled in-process."""
        from pilosa_tpu.parallel.cluster import fan_in
        from pilosa_tpu.serve.admission import rpc_class

        local_id = self.api.cluster.local_id
        peers = [n for n in self.api.cluster.sorted_nodes()
                 if n.id != local_id and n.uri]
        with self._peer_client_lock:
            client = self.peer_client
            if client is None:
                from pilosa_tpu.server.client import InternalClient

                client = self.peer_client = InternalClient()
                self._owns_peer_client = True

        def fetch(node):
            with rpc_class("internal"):
                out = client.debug_json(node.uri, path,
                                        timeout=self.fanin_timeout)
                if not isinstance(out, dict):
                    # a 200 with an empty/None body (peer mid-restart
                    # behind a proxy) must degrade like an error, not
                    # crash the whole merge downstream
                    raise ValueError(f"peer returned non-JSON-object "
                                     f"debug body: {out!r}")
                return out

        sections, errors = fan_in(peers, fetch, self.fanin_timeout + 0.5)
        return local_id, sections, errors

    @route("GET", "/debug/cluster/queries")
    def handle_debug_cluster_queries(self, req, params, path, body):
        """One merged view of query records across the cluster: every
        node's /debug/queries section plus a flat ``recent`` merge
        (each record stamped with its node) sorted newest-first, and
        the cluster's ``slow`` records sorted slowest-first.  A dead
        or drowning peer degrades to an entry in ``errors``."""
        qs = ""
        passthrough = {k: v for k, v in params.items()
                       if k in ("min_ms", "sort")}
        if passthrough:
            from urllib.parse import urlencode

            qs = "?" + urlencode(passthrough)
        # assemble the local section FIRST: it validates the params, so
        # a bad min_ms 400s before any peer traffic is spent
        local_section = self._debug_queries_payload(params)
        local_id, sections, errors = self._fan_in("/debug/queries" + qs)
        sections[local_id] = local_section
        merged = []
        for node_id, sec in sections.items():
            for rec in (sec.get("recent") or []):
                merged.append({**rec, "node": node_id})
        merged.sort(key=lambda d: d.get("startTime", 0), reverse=True)
        slow = sorted((d for d in merged if d.get("slow")),
                      key=lambda d: d.get("elapsedMs", 0), reverse=True)
        self._json(req, {
            "nodes": sections,
            "errors": errors,
            "recent": merged[:512],
            "slow": slow[:128],
        })

    @route("GET", "/debug/cluster/devices")
    def handle_debug_cluster_devices(self, req, params, path, body):
        """One merged view of device health across the cluster: every
        node's /debug/devices section plus cluster totals (compiles,
        compile wall time, transfer bytes, residency usage/evictions)."""
        from pilosa_tpu import devobs

        local_id, sections, errors = self._fan_in("/debug/devices")
        sections[local_id] = devobs.observer().snapshot()
        totals = {"compiles": 0, "compileMs": 0.0, "transferBytes": 0,
                  "residencyBytes": 0, "evictions": 0}
        for sec in sections.values():
            totals["compiles"] += (sec.get("compile") or {}).get("total", 0)
            totals["compileMs"] += (sec.get("compile") or {}).get(
                "totalMs", 0.0)
            totals["transferBytes"] += (sec.get("transfer") or {}).get(
                "bytes", 0)
            res = sec.get("residency") or {}
            totals["residencyBytes"] += res.get("total", 0)
            totals["evictions"] += res.get("evictions", 0)
        totals["compileMs"] = round(totals["compileMs"], 3)
        self._json(req, {
            "nodes": sections,
            "errors": errors,
            "totals": totals,
        })

    # ------------------------------------------- trace autopsy + journal

    def _local_trace_payload(self, trace_id: str) -> dict:
        """This node's contribution to a trace: flight records whose
        (normalized) trace id matches, plus journal events stamped
        with it."""
        from pilosa_tpu import observe

        records = []
        recorder = getattr(self.api.executor, "recorder", None)
        if recorder is not None:
            records = [r.to_dict()
                       for r in recorder.records_for_trace(trace_id)]
        return {
            "records": records,
            "events": observe.journal().events(trace_id=trace_id,
                                               limit=256),
        }

    @route("GET", "/debug/trace/{id}")
    def handle_debug_trace(self, req, params, path, body):
        """Distributed query autopsy: fan per-node flight records in
        from every peer and assemble ONE causal span tree for the
        trace — admission wait, coalescer window, stages, per-node
        remote maps (the hedge loser's side included), reduce — with
        per-span walls that sum to the observed latency
        (pilosa_tpu.traceasm).  ``?local=1`` returns just this node's
        records + events (the fan-in target).  Dead peers degrade to
        ``errors``, the /debug/cluster/* contract."""
        import re as _re

        from pilosa_tpu import observe, traceasm

        trace_id = path["id"]
        if not _re.fullmatch(r"[0-9a-fA-F]{1,64}", trace_id):
            raise ValueError(f"malformed trace id: {trace_id!r}")
        local = self._local_trace_payload(trace_id)
        if params.get("local"):
            self._json(req, local)
            return
        local_id, sections, errors = self._fan_in(
            f"/debug/trace/{trace_id}?local=1")
        sections[local_id] = local
        observe.bump_trace("trace.fanins", max(0, len(sections) - 1))
        if errors:
            observe.bump_trace("trace.errors", len(errors))
        out = traceasm.assemble_trace(sections, errors, trace_id)
        observe.bump_trace("trace.assemblies")
        if out["root"] is None:
            observe.bump_trace("trace.orphans")
        self._json(req, out)

    @route("GET", "/debug/events")
    def handle_debug_events(self, req, params, path, body):
        """This node's event journal (pilosa_tpu.observe.EventJournal):
        structured state-transition events, oldest first.  ``?since=N``
        keeps events with seq > N (the incremental-poll cursor);
        ``?kind=prefix`` filters by kind prefix (``kind=breaker``
        covers open/half-open/close); ``?trace=id`` keeps events
        stamped with that trace; ``?limit=N`` keeps the newest N."""
        from pilosa_tpu import observe

        j = observe.journal()
        self._json(req, {
            "node": j.node_id,
            "events": j.events(
                since=int(params.get("since", 0) or 0),
                kind=params.get("kind") or None,
                trace_id=params.get("trace") or None,
                limit=int(params.get("limit", 512) or 512)),
            "counters": j.counters(),
        })

    @route("GET", "/debug/cluster/events")
    def handle_debug_cluster_events(self, req, params, path, body):
        """The merged cluster timeline: every node's journal slice,
        wall-clock ordered, so "p99 spiked because node2's breaker
        opened mid-backfill" is one request.  Same ``?since=``/
        ``?kind=``/``?trace=``/``?limit=`` filters as /debug/events
        (applied per node before the merge); dead peers degrade to
        ``errors``."""
        from urllib.parse import urlencode

        from pilosa_tpu import traceasm

        passthrough = {k: v for k, v in params.items()
                       if k in ("since", "kind", "trace", "limit")}
        qs = "?" + urlencode(passthrough) if passthrough else ""
        # local section FIRST: it validates the params, so a bad
        # since/limit 400s before any peer traffic is spent
        since = int(params.get("since", 0) or 0)
        kind = params.get("kind") or None
        from pilosa_tpu import observe

        j = observe.journal()
        local_section = {
            "node": j.node_id,
            "events": j.events(
                since=since, kind=kind,
                trace_id=params.get("trace") or None,
                limit=int(params.get("limit", 512) or 512)),
            "counters": j.counters(),
        }
        local_id, sections, errors = self._fan_in("/debug/events" + qs)
        sections[local_id] = local_section
        self._json(req, traceasm.merge_events(sections, errors,
                                              since=since, kind=kind))

    @route("GET", "/debug/peers")
    def handle_debug_peers(self, req, params, path, body):
        """Per-peer failure-handling state (parallel/cluster.py): each
        peer's circuit-breaker state machine (state, consecutive
        failures, transition + fast-fail counters), latency EWMA /
        deviation / sample count (the hedged-read trigger signal), and
        membership state; plus this node's hedge counters."""
        ex = self.api.executor
        with ex._hedge_lock:
            hedge = {"rpcs": ex._hedge_rpcs, "issued": ex._hedge_issued,
                     "wins": ex._hedge_wins}
        self._json(req, {
            "local": self.api.cluster.local_id,
            "peers": self.api.cluster.debug_peers(),
            "hedge": hedge,
        })

    @route("GET", "/debug/antientropy")
    def handle_debug_antientropy(self, req, params, path, body):
        """Self-healing replication state (parallel/syncer.py +
        parallel/hints.py): the resumable anti-entropy cursor, the
        last round's outcome (fragments walked, dirty / reconciled /
        pushed block counts, classified peer failures, duration), the
        cumulative ae.* counters with the digest-cache hit rate, the
        [replication] write policy in force, and each peer's hint
        queue depth / bytes / oldest-hint age."""
        from pilosa_tpu.parallel import hints as _hints
        from pilosa_tpu.parallel import syncer as _syncer

        node = self.api.node
        ctrs = _syncer.counters()
        hits = ctrs["ae.digest_cache_hits"]
        misses = ctrs["ae.digest_cache_misses"]
        cfg = _hints.config()
        # one snapshot read: the AE thread clears ae_cursor on slice
        # completion, and a two-read None-check would race it
        cur = node.ae_cursor
        self._json(req, {
            "cursor": None if cur is None else list(cur),
            "lastRound": node.ae_last_round or None,
            "counters": ctrs,
            "digestCacheHitRate": (
                round(hits / (hits + misses), 4)
                if hits + misses else None),
            "replication": {
                "writePolicy": cfg.write_policy,
                "hintMaxBytes": cfg.hint_max_bytes,
                "hintMaxAge": cfg.hint_max_age,
                "replayInterval": cfg.replay_interval,
            },
            "hints": node.hints.debug(),
            "hintCounters": _hints.counters(),
        })

    @route("GET", "/debug/rebalance")
    def handle_debug_rebalance(self, req, params, path, body):
        """Online rebalance state (parallel/rebalance.py): whether a
        plan is active, the per-shard state machine (dual-write /
        backfill / cutover / dropped with old and new owner sets), the
        cumulative rebalance.* counters, the persisted cursor path,
        and the last finished plan's outcome."""
        self._json(req, self.api.rebalance_status())

    @route("GET", "/debug/failpoints")
    def handle_debug_failpoints(self, req, params, path, body):
        """Failpoint registry state (pilosa_tpu.faultinject): armed
        points with their specs and call/trigger counters, plus the
        full compiled-in site inventory."""
        from pilosa_tpu import faultinject

        self._json(req, faultinject.snapshot())

    @route("POST", "/debug/failpoints")
    def handle_post_failpoints(self, req, params, path, body):
        """Arm/disarm failpoints live: ``{"arm": "<spec>"}`` arms
        (grammar in the faultinject module docstring), ``{"disarm":
        "<name>"}`` disarms one point, ``{"disarm": true}`` disarms
        everything.  Returns the post-change registry snapshot — the
        ops surface ``tools/loadgen.py --chaos`` toggles on a
        schedule."""
        from pilosa_tpu import faultinject

        d = json.loads(body or b"{}")
        if d.get("arm"):
            faultinject.arm(str(d["arm"]))
        dis = d.get("disarm")
        if dis is True or dis == "all":
            faultinject.disarm()
        elif isinstance(dis, str) and dis:
            faultinject.disarm(dis)
        self._json(req, faultinject.snapshot())

    @route("GET", "/debug/admission")
    def handle_debug_admission(self, req, params, path, body):
        """Admission-gate state: per-class caps, in-flight counts,
        queue depths, EWMA service times, and shed/expired totals
        (serve/admission.AdmissionController.debug)."""
        if self.admission is None:
            self._json(req, {"enabled": False})
            return
        out = self.admission.debug()
        out["acceptThreads"] = {
            "active": self._threads_active,
            "max": self.max_threads,
        }
        self._json(req, out)

    @route("GET", "/debug/tenants")
    def handle_debug_tenants(self, req, params, path, body):
        """Per-tenant isolation state (serve/tenant.py): the [tenants]
        policy in force (quotas per configured tenant + the default
        tier), and per tenant the admission picture (admitted / shed /
        expired / in-flight / waiting / queue-wait EWMA, aggregated
        across classes), result-cache bytes + hit/miss/fill/eviction
        counters against the soft budget, and residency HBM/host-tier
        bytes with the demotion pressure charged — the one surface an
        abusive-tenant triage needs."""
        from pilosa_tpu.runtime import residency as _residency
        from pilosa_tpu.runtime import resultcache as _resultcache

        cfg = _tenant.config()
        admission = (self.admission.tenants_debug()
                     if self.admission is not None else {})
        cache = _resultcache.cache().tenant_stats()
        res = _residency.manager().tenant_stats()
        tenants: dict[str, dict] = {}
        for name in sorted(set(admission) | set(cache) | set(res)):
            tenants[name] = {
                "admission": admission.get(name),
                "cache": cache.get(name),
                "residency": res.get(name),
            }
        self._json(req, {
            "enabled": cfg.enabled,
            "default": {
                "share": cfg.default_quota.share,
                "queue": cfg.default_quota.queue,
                "cacheShare": cfg.default_quota.cache_share,
                "residencyShare": cfg.default_quota.residency_share,
            },
            "quotas": {
                n: {"share": q.share, "queue": q.queue,
                    "cacheShare": q.cache_share,
                    "residencyShare": q.residency_share}
                for n, q in cfg.quotas.items()
            },
            "tenants": tenants,
        })

    @route("GET", "/debug/vars")
    def handle_debug_vars(self, req, params, path, body):
        snap = {}
        if self.stats is not None and hasattr(self.stats, "snapshot"):
            self._publish_all_gauges()
            snap = self.stats.snapshot()
        self._json(req, snap)

    def _publish_all_gauges(self) -> None:
        """Push every module gauge family into the stats registry —
        the ONE list both scrape surfaces (/metrics and /debug/vars)
        share, so a new family cannot render on one and drift off the
        other.  Telemetry never fails a scrape."""
        from pilosa_tpu import devobs
        from pilosa_tpu import faultinject as _faultinject
        from pilosa_tpu import observe as _observe_mod
        from pilosa_tpu import perfobs as _perfobs
        from pilosa_tpu import stagecheck as _stagecheck
        from pilosa_tpu.ingest import compactor
        from pilosa_tpu.models import fragment as _fragment
        from pilosa_tpu.ops import containers as _containers
        from pilosa_tpu.ops import tape
        from pilosa_tpu.parallel import hints as _hints
        from pilosa_tpu.parallel import meshexec as _meshexec
        from pilosa_tpu.parallel import rebalance as _rebalance
        from pilosa_tpu.parallel import syncer as _syncer
        from pilosa_tpu.runtime import resultcache

        try:
            with self._wire_lock:
                responses, sends = self.responses, self.sends
            # answers written and the sends they took: equal while
            # every body fitted ONE_SEND_MAX
            self.stats.gauge("http.responses", responses)
            self.stats.gauge("http.sends", sends)
            devobs.observer().publish_gauges(self.stats)
            resultcache.cache().publish_gauges(self.stats)
            compactor.compactor().publish_gauges(self.stats)
            tape.publish_gauges(self.stats)
            _containers.publish_gauges(self.stats)
            _stagecheck.publish_gauges(self.stats)
            _meshexec.publish_gauges(self.stats)
            # engine observatory: launch/bytes totals, cost-table
            # size, per-engine tagged bandwidth — zeros on a clean
            # server
            _perfobs.publish_gauges(self.stats)
            # chaos-round families: breakers, hedged reads, failpoints,
            # partial degradation — zeros on a clean server so the
            # families are alert-able before the first fault
            self.api.cluster.publish_breaker_gauges(self.stats)
            self.api.executor.publish_chaos_gauges(self.stats)
            _faultinject.publish_gauges(self.stats)
            # self-healing replication families: anti-entropy rounds,
            # hinted handoff (with this node's live queue depth), and
            # WAL replay health — zeros on a clean server
            _syncer.publish_gauges(self.stats)
            _hints.publish_gauges(self.stats, self.api.node.hints)
            # online-rebalance families: plan/shard-state gauges plus
            # dual-write / bytes-streamed / abort totals — zeros on a
            # clean server (and on non-coordinator nodes)
            _rebalance.publish_gauges(
                self.stats, getattr(self.api.node, "rebalance", None))
            _fragment.publish_wal_gauges(self.stats)
            # per-tenant isolation totals (zeros while [tenants] is
            # off — the family stays alert-able before the first
            # isolated tenant)
            _tenant.publish_gauges(self.stats, self.admission)
            # event journal + trace-assembly families — zeros on a
            # clean server so both are scrape-visible before the
            # first event or /debug/trace fan-in
            _observe_mod.publish_journal_gauges(self.stats)
        except Exception:  # noqa: BLE001
            pass


#: built once every ``@route`` of ``Handler`` has registered
_ROUTE_INDEX = _index_routes()


def _parse_ts(t):
    import datetime as dt

    if isinstance(t, (int, float)):
        # reference ImportRequest carries unix nanos; accept seconds too
        if t > 1 << 40:
            t = t / 1e9
        return dt.datetime.fromtimestamp(t, dt.timezone.utc).replace(tzinfo=None)
    return dt.datetime.fromisoformat(str(t).replace("Z", ""))
