"""The served connection on the wire: raw sockets against a live
``Server``.

``server/handler.py`` parses the request head itself (no
``email.parser``) and writes every answer in one send; what a client
can see of the stdlib's behaviour is kept, and each rule kept is one
case here.  The last test holds the query answers byte for byte to
what the tree before that change sent for the same calls (recorded
once from it, as literals)."""

from __future__ import annotations

import json
import re
import socket
import time

import pytest

from pilosa_tpu import proto
from pilosa_tpu.server import handler
from pilosa_tpu.server.server import Server

COUNT = b"Count(Row(f=10))"


@pytest.fixture
def srv(tmp_path):
    s = Server(str(tmp_path / "wire"), admission_query_cap=1,
               admission_query_queue=0)
    s.open()
    s.api.create_index("i")
    s.api.create_field("i", "f")
    s.api.import_bits("i", "f", [10, 10, 10, 11], [1, 2, 70000, 2])
    yield s
    s.close()


class Wire:
    """One client connection: raw bytes out, parsed responses in."""

    def __init__(self, srv):
        self.sock = socket.create_connection(
            ("127.0.0.1", srv.handler.port), timeout=10)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.rfile = self.sock.makefile("rb")

    def send(self, data: bytes) -> None:
        self.sock.sendall(data)

    def response(self) -> tuple[int, dict, bytes]:
        """(status, headers with lower-case names, body)."""
        status = self.rfile.readline()
        assert status.startswith(b"HTTP/1.1 "), status
        headers = {}
        while True:
            line = self.rfile.readline()
            if line in (b"\r\n", b""):
                break
            name, _, value = line.decode("latin-1").partition(":")
            headers[name.lower()] = value.strip()
        body = self.rfile.read(int(headers.get("content-length", 0)))
        return int(status.split()[1]), headers, body

    def closed(self) -> bool:
        """The server has closed its side (EOF, nothing more sent)."""
        return self.rfile.read(1) == b""

    def close(self) -> None:
        self.rfile.close()
        self.sock.close()


def request(method: str = "POST", path: str = "/index/i/query",
            body: bytes = COUNT, version: str = "HTTP/1.1",
            headers: tuple = (("Content-Type", "text/plain"),),
            length: str | None = None) -> bytes:
    head = f"{method} {path} {version}\r\nHost: wire\r\n"
    for k, v in headers:
        head += f"{k}: {v}\r\n"
    if body or length is not None:
        head += f"Content-Length: {len(body) if length is None else length}\r\n"
    return head.encode("latin-1") + b"\r\n" + body


def results(body: bytes):
    return json.loads(body)["results"]


def _in_order(w, srv):
    calls = [b"Count(Row(f=10))", b"Count(Row(f=11))",
             b"Count(Intersect(Row(f=10), Row(f=11)))",
             b"Count(Union(Row(f=10), Row(f=11)))", b"Count(Row(f=12))"]
    for call in calls:
        w.send(request(body=call))
    got = [w.response() for _ in calls]
    assert [s for s, _, _ in got] == [200] * 5
    assert [results(b)[0] for _, _, b in got] == [3, 1, 1, 3, 0]
    assert all("connection" not in h for _, h, _ in got)


def _pipelined_in_one_segment(w, srv):
    w.send(request(body=b"Count(Row(f=11))") + request(body=COUNT))
    assert results(w.response()[2]) == [1]
    assert results(w.response()[2]) == [3]


def _body_split_across_segments(w, srv):
    whole = request()
    w.send(whole[:-9])
    time.sleep(0.05)
    w.send(whole[-9:-4])
    time.sleep(0.05)
    w.send(whole[-4:])
    assert results(w.response()[2]) == [3]
    w.send(request())  # and the connection is where it should be
    assert results(w.response()[2]) == [3]


def _http10_closes(w, srv):
    w.send(request(version="HTTP/1.0"))
    status, headers, body = w.response()
    assert (status, results(body)) == (200, [3])
    assert headers["connection"] == "close"
    assert w.closed()


def _http10_keep_alive_stays(w, srv):
    ka = (("Content-Type", "text/plain"), ("Connection", "keep-alive"))
    w.send(request(version="HTTP/1.0", headers=ka))
    status, headers, body = w.response()
    assert (status, results(body)) == (200, [3])
    assert "connection" not in headers
    w.send(request(version="HTTP/1.0", headers=ka))
    assert results(w.response()[2]) == [3]


def _connection_close_honoured_and_echoed(w, srv):
    w.send(request(headers=(("Content-Type", "text/plain"),
                            ("Connection", "Close"))))
    status, headers, body = w.response()
    assert (status, results(body)) == (200, [3])
    assert headers["connection"] == "close"
    assert w.closed()


def _refused(w, data: bytes, status: int, bare: bool = False) -> None:
    """``data`` is answered ``status`` by the stdlib's error page, with
    ``Connection: close``, and the socket closes.  ``bare``: the
    request line gave no version that could be read, so the stdlib
    answers as to HTTP/0.9, the page alone with no head."""
    w.send(data)
    if bare:
        page = w.rfile.read()  # to EOF: the socket closed
        assert page.startswith(b"<!DOCTYPE HTML>")
    else:
        got, headers, page = w.response()
        assert got == status
        assert headers["connection"] == "close"
        assert w.closed()
    assert f"Error code: {status}".encode() in page


def _malformed_request_line_400(w, srv):
    _refused(w, b"NONSENSE\r\n\r\n", 400, bare=True)


def _four_words_400(w, srv):
    _refused(w, b"GET / extra HTTP/1.1\r\n\r\n", 400)


def _bad_version_400(w, srv):
    _refused(w, b"GET /version HTTP/1.x\r\n\r\n", 400, bare=True)


def _http2_505(w, srv):
    _refused(w, b"GET /version HTTP/2.0\r\n\r\n", 505, bare=True)


def _http09_post_400(w, srv):
    _refused(w, b"POST /index/i/query\r\n\r\n", 400, bare=True)


def _http09_answer_is_the_body_alone(w, srv):
    w.send(b"GET /index/i\r\n\r\n")
    assert json.loads(w.rfile.read())["name"] == "i"  # read to EOF


def _header_line_over_64k_431(w, srv):
    _refused(w, request(method="GET", path="/version", body=b"",
                        headers=(("X-Long", "a" * 65536),)), 431)


def _header_line_of_64k_served(w, srv):
    # "X-Long: " + value + CRLF is exactly 65,536 bytes
    w.send(request(method="GET", path="/version", body=b"",
                   headers=(("X-Long", "a" * (65536 - 10)),)))
    assert w.response()[0] == 200


def _hundred_and_first_header_431(w, srv):
    many = tuple((f"X-H{i}", "v") for i in range(100))  # + Host
    _refused(w, request(method="GET", path="/version", body=b"",
                        headers=many), 431)


def _hundred_headers_served(w, srv):
    many = tuple((f"X-H{i}", "v") for i in range(99))  # + Host
    w.send(request(method="GET", path="/version", body=b"", headers=many))
    assert w.response()[0] == 200


def _bad_content_length(w, length: str):
    w.send(request(length=length))
    status, headers, body = w.response()
    assert status == 400
    assert "Content-Length" in json.loads(body)["error"]
    # how much body follows cannot be known: the connection closes
    assert headers["connection"] == "close"
    assert w.closed()


def _content_length_not_a_number_400(w, srv):
    _bad_content_length(w, "sixteen")


def _content_length_negative_400(w, srv):
    _bad_content_length(w, "-1")


def _header_names_in_any_case(w, srv):
    body = b'{"query": "Count(Row(f=10))"}'
    w.send(b"POST /index/i/query HTTP/1.1\r\nhOsT: wire\r\n"
           b"CONTENT-TYPE:application/json\r\n"
           b"content-length:   " + str(len(body)).encode()
           + b"  \r\n\r\n" + body)
    status, _, got = w.response()
    assert (status, results(got)) == (200, [3])


def _first_of_a_repeated_header_wins(w, srv):
    # were the second Content-Type taken, the raw PQL body would be
    # parsed as JSON: 400
    w.send(request(headers=(("Content-Type", "text/plain"),
                            ("Content-Type", "application/json"))))
    status, _, body = w.response()
    assert (status, results(body)) == (200, [3])


def _expect_100_continue(w, srv):
    whole = request(headers=(("Content-Type", "text/plain"),
                             ("Expect", "100-continue")))
    head, body = whole[:-len(COUNT)], whole[-len(COUNT):]
    w.send(head)
    assert w.rfile.readline() == b"HTTP/1.1 100 Continue\r\n"
    assert w.rfile.readline() == b"\r\n"
    w.send(body)
    status, _, got = w.response()
    assert (status, results(got)) == (200, [3])


def _unknown_method_501(w, srv):
    _refused(w, b"BREW /version HTTP/1.1\r\nHost: wire\r\n\r\n", 501)


def _line_without_colon_400(w, srv):
    _refused(w, b"GET /version HTTP/1.1\r\nHost wire\r\n\r\n", 400)


def _space_before_colon_400(w, srv):
    _refused(w, b"GET /version HTTP/1.1\r\nHost : wire\r\n\r\n", 400)


def _obsolete_line_folding_400(w, srv):
    _refused(w, b"GET /version HTTP/1.1\r\nX-Folded: a\r\n b\r\n\r\n", 400)


def _shed(w, status: int, reason: str, headers: dict, body: bytes):
    out = json.loads(body)
    assert out["reason"] == reason and out["class"] == "query"
    assert int(headers["retry-after"]) >= 1
    assert headers["connection"] == "close"
    assert w.closed()


def _shed_429_says_close_and_closes(w, srv):
    ticket = srv.admission.acquire("query")  # cap 1, queue 0: full
    try:
        w.send(request())
        status, headers, body = w.response()
    finally:
        ticket.release()
    assert status == 429
    _shed(w, status, "queue-full", headers, body)


def _shed_503_says_close_and_closes(w, srv):
    w.send(request(headers=(("Content-Type", "text/plain"),
                            ("X-Pilosa-Deadline", "0"))))
    status, headers, body = w.response()
    assert status == 503
    _shed(w, status, "expired", headers, body)


def _draining_503_says_close_and_closes(w, srv):
    w.send(request())
    assert w.response()[0] == 200
    srv.handler._draining = True  # what close() sets first
    try:
        w.send(request())
        status, headers, body = w.response()
    finally:
        srv.handler._draining = False
    assert (status, body) == (503, b"")
    assert headers["content-length"] == "0"
    assert headers["retry-after"] == "1"
    assert headers["connection"] == "close"
    assert w.closed()


def _not_found_404_keeps_the_connection(w, srv):
    w.send(request(method="GET", path="/nope", body=b""))
    status, headers, body = w.response()
    assert (status, json.loads(body)) == (404, {"error": "not found"})
    assert "connection" not in headers
    w.send(request())
    assert results(w.response()[2]) == [3]


def _query_string_and_trailing_slash(w, srv):
    w.send(request(path="/index/i/query/?shards=0&profile=1"))
    status, _, body = w.response()
    out = json.loads(body)
    assert (status, out["results"]) == (200, [2])  # shard 0 alone
    assert out["profile"]["pql"] == COUNT.decode()


def _answer_over_64k_arrives_whole(w, srv):
    cols = list(range(0, 40000, 2))
    srv.api.import_bits("i", "f", [12] * len(cols), cols)
    w.send(request(body=b"Row(f=12)"))
    status, headers, body = w.response()
    assert status == 200 and int(headers["content-length"]) > 64 << 10
    assert results(body)[0]["columns"] == cols
    w.send(request())
    assert results(w.response()[2]) == [3]


def _every_answer_has_server_and_date(w, srv):
    from email.utils import parsedate_to_datetime

    w.send(request())
    _, headers, _ = w.response()
    assert headers["server"].startswith("BaseHTTP/")
    at = parsedate_to_datetime(headers["date"]).timestamp()
    assert abs(at - time.time()) < 5
    assert list(headers) == ["server", "date", "content-type",
                             "content-length"]


RULES = [
    _in_order,
    _pipelined_in_one_segment,
    _body_split_across_segments,
    _http10_closes,
    _http10_keep_alive_stays,
    _connection_close_honoured_and_echoed,
    _malformed_request_line_400,
    _four_words_400,
    _bad_version_400,
    _http2_505,
    _http09_post_400,
    _http09_answer_is_the_body_alone,
    _header_line_over_64k_431,
    _header_line_of_64k_served,
    _hundred_and_first_header_431,
    _hundred_headers_served,
    _content_length_not_a_number_400,
    _content_length_negative_400,
    _header_names_in_any_case,
    _first_of_a_repeated_header_wins,
    _expect_100_continue,
    _unknown_method_501,
    _line_without_colon_400,
    _space_before_colon_400,
    _obsolete_line_folding_400,
    _shed_429_says_close_and_closes,
    _shed_503_says_close_and_closes,
    _draining_503_says_close_and_closes,
    _not_found_404_keeps_the_connection,
    _query_string_and_trailing_slash,
    _answer_over_64k_arrives_whole,
    _every_answer_has_server_and_date,
]


@pytest.mark.parametrize("rule", RULES,
                         ids=[r.__name__.lstrip("_") for r in RULES])
def test_wire_rule(srv, rule):
    w = Wire(srv)
    try:
        rule(w, srv)
    finally:
        w.close()


@pytest.mark.parametrize("method", ["GET", "POST", "DELETE"])
def test_find_route_is_the_first_registered_match(method):
    """The indexed look-up takes the route, and captures the segments,
    that trying ``_ROUTES`` in registration order took."""
    mine = [r for r in handler._ROUTES if r[0] == method]
    assert mine
    for _, rx, _, _ in mine:
        path = re.sub(r"\(\?P<\w+>[^)]*\)", "x", rx.pattern[1:-1])
        first, name, klass = next(
            (r.match(path), n, k) for _, r, n, k in mine if r.match(path))
        match, got_name, got_klass = handler.find_route(method, path)
        assert (got_name, got_klass) == (name, klass), path
        assert match.groupdict() == first.groupdict(), path
    assert handler.find_route(method, "/no/such/route") is None
    assert handler.find_route("BREW", "/version") is None


def _query_request(pql: str) -> bytes:
    return proto.encode(proto.QUERY_REQUEST, {
        "query": pql, "shards": [], "remote": False,
        "columnAttrs": False, "excludeRowAttrs": False,
        "excludeColumns": False})


PLAIN = (("Content-Type", "text/plain"),)
PROTO = (("Content-Type", "application/x-protobuf"),
         ("Accept", "application/x-protobuf"))

#: call -> (status, Content-Type, Content-Length, body) as the tree
#: before the one-parse-one-send change (b74becb) answered it on this
#: fixture's data
PARENT_SENT = {
    "json": (
        request(), 200, "application/json", "16", b'{"results": [3]}'),
    "json-row": (
        request(body=b'{"query": "Row(f=10)"}',
                headers=(("Content-Type", "application/json"),)),
        200, "application/json", "41",
        b'{"results": [{"columns": [1, 2, 70000]}]}'),
    "json-error": (
        request(body=b"Bogus("), 400, "application/json", "50",
        b'{"error": "expected field name at line 1, char 7"}'),
    "json-no-index": (
        request(path="/index/nope/query"), 400, "application/json", "34",
        b'{"error": "index not found: nope"}'),
    "not-found": (
        request(method="GET", path="/nope", body=b""), 404,
        "application/json", "22", b'{"error": "not found"}'),
    "protobuf": (
        request(body=_query_request("Count(Row(f=10))"), headers=PROTO),
        200, "application/protobuf", "6", b"\x12\x04\x10\x030\x04"),
    "protobuf-error": (
        request(body=_query_request("Bogus("), headers=PROTO),
        400, "application/protobuf", "39",
        b"\n%expected field name at line 1, char 7"),
    "shed-expired": (
        request(headers=PLAIN + (("X-Pilosa-Deadline", "0"),)),
        503, "application/json", "109",
        b'{"error": "query request expired (admission control; retry '
        b'after 1s)", "reason": "expired", "class": "query"}'),
}


@pytest.mark.parametrize("call", list(PARENT_SENT))
def test_answer_is_byte_equal_to_the_parents(srv, call):
    sent, status, ctype, length, body = PARENT_SENT[call]
    w = Wire(srv)
    try:
        w.send(sent)
        got_status, headers, got_body = w.response()
    finally:
        w.close()
    assert (got_status, headers["content-type"],
            headers["content-length"], got_body) == (
        status, ctype, length, body)


def test_sends_equal_responses_until_a_body_passes_64k(srv):
    """``http.sends`` / ``http.responses`` on ``/debug/vars`` and
    ``/metrics``: equal after small answers of every kind, one apart
    after one answer over 64 KiB."""
    w = Wire(srv)
    try:
        for sent, *_ in PARENT_SENT.values():
            v = Wire(srv)  # the shed closes its connection
            v.send(sent)
            v.response()
            v.close()

        def counters():
            w.send(request(method="GET", path="/debug/vars", body=b""))
            snap = json.loads(w.response()[2])
            return snap["http.responses"], snap["http.sends"]

        responses, sends = counters()
        assert responses == sends >= len(PARENT_SENT)
        cols = list(range(0, 40000, 2))
        srv.api.import_bits("i", "f", [12] * len(cols), cols)
        w.send(request(body=b"Row(f=12)"))
        assert w.response()[0] == 200
        # + this Row (two sends) + the /debug/vars read before it (one)
        assert counters() == (responses + 2, sends + 3)
        w.send(request(method="GET", path="/metrics", body=b""))
        text = w.response()[2].decode()
        assert "\nhttp_responses " in text and "\nhttp_sends " in text
    finally:
        w.close()
