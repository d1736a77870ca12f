"""The load generator: the open-loop schedule, latency from the due
instant, the generator's own lateness, and the window's arithmetic."""

import http.server
import json
import threading
import time

import numpy as np
import pytest

from perfbench import loadgen, mix as mixmod
from perfbench.loadgen import Record, Window, schedule, summarize


class _Stub(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    delay_s = 0.0
    status = 200

    def do_POST(self):  # noqa: N802
        body = self.rfile.read(int(self.headers["Content-Length"]))
        time.sleep(type(self).delay_s)
        out = json.dumps({"results": [len(body)],
                          "profile": {"elapsedMs": 1.0}}).encode()
        # one write: headers and body in two would meet Nagle's delay
        self.wfile.write(
            f"HTTP/1.1 {type(self).status} X\r\nContent-Length: "
            f"{len(out)}\r\n\r\n".encode() + out)

    def log_message(self, *a):
        pass


@pytest.fixture
def stub():
    class Handler(_Stub):
        pass
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield Handler, srv.server_address[1]
    srv.shutdown()
    srv.server_close()
    t.join(timeout=10)
    assert not t.is_alive()


@pytest.mark.parametrize("rate,seconds", [(10.0, 30.0), (40.0, 5.0),
                                          (0.5, 4.0)])
def test_schedule_is_the_same_gaps_in_another_order(rate, seconds):
    a = schedule(rate, seconds, np.random.default_rng(1))
    b = schedule(rate, seconds, np.random.default_rng(2 ** 31 + 5))
    assert len(a) == len(b) == round(rate * seconds)
    assert 0 < a[0] and a[-1] < seconds and (np.diff(a) > 0).all()
    gaps = lambda d: np.sort(np.diff(np.concatenate(([0.0], d))))  # noqa
    assert np.allclose(gaps(a), gaps(b))
    assert not np.allclose(a, b)
    # exponential gaps: about 37% of them longer than the mean
    g = gaps(a)
    if len(g) > 50:
        assert 0.3 < (g > g.mean()).mean() < 0.45


def test_open_loop_times_from_the_due_instant_and_reports_lateness(stub):
    handler, port = stub
    handler.delay_s = 0.2
    w = Window("127.0.0.1", port, "/q", ["a", "bb", "ccc", "dddd"])
    # four requests due together, ONE thread: each waits for the ones
    # before it, and that wait is part of its latency
    w.open_loop(np.array([0.05, 0.05, 0.05, 0.05]), [0, 1, 2, 3], threads=1)
    recs = w.records
    assert [r.status for r in recs] == [200] * 4
    assert sorted(r.result for r in recs) == [1, 2, 3, 4]
    lat = sorted(r.latency_ms for r in recs)
    assert lat[0] == pytest.approx(200, abs=60)
    assert lat[3] == pytest.approx(800, abs=120)
    # the service time alone would have said 200 ms four times
    assert all((r.done - r.sent) * 1e3 < 300 for r in recs)
    s = summarize(recs, seconds=1.0, limit_ms=500)
    assert s["lateness_p95_ms"] == pytest.approx(600, abs=120)
    assert s["attempted"] == 4 and s["failed"] == 0
    assert s["goodput_qps"] == 2.0  # two inside the limit, over 1 s


def test_a_request_is_not_sent_before_it_is_due(stub):
    handler, port = stub
    w = Window("127.0.0.1", port, "/q", ["x"])
    w.open_loop(np.array([0.3]), [0], threads=4)
    (r,) = w.records
    assert r.sent >= 0.3 and r.latency_ms < 100


def test_closed_loop_sends_the_next_on_the_answer_and_stops_at_the_end(stub):
    handler, port = stub
    handler.delay_s = 0.05
    w = Window("127.0.0.1", port, "/q", ["a", "b", "c"])
    w.closed_loop([[0, 1], [2]], seconds=0.5)
    recs = w.records
    assert all(r.due < 0.5 for r in recs)
    assert 12 <= len(recs) <= 20  # two clients x about ten a second
    by_client = [[r for r in recs if r.query in qs] for qs in ([0, 1], [2])]
    for mine in by_client:
        mine.sort(key=lambda r: r.sent)
        assert all(b.sent >= a.done for a, b in zip(mine, mine[1:]))
    assert {r.query for r in by_client[0]} == {0, 1}  # wraps around


def test_a_closed_loop_latency_runs_from_the_sending(stub):
    handler, port = stub
    handler.delay_s = 0.02
    w = Window("127.0.0.1", port, "/q", ["a"])
    w.closed_loop([[0]], seconds=0.3)
    assert len(w.records) >= 5
    assert all(r.sent - r.due < 0.005 and 15 < r.latency_ms < 100
               for r in w.records)


def test_a_failed_request_is_attempted_failed_and_never_good(stub):
    handler, port = stub
    handler.status = 503
    w = Window("127.0.0.1", port, "/q", ["a"])
    w.open_loop(np.array([0.0, 0.01]), [0, 0], threads=2)
    s = summarize(w.records, seconds=1.0, limit_ms=1000)
    assert (s["attempted"], s["failed"], s["goodput_qps"]) == (2, 2, 0.0)


def test_summarize_counts_late_and_unfinished_as_missing_the_limit():
    recs = [Record(0, due=0.0, sent=0.0, done=0.1, status=200),
            Record(0, due=0.1, sent=0.1, done=0.9, status=200),  # late
            Record(0, due=1.9, sent=1.9, done=2.05, status=200),  # after close
            Record(0, due=0.2, sent=0.25, done=0.3, status=0)]  # failed
    s = summarize(recs, seconds=2.0, limit_ms=500)
    assert s["goodput_qps"] == 0.5
    assert s["failed"] == 1 and s["unfinished_at_close"] == 1
    assert s["read_p50_ms"] == pytest.approx(100)
    assert s["read_p95_ms"] == pytest.approx(800)


@pytest.mark.parametrize("n,q,want", [(1, 0.95, 0), (20, 0.95, 18),
                                      (100, 0.5, 49), (200, 0.95, 189)])
def test_percentile_is_nearest_rank(n, q, want):
    assert loadgen.percentile(list(range(n)), q) == want


def _shape(q):
    """A call with its rows and thresholds taken out."""
    if isinstance(q, list):
        return [_shape(x) for x in q] if q[:1] != ["row"] else ["row", q[1]]
    return q if isinstance(q, str) or q is None else 0


@pytest.mark.parametrize("mix", ["seg-dense", "dash-rehearse"])
def test_the_calls_of_a_run_are_drawn_from_the_seed(mix):
    from test_perfbench_oracle import load_mix

    traffic = load_mix(mix)
    n_rows = {"trait": 40, "demo": 64, "cab_type": 3, "passenger_count": 10,
              "pickup_year": 8, "pickup_month": 12, "pickup_mday": 31,
              "pickup_time": 48, "dist_miles": 51, "pickup_grid_id": 40}
    a = mixmod.build(traffic, n_rows, seed=5, seconds=6.0)
    b = mixmod.build(traffic, n_rows, seed=2 ** 31 + 11, seconds=6.0)
    a2 = mixmod.build(traffic, n_rows, seed=5, seconds=6.0)
    warm = mixmod.warm_texts(traffic, n_rows, 5)
    # the same seed sends the same calls; another seed sends other
    # rows in the same number of each shape or panel kind
    assert a.texts == a2.texts and a.order == a2.order
    assert a.texts != b.texts and a.order != b.order
    kinds = lambda m: sorted(json.dumps(_shape(q)) if mix == "seg-dense"  # noqa: E731
                             else q[0].replace("min", "sum").replace(
                                 "max", "sum") for q in m.queries)
    assert kinds(a) == kinds(b)
    assert sorted(a.order) == sorted(b.order) == list(range(len(a.texts)))
    assert warm == mixmod.warm_texts(traffic, n_rows, 5)
    assert warm != mixmod.warm_texts(traffic, n_rows, 6)
    assert len(warm) == traffic["warmup_requests"]
    assert not set(warm) >= set(a.texts)  # other draws
    if traffic["loop"] == "open":
        assert len(a.due) == len(a.texts) == round(traffic["rate_qps"] * 6)
        assert (a.due == a2.due).all() and not np.allclose(a.due, b.due)
    else:
        assert len(a.per_client) == traffic["clients"]
        assert sorted(i for c in a.per_client for i in c) == sorted(a.order)


def test_set_trees_use_every_shape_with_different_rows():
    traffic = mixmod.load_traffic("seg-dense")
    shapes = traffic["params"]["shapes"]
    assert len(shapes) >= 12
    qs = mixmod.family(traffic).generate(
        {**traffic["params"], "zipf_s": 1.0}, {"demo": 256},
        np.random.default_rng(0), 3 * len(shapes))
    from perfbench.oracle import leaves
    for i, q in enumerate(qs):
        rows = [leaf[2] for leaf in leaves(q)]
        assert len(set(rows)) == len(rows) >= 2
        assert q[1][0] == shapes[i % len(shapes)][0]
    # Zipf: the first ranks are drawn far more often than the last
    rows = [leaf[2] for q in qs for leaf in leaves(q)]
    assert sum(r < 16 for r in rows) > sum(r >= 128 for r in rows)
