"""The load generator: open loop (requests due on a schedule, whatever
the server does) and closed loop (each client sends its next request
when the last one is answered).  One process, plain threads.

Corrected copy of the idea in ``tools/loadgen.py``: there a request's
latency runs from the moment it was sent; here an open-loop request's
latency runs from the instant it was DUE, so a stall shows as the wait
it imposes on every request behind it, and how late the generator
itself ran is reported beside the latencies."""

from __future__ import annotations

import dataclasses
import json
import threading
import time

import numpy as np

from perfbench.server import Conn

REQUEST_TIMEOUT_S = 60.0


@dataclasses.dataclass
class Record:
    """One request of the window.  Times are seconds from the window's
    start, on the host's monotonic clock."""
    query: int          # index into the run's query list
    due: float
    sent: float = 0.0
    done: float = 0.0
    status: int = 0     # HTTP status; 0 = transport error or timeout
    result: object = None
    profile: dict | None = None

    @property
    def latency_ms(self) -> float:
        return (self.done - self.due) * 1e3


def schedule(rate: float, seconds: float, rng: np.random.Generator
             ) -> np.ndarray:
    """Due times of an open-loop window.  The gaps are the same set for
    every seed -- n = rate x seconds quantiles of the exponential
    distribution, mean 1/rate -- in an order drawn from the seed: Poisson
    arrivals whose count and whose bursts' sizes do not change from run
    to run, only where in the window they fall."""
    n = max(1, round(rate * seconds))
    gaps = -np.log(1.0 - (np.arange(n) + 0.5) / n) / rate
    gaps *= seconds / gaps.sum() * n / (n + 1)
    return np.cumsum(rng.permutation(gaps))


class Window:
    """Drives one measured window and keeps its records."""

    def __init__(self, host: str, port: int, path: str, texts: list[str]):
        self.host, self.port, self.path = host, port, path
        self.texts = [t.encode() for t in texts]
        self.records: list[Record] = []
        self._lock = threading.Lock()
        self.t0 = 0.0

    def _send(self, conn: Conn, rec: Record) -> None:
        rec.sent = time.monotonic() - self.t0
        try:
            status, body = conn.request("POST", self.path,
                                        self.texts[rec.query], "text/plain")
            rec.status = status
            if status == 200:
                out = json.loads(body)
                rec.result = out["results"][0]
                rec.profile = out.get("profile")
        except (OSError, ValueError, KeyError, IndexError):
            conn.close()  # status stays 0: a failed request
        rec.done = time.monotonic() - self.t0
        with self._lock:
            self.records.append(rec)

    def open_loop(self, due: np.ndarray, order: list[int],
                  threads: int) -> None:
        """Request i (query ``order[i]``) is due at ``due[i]``.  The
        next free thread takes the next request and sleeps until it is
        due; with every thread busy a request goes out late, and its
        latency still runs from when it was due."""
        nxt = iter(range(len(due)))
        take = threading.Lock()

        def worker() -> None:
            conn = Conn(self.host, self.port, REQUEST_TIMEOUT_S)
            while True:
                with take:
                    i = next(nxt, None)
                if i is None:
                    break
                wait = due[i] - (time.monotonic() - self.t0)
                if wait > 0:
                    time.sleep(wait)
                self._send(conn, Record(order[i], float(due[i])))
            conn.close()

        self._run([threading.Thread(target=worker) for _ in range(threads)])

    def closed_loop(self, per_client: list[list[int]], seconds: float
                    ) -> None:
        """Each client works through its own list (again from the top
        if it runs out), sends its next request when the last one is
        answered, and sends nothing new once the window is over."""
        def client(mine: list[int]) -> None:
            conn = Conn(self.host, self.port, REQUEST_TIMEOUT_S)
            i = 0
            while (now := time.monotonic() - self.t0) < seconds:
                self._send(conn, Record(mine[i % len(mine)], now))
                i += 1
            conn.close()

        self._run([threading.Thread(target=client, args=(m,))
                   for m in per_client])

    def _run(self, threads: list[threading.Thread]) -> None:
        self.t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        self.records.sort(key=lambda r: r.due)


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile."""
    s = sorted(values)
    return s[min(len(s) - 1, max(0, int(np.ceil(q * len(s))) - 1))]


def summarize(records: list[Record], seconds: float, limit_ms: float
              ) -> dict:
    """The end-to-end numbers of a window, and how late the generator
    ran.  A request that failed keeps the time it took to fail and
    never counts as good."""
    lat = [r.latency_ms for r in records]
    good = sum(1 for r in records if r.status == 200
               and r.done <= seconds and r.latency_ms <= limit_ms)
    late = [(r.sent - r.due) * 1e3 for r in records]
    return {"attempted": len(records),
            "failed": sum(1 for r in records if r.status != 200),
            "read_p50_ms": percentile(lat, 0.50),
            "read_p90_ms": percentile(lat, 0.90),
            "read_p95_ms": percentile(lat, 0.95),
            "read_p99_ms": percentile(lat, 0.99),
            "goodput_qps": good / seconds,
            "lateness_p95_ms": percentile(late, 0.95),
            "unfinished_at_close": sum(1 for r in records
                                       if r.done > seconds)}
