"""Form a coalescer batch the way a serving process forms one: behind
a launch in flight.

A leader that finds no launch in flight flushes at once, so N threads
released together no longer meet in one bucket by a long window alone.
These helpers hold one launch in flight through ``Coalescer.in_flight``
— the scope ``Coalescer._flush`` itself opens around a batch's launch —
start the query threads inside it, and let go once every one of them
sits in a bucket (or has finished: a bucket that fills flushes at once,
and a query that bypasses the coalescer never queues)."""

from __future__ import annotations

import threading
import time


def queued(co) -> int:
    """Queries waiting in the coalescer's unsealed buckets."""
    with co._lock:
        return sum(len(b.items) for b in co._pending.values())


def wait_until(pred, timeout: float = 20.0) -> bool:
    """Poll ``pred`` until it holds or ``timeout`` runs out -> whether
    it held."""
    end = time.monotonic() + timeout
    while not pred():
        if time.monotonic() > end:
            return False
        time.sleep(0.0005)
    return True


def run_behind_launch(co, threads, timeout: float = 60.0) -> None:
    """Start ``threads`` (one coalesced query each) while a launch is
    held in flight, release it when all of them wait in a bucket or
    are done, then join them."""
    with co.in_flight():
        for t in threads:
            t.start()
        wait_until(lambda: queued(co) + sum(not t.is_alive()
                                            for t in threads)
                   >= len(threads), timeout)
    for t in threads:
        t.join(timeout)
    assert not any(t.is_alive() for t in threads), "query threads hung"


def map_behind_launch(co, fn, n: int, timeout: float = 60.0) -> list:
    """``[fn(0), ..., fn(n-1)]``, each call on its own thread, batched
    behind one held launch.  The first exception is re-raised."""
    out = [None] * n
    err = []

    def run(i):
        try:
            out[i] = fn(i)
        except BaseException as e:  # noqa: BLE001
            err.append(e)

    run_behind_launch(
        co, [threading.Thread(target=run, args=(i,)) for i in range(n)],
        timeout)
    assert not err, err
    return out
