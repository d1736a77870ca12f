"""Device, from the host: the share of the window (first root start to
last root end of the profiled reads, on the server's span clock) in which
at least one request is in the server and NO launch is in flight (no
``launch.dispatch`` or ``launch.ready`` span of any request is open): the
chip waits for the host.  With ``server_empty_pct`` it splits
``device_idle_pct``."""

from perfbench import spans as sp


def read(cap):
    t = sp.timeline(cap.profiled())
    if t is None:
        return None
    lo, hi, roots, flights = t
    busy = sp.union(roots)
    # requests present and a launch in flight: flights lie inside roots
    return 100.0 * (busy - sp.union(flights)) / (hi - lo) if hi > lo \
        else None
