"""Device, from the host: the share of the window (first root start to
last root end of the profiled reads) in which no root span is open --
nobody is asking.  Higher is better at a fixed offered rate: the requests
leave sooner."""

from perfbench import spans as sp


def read(cap):
    t = sp.timeline(cap.profiled())
    if t is None:
        return None
    lo, hi, roots, _ = t
    return 100.0 * (1.0 - sp.union(roots) / (hi - lo)) if hi > lo else None
