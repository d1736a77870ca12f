"""Device: the window's reads that stood behind a compile they did not
pay for -- their root overlaps a compile event of the window by 5 ms or
more (``perfbench/compiles.py``)."""

from perfbench import compiles


def read(cap):
    events = compiles.in_window(cap)
    if events is None:
        return None
    return float(compiles.reads(compiles.blocked(cap.profiled(), events)))
