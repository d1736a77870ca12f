"""Device: the window's compile events that the persistent compile cache
had no entry for (``persistent`` = ``miss``): a program no earlier
process of this checkout had compiled."""

from perfbench import compiles


def read(cap):
    events = compiles.in_window(cap)
    if events is None:
        return None
    return float(sum(e["persistent"] == "miss" for e in events))
