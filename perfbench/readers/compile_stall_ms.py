"""Device: how long the window had a compile running -- the union of the
``compile.events`` of ``/debug/devices`` (read after the window) that
start inside it, in milliseconds.  0 in a window that compiled nothing;
seconds where a program compiled cold."""

from perfbench import compiles


def read(cap):
    events = compiles.in_window(cap)
    return None if events is None else compiles.stall_ms(events)
