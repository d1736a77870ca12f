"""Device: programs compiled inside the window (``/debug/devices``
``compile.total`` after minus before).  The warm-up aims at 0."""


def read(cap):
    try:
        return float(cap.devices_after["compile"]["total"]
                     - cap.devices_before["compile"]["total"])
    except KeyError:
        return None
