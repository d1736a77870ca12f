"""Device: share of the traced span in which no operation ran on the
chip (1 - union of device-operation intervals / span)."""


def read(cap):
    if not cap.trace or not cap.trace["devices"] or cap.trace_span is None:
        return None
    lo, hi = cap.trace_span
    return 100.0 * (1.0 - cap.trace["busy_s"] / (hi - lo))
