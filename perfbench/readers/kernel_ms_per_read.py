"""Kernels: summed durations of the device operations in the traced
span, over the reads launched in it."""


def read(cap):
    n = len(cap.launched_in_trace())
    if not cap.trace or not cap.trace["devices"] or not n:
        return None
    return cap.trace["op_seconds"] * 1e3 / n
