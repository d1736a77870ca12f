"""Kernels: bytes the reads launched in the traced span NEED
(``perfbench/bytes_needed.py``) over the device's busy time, as a share
of the memory roof of this device kind (``perfbench/peaks.json``; a
kind that is not there is an error)."""

from perfbench.bytes_needed import read_bytes, roof_share_pct


def read(cap):
    recs = cap.launched_in_trace()
    if not cap.trace or not cap.trace["devices"] or not recs or cap.trace["busy_s"] <= 0:
        return None
    if cap.device_kind not in cap.peaks:
        raise KeyError(f"no peak for device kind {cap.device_kind!r} in "
                       "perfbench/peaks.json")
    total = sum(read_bytes(cap.meta, cap.queries[r.query]) for r in recs)
    return roof_share_pct(total, cap.trace["busy_s"],
                          cap.peaks[cap.device_kind]["hbm_gbps"])
