"""Admission: the ``admission.wait`` span (the handler, around the
ticket), 95th percentile over the window's reads."""

from perfbench import spans as sp
from perfbench.loadgen import percentile


def read(cap):
    ms = [sp.total(spans, "admission.wait")
          for spans in map(sp.of, cap.profiled()) if spans is not None]
    return percentile(ms, 0.95) if ms else None
