"""Coalescer: the window a read sat in (``coalesce.wait``: submit to the
start of the batch's flush; 0 for a read that ran alone), median over the
reads that launched."""

import statistics

from perfbench import spans as sp


def read(cap):
    ms = [sp.total(spans, "coalesce.wait")
          for spans in map(sp.of, cap.launched()) if spans is not None]
    return statistics.median(ms) if ms else None
