"""Executor: the time of a read's root span that no phase names -- root
minus the union of the spans that are no envelope (``http.request``,
``exec``, ``call.<Name>``, ``map``, ``map.fused``): ``unattributed_pct``'s
arithmetic, in milliseconds -- median over the reads that launched.
``tools/route_table.py`` prints where it lies (``perfbench/gaps.py``)."""

import statistics

from perfbench import gaps
from perfbench import spans as sp


def read(cap):
    ms = [gaps.unattributed_ms(spans)
          for spans in map(sp.of, cap.launched()) if spans is not None]
    return statistics.median(ms) if ms else None
