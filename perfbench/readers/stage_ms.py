"""Staging: host work that readies operands -- the summed self time of a
read's ``stage`` spans (a range compare that launches while it stages is
its child and not counted) -- median over the reads that launched."""

import statistics

from perfbench import spans as sp


def read(cap):
    ms = [sp.self_total(spans, "stage")
          for spans in map(sp.of, cap.launched()) if spans is not None]
    return statistics.median(ms) if ms else None
