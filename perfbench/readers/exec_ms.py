"""Staging + engines as the host sees them: the flight record's
``elapsedMs`` of reads that were not cached, median.  A timing from
outside the layer; the tracing issue splits it."""

import statistics


def read(cap):
    ms = [r.profile["elapsedMs"] for r in cap.launched()
          if "elapsedMs" in r.profile]
    return statistics.median(ms) if ms else None
