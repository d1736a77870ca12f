"""What the host waited for the device: ``launch.ready``
(``block_until_ready`` after the jitted call returned), summed over a
read's launches, median over batch leaders and reads that ran alone: the
host's upper bound on device time per launch."""

import statistics

from perfbench import spans as sp


def read(cap):
    ms = [sp.total(spans, "launch.ready")
          for spans in map(sp.of, cap.launched())
          if spans is not None and sp.own_launches(spans)]
    return statistics.median(ms) if ms else None
