"""The host's share of a launch: ``launch`` minus ``launch.ready`` --
stacking the batch, building tapes, the jitted call until it returns --
median over batch leaders and reads that ran alone (a follower's launch
span is its leader's, by ``link``, and is left out)."""

import statistics

from perfbench import spans as sp


def read(cap):
    ms = []
    for spans in map(sp.of, cap.launched()):
        own = sp.own_launches(spans) if spans is not None else []
        if own:
            ms.append(sum(map(sp.ms, own))
                      - sp.total(spans, "launch.ready"))
    return statistics.median(ms) if ms else None
