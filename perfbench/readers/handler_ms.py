"""HTTP + PQL front end, from inside: the root ``http.request`` span minus
the ``exec`` span and the ``admission.wait`` span -- dispatch, body read,
parse, serialisation, the handler's own glue -- median over the window's
reads.  (``front_ms`` times the same layer from the client's side.)"""

import statistics

from perfbench import spans as sp


def read(cap):
    ms = []
    for r in cap.profiled():
        spans = sp.of(r)
        if spans is not None:
            ms.append(sp.ms(sp.root(spans)) - sp.total(spans, "exec")
                      - sp.total(spans, "admission.wait"))
    return statistics.median(ms) if ms else None
