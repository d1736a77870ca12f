"""HTTP + PQL front end: what the client waited beyond the server's own
flight record of the request (``elapsedMs``), median over the window."""

import statistics


def read(cap):
    gaps = [(r.done - r.sent) * 1e3 - r.profile["elapsedMs"]
            for r in cap.profiled() if "elapsedMs" in r.profile]
    return statistics.median(gaps) if gaps else None
