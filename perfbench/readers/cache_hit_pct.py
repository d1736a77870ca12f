"""Result cache: share of the window's reads whose flight record says
``cached`` (answered with no device work)."""


def read(cap):
    recs = cap.profiled()
    if not recs:
        return None
    return 100.0 * sum(1 for r in recs if r.profile.get("cached")) / len(recs)
