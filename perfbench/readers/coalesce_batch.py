"""Coalescer: queries that shared a launch.  Mean width of the batch a
launched read ran in: ``coalescer.batch`` where its path was
``coalesced``, and 1 for a read that ran alone on any other path."""


def read(cap):
    b = [r.profile["coalescer"]["batch"]
         if r.profile.get("path") == "coalesced" and r.profile.get("coalescer")
         else 1 for r in cap.launched()]
    return sum(b) / len(b) if b else None
