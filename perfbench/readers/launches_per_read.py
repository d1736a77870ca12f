"""Executor: device launches a read that was not cached took
(``deviceLaunches`` on its flight record), mean over the window."""


def read(cap):
    n = [r.profile["deviceLaunches"] for r in cap.launched()
         if "deviceLaunches" in r.profile]
    return sum(n) / len(n) if n else None
