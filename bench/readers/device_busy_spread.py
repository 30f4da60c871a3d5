"""Spread of the device planes' busy shares, in percent of the window:
the largest share in which an operation ran on one chip less the
smallest.  0 means every chip was kept as busy as the others."""

from bench.trace import union_ns


def read(r):
    t = r.trace
    if not t.device or t.window_ns <= 0:
        return None
    busy = [union_ns([ev[1:3] for ev in evs], t.lo, t.hi)
            for evs in t.device.values()]
    return 100.0 * (max(busy) - min(busy)) / t.window_ns
