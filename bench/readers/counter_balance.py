"""Balance of a set of program counters over the window: the largest
rise among ``counters`` over their mean rise, so 1.0 is an even split
and ``len(counters)`` all on one.  Nothing where the program kept none
of the counters, or none rose."""


def read(r, counters):
    kept = r.run.counters1 or {}
    if not any(c in kept for c in counters):
        return None
    rises = [r.counter(c) for c in counters]
    if sum(rises) <= 0:
        return None
    return max(rises) * len(rises) / sum(rises)
