"""Ratio of program counters over the window: the rise of the ``num``
counters over the rise of the ``den`` counters, or over a count the
driver keeps (``per``), times ``scale``."""


def read(r, num, den=(), per=None, scale=1.0):
    top = sum(r.counter(c) for c in num)
    bottom = r.counts.get(per, 0) if per else sum(r.counter(c) for c in den)
    if bottom <= 0:
        return None
    return scale * top / bottom
