"""``counter_ratio`` for a counter that older programs do not keep: the
ratio where the program registered every ``num`` counter by the end of
the window, and nothing where it did not (a program without the counter
would otherwise read as a ratio of 0)."""

from bench.readers import counter_ratio


def read(r, num, den=(), per=None, scale=1.0):
    kept = r.run.counters1 or {}
    if not all(c in kept for c in num):
        return None
    return counter_ratio.read(r, num, den=den, per=per, scale=scale)
