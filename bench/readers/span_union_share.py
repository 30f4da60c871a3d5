"""Share of the window, in percent, covered by the program's spans of
one name (on any thread)."""

from bench.trace import union_ns


def read(r, span):
    iv = [(s, e) for name, _, s, e in r.spans if name == span]
    if not iv or r.window_s <= 0:
        return None
    return 100.0 * union_ns(iv, r.run.t0, r.run.t1) / r.window_s
