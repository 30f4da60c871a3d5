"""Self time, in milliseconds per unit of a driver count, of the
program's spans of one name in the window: each span's time inside the
window less the part of it that nested spans on the same thread cover."""

from bench.trace import union_ns


def read(r, span, per):
    n = r.counts.get(per, 0)
    mine = [(tid, s, e) for name, tid, s, e in r.spans if name == span]
    if not mine or n <= 0:
        return None
    lo, hi = r.run.t0, r.run.t1
    total = 0.0
    for tid, s, e in mine:
        kids = [(cs, ce) for name, ctid, cs, ce in r.spans
                if ctid == tid and cs >= s and ce <= e
                and (cs, ce) != (s, e)]
        s, e = max(s, lo), min(e, hi)
        if e > s:
            total += (e - s) - union_ns(kids, s, e)
    return 1e3 * total / n
