"""How many of the program's spans of one name are open at once, on
average, while any one is: the sum of their durations inside the window
over the length of their union.  Read on ``pipeline.decode_fields``, it
is the mean number of clients inside device decode while any one is;
1.0 means that no client ever waited behind another."""

from bench.trace import union_ns


def read(r, span):
    lo, hi = r.run.t0, r.run.t1
    iv = [(max(s, lo), min(e, hi)) for name, _, s, e in r.spans
          if name == span]
    iv = [(s, e) for s, e in iv if e > s]
    union = union_ns(iv, lo, hi)
    if union <= 0:
        return None
    return sum(e - s for s, e in iv) / union
