"""What the per-layer readers (``bench/readers/``) read in a traced run.

One object per run, built once the window has closed:

``trace``     the profiler trace reduced to the window (bench/trace.py),
              with the program's spans as host labels for idle gaps;
``spans``     the program's obs spans in the window, as (name, thread,
              start, end) on the ``time.perf_counter`` clock;
``counter(name)``  how much a program counter rose over the window;
``counts``    what the traffic driver counted (queries, stream
              windows made durable, ...);
``window_s``  the window's length on the host clock;
``peaks``     the device's published peaks (bench/peaks.json);
``figures``   kernel operation and byte counts, printed on an earlier
              line (readers add to it).
"""
from __future__ import annotations

from bench import trace as trace_mod


class Readings:
    def __init__(self, run, peaks, counts=None):
        self.run = run
        self.peaks = peaks or {}
        self.counts = dict(counts or {})
        self.window_s = run.window_s
        self.spans = run.program_spans()
        self.figures = {}
        events = trace_mod.load_xspace(run.trace_dir)
        anchor = trace_mod.anchor_ns(events)
        # perf_counter seconds -> trace ns, through the anchor
        off = anchor - run._anchor * 1e9
        lo = run.t0 * 1e9 + off
        hi = run.t1 * 1e9 + off
        labels = [(name, s * 1e9 + off, e * 1e9 + off)
                  for name, _, s, e in self.spans]
        self.trace = trace_mod.Reduced(events, lo, hi, labels)

    def counter(self, name: str) -> int:
        return self.run.counter_delta(name)
