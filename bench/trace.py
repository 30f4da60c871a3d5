"""Reduction of a profiler trace to the numbers the benchmark reports.

A run with ``--trace 1`` records the JAX profiler over the measured
window.  This module turns the recorded XSpace into plain event lists
and reduces them:

* ``busy_ns``: the union of the intervals in which an operation ran on
  the device, clipped to the window (the idle share is 1 - busy/window);
* ``ops_matching``: the device operations whose label contains given
  strings (a kernel's calls, with their HLO text for the byte count);
* ``breakdown()``: the device operations that took most time, and the
  longest idle gaps, each labelled with what the host was doing then:
  the innermost of the benchmark's own annotations and the program's
  spans that covers the gap's midpoint.

A device operation is labelled ``<XLA module>/<HLO op>`` (the program
that ran it, without its fingerprint, and the instruction's name); the
instruction's full text is kept only for Pallas kernels
(``tpu_custom_call``), whose operand and result shapes give the bytes a
call moves.  The window is placed on the trace's clock by the
``bench.clock_anchor`` annotation, whose start the harness also reads on
``time.perf_counter``.  Events are ``(label, start_ns, end_ns, text)``
lists, so that a small recorded trace can be stored as JSON and reduced
again in a test.
"""
from __future__ import annotations

import bisect
import glob
import os

ANCHOR = "bench.clock_anchor"
DEVICE_PREFIX = "/device:TPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'


def _plane_events(plane):
    """(label, start, end, text) of one TPU plane's operations."""
    mods = []
    ops = []
    for line in plane.lines:
        if line.name == MODULES_LINE:
            mods = sorted((ev.start_ns, ev.end_ns, ev.name.split("(")[0])
                          for ev in line.events)
        elif line.name == OPS_LINE:
            ops = [(ev.name, ev.start_ns, ev.end_ns) for ev in line.events]
    starts = [m[0] for m in mods]
    out = []
    for name, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        mod = mods[i][2] if i >= 0 and mods[i][1] >= e else "?"
        op = name.split(" = ", 1)[0]
        out.append((f"{mod}/{op}", s, e,
                    name if KERNEL_MARK in name else ""))
    return out


def load_xspace(trace_dir: str) -> dict:
    """Plain events of the newest ``*.xplane.pb`` under ``trace_dir``:
    ``{"device": {plane: [events]}, "host": [events]}`` with each TPU
    plane's operations and the host's ``bench.*`` annotations."""
    import jax

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(files[-1])
    device, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            device[plane.name] = _plane_events(plane)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                host += [(ev.name, ev.start_ns, ev.end_ns, "")
                         for ev in line.events
                         if ev.name.startswith("bench.")]
    return {"device": device, "host": host}


def union_ns(intervals, lo, hi) -> float:
    """Length of the union of ``(start, end)`` intervals within
    [lo, hi]."""
    total = 0.0
    cur_s = cur_e = None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo, hi):
    """Idle gaps ``(start, end)`` between the union of intervals in
    [lo, hi], the edges of the window included."""
    out = []
    t = lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


class Reduced:
    """A trace placed on the window: ``lo``/``hi`` in trace ns."""

    def __init__(self, events: dict, lo: float, hi: float, labels=()):
        self.device = events["device"]
        self.lo, self.hi = lo, hi
        # host labels: the benchmark's annotations plus the program's
        # spans, each (name, start_ns, end_ns) on the trace clock
        self.labels = [x[:3] for x in events["host"] if x[0] != ANCHOR]
        self.labels += list(labels)

    @property
    def window_ns(self) -> float:
        return self.hi - self.lo

    def busy_ns(self) -> float:
        """Busy time averaged over the device planes."""
        if not self.device:
            return 0.0
        return sum(union_ns([ev[1:3] for ev in evs], self.lo, self.hi)
                   for evs in self.device.values()) / len(self.device)

    def ops_matching(self, match):
        """Device operations in the window whose label contains every
        string of ``match``, as (label, start, end, text) clipped to
        the window."""
        out = []
        for evs in self.device.values():
            for name, s, e, text in evs:
                if e > self.lo and s < self.hi and all(
                        m in name for m in match):
                    out.append((name, max(s, self.lo), min(e, self.hi),
                                text))
        return out

    def label_at(self, t: float) -> str:
        """The innermost (shortest) host label covering instant t."""
        best = None
        for name, s, e in self.labels:
            if s <= t <= e and (best is None or e - s < best[1]):
                best = (name, e - s)
        return best[0] if best else "host:unlabelled"

    def breakdown(self, n: int = 10) -> dict:
        by_op = {}
        for evs in self.device.values():
            for name, s, e, _ in evs:
                if e > self.lo and s < self.hi:
                    by_op[name] = by_op.get(name, 0.0) + (
                        min(e, self.hi) - max(s, self.lo))
        k = max(len(self.device), 1)
        ops = sorted(by_op.items(), key=lambda x: -x[1])[:n]
        plane = next(iter(self.device.values()), [])
        idle = gaps([ev[1:3] for ev in plane], self.lo, self.hi)
        by_label = {}
        for s, e in idle:
            lab = self.label_at((s + e) / 2)
            by_label[lab] = by_label.get(lab, 0.0) + (e - s)
        top = sorted(by_label.items(), key=lambda x: -x[1])[:n]
        return {"device_ops": [[name, ns / k / 1e9] for name, ns in ops],
                "idle_gaps": [[name, ns / 1e9] for name, ns in top]}


def anchor_ns(events: dict) -> float:
    starts = [ev[1] for ev in events["host"] if ev[0] == ANCHOR]
    if not starts:
        raise ValueError(f"the trace has no {ANCHOR} annotation")
    return min(starts)


def cut(events: dict, lo: float, hi: float) -> dict:
    """The events that overlap [lo, hi], with the anchor kept: a small
    recorded trace for the reduction's test."""
    def keep(evs):
        return [x for x in evs if x[2] > lo and x[1] < hi]
    return {"device": {k: keep(v) for k, v in events["device"].items()},
            "host": keep(events["host"]) + [
                x for x in events["host"] if x[0] == ANCHOR]}
