"""The trace reduction on small traces recorded on a TPU v5e
(``bench/tests/data/trace_*.json``: the device operations and
``bench.*`` annotations of a slice of each cell's traced window, made
by ``record_trace.py``), checked against plain recomputations."""
import json
import os

import pytest

from bench import kernels, trace

DATA = os.path.join(os.path.dirname(__file__), "data")
FILES = sorted(f for f in (os.listdir(DATA) if os.path.isdir(DATA) else ())
               if f.startswith("trace_"))


def _load(name):
    with open(os.path.join(DATA, name)) as f:
        rec = json.load(f)
    events = {"device": {k: [tuple(x) for x in v]
                         for k, v in rec["events"]["device"].items()},
              "host": [tuple(x) for x in rec["events"]["host"]]}
    return events, rec["lo"], rec["hi"]


def _busy_by_sweep(evs, lo, hi):
    """Union length by a sweep over every start and end point."""
    pts = sorted({lo, hi} | {min(max(p, lo), hi)
                             for _, s, e, _ in evs for p in (s, e)})
    busy = 0.0
    for a, b in zip(pts, pts[1:]):
        mid = (a + b) / 2
        if any(s <= mid < e for _, s, e, _ in evs):
            busy += b - a
    return busy


@pytest.fixture(params=FILES)
def recorded(request):
    return _load(request.param)


def test_both_cells_have_a_recorded_trace():
    assert {"trace_ingest.json", "trace_query.json"} <= set(FILES)


def test_busy_union_matches_a_sweep(recorded):
    events, lo, hi = recorded
    red = trace.Reduced(events, lo, hi)
    (evs,) = events["device"].values()
    assert evs, "the recorded slice holds device operations"
    want = _busy_by_sweep(evs, lo, hi)
    assert red.busy_ns() == pytest.approx(want, rel=1e-9, abs=1.0)
    assert 0 < red.busy_ns() <= red.window_ns


def test_breakdown_sums_device_time_and_idle_gaps(recorded):
    events, lo, hi = recorded
    red = trace.Reduced(events, lo, hi)
    (evs,) = events["device"].values()
    by_name = {}
    for name, s, e, _ in evs:
        if e > lo and s < hi:
            by_name[name] = by_name.get(name, 0) + min(e, hi) - max(s, lo)
    top = max(by_name, key=by_name.get)
    assert sum(e - s for _, s, e, _ in red.ops_matching([top])) \
        >= by_name[top]
    bd = red.breakdown()
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert bd["device_ops"][0] == [top, by_name[top] / 1e9]
    idle = sum(b - a for a, b in trace.gaps(
        [(s, e) for _, s, e, _ in evs], lo, hi))
    assert idle == pytest.approx(red.window_ns - red.busy_ns(), abs=1.0)
    assert sum(s for _, s in bd["idle_gaps"]) == pytest.approx(idle / 1e9)


def test_kernel_calls_need_their_operands_once():
    events, lo, hi = _load("trace_ingest.json")
    calls = [c for c in trace.Reduced(events, lo, hi).ops_matching(
        ["%dualquant_lorenzo_residual_pallas"]) if c[3]]
    assert calls
    # three distinct (T, 256, 256) int32 inputs, each passed twice, a
    # scalar, and the int32 residual: 4 planes and 4 bytes
    plane = 24 * 256 * 256 * 4
    assert {kernels.call_bytes(c[3]) for c in calls} == {4 * plane + 4}
    assert {kernels.call_elements(c[3]) for c in calls} == {24 * 256 * 256}


def test_gaps_are_labelled_by_the_innermost_annotation():
    events = {"device": {"/device:TPU:0": [("m/op", 10, 20, ""),
                                           ("m/op", 40, 50, "")]},
              "host": [("bench.clock_anchor", 0, 1, ""),
                       ("bench.outer", 0, 100, ""),
                       ("bench.inner", 25, 35, "")]}
    red = trace.Reduced(events, 0, 100)
    bd = red.breakdown()
    assert dict(bd["idle_gaps"]) == pytest.approx(
        {"bench.outer": (10 + 50) / 1e9, "bench.inner": 20 / 1e9})
    assert red.busy_ns() == 20
    assert trace.anchor_ns(events) == 0
