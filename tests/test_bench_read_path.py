"""The benchmark's readings of the analyst read path: the program's
spans placed on the profiler's clock, the span-overlap, busy-spread and
counter-balance readers, and a traced CPU rehearsal of the track-query
cell that reports every read-path metric."""
import argparse
import glob
import os
import sys
import time
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness, run  # noqa: E402
from bench import trace as bench_trace  # noqa: E402
from bench.readers import (  # noqa: E402
    counter_balance, counter_ratio_kept, device_busy_spread, span_overlap)
from bench.tests import tiny  # noqa: E402
from repro import obs  # noqa: E402
from repro.obs import trace as obs_trace  # noqa: E402

READ_PATH_METRICS = [
    "stage_ms_per_query.open", "stage_ms_per_query.fetch",
    "stage_ms_per_query.unpack", "stage_ms_per_query.sections",
    "stage_ms_per_query.device_decode", "stage_ms_per_query.rebuild",
    "device_decode_concurrency", "duplicate_decode_share",
    "decode_joined_share"]


@pytest.fixture
def traced(monkeypatch):
    monkeypatch.setattr(obs, "_enabled", True)
    obs_trace.reset()
    yield
    obs_trace.reset()


def _profiler_events(trace_dir, name):
    """(start_ns, end_ns) of the profiler's host events called name."""
    import jax

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    return [(ev.start_ns, ev.end_ns) for plane in pd.planes
            if plane.name.startswith("/host") for line in plane.lines
            for ev in line.events if ev.name == name]


def test_span_mirror_sits_on_the_anchored_clock(traced, tmp_path,
                                                monkeypatch):
    """A span's profiler mirror and its perf_counter event, placed on
    the trace's clock through ``bench.clock_anchor`` as the readings
    place every span, start and end within 1 ms of each other."""
    monkeypatch.setattr(harness, "CACHE", str(tmp_path))
    r = harness.Run(argparse.Namespace(seed=1, seconds=1.0, trace=1),
                    {"name": "clock"}, {}, {}, {}, time.perf_counter())
    r.window_start()
    time.sleep(0.01)
    with obs.span("test.mirror"):
        time.sleep(0.02)
    time.sleep(0.01)
    r.window_end()
    off = bench_trace.anchor_ns(bench_trace.load_xspace(r.trace_dir)) \
        - r._anchor * 1e9
    ((_, _, s, e),) = [x for x in r.program_spans()
                       if x[0] == "test.mirror"]
    ((ps, pe),) = _profiler_events(r.trace_dir, "test.mirror")
    assert abs(s * 1e9 + off - ps) < 1e6
    assert abs(e * 1e9 + off - pe) < 1e6
    assert pe - ps >= 0.02e9


def _spans(*intervals):
    return types.SimpleNamespace(
        spans=[("x", 1, s, e) for s, e in intervals] + [("y", 1, 0, 9)],
        run=types.SimpleNamespace(t0=0.0, t1=10.0))


@pytest.mark.parametrize("intervals, want", [
    ([(1, 2), (3, 5)], 1.0),            # disjoint: nobody waited
    ([(1, 3), (1, 3)], 2.0),            # two fully overlapping
    ([(1, 3), (3, 5), (1, 5)], 2.0),    # 8 s of spans over 4 s
    ([(-4, 2), (1, 2)], 1.5),           # clipped to the window
])
def test_span_overlap(intervals, want):
    assert span_overlap.read(_spans(*intervals), "x") == pytest.approx(want)


def test_span_overlap_reads_nothing_without_spans():
    assert span_overlap.read(_spans(), "x") is None
    assert span_overlap.read(_spans((11, 12)), "x") is None


@pytest.mark.parametrize("kept, want", [
    ({"q.dup": {"value": 3}, "q.dec": {"value": 12}}, 25.0),
    ({"q.dec": {"value": 12}}, None),   # a program without the counter
])
def test_counter_ratio_kept(kept, want):
    r = types.SimpleNamespace(
        run=types.SimpleNamespace(counters1=kept),
        counter=lambda c: kept.get(c, {}).get("value", 0), counts={})
    got = counter_ratio_kept.read(r, num=["q.dup"], den=["q.dec"],
                                  scale=100.0)
    assert got == (None if want is None else pytest.approx(want))


def _planes(*planes):
    """Readings whose trace holds one device plane per list of (start,
    end) operations, over a window [0, 100)."""
    device = {f"/device:TPU:{i}": [("m/op", s, e, "") for s, e in ops]
              for i, ops in enumerate(planes)}
    return types.SimpleNamespace(trace=bench_trace.Reduced(
        {"device": device, "host": []}, 0.0, 100.0))


@pytest.mark.parametrize("planes, want", [
    ([[(0, 50)], [(50, 100)]], 0.0),            # as busy as each other
    ([[(0, 80)], [(10, 30), (20, 40)]], 50.0),  # 80% against 30%
    ([[(-10, 20)], [(90, 120)], [(0, 5)]], 15.0),  # clipped to the window
    ([[(0, 30)]], 0.0),                         # one chip
])
def test_device_busy_spread(planes, want):
    assert device_busy_spread.read(_planes(*planes)) == pytest.approx(want)


def test_device_busy_spread_reads_nothing_without_planes():
    assert device_busy_spread.read(_planes()) is None


@pytest.mark.parametrize("rises, want", [
    ({"c0": 3, "c1": 3, "c2": 3, "c3": 3}, 1.0),   # even
    ({"c0": 6, "c1": 2, "c2": 2, "c3": 2}, 2.0),
    ({"c0": 5}, 4.0),                             # all on one chip
    ({"c0": 0, "c1": 0}, None),                   # nothing decoded
    ({}, None),                                   # an older program
])
def test_counter_balance(rises, want):
    r = types.SimpleNamespace(
        run=types.SimpleNamespace(counters1={
            c: {"value": n} for c, n in rises.items()}),
        counter=lambda c: rises.get(c, 0))
    got = counter_balance.read(r, counters=["c0", "c1", "c2", "c3"])
    assert got == (None if want is None else pytest.approx(want))


def test_traced_query_rehearsal_reports_read_path_metrics(
        traced, tmp_path, monkeypatch):
    """A traced run of the track-query cell on the CPU reports every
    read-path metric, from the program's own spans and counters."""
    for var in ("REPRO_OBS", "JAX_COMPILATION_CACHE_DIR",
                "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"):
        monkeypatch.delenv(var, raising=False)   # run_cell sets them
    tiny.use_cache(tmp_path, monkeypatch)
    result = run.run_cell(tiny.args("isabel_archive.track_query", trace=1),
                          check_chips=False, out=lambda _: None,
                          resize=tiny.resize, spec=tiny.SPEC)
    assert result["correct"], result["checks"]
    got = {k: v["value"] for k, v in result["metrics"].items()}
    missing = [m for m in READ_PATH_METRICS if got.get(m) is None]
    assert not missing, got
    for m in READ_PATH_METRICS[:6]:
        assert got[m] >= 0
    assert got["stage_ms_per_query.device_decode"] > 0
    assert got["device_decode_concurrency"] >= 1.0
    assert got["duplicate_decode_share"] == 0     # single-flight decode
    assert 0 <= got["decode_joined_share"] <= 100
