"""Read-path unit decodes placed on several devices.

``analysis.configure_read_devices`` spreads the units a thread leads
over a set of devices, the least loaded first.  A unit must decode to
the same bytes on every device, queries answered from several devices
must equal the one-device answers and the plain reference's tracks,
and with no configuration everything stays on the default device.

The multi-device cases run in one subprocess with four virtual CPU
devices (``--xla_force_host_platform_device_count=4``; the pytest
process has one device), which reports what it saw as JSON; the tests
below assert on that report.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.analysis import query as query_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import json
import threading

import jax
import numpy as np

from bench import reference
from bench.drivers.track_query import compare_answer
from bench.fields import advected_turbulence
from repro import analysis, obs
from repro.core import (CompressionConfig, TileGrid, backend,
                        compress_stream, decompress, decompress_region,
                        pipeline)
from repro.obs import trace as obs_trace

devs = jax.devices()
assert len(devs) == 4, devs
obs.enable()

# every decode's output device and the SL stepper's, in call order
placed, stepped = [], []
_decode_fields = pipeline.PlanExecutor.decode_fields


def decode_fields(self, *a):
    xu, xv = _decode_fields(self, *a)
    placed.append(sorted(d.id for d in xu.devices()))
    return xu, xv


pipeline.PlanExecutor.decode_fields = decode_fields
_sl_stepper = backend.sl_stepper


def sl_stepper(*a):
    step = _sl_stepper(*a)

    def recorded(xu, xv, g2f):
        pu, pv = step(xu, xv, g2f)
        stepped.append(sorted(d.id for d in pu.devices()))
        return pu, pv
    return recorded


backend.sl_stepper = sl_stepper


def counts():
    snap = obs.snapshot()
    names = ["query.units_decoded", "query.decode_dup"] + [
        f"query.units_decoded.chip{i}" for i in range(4)]
    return {n: int(snap.get(n, {}).get("value", 0)) for n in names}


def delta(c0, c1):
    return {k: c1[k] - c0[k] for k in c0}


def track_dict(t):
    return {"face_ids": np.asarray(t.face_ids).tolist(),
            "nodes": np.asarray(t.nodes).tolist(),
            "types": np.asarray(t.types).tolist(),
            "is_loop": bool(t.is_loop)}


u, v = advected_turbulence.generate(T=8, H=64, W=64, seed=3)
lo = float(min(u.min(), v.min()))
hi = float(max(u.max(), v.max()))
cfg = CompressionConfig(eb=1e-3, mode="rel", predictor="mop",
                        codec="device", dt=1.0, dx=1.0, dy=1.0)
blob, _ = compress_stream(((u[t], v[t]) for t in range(len(u))), cfg,
                          TileGrid(tile_h=32, tile_w=32, window_t=8),
                          value_range=(lo, hi))
source = analysis.ContainerSource(blob)
hdr = source.header()
n_tracks = len(analysis.track_summaries(blob))
out = {"n_units": len(hdr["units"]), "n_tracks": n_tracks}

# -- no configure_read_devices: the default device only ---------------
analysis.configure_unit_cache(0)
c0 = counts()
del placed[:], stepped[:]
single = [analysis.decode_for_track(blob, k).track for k in range(n_tracks)]
out["default"] = {"placed": placed[:], "stepped": stepped[:],
                  "counts": delta(c0, counts())}

# -- each unit on each device against the one-device decode ----------
ex = pipeline.executor_from_header(hdr)
same, sl_frames = [], []
for e in hdr["units"]:
    uh, secs = source.unit(e)
    ref_u, ref_v = ex.decode_unit(uh, secs)
    del placed[:], stepped[:]
    row = []
    for i, d in enumerate(devs):
        got_u, got_v = ex.decode_unit(uh, secs, device=d, chip=i)
        row.append(got_u.tobytes() == ref_u.tobytes()
                   and got_v.tobytes() == ref_v.tobytes())
    same.append({"equal": row, "placed": placed[:],
                 "stepped": sorted({tuple(s) for s in stepped})})
    sl_frames.append(ex._sl_frames(
        pipeline.encode.parse_field_sections(
            secs, tuple(b - a for a, b in zip(uh["box"][::2],
                                              uh["box"][1::2])))[2]))
out["units"] = same
out["sl_frames"] = sl_frames

# -- 8 threads of track queries over four devices --------------------
analysis.configure_read_devices(devs)    # no cache: every miss decodes
ur, vr = decompress(blob)
scale = reference.fixed_scale(lo, hi)
ref_tracks = reference.extract_tracks(reference.to_fixed(ur, scale),
                                      reference.to_fixed(vr, scale))
answers, errors = [], []
lock = threading.Lock()


def client(c):
    rng = np.random.default_rng(c)
    for k in np.concatenate([rng.permutation(n_tracks)] * 2):
        try:
            t = analysis.decode_for_track(blob, int(k)).track
        except Exception as exc:
            errors.append(repr(exc))
            continue
        with lock:
            answers.append((int(k), t))


c0 = counts()
threads = [threading.Thread(target=client, args=(c,)) for c in range(8)]
for t in threads:
    t.start()
for t in threads:
    t.join(120)
vs_single = [track_dict(t) == track_dict(single[k]) for k, t in answers]
vs_ref = [compare_answer(ref_tracks[k], t) for k, t in answers]
out["threads"] = {
    "alive": sum(t.is_alive() for t in threads), "errors": errors[:3],
    "answers": len(answers), "vs_single": all(vs_single),
    "wrong": sum(w for w, _ in vs_ref),
    "max_gap": max(g for _, g in vs_ref),
    "n_ref": len(ref_tracks), "counts": delta(c0, counts())}

# -- four distinct misses at once: one per device ----------------------
analysis.configure_unit_cache(0)
barrier = threading.Barrier(4, timeout=60)
_decode_unit = pipeline.PlanExecutor.decode_unit


def held(self, uh, secs, **placement):
    barrier.wait()       # all four have taken a device before any decodes
    return _decode_unit(self, uh, secs, **placement)


pipeline.PlanExecutor.decode_unit = held
boxes = [tuple(e["box"]) for e in hdr["units"]][:4]
c0 = counts()
obs_trace.reset()
del placed[:]
threads = [threading.Thread(target=decompress_region, args=(blob, b))
           for b in boxes]
for t in threads:
    t.start()
for t in threads:
    t.join(120)
pipeline.PlanExecutor.decode_unit = _decode_unit
spans = [ev["args"].get("device") for ev in obs_trace.events()
         if ev.get("name") == "pipeline.decode_fields"]
out["concurrent"] = {"alive": sum(t.is_alive() for t in threads),
                     "placed": sorted(placed), "span_devices": sorted(spans),
                     "counts": delta(c0, counts())}

# -- warm-up on every device, and a device that decodes differently ---
analysis.configure_unit_cache(4)
analysis.decode_for_track(blob, 0)
out["warm"] = {"differ": len(analysis.warm_read_path(blob)),
               "cached_after": analysis.unit_cache.stats()["entries"]}


def off_by_one_on_chip2(self, uh, secs, device=None, chip=None):
    u_rec, v_rec = _decode_unit(self, uh, secs, device=device, chip=chip)
    if chip == 2 and tuple(uh["box"]) == boxes[1]:
        u_rec = u_rec.copy()
        u_rec.flat[0] = np.nextafter(u_rec.flat[0], np.float32(np.inf))
    return u_rec, v_rec


pipeline.PlanExecutor.decode_unit = off_by_one_on_chip2
out["warm"]["planted"] = [tuple(e["box"]) == boxes[1]
                          for e in analysis.warm_read_path(blob)]
pipeline.PlanExecutor.decode_unit = _decode_unit
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def report():
    env = dict(os.environ)
    flag = "--xla_force_host_platform_device_count=4"
    env["XLA_FLAGS"] = f"{env.get('XLA_FLAGS', '')} {flag}".strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join([os.path.join(ROOT, "src"), ROOT])
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(SCRIPT)],
                       capture_output=True, text=True, env=env, cwd=ROOT,
                       timeout=900)
    assert r.returncode == 0, r.stdout[-2000:] + "\n" + r.stderr[-6000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_archive_has_units_and_sl_frames(report):
    assert report["n_units"] == 4 and report["n_tracks"] > 0
    assert sum(report["sl_frames"]) > 0     # the SL stepper runs


def test_default_decodes_on_device_zero(report):
    d = report["default"]
    assert d["placed"] and all(p == [0] for p in d["placed"])
    assert d["stepped"] and all(p == [0] for p in d["stepped"])
    c = d["counts"]
    assert c["query.units_decoded"] == c["query.units_decoded.chip0"] > 0
    assert c["query.units_decoded.chip1"] == 0


def test_unit_bytes_equal_on_every_device(report):
    for unit in report["units"]:
        assert unit["equal"] == [True] * 4
        assert unit["placed"] == [[0], [1], [2], [3]]


def test_sl_stepper_runs_on_the_unit_device(report):
    stepped = [s for unit in report["units"] for s in unit["stepped"]]
    assert sorted({tuple(s) for s in stepped}) == [(0,), (1,), (2,), (3,)]


def test_threaded_queries_match_single_device_and_reference(report):
    t = report["threads"]
    assert t["alive"] == 0 and not t["errors"]
    assert t["answers"] == 8 * 2 * report["n_tracks"]
    assert t["n_ref"] == report["n_tracks"]
    assert t["vs_single"]
    assert t["wrong"] == 0 and t["max_gap"] <= 1e-6
    assert t["counts"]["query.decode_dup"] == 0
    assert t["counts"]["query.units_decoded"] > 0


def test_concurrent_misses_reach_every_device(report):
    c = report["concurrent"]
    assert c["alive"] == 0
    assert c["placed"] == [[0], [1], [2], [3]]
    assert c["span_devices"] == [0, 1, 2, 3]


@pytest.mark.parametrize("phase", ["threads", "concurrent"])
def test_chip_counters_sum_to_units_decoded(report, phase):
    c = report[phase]["counts"]
    per_chip = [c[f"query.units_decoded.chip{i}"] for i in range(4)]
    assert sum(per_chip) == c["query.units_decoded"]
    if phase == "concurrent":
        assert per_chip == [1, 1, 1, 1]


def test_warm_up_finds_devices_agree_and_empties_cache(report):
    assert report["warm"]["differ"] == 0
    assert report["warm"]["cached_after"] == 0


def test_warm_up_names_a_unit_one_device_decodes_differently(report):
    assert report["warm"]["planted"] == [True]


def test_least_loaded_device_ties_to_lowest_index():
    rd = query_mod._ReadDevices()
    assert rd.devices == [None]
    rd.configure(["a", "b", "c"])
    with rd.least_loaded() as first:
        with rd.least_loaded() as second:
            with rd.least_loaded() as third:
                with rd.least_loaded() as fourth:
                    assert [first, second, third, fourth] == [
                        (0, "a"), (1, "b"), (2, "c"), (0, "a")]
        with rd.least_loaded() as again:
            assert again == (1, "b")
    with rd.least_loaded() as idle:
        assert idle == (0, "a")


def test_reconfigure_mid_decode_keeps_counts_apart():
    rd = query_mod._ReadDevices()
    rd.configure(["a", "b"])
    with rd.least_loaded() as held:
        assert held == (0, "a")
        assert rd.configure(None) == [None]
        with rd.least_loaded() as fresh:
            assert fresh == (0, None)
    with rd.least_loaded() as after:
        assert after == (0, None)
