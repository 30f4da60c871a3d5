"""Controls and planted faults: what the comparison must call wrong.

The control puts the plain reference in the program's place, one
precision below what the configurations state (float32 frames, so
bfloat16):

* ingest: the window's frames come back as the originals rounded to
  bfloat16 instead of the program's decode;
* track_query: every answer is the reference's own track extracted
  from the archive's full decode rounded to bfloat16.

The planted faults break the timed path underneath a whole run:

* ``altered``: ingest frames reach the compressor shifted by five times
  the bound (a value altered where the archive is produced); a track
  query's polyline comes back with one node moved by 1e-4 cells;
* ``half``: every other unit is never written; every other query fails.

Chip-size readings of the controls, with no window (nothing of the
control runs on the program's timed path):

    python3 bench/controls.py --workload <cell> --seeds 11,12,13
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import threading
import types

import ml_dtypes
import numpy as np

if __name__ == "__main__":
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [_root, os.path.join(_root, "src")]

from bench import common, harness, reference  # noqa: E402


def bf16(x):
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16) \
        .astype(np.float32)


# -- controls ---------------------------------------------------------------

def ingest_control(driver):
    """The window reads back as the bfloat16 originals."""
    def read_back(ta, tb):
        return bf16(driver.u[ta:tb]), bf16(driver.v[ta:tb])
    driver.read_back = read_back


def _bf16_tracks(driver):
    from repro.core import decompress

    ur, vr = decompress(driver.path)
    lo, hi = driver.meta["range"]
    s = reference.fixed_scale(lo, hi)
    return reference.extract_tracks(
        reference.to_fixed(bf16(ur), s), reference.to_fixed(bf16(vr), s),
        spiral_tol=driver.cfg_file["analysis"]["spiral_tol"])


def _as_track(t):
    return types.SimpleNamespace(face_ids=t["face_ids"], nodes=t["nodes"],
                                 types=t["types"], is_loop=t["is_loop"])


def query_control(driver):
    """Every answer of the window is the bfloat16 reference's track."""
    check = driver.check

    def controlled():
        tracks = _bf16_tracks(driver)
        driver.answers = [
            (k, _as_track(tracks[k]) if k < len(tracks) else None,
             issued, replied)
            for k, _, issued, replied in driver.answers]
        return check()
    driver.check = controlled


CONTROLS = {"stream_ingest": ingest_control, "track_query": query_control}


# -- planted faults ----------------------------------------------------------

def plant(fault: str, traffic: dict, monkeypatch):
    """Break the program's timed path under a run (tests only)."""
    from repro import analysis
    from repro.core import stream_engine, tiling

    if traffic["driver"] == "stream_ingest":
        if fault == "altered":
            add = tiling._add_frame

            def shifted(st, t, u_t, v_t, ufp_t=None, vfp_t=None):
                if t >= st.grid.window_t:
                    u_t = np.asarray(u_t, np.float32) + np.float32(
                        5 * st.eb_abs)
                    ufp_t = None
                return add(st, t, u_t, v_t, ufp_t, vfp_t)
            monkeypatch.setattr(tiling, "_add_frame", shifted)
        else:
            write = stream_engine._Session.write_unit
            seen = [0]

            def half(self, p):
                seen[0] += 1
                if seen[0] % 2:
                    write(self, p)
            monkeypatch.setattr(stream_engine._Session, "write_unit", half)
        return
    query = analysis.decode_for_track
    calls = [0]

    def broken(src, k, *a, **kw):
        if not threading.current_thread().name.startswith("bench-client"):
            return query(src, k, *a, **kw)       # warm-up: untouched
        calls[0] += 1
        if fault == "half" and calls[0] % 2:
            raise RuntimeError("planted: query dropped")
        res = query(src, k, *a, **kw)
        if fault == "altered" and res.track is not None:
            nodes = np.array(res.track.nodes)
            nodes[0, 1] += 1e-4
            res = dataclasses.replace(
                res, track=dataclasses.replace(res.track, nodes=nodes))
        return res
    monkeypatch.setattr(analysis, "decode_for_track", broken)


# -- chip-size control readings ----------------------------------------------

def ingest_readings(config, traffic, seed, window=2):
    """The ingest check's numbers for the bfloat16 control on stream
    window ``window`` (and the frame before it) of ``seed``."""
    u, v = common.field(config, traffic["frames"],
                        common.seed_shift(config, seed))
    lo, hi = common.value_range(u, v)
    wt = config["tiling"]["window_t"]
    ta, tb = window * wt - 1, (window + 1) * wt
    return common.compare_frames(config, u[ta:tb], v[ta:tb],
                                 bf16(u[ta:tb]), bf16(v[ta:tb]), lo, hi)


def query_readings(driver):
    """The query check's numbers for the bfloat16 control over the
    keys the seed's window would ask (no window is run)."""
    from bench.drivers.track_query import compare_answer

    tracks = _bf16_tracks(driver)
    wrong, gap = 0, 0.0
    for k in driver.keys:
        t = tracks[k] if k < len(tracks) else None
        w, g = compare_answer(driver.ref[k], t and _as_track(t))
        wrong += w
        gap = max(gap, g)
    return {"queries_wrong": (wrong, 0), "max_node_gap": (
        gap, driver.cfg_file["analysis"]["position_limit"])}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    a = ap.parse_args(argv)
    spec = harness.load_spec(parked=True)
    cell, entry, config, traffic = harness.cell_files(spec, a.workload)
    harness.configure_jax_cache()
    for seed in [int(s) for s in a.seeds.split(",")]:
        if traffic["driver"] == "stream_ingest":
            out = ingest_readings(config, traffic, seed)
        else:
            from bench.drivers import track_query

            run = harness.Run(argparse.Namespace(
                seed=seed, seconds=spec["run_seconds"], trace=0), cell,
                entry, config, traffic, 0.0)
            d = track_query.Driver(run)
            d.setup()
            out = query_readings(d)
        print(json.dumps({"control": "bfloat16", "workload": a.workload,
                          "seed": seed, "checks": out}), flush=True)


if __name__ == "__main__":
    main()
