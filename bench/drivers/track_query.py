"""Track queries against a finished archive, from analysts' clients.

Set-up builds (once per checkout and program) the configuration's
archive with the program's streaming writer, decodes it whole with the
program's full decoder, checks that decode against the original field
with the plain reference (pointwise bound, every face predicate, track
count), and extracts every track from it with the plain reference; the
archive and those reference tracks are kept under ``bench/.cache``.
Each run sizes the program's decoded-unit cache as the configuration
says, decodes every unit once through track queries (every decode
shape is built then), empties the cache, and drives the window as a
closed loop: ``clients`` analysts, each issuing
``analysis.decode_for_track(path, k)`` and, with no think time, the
next query as soon as its reply is in.  Clients issue queries for
``--seconds``; the window ends with the last reply.  The end-to-end
numbers are the replies per second over the window and the 95th
percentile, over every query of the window, of the time from issue to
reply.  Keys come in blocks of ``keys_per_block``: each block is the
same Zipf(``zipf_s``) multiset over a fixed rank order of the tracks,
in an order drawn from the seed, and client c asks keys c, c + clients,
... of the sequence, so every seed asks the same keys in another order.

Every answer of the window is compared with the reference track: face
ids in polyline order, loop flag and node types exactly, node positions
to ``position_limit`` grid units.
"""
from __future__ import annotations

import json
import os
import shutil
import threading
import time

import numpy as np

from bench import common, harness, reference
from bench.readings import Readings

BLOCKS = 16   # key blocks in a sequence; clients wrap around past them


def zipf_counts(n_keys: int, n: int, s: float) -> np.ndarray:
    """How often each rank is asked among n queries: Zipf(s) shares
    apportioned by largest remainder, the same for every seed."""
    p = 1.0 / np.arange(1, n_keys + 1) ** s
    p /= p.sum()
    c = np.floor(p * n).astype(np.int64)
    rest = np.argsort(-(p * n - c), kind="stable")[: n - c.sum()]
    c[rest] += 1
    return c


def _save_tracks(path, tracks):
    lens = np.array([len(t["face_ids"]) for t in tracks], np.int64)
    np.savez(path, lens=lens,
             face_ids=np.concatenate([t["face_ids"] for t in tracks]),
             nodes=np.concatenate([t["nodes"] for t in tracks]),
             types=np.concatenate([t["types"] for t in tracks]),
             loops=np.array([t["is_loop"] for t in tracks]))


def _load_tracks(path):
    z = np.load(path)
    ptr = np.concatenate([[0], np.cumsum(z["lens"])])
    return [{"face_ids": z["face_ids"][a:b], "nodes": z["nodes"][a:b],
             "types": z["types"][a:b], "is_loop": bool(loop)}
            for a, b, loop in zip(ptr[:-1], ptr[1:], z["loops"])]


def compare_answer(ref: dict, track) -> tuple:
    """(wrong, node gap) of one answer: wrong is 1 when the polyline's
    face ids, loop flag or node types differ from the reference."""
    if track is None:
        return 1, float("inf")
    if (len(track.face_ids) != len(ref["face_ids"])
            or not np.array_equal(track.face_ids, ref["face_ids"])
            or bool(track.is_loop) != ref["is_loop"]):
        return 1, float("inf")
    wrong = int(not np.array_equal(track.types, ref["types"]))
    return wrong, float(np.abs(np.asarray(track.nodes) - ref["nodes"]).max())


class Driver:
    def __init__(self, run):
        self.run = run
        self.cfg_file = run.config
        self.mix = run.traffic
        self.attempted = self.failed = 0
        self.answers = []         # (key, track, issued, replied)

    # -- archive (cached per checkout, program and configuration) -------
    def _archive_dir(self):
        conf = self.cfg_file
        key = common.tree_digest(
            os.path.join(harness.ROOT, "src", "repro"),
            os.path.join(harness.ROOT, self.run.config_entry["file"]),
            os.path.join(harness.BENCH, "reference.py"),
            os.path.join(harness.BENCH, "fields"))
        return os.path.join(harness.CACHE, "archive",
                            f"{conf['name']}-{key}")

    def _build(self, d):
        from repro.core import compress_stream, decompress

        conf = self.cfg_file
        u, v = common.field(conf, conf["frames"])
        lo, hi = common.value_range(u, v)
        cfg, grid = common.program_config(conf)
        tmp = d + ".tmp"
        shutil.rmtree(tmp, ignore_errors=True)
        os.makedirs(tmp)
        path = os.path.join(tmp, "archive.cptt")
        compress_stream(((u[t], v[t]) for t in range(len(u))), cfg, grid,
                        value_range=(lo, hi), sink=path,
                        **conf.get("engine", {}))
        ur, vr = decompress(path)
        verdict = common.compare_frames(conf, u, v, ur, vr, lo, hi)
        scale = reference.fixed_scale(lo, hi)
        tracks = reference.extract_tracks(
            reference.to_fixed(ur, scale), reference.to_fixed(vr, scale),
            spiral_tol=conf["analysis"]["spiral_tol"])
        _save_tracks(os.path.join(tmp, "tracks.npz"), tracks)
        with open(os.path.join(tmp, "archive.json"), "w") as f:
            json.dump({"verdict": verdict, "range": [lo, hi],
                       "n_tracks": len(tracks)}, f)
        os.replace(tmp, d)

    # -- driver protocol ---------------------------------------------------
    def setup(self):
        from repro import analysis

        d = self._archive_dir()
        if not os.path.exists(os.path.join(d, "archive.json")):
            self._build(d)
        self.path = os.path.join(d, "archive.cptt")
        with open(os.path.join(d, "archive.json")) as f:
            self.meta = json.load(f)
        self.ref = _load_tracks(os.path.join(d, "tracks.npz"))
        n_tracks = len(analysis.track_summaries(self.path))
        if n_tracks != len(self.ref):
            raise harness.BenchError(
                f"the archive's index lists {n_tracks} tracks, the "
                f"reference extracted {len(self.ref)}")
        conf = self.cfg_file
        t = conf["tiling"]
        unit_bytes = t["window_t"] * t["tile_h"] * t["tile_w"] * 2 * 4
        self.cache_mb = (conf["analysis"]["cache_units"] + 0.5) \
            * unit_bytes / 2**20
        analysis.configure_unit_cache(self.cache_mb)
        # warm-up: tracks that between them cover every unit, so every
        # unit's decode is built; the window then starts on an empty cache
        seen = set()
        for k in range(n_tracks):
            offs = {e["off"] for e in analysis.track_read_plan(self.path, k)}
            if offs - seen:
                analysis.decode_for_track(self.path, k)
                seen |= offs
        analysis.configure_unit_cache(self.cache_mb)
        self._plan(n_tracks)

    def _plan(self, n_tracks):
        mix, run = self.mix, self.run
        per = mix["keys_per_block"]
        # a fixed rank order of the tracks (from the archive's field
        # seed), and Zipf counts over it; the seed orders each block
        ranks = np.random.default_rng(
            self.cfg_file["field_seed"]).permutation(n_tracks)
        block = np.repeat(ranks, zipf_counts(n_tracks, per, mix["zipf_s"]))
        rng = np.random.default_rng(run.seed)
        self.keys = np.concatenate([rng.permutation(block)
                                    for _ in range(BLOCKS)])

    def _client(self, c):
        from repro import analysis

        n, step = len(self.keys), self.mix["clients"]
        i = c
        while True:
            issued = time.perf_counter()
            if issued >= self.t_stop:
                return
            k = int(self.keys[i % n])
            i += step
            try:
                with self.run.annotate("bench.query", track=k):
                    track = analysis.decode_for_track(self.path, k).track
            except Exception as e:       # a failed query counts as failed
                self.errors.append(repr(e))
                track = None
            replied = time.perf_counter()
            with self._lock:
                self.answers.append((k, track, issued, replied))

    def window(self):
        run = self.run
        self.errors = []
        self._lock = threading.Lock()
        clients = [threading.Thread(target=self._client, args=(c,),
                                    name=f"bench-client-{c}")
                   for c in range(self.mix["clients"])]
        run.window_start()
        self.t_stop = run.t0 + run.seconds
        for t in clients:
            t.start()
        for t in clients:
            t.join()
        run.window_end(max(a[3] for a in self.answers))
        self.attempted = len(self.answers)
        self.failed = sum(1 for a in self.answers if a[1] is None)
        self.notes = {"errors": self.errors[:3]}

    def _p95_ms(self):
        lat = [a[3] - a[2] for a in self.answers if a[1] is not None]
        return float(np.quantile(lat, 0.95, method="higher")) * 1e3 \
            if lat else None

    def end_to_end(self) -> dict:
        answered = self.attempted - self.failed
        return {"queries_per_s": answered / self.run.window_s,
                "query_p95_ms": self._p95_ms()}

    def release(self):
        from repro import analysis

        analysis.configure_unit_cache(0)

    def check(self) -> dict:
        wrong, gap = 0, 0.0
        for k, track, _, _ in self.answers:
            w, g = compare_answer(self.ref[k], track)
            wrong += w
            gap = max(gap, g)
        checks = {name: tuple(x) for name, x in self.meta["verdict"].items()}
        checks["queries_wrong"] = (wrong, 0)
        checks["max_node_gap"] = (gap, self.cfg_file["analysis"]
                                  ["position_limit"])
        checks["queries_failed"] = (self.failed, 0)
        return checks

    def readings(self, peaks):
        return Readings(self.run, peaks,
                        counts={"queries": len(self.answers)})
