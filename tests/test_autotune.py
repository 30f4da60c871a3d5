"""Autotune subsystem: cost model vs measured spans, search determinism,
byte-identity of autotuned containers, calibration-table versioning."""
import dataclasses
import importlib
import json

import numpy as np
import pytest

from repro import autotune, obs
from repro.autotune import costmodel

# the package re-exports the calibrate() *function*; the module needs
# an explicit import
calibrate_mod = importlib.import_module("repro.autotune.calibrate")
from repro.core import CompressionConfig, compress, tiling

SHAPES = ((4, 24, 24), (6, 32, 32))


def _field(shape, seed=3):
    rng = np.random.default_rng(seed)
    base = np.cumsum(rng.normal(size=shape).astype(np.float32), axis=0)
    return base, base[::-1].copy()


@pytest.fixture(scope="module")
def table(tmp_path_factory):
    """One real calibration per module (numpy backend: no jit compile
    noise, runs on any host)."""
    path = str(tmp_path_factory.mktemp("calib") / "table.json")
    return autotune.calibrate(shapes=SHAPES, backends=("numpy",),
                              path=path)


def _synthetic_table():
    """A fixed hand-written table: determinism tests must not depend on
    what a live calibration happened to measure."""
    coeffs = {}
    for be in ("xla", "numpy"):
        for i, stage in enumerate(costmodel.STAGES):
            coeffs[(be, stage)] = (1e-4 * (i + 1), 1e-8 * (i + 2))
    return autotune.CalibrationTable(device_kind="cpu", coeffs=coeffs)


# ----------------------------------------------------------------------
# cost model vs obs-measured stage times
# ----------------------------------------------------------------------

class TestPrediction:
    # calibrated on these very shapes, the affine model must land well
    # within an order of magnitude of the measured per-stage time
    FACTOR = 10.0
    NOISE_FLOOR_S = 1e-3

    @pytest.mark.parametrize("shape", SHAPES)
    def test_monolithic_stage_times_within_factor(self, table, shape):
        u, v = _field(shape)
        cfg = CompressionConfig(eb=1e-2, backend="numpy",
                                track_index=False)
        was = obs.enabled()
        try:
            obs.enable()
            compress(u, v, cfg)             # warm any lazy state
            before = obs.stage_durations("pipeline.")
            compress(u, v, cfg)
            after = obs.stage_durations("pipeline.")
        finally:
            obs.enable() if was else obs.disable()

        model = autotune.CostModel(coeffs=table.coeffs,
                                   kind=table.device_kind)
        cand = autotune.PlanCandidate(grid=None, backend="numpy")
        wl = costmodel.Workload(T=shape[0], H=shape[1], W=shape[2])
        pred = model.predict(cand, wl)["stages"]

        checked = 0
        for span, stage in calibrate_mod.SPAN_STAGES.items():
            if not span.startswith("pipeline."):
                continue
            b = before.get(span, {"sum_s": 0.0})
            meas = after.get(span, {"sum_s": 0.0})["sum_s"] - b["sum_s"]
            if meas < self.NOISE_FLOOR_S:
                continue                    # below timer noise: skip
            ratio = pred[stage] / meas
            assert 1.0 / self.FACTOR <= ratio <= self.FACTOR, \
                f"{stage}: predicted {pred[stage]:.5f}s vs measured " \
                f"{meas:.5f}s (x{ratio:.2f}) out of the {self.FACTOR}x " \
                "gate"
            checked += 1
        assert checked >= 1, "no stage rose above the noise floor"

    def test_seeds_exist_for_every_stage_and_backend(self):
        for kind in ("tpu", "gpu", "cpu"):
            for be in ("pallas", "xla", "numpy"):
                seeds = costmodel.seed_coeffs(kind, be)
                assert set(seeds) == set(costmodel.STAGES)
                assert all(c0 > 0 and c1 > 0
                           for c0, c1 in seeds.values())


# ----------------------------------------------------------------------
# search determinism
# ----------------------------------------------------------------------

class TestSearchDeterminism:
    def test_same_table_same_ranking(self):
        t = _synthetic_table()
        runs = []
        for _ in range(3):
            model = autotune.CostModel(coeffs=dict(t.coeffs),
                                       kind=t.device_kind)
            ranked = autotune.search((8, 40, 40), model=model)
            runs.append([r.cand.key for r in ranked])
        assert runs[0] == runs[1] == runs[2]

    def test_candidate_order_does_not_matter(self):
        t = _synthetic_table()
        model = autotune.CostModel(coeffs=t.coeffs, kind=t.device_kind)
        cands = autotune.enumerate_candidates((8, 40, 40))
        fwd = autotune.search((8, 40, 40), model=model, candidates=cands)
        rev = autotune.search((8, 40, 40), model=model,
                              candidates=list(reversed(cands)))
        assert [r.cand for r in fwd] == [r.cand for r in rev]

    def test_stream_ranking_deterministic_and_tiled(self):
        t = _synthetic_table()
        model = autotune.CostModel(coeffs=t.coeffs, kind=t.device_kind)
        a = autotune.search((16, 48, 48), model=model, stream=True)
        b = autotune.search((16, 48, 48), model=model, stream=True)
        assert [r.cand for r in a] == [r.cand for r in b]
        assert all(r.cand.grid is not None for r in a), \
            "a stream must never rank a monolithic candidate"

    def test_enumeration_covers_the_issue_space(self):
        cands = autotune.enumerate_candidates((16, 64, 64), stream=True)
        assert any(c.async_engine for c in cands)
        assert any(not c.async_engine for c in cands)
        assert {c.codec for c in cands} == {"host", "device"}
        assert len({c.batch_cap for c in cands}) > 1
        assert len({c.grid for c in cands}) > 3
        assert any(c.q_out_units for c in cands if c.async_engine)


# ----------------------------------------------------------------------
# byte identity
# ----------------------------------------------------------------------

class TestByteIdentity:
    def test_autotuned_equals_hand_configured_plan(self):
        u, v = _field((6, 32, 32))
        cfg = CompressionConfig(eb=1e-2, track_index=False)
        tuned = autotune.tune_config(u, v, cfg,
                                     table=_synthetic_table(),
                                     measure=False)
        blob_auto, _ = compress(u, v, tuned)
        # the same plan, configured by hand from the report
        hand = dataclasses.replace(tuned)
        if hand.tiling is None:
            blob_hand, _ = compress(u, v, hand)
        else:
            blob_hand, _ = tiling.compress_tiled(u, v, hand, hand.tiling)
        assert blob_auto == blob_hand

    def test_compress_autotune_entry_point(self, monkeypatch):
        u, v = _field((4, 24, 24))
        monkeypatch.setattr(autotune, "load_or_calibrate",
                            lambda path=None: _synthetic_table())
        blob, stats = compress(u, v,
                               CompressionConfig(eb=1e-2,
                                                 track_index=False),
                               autotune=True)
        assert blob and stats["ratio"] > 0
        assert autotune.last_report() is not None
        assert "chosen" in autotune.explain()

    def test_scheduling_knobs_never_change_bytes(self):
        # batch_cap / queue bounds are pure scheduling: same plan,
        # different caps, identical container (DESIGN.md #15)
        u, v = _field((6, 32, 32))
        grid = tiling.TileGrid(tile_h=16, tile_w=16, window_t=3)
        base = CompressionConfig(eb=1e-2, backend="numpy",
                                 track_index=False)
        blobs = set()
        for cap in (1, 3, 8):
            cfg = dataclasses.replace(base, batch_cap=cap)
            blob, _ = tiling.compress_tiled(u, v, cfg, grid)
            blobs.add(blob)
        assert len(blobs) == 1

    def test_autotune_refused_on_resume(self):
        with pytest.raises(ValueError, match="resume"):
            tiling.compress_stream(iter(()), autotune=True, resume=True,
                                   value_range=(0.0, 1.0))


# ----------------------------------------------------------------------
# calibration-table versioning
# ----------------------------------------------------------------------

class TestTableVersioning:
    def _write(self, path, **overrides):
        payload = {
            "format": calibrate_mod.TABLE_FORMAT,
            "version": calibrate_mod.TABLE_VERSION,
            "device_kind": costmodel.device_kind(),
            "meta": {},
            "entries": [{"backend": "numpy", "stage": "derive_eb",
                         "c0": 1e-4, "c1": 1e-8}],
        }
        payload.update(overrides)
        path.write_text(json.dumps(payload))
        return str(path)

    def test_good_table_roundtrips(self, tmp_path):
        p = self._write(tmp_path / "ok.json")
        t = autotune.load_table(p)
        assert t.coeffs[("numpy", "derive_eb")] == (1e-4, 1e-8)

    def test_stale_version_refused_typed(self, tmp_path):
        p = self._write(tmp_path / "stale.json",
                        version=calibrate_mod.TABLE_VERSION + 1)
        with pytest.raises(autotune.CalibrationTableError) as ei:
            autotune.load_table(p)
        assert ei.value.reason == "stale"
        assert isinstance(ei.value, ValueError)

    def test_foreign_device_refused_typed(self, tmp_path):
        p = self._write(tmp_path / "foreign.json",
                        device_kind="not-this-hardware")
        with pytest.raises(autotune.CalibrationTableError) as ei:
            autotune.load_table(p)
        assert ei.value.reason == "foreign"

    def test_corrupt_table_refused_typed(self, tmp_path):
        p = tmp_path / "bad.json"
        p.write_text("{not json")
        with pytest.raises(autotune.CalibrationTableError) as ei:
            autotune.load_table(str(p))
        assert ei.value.reason == "corrupt"
        p2 = self._write(tmp_path / "badfmt.json", format="something")
        with pytest.raises(autotune.CalibrationTableError) as ei:
            autotune.load_table(p2)
        assert ei.value.reason == "corrupt"

    def test_refused_table_triggers_recalibration(self, tmp_path,
                                                  monkeypatch):
        p = self._write(tmp_path / "stale.json",
                        version=calibrate_mod.TABLE_VERSION + 1)
        fresh = _synthetic_table()
        called = {}

        def fake_calibrate(path=None, **kw):
            called["path"] = path
            return fresh

        monkeypatch.setattr(calibrate_mod, "calibrate", fake_calibrate)
        out = calibrate_mod.load_or_calibrate(p)
        assert out is fresh and called["path"] == p

    def test_saved_table_reloads_identically(self, table, tmp_path):
        # the module-scope real calibration: save/load is lossless
        assert table.version == calibrate_mod.TABLE_VERSION
        assert table.coeffs, "calibration fitted no coefficients"
        assert all(c1 >= 0 for _, c1 in table.coeffs.values())
        p = str(tmp_path / "roundtrip.json")
        autotune.save_table(table, p)
        reloaded = autotune.load_table(p, expect_kind=table.device_kind)
        assert reloaded.coeffs == table.coeffs
        assert reloaded.device_kind == table.device_kind
