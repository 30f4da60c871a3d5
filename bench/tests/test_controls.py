"""The comparison must call the control and each planted fault wrong:
whole CPU runs at a tiny size, with the check run as on the chip."""
import pytest

from bench import controls, run
from bench.tests import tiny

CELLS = {"isabel_insitu.ingest": "stream_ingest",
         "isabel_archive.track_query": "track_query"}


def _run(cell, patch=None):
    return run.run_cell(tiny.args(cell), check_chips=False,
                        out=lambda _: None, resize=tiny.resize, patch=patch,
                        spec=tiny.SPEC)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_bfloat16_control_is_not_correct(cell, tmp_path, monkeypatch):
    tiny.use_cache(tmp_path, monkeypatch)
    result = _run(cell, patch=controls.CONTROLS[CELLS[cell]])
    failing = [k for k, c in result["checks"].items()
               if c["value"] > c["limit"]]
    assert not result["correct"] and failing


@pytest.mark.parametrize("fault", ["altered", "half"])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_planted_fault_is_not_correct(cell, fault, tmp_path, monkeypatch):
    tiny.use_cache(tmp_path, monkeypatch)
    controls.plant(fault, {"driver": CELLS[cell]}, monkeypatch)
    result = _run(cell)
    failing = [k for k, c in result["checks"].items()
               if c["value"] > c["limit"]]
    assert not result["correct"] and failing, result["checks"]
