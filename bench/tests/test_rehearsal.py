"""CPU rehearsal of both cells: a whole run through ``run.run_cell`` at
a tiny size (no chip, so no metric line is printed), whose comparison
with the plain reference must pass."""
import pytest

from bench import run
from bench.tests import tiny

CELLS = ["isabel_insitu.ingest", "isabel_archive.track_query"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct_on_cpu(cell, tmp_path, monkeypatch, capsys):
    tiny.use_cache(tmp_path, monkeypatch)
    lines = []
    result = run.run_cell(tiny.args(cell), check_chips=False,
                          out=lines.append, resize=tiny.resize,
                          spec=tiny.SPEC)
    checks = {k: v["value"] for k, v in result["checks"].items()}
    assert result["correct"], checks
    assert result["attempted"] > 0 and result["failed"] == 0
    assert {"max_err_over_eb", "false_cases"} <= set(result["checks"])
    assert capsys.readouterr().out == ""      # no result line printed
    assert lines and "compiles" in lines[0]
