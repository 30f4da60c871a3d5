"""The harness's contract: every name in BENCHMARK.json and in the
parked cells resolves to its files, a run without a TPU prints no
result, and a directory without the program under test fails."""
import importlib
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import harness

ROOT = harness.ROOT
SPEC = harness.load_spec()
ALL = harness.load_spec(parked=True)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_name_resolves_to_its_files():
    for c in ALL["configs"]:
        assert NAME.match(c["name"]) and os.path.exists(
            os.path.join(ROOT, c["file"]))
    for w in ALL["workloads"]:
        cell, _, config, traffic = harness.cell_files(ALL, w["name"])
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        importlib.import_module(f"bench.drivers.{traffic['driver']}")
        e2e = harness.metrics_of(ALL, "end_to_end", w["name"])
        assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
        assert harness.metrics_of(ALL, "per_layer", w["name"],
                                  [m["name"] for m in e2e])
    e2e_names = {m["name"] for m in ALL["end_to_end"]}
    for m in ALL["per_layer"]:
        assert NAME.match(m["name"]) and m["moves"] in e2e_names
        read, args = harness.load_reader(m["name"])
        assert callable(read)


def test_parked_cells_stay_out_of_the_benchmark():
    parked = {w["name"] for w in ALL["workloads"]} - {
        w["name"] for w in SPEC["workloads"]}
    assert parked and not parked & {
        c for m in SPEC["end_to_end"] + SPEC["per_layer"]
        for c in m.get("workloads", ())}


def test_peaks_refuse_an_unknown_kind():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchError):
        harness.peaks_for("TPU v9 imaginary")


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         SPEC["workloads"][0]["name"], "--seed", "3000000001",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(ROOT)
    assert p.returncode == 3 and p.stdout == "", p.stderr[-2000:]
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_fail(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout == ""
