"""CPU rehearsal of the track-query cells beside the first: the uniform
control on one device, and the four-chip cell on four virtual CPU
devices (a subprocess: this process has one device), with a planted
fault on a unit decoded off device 0 that the comparison must catch."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from bench import harness, run
from bench.tests import tiny

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FOUR = "isabel_archive_4chip.track_query_16c"
UNIFORM = "isabel_archive.track_query_uniform"


def test_uniform_cell_asks_every_track_once_per_block(tmp_path,
                                                       monkeypatch):
    tiny.use_cache(tmp_path, monkeypatch)
    seen = {}

    def keep(driver):
        seen["driver"] = driver
    result = run.run_cell(tiny.args(UNIFORM), check_chips=False,
                          out=lambda _: None, resize=tiny.resize,
                          patch=keep, spec=tiny.SPEC)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    d = seen["driver"]
    n_tracks = len(d.ref)
    per = d.mix["keys_per_block"]
    block = sorted(d.keys[:per].tolist())
    assert block == sorted(d.keys[per:2 * per].tolist())
    counts = [block.count(k) for k in range(n_tracks)]
    assert max(counts) - min(counts) <= 1      # uniform over the tracks


def test_four_chip_cell_fails_cleanly_without_placement(tmp_path,
                                                        monkeypatch):
    """On a program without read-device placement the cell stops at
    once with a BenchError (run.py exits 3), before any archive build."""
    from repro import analysis

    tiny.use_cache(tmp_path, monkeypatch)
    monkeypatch.delattr(analysis, "configure_read_devices")
    with pytest.raises(harness.BenchError, match="configure_read_devices"):
        run.run_cell(tiny.args(FOUR), check_chips=False,
                     out=lambda _: None, resize=tiny.resize, spec=tiny.SPEC)
    assert not os.path.exists(os.path.join(tmp_path, "archive"))


SCRIPT = r"""
import dataclasses
import json
import sys
import threading

import numpy as np

from bench import harness, run
from bench.tests import tiny
from repro import analysis, obs
from repro.core import pipeline

harness.CACHE = sys.argv[1]
cell = sys.argv[2]
obs.enable()        # the program was imported before run_cell sets REPRO_OBS


def chips():
    snap = obs.snapshot()
    return [int(snap.get(f"query.units_decoded.chip{i}", {}).get("value", 0))
            for i in range(4)]


def result_of(r, before):
    return {"correct": r["correct"], "attempted": r["attempted"],
            "failed": r["failed"],
            "checks": {k: v["value"] for k, v in r["checks"].items()},
            "metrics": {k: v["value"] for k, v in r["metrics"].items()},
            "chips": [b - a for a, b in zip(before, chips())]}


out = {}
before = chips()
sound = run.run_cell(tiny.args(cell, trace=1), check_chips=False,
                     out=lambda _: None, resize=tiny.resize, spec=tiny.SPEC)
out["sound"] = result_of(sound, before)

# planted: one answer whose units include one decoded off device 0
# comes back with a node moved by 1e-4 cells
tls = threading.local()
decode_unit = pipeline.PlanExecutor.decode_unit


def noted(self, uh, secs, device=None, chip=None):
    if chip:
        tls.off_zero = True
    return decode_unit(self, uh, secs, device=device, chip=chip)


query = analysis.decode_for_track
planted = []


def broken(src, k, *a, **kw):
    tls.off_zero = False
    res = query(src, k, *a, **kw)
    client = threading.current_thread().name.startswith("bench-client")
    if client and tls.off_zero and not planted and res.track is not None:
        planted.append(k)
        nodes = np.array(res.track.nodes)
        nodes[0, 1] += 1e-4
        res = dataclasses.replace(
            res, track=dataclasses.replace(res.track, nodes=nodes))
    return res


pipeline.PlanExecutor.decode_unit = noted
analysis.decode_for_track = broken
before = chips()
bad = run.run_cell(tiny.args(cell, seed=8), check_chips=False,
                   out=lambda _: None, resize=tiny.resize, spec=tiny.SPEC)
out["planted"] = dict(result_of(bad, before), n_planted=len(planted))
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four_chip(tmp_path_factory):
    env = dict(os.environ)
    flag = "--xla_force_host_platform_device_count=4"
    env["XLA_FLAGS"] = f"{env.get('XLA_FLAGS', '')} {flag}".strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join([ROOT, os.path.join(ROOT, "src")])
    for var in ("REPRO_OBS", "JAX_COMPILATION_CACHE_DIR"):
        env.pop(var, None)
    cache = str(tmp_path_factory.mktemp("cache"))
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(SCRIPT),
                        cache, FOUR], capture_output=True, text=True,
                       env=env, cwd=ROOT, timeout=900)
    assert r.returncode == 0, r.stdout[-2000:] + "\n" + r.stderr[-6000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_four_chip_cell_runs_correct_on_four_devices(four_chip):
    s = four_chip["sound"]
    assert s["correct"], s["checks"]
    assert s["attempted"] > 0 and s["failed"] == 0
    assert s["checks"]["compiles_in_window"] == 0
    assert s["checks"]["units_differ_across_chips"] == 0
    assert sum(1 for n in s["chips"] if n > 0) >= 2, s["chips"]
    # no TPU plane on the CPU, so only the counter metric reads
    assert set(s["metrics"]) == {"chip_decode_balance"}
    assert 1.0 <= s["metrics"]["chip_decode_balance"] <= 4.0


def test_moved_node_off_device_zero_is_caught(four_chip):
    p = four_chip["planted"]
    assert p["n_planted"] == 1
    c = p["checks"]
    assert not p["correct"]
    assert c["queries_wrong"] > 0 or c["max_node_gap"] > 1e-6, c
