"""Tiny sizes of the benchmark's cells, for runs on the CPU: the same
configurations and mixes with the scale cut and nothing else changed."""
import argparse
import copy

from bench import harness

SPEC = harness.load_spec(parked=True)   # the parked cells rehearse too


def resize(config, traffic):
    config, traffic = copy.deepcopy(config), copy.deepcopy(traffic)
    config["field"].update(H=48, W=48)
    config["tiling"].update(tile_h=24, tile_w=24, window_t=6)
    if "frames" in config:
        config["frames"] = 12
    if traffic["driver"] == "stream_ingest":
        traffic.update(frames=6 * 14 + 1)
    return config, traffic


def args(workload, seed=7, seconds=1.0, trace=0):
    return argparse.Namespace(workload=workload, seed=seed,
                              seconds=seconds, trace=trace)


def use_cache(tmp_path, monkeypatch):
    """Keep the runs' files (archives, streams, compile cache) out of
    the checkout's own cache."""
    monkeypatch.setattr(harness, "CACHE", str(tmp_path))
