"""Pieces the traffic drivers share: building the program's
configuration from a configuration file, generating frames, and the
comparison of decoded frames with the originals."""
from __future__ import annotations

import hashlib
import importlib
import os

import numpy as np

from bench import harness, reference


def field(config: dict, T: int, shift: int = 0):
    """(u, v) float32 frames (T, H, W) of the configuration's field (its
    ``field_seed``), translated by ``shift`` cells."""
    f = config["field"]
    gen = importlib.import_module(f"bench.fields.{f['generator']}")
    return gen.generate(T=T, H=f["H"], W=f["W"], seed=config["field_seed"],
                        shift=shift, **f.get("params", {}))


def seed_shift(config: dict, seed: int) -> int:
    """The translation a run's seed gives the configuration's field: the
    same flow, met at another moment, so every seed brings the same
    work."""
    return int(np.random.default_rng(seed).integers(config["field"]["W"]))


def program_config(config: dict):
    """(CompressionConfig, TileGrid) of the configuration file."""
    from repro.core import CompressionConfig, TileGrid

    cfg = CompressionConfig(**config["compression"])
    return cfg, TileGrid(**config["tiling"])


def value_range(u, v):
    """The stream's stated value range: exact min and max over both
    components (float32 values, so exact in float64)."""
    return (float(min(u.min(), v.min())), float(max(u.max(), v.max())))


def eb_abs(config: dict, lo: float, hi: float) -> float:
    """The stated pointwise bound: ``eb`` times the float32 value range
    for ``mode == "rel"`` (the range is a float32 difference, as the
    field is float32), ``eb`` itself for ``"abs"``."""
    c = config["compression"]
    if c.get("mode", "rel") == "abs":
        return float(c["eb"])
    return float(c["eb"]) * float(np.float32(hi) - np.float32(lo))


def compare_frames(config, u, v, ur, vr, lo, hi):
    """The guarantees on one block of frames: ``max_err_over_eb``
    (pointwise bound, limit 1), ``false_cases`` (faces whose predicate
    changed, limit 0) and ``tracks_changed`` (|tracks before - after|,
    limit 0)."""
    bound = eb_abs(config, lo, hi)
    err = max(float(np.abs(ur.astype(np.float64) - u).max()),
              float(np.abs(vr.astype(np.float64) - v).max()))
    scale = reference.fixed_scale(lo, hi)
    U0, V0 = reference.to_fixed(u, scale), reference.to_fixed(v, scale)
    U1, V1 = reference.to_fixed(ur, scale), reference.to_fixed(vr, scale)
    fc = reference.false_cases(U0, V0, U1, V1)
    n0 = len(reference.extract_tracks(U0, V0, geometry=False))
    n1 = len(reference.extract_tracks(U1, V1, geometry=False))
    return {"max_err_over_eb": (err / bound, 1.0),
            "false_cases": (fc["FC_t"] + fc["FC_s"], 0),
            "tracks_changed": (abs(n0 - n1), 0)}


def tree_digest(*paths) -> str:
    """sha256 over the files under ``paths`` (name and bytes), so a
    cache built by one program is never read by another."""
    h = hashlib.sha256()
    files = []
    for p in paths:
        if os.path.isdir(p):
            for d, _, names in os.walk(p):
                files += [os.path.join(d, n) for n in names
                          if n.endswith((".py", ".json"))]
        else:
            files.append(p)
    for f in sorted(files):
        h.update(os.path.relpath(f, harness.ROOT).encode())
        with open(f, "rb") as fh:
            h.update(fh.read())
    return h.hexdigest()[:16]

