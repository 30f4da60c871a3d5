#!/usr/bin/env python3
"""Smoke run of the compressor's main path on one TPU chip.

Drives compress -> decompress -> streamed archive -> decode -> track
query once, through the public entry points and with the ``pallas``
backend the chip defaults to, on one field the size of an SDRBench
Hurricane ISABEL run: 48 frames of 500 x 500 (u, v), generated from
``--seed`` (``synthetic.advected_turbulence``).  Every check uses the
repo's own references: the pointwise bound, FC_t = FC_s = 0 with the
trajectory count kept, pallas/xla container equality, bitwise equal
decodes, and track polylines equal to full-field extraction.

    python chip_smoke.py                  # one chip, every phase
    python chip_smoke.py --chips4 --expect-sha256 HEX
        # four chips: only compress_tiled with tile units shard-mapped
        # over the ("tiles",) mesh, checked against the digest of the
        # one-chip streamed container (printed by a plain run)

Each phase prints one JSON line: wall and compile seconds, the device's
peak bytes in use so far, and its checks.  The last line is
{"ok": true, "device": {...}} only when every phase passed.  With no
TPU the script exits 2 before any phase and prints no result line; any
exception or failed check exits non-zero.  Everything runs in this one
process (a chip belongs to one process at a time).
"""
from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# SDRBench Hurricane ISABEL: 500 x 500 horizontal slices, 48 time steps
FRAMES, SIZE = 48, 500
EB_REL = 1e-3
# 2 x 2 tiles x 2 windows: all eight units share one halo-extended
# shape (25, 251, 251), so the stream compiles one stage set, and each
# window's four tiles split across four chips in the --chips4 run
GRID = dict(tile_h=250, tile_w=250, window_t=24)
N_TRACK_QUERIES = 3


class SmokeFailure(RuntimeError):
    pass


def check(name, ok, **detail):
    if not ok:
        raise SmokeFailure(f"check {name} failed: {detail}")
    return name


class Phase:
    """Times one phase and prints its JSON line on success.  Compile
    seconds sum JAX's backend-compile events inside the phase."""

    compile_s = 0.0
    n_compiles = 0

    @classmethod
    def listen(cls):
        import jax

        def on_event(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                cls.compile_s += secs
                cls.n_compiles += 1
        jax.monitoring.register_event_duration_secs_listener(on_event)

    def __init__(self, name):
        self.name = name
        self.info = {}
        self.checks = []

    def check(self, name, ok, **detail):
        self.checks.append(check(name, ok, **detail))

    def __enter__(self):
        self.c0, self.n0 = Phase.compile_s, Phase.n_compiles
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is not None:
            return False
        import jax

        line = {"phase": self.name,
                "wall_s": time.perf_counter() - self.t0,
                "compile_s": Phase.compile_s - self.c0,
                "n_compiles": Phase.n_compiles - self.n0,
                "peak_bytes_in_use": [
                    (d.memory_stats() or {}).get("peak_bytes_in_use")
                    for d in jax.local_devices()],
                **self.info, "checks": self.checks}
        print(json.dumps(line), flush=True)
        return False


def field(seed, T, H, W):
    from repro.data import synthetic

    return synthetic.advected_turbulence(T=T, H=H, W=W, seed=seed)


def base_config(**kw):
    from repro.core import CompressionConfig

    # advected_turbulence is in grid units per frame: dt = dx = dy = 1
    return CompressionConfig(eb=EB_REL, mode="rel", predictor="mop",
                             dt=1.0, dx=1.0, dy=1.0, **kw)


def stream_config():
    # the device entropy stage: puts the histogram kernel on the path
    return dataclasses.replace(base_config(), codec="device")


def phase_device(n_chips):
    """The chip is there, the default backend is pallas, and each op
    bound to pallas lowers to a Mosaic kernel (``tpu_custom_call``),
    which interpret mode never emits."""
    import jax
    import jax.numpy as jnp

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"no TPU: JAX sees {devs[0].platform} devices",
              file=sys.stderr)
        sys.exit(2)
    with Phase("device") as ph:
        ph.check("device_count", len(devs) == n_chips, count=len(devs))
        ph.check("repro_backend_unset", not os.environ.get("REPRO_BACKEND"))
        from repro.core import backend, entropy, pipeline, quantize

        be = backend.resolve()
        ph.check("default_backend_pallas", be == "pallas", backend=be)
        table = backend.op_bindings(be)
        ph.info["op_bindings"] = table
        T, H, W = 2, 128, 128
        xi_unit, n_levels = quantize.ladder(1 << 20)
        fns = pipeline.unit_fns((T, H, W), 16, n_levels, "mop", be,
                                table["lorenzo"])
        i64 = jax.ShapeDtypeStruct((T, H, W), jnp.int64)
        lowered = {
            "lorenzo": fns.lorenzo_stage.lower(
                i64, i64, i64, jax.ShapeDtypeStruct((T, H, W), jnp.bool_),
                xi_unit),
            "cptest": fns.face_subset.lower(
                jax.ShapeDtypeStruct((T * H * W,), jnp.int64),
                jax.ShapeDtypeStruct((T * H * W,), jnp.int64),
                jax.ShapeDtypeStruct((3, 1024), jnp.int64)),
            "entropy": entropy.entropy_fns(be).symbolize.lower(
                jax.ShapeDtypeStruct((2, 4096), jnp.int64)),
        }
        for op, low in lowered.items():
            ph.check(f"{op}_compiled",
                     table[op] != "pallas"
                     or "tpu_custom_call" in low.as_text(), binding=table[op])
        ph.info["pallas_ops"] = sorted(op for op, b in table.items()
                                       if b == "pallas")
    return table


def phase_in_memory(u, v, table):
    import numpy as np

    from repro import analysis
    from repro.core import compress, decompress, encode, fixedpoint, \
        trajectory

    with Phase("in_memory") as ph:
        cfg = base_config()
        t0 = time.perf_counter()
        blob, st = compress(u, v, cfg)
        ph.info["compress_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        ur, vr = decompress(blob)
        ph.info["decompress_s"] = time.perf_counter() - t0
        header, _ = encode.unpack(blob)
        ph.info.update(shape=list(u.shape), ratio=st["ratio"],
                       verify_rounds=st["verify_rounds"],
                       bindings=st["bindings"],
                       sl_backend=header["sl_backend"])
        ph.check("bindings", st["bindings"] == table, got=st["bindings"])
        ph.check("header_sl_backend",
                 header["sl_backend"] == st["bindings"]["semilagrange"])
        err = max(np.abs(ur.astype(np.float64) - u).max(),
                  np.abs(vr.astype(np.float64) - v).max())
        ph.info["max_abs_err"], ph.info["eb_abs"] = float(err), st["eb_abs"]
        ph.check("error_bound", err <= st["eb_abs"])
        t0 = time.perf_counter()
        uo, vo = fixedpoint.refix(u, v, st["scale"])
        ufp, vfp = fixedpoint.refix(ur, vr, st["scale"])
        p0 = trajectory.face_predicate_tables(uo, vo)
        p1 = trajectory.face_predicate_tables(ufp, vfp)
        fc = trajectory.false_cases_from_tables(p0, p1)
        n_orig = trajectory.extract_tracks(uo, vo, tables=p0)["n_tracks"]
        tracks = analysis.extract(ufp, vfp, tables=p1)
        ph.info["reference_s"] = time.perf_counter() - t0
        ph.info.update(FC_t=fc["FC_t"], FC_s=fc["FC_s"], n_tracks=n_orig)
        ph.check("false_cases_zero", fc["FC_t"] == 0 and fc["FC_s"] == 0)
        ph.check("tracks_kept", tracks.n_tracks == n_orig,
                 rec=tracks.n_tracks, orig=n_orig)
    return ur, vr, tracks


def phase_parity(u, v):
    """Lorenzo containers from the compiled kernels equal the xla
    ones byte for byte (integer exactness, DESIGN.md #4)."""
    from repro.core import compress

    with Phase("backend_parity") as ph:
        blobs = {}
        for be in ("pallas", "xla"):
            cfg = dataclasses.replace(base_config(), predictor="lorenzo",
                                      backend=be)
            blobs[be], st = compress(u, v, cfg)
            ph.info[f"bindings_{be}"] = st["bindings"]
        ph.check("lorenzo_kernel_bound",
                 ph.info["bindings_pallas"]["lorenzo"] == "pallas")
        ph.check("containers_equal", blobs["pallas"] == blobs["xla"],
                 sizes=[len(b) for b in blobs.values()])


def phase_stream(u, v, ur, vr, tracks):
    import numpy as np

    from repro import analysis
    from repro.core import TileGrid, compress_stream, decompress

    with Phase("streamed_archive") as ph, \
            tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "isabel.cptt")
        lo = float(min(u.min(), v.min()))
        hi = float(max(u.max(), v.max()))
        t0 = time.perf_counter()
        _, st = compress_stream(((u[t], v[t]) for t in range(u.shape[0])),
                                stream_config(), TileGrid(**GRID),
                                value_range=(lo, hi), sink=path,
                                async_engine=True)
        ph.info["stream_s"] = time.perf_counter() - t0
        ph.info.update(n_units=st["n_units"], bytes=os.path.getsize(path),
                       bindings=st["bindings"])
        with open(path, "rb") as f:
            ph.info["sha256"] = hashlib.sha256(f.read()).hexdigest()
        t0 = time.perf_counter()
        us, vs = decompress(path)
        ph.info["decompress_s"] = time.perf_counter() - t0
        ph.check("decode_equals_monolithic",
                 np.array_equal(us, ur) and np.array_equal(vs, vr))
        n = tracks.n_tracks
        ph.check("index_track_count",
                 len(analysis.track_summaries(path)) == n)
        picks = sorted({0, n // 2, n - 1}) if n else []
        t0 = time.perf_counter()
        for k in picks[:N_TRACK_QUERIES]:
            res = analysis.decode_for_track(path, k)
            ref = tracks.track(k)
            ph.check(f"track_{k}",
                     np.array_equal(res.track.face_ids, ref.face_ids)
                     and np.array_equal(res.track.nodes, ref.nodes)
                     and np.array_equal(res.track.types, ref.types)
                     and res.track.is_loop == ref.is_loop,
                     units_read=res.units_read)
        ph.info["track_queries"] = picks
        ph.info["query_s"] = time.perf_counter() - t0


def phase_four_chips(u, v, expect_sha256):
    """compress_tiled over the 4-device tiles mesh: the container equals
    the one-chip container, and every device held a shard."""
    import jax

    from repro.core import TileGrid, compress_tiled

    with Phase("four_chip_tiled") as ph:
        cfg = dataclasses.replace(stream_config(), batch_units=True)
        t0 = time.perf_counter()
        blob, st = compress_tiled(u, v, cfg, TileGrid(**GRID))
        ph.info["compress_s"] = time.perf_counter() - t0
        digest = hashlib.sha256(blob).hexdigest()
        ph.info.update(n_units=st["n_units"], sha256=digest)
        ph.check("equals_one_chip_container", digest == expect_sha256)
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.local_devices()]
        ph.check("every_device_held_work", all(p > 0 for p in peaks),
                 peaks=peaks)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--frames", type=int, default=FRAMES,
                    help="time steps T (cut only where one chip cannot "
                         "hold the field)")
    ap.add_argument("--chips4", action="store_true",
                    help="run only the four-chip tiled phase")
    ap.add_argument("--expect-sha256",
                    help="with --chips4: digest of the one-chip container")
    args = ap.parse_args(argv)
    if args.chips4 and not args.expect_sha256:
        ap.error("--chips4 needs --expect-sha256 from a one-chip run")

    import jax

    n_chips = 4 if args.chips4 else 1
    Phase.listen()
    table = phase_device(n_chips)
    t0 = time.perf_counter()
    u, v = field(args.seed, args.frames, SIZE, SIZE)
    print(json.dumps({"phase": "field", "shape": list(u.shape),
                      "seed": args.seed,
                      "wall_s": time.perf_counter() - t0}), flush=True)
    if args.chips4:
        phase_four_chips(u, v, args.expect_sha256)
    else:
        ur, vr, tracks = phase_in_memory(u, v, table)
        phase_parity(u, v)
        phase_stream(u, v, ur, vr, tracks)
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
