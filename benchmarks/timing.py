"""Fig. 8 analogue + perf-trajectory emitter.

``main()`` reproduces the paper-style wall-time table (baselines vs our
predictors).  ``bench_compress()`` is the BENCH_compress.json emitter
this repo tracks from PR 1 on: encode/decode MB/s per predictor x
backend on the synthetic suite, plus a seed-vs-fused A/B on a
64x256x256 mop encode (cfg.fused=False replays the seed pipeline, so
the speedup is measured in the same run under identical accounting).

    PYTHONPATH=src python benchmarks/timing.py            # full emit
    PYTHONPATH=src python benchmarks/timing.py --smoke    # CI-sized
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import time

import numpy as np

from repro import obs
from repro.baselines import REGISTRY
from repro.core import CompressionConfig, compress, decompress

try:
    from . import datasets
except ImportError:  # invoked as a script: python benchmarks/timing.py
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import datasets


def _mbps(mb, t):
    """MB/s with 4 significant digits.

    The old ``round(rate, 2)`` truncated any rate below 0.005 MB/s
    (tiny smoke fields, slow arms) to a literal 0.0, which made the
    JSON unusable for ratio gates -- check_schema.py now rejects
    zero throughputs outright."""
    rate = mb / max(t, 1e-9)
    if rate <= 0.0:
        return 0.0
    return round(rate, max(0, 3 - int(math.floor(math.log10(rate)))))


def _warmup(*arms, n=1):
    """Run every benchmark arm ``n`` times untimed before anything is
    put on the clock.

    ONE shared helper, applied uniformly: the first call of an arm pays
    jit compilation and executable-registry fills, and attributing that
    to whichever arm happens to run first skews the A/B -- the PR 7
    batched-lorenzo artifact reported a 0.124x "slowdown" that was
    entirely the batched arm's cold-compile bill (in --smoke, repeat=1,
    so best-of cannot absorb it either).  Sections must not hand-roll
    their own warmups; call this with every arm they time."""
    for _ in range(max(n, 1)):
        for arm in arms:
            arm()


def _span_time(name, fn, **attrs):
    """Run ``fn()`` inside an obs span and return ``(result, seconds)``.

    Section timings derive from the span's own clock (``dur_s``) so the
    number in BENCH_compress.json is the same one a Perfetto trace of
    the run shows; the perf_counter fallback only covers obs-disabled
    runs (where the span is the shared no-op)."""
    t0 = time.perf_counter()
    with obs.span(name, **attrs) as sp:
        out = fn()
    return out, sp.dur_s or (time.perf_counter() - t0)


def _time_ours(u, v, cfg):
    (blob, stats), tc = _span_time("bench.compress", lambda: compress(u, v, cfg))
    _, td = _span_time("bench.decompress", lambda: decompress(blob))
    return blob, stats, tc, td


def main(small=True, eb=1e-2, log=print):
    rows = []
    for name, (u, v, meta) in datasets.load_all(small).items():
        mb = (u.nbytes + v.nbytes) / 2**20
        for bname, fn in REGISTRY.items():
            res = fn(u, v, eb=eb, mode="rel")
            rows.append({
                "dataset": name, "method": bname,
                "t_c": round(res["t_compress"], 3),
                "t_d": round(res["t_decompress"], 3),
                "MBps_c": _mbps(mb, res["t_compress"]),
            })
        for pred in ("lorenzo", "sl", "mop"):
            cfg = CompressionConfig(eb=eb, mode="rel", predictor=pred, **meta)
            _, stats, tc, td = _time_ours(u, v, cfg)
            rows.append({
                "dataset": name, "method": f"ours-{pred}",
                "t_c": round(tc, 3), "t_d": round(td, 3),
                "MBps_c": _mbps(mb, tc),
            })
        for r in rows[-9:]:
            log(f"[timing] {name} {r['method']:12s} tc={r['t_c']}s "
                f"td={r['t_d']}s")
    return rows


def _bench_tiled(eb, shape, repeat, log):
    """Tiled-vs-monolithic encode/decode MB/s on one field, asserting
    the tiled container decodes bit-identically to the monolithic fused
    pipeline (the tiled subsystem's core guarantee)."""
    from repro.analysis import query as query_mod
    from repro.core import (TileGrid, compress_tiled, decompress_region,
                            decompress_tiled)
    from repro.core import tiling as tiling_mod
    from repro.data import synthetic

    T, H, W = shape
    u, v = synthetic.advected_turbulence(T=T, H=H, W=W)
    mb = (u.nbytes + v.nbytes) / 2**20
    grid = TileGrid(tile_h=max(H // 2, 1), tile_w=max(W // 2, 1),
                    window_t=max(T // 2, 1))
    import dataclasses as _dc
    cfg = CompressionConfig(eb=eb, mode="rel", predictor="mop",
                            backend="xla", verify=True, fused=True,
                            track_index=False)
    cfg_idx = _dc.replace(cfg, track_index=True)
    _warmup(lambda: compress(u, v, cfg),
            lambda: compress_tiled(u, v, cfg, grid),
            lambda: compress_tiled(u, v, cfg_idx, grid))
    tc_m, td_m, tc_t, td_t, tc_i = [], [], [], [], []
    blob_m = blob_t = None
    stats_t = None
    for _ in range(repeat):
        (blob_m, _), dt = _span_time(
            "bench.encode_monolithic", lambda: compress(u, v, cfg))
        tc_m.append(dt)
        (um, vm), dt = _span_time(
            "bench.decode_monolithic", lambda: decompress(blob_m))
        td_m.append(dt)
        (blob_t, stats_t), dt = _span_time(
            "bench.encode_tiled", lambda: compress_tiled(u, v, cfg, grid))
        tc_t.append(dt)
        # decode times must measure DECODE, not decoded-unit cache hits
        query_mod.unit_cache.clear()
        (ut, vt), dt = _span_time(
            "bench.decode_tiled", lambda: decompress_tiled(blob_t))
        td_t.append(dt)
        # indexing overhead: same encode with the sidecar track index
        _, dt = _span_time(
            "bench.encode_tiled_indexed",
            lambda: compress_tiled(u, v, cfg_idx, grid))
        tc_i.append(dt)
    identical = bool(np.array_equal(um, ut) and np.array_equal(vm, vt))
    assert identical, "tiled decode diverged from monolithic"
    # random-access: decode one tile-interior region, count units read
    # (cold cache: the point is the partial-read cost, not a cache hit)
    region = (0, min(2, T), 0, min(8, H), 0, min(8, W))
    n_read = len(tiling_mod.read_plan(blob_t, region))
    query_mod.unit_cache.clear()
    _, t_region = _span_time("bench.decode_region",
                             lambda: decompress_region(blob_t, region))
    out = {
        "field": f"advected_turbulence {T}x{H}x{W}",
        "predictor": "mop", "backend": "xla",
        "MB": round(mb, 2),
        "n_units": stats_t["n_units"],
        "tiling": stats_t["tiling"],
        "t_encode_monolithic": round(min(tc_m), 3),
        "t_encode_tiled": round(min(tc_t), 3),
        "t_encode_tiled_indexed": round(min(tc_i), 3),
        "t_decode_monolithic": round(min(td_m), 3),
        "t_decode_tiled": round(min(td_t), 3),
        "MBps_encode_monolithic": _mbps(mb, min(tc_m)),
        "MBps_encode_tiled": _mbps(mb, min(tc_t)),
        "MBps_encode_tiled_indexed": _mbps(mb, min(tc_i)),
        "MBps_decode_monolithic": _mbps(mb, min(td_m)),
        "MBps_decode_tiled": _mbps(mb, min(td_t)),
        "bit_identical": identical,
        "region_decode_units_read": n_read,
        "t_region_decode": round(t_region, 4),
    }
    log(f"[bench] tiled-vs-monolithic {T}x{H}x{W} "
        f"({stats_t['n_units']} units): enc "
        f"{out['MBps_encode_monolithic']} -> {out['MBps_encode_tiled']} "
        f"MB/s, dec {out['MBps_decode_monolithic']} -> "
        f"{out['MBps_decode_tiled']} MB/s, bit_identical={identical}")
    return out


def _bench_batched(eb, shape, repeat, log):
    """Batched-vs-sequential unit execution (pipeline.BatchFns): encode
    MB/s with same-signature units stacked through the vmapped stages +
    ("tiles",) mesh vs the per-unit Python loop, asserting the two
    containers are BYTE-equal for both predictor families (the unit-
    batching guarantee, DESIGN.md #10)."""
    import dataclasses as _dc

    from repro.core import TileGrid, compress_tiled
    from repro.data import synthetic

    T, H, W = shape
    u, v = synthetic.advected_turbulence(T=T, H=H, W=W)
    mb = (u.nbytes + v.nbytes) / 2**20
    grid = TileGrid(tile_h=max(H // 2, 1), tile_w=max(W // 2, 1),
                    window_t=max(T // 2, 1))
    rows = []
    identical = True
    n_units = 0
    for pred in ("lorenzo", "mop"):
        cfg_b = CompressionConfig(eb=eb, mode="rel", predictor=pred,
                                  backend="xla", verify=True, fused=True,
                                  track_index=False, batch_units=True)
        cfg_s = _dc.replace(cfg_b, batch_units=False)
        _warmup(lambda: compress_tiled(u, v, cfg_b, grid),
                lambda: compress_tiled(u, v, cfg_s, grid))
        tb, ts = [], []
        blob_b = blob_s = None
        # the speedup gate compares two near-parity arms; a single
        # sample per arm flips the ratio by +/-15% run to run
        for _ in range(max(repeat, 3)):
            (blob_b, stats_b), dt = _span_time(
                "bench.encode_batched", lambda: compress_tiled(
                    u, v, cfg_b, grid), predictor=pred)
            tb.append(dt)
            (blob_s, _), dt = _span_time(
                "bench.encode_sequential", lambda: compress_tiled(
                    u, v, cfg_s, grid), predictor=pred)
            ts.append(dt)
        same = blob_b == blob_s
        assert same, f"batched {pred} diverged from sequential bytes"
        identical = identical and same
        n_units = stats_b["n_units"]
        rows.append({
            "predictor": pred,
            "n_units": stats_b["n_units"],
            "t_encode_sequential": round(min(ts), 3),
            "t_encode_batched": round(min(tb), 3),
            "MBps_encode_sequential": _mbps(mb, min(ts)),
            "MBps_encode_batched": _mbps(mb, min(tb)),
            "speedup": round(min(ts) / max(min(tb), 1e-9), 3),
            "bytes_equal": same,
        })
        log(f"[bench] batched-vs-sequential {pred:8s} "
            f"({stats_b['n_units']} units): "
            f"{rows[-1]['MBps_encode_sequential']} -> "
            f"{rows[-1]['MBps_encode_batched']} MB/s "
            f"({rows[-1]['speedup']}x), bytes_equal={same}")
    assert n_units >= 8, f"batched A/B needs >= 8 units, got {n_units}"
    return {
        "field": f"advected_turbulence {T}x{H}x{W}",
        "backend": "xla",
        "MB": round(mb, 2),
        "n_units": n_units,
        "rows": rows,
        "bit_identical": identical,
    }


def _bench_async(eb, shape, repeat, log, frame_latency=0.02):
    """Async-vs-serial streaming engine (core/stream_engine.py).

    Two scenarios, both asserting the containers are BYTE-equal to
    compress_tiled (the engine's core guarantee: only scheduling
    changes, never the bytes):

    * *archive* (the headline ``speedup``): frames arrive from a paced
      producer (``frame_latency`` seconds each -- the paper's streaming
      use case, archiving simulation output as it is produced).  The
      async engine overlaps production latency with device encode, so
      pipeline time approaches max(produce, encode) instead of their
      sum.
    * *unpaced* (``speedup_unpaced``): an in-memory source with zero
      production latency.  This only beats serial when spare cores
      exist beyond what XLA already uses -- expect ~1.0 on small hosts.

    Also reports the decoded-unit cache effect: the second of two
    identical track queries must issue strictly fewer range reads.
    """
    from repro import analysis
    from repro.core import TileGrid, compress_stream, compress_tiled
    from repro.data import synthetic

    T, H, W = shape
    u, v = synthetic.advected_turbulence(T=T, H=H, W=W)
    mb = (u.nbytes + v.nbytes) / 2**20
    grid = TileGrid(tile_h=max(H // 2, 1), tile_w=max(W // 2, 1),
                    window_t=max(T // 4, 1))
    cfg = CompressionConfig(eb=eb, mode="rel", predictor="mop",
                            backend="xla", verify=True, fused=True,
                            track_index=True)
    vr = (float(min(u.min(), v.min())), float(max(u.max(), v.max())))

    def frames(latency=0.0):
        for t in range(T):
            if latency:
                time.sleep(latency)     # paced producer (solver step)
            yield u[t], v[t]

    blob_t, stats_t = compress_tiled(u, v, cfg, grid)
    # warm both engines unpaced (the paced arms time a producer, not a
    # compile; the engines share the same executables either way)
    _warmup(lambda: compress_stream(frames(), cfg, grid, value_range=vr),
            lambda: compress_stream(frames(), cfg, grid, value_range=vr,
                                    async_engine=True))
    t_ser, t_asy, t_ser0, t_asy0 = [], [], [], []
    blob_s = blob_a = None
    for _ in range(repeat):
        (blob_s, _), dt = _span_time(
            "bench.stream_serial", lambda: compress_stream(
                frames(frame_latency), cfg, grid, value_range=vr))
        t_ser.append(dt)
        (blob_a, _), dt = _span_time(
            "bench.stream_async", lambda: compress_stream(
                frames(frame_latency), cfg, grid, value_range=vr,
                async_engine=True))
        t_asy.append(dt)
        _, dt = _span_time(
            "bench.stream_serial_unpaced", lambda: compress_stream(
                frames(), cfg, grid, value_range=vr))
        t_ser0.append(dt)
        _, dt = _span_time(
            "bench.stream_async_unpaced", lambda: compress_stream(
                frames(), cfg, grid, value_range=vr, async_engine=True))
        t_asy0.append(dt)
    identical = bool(blob_s == blob_t and blob_a == blob_t)
    assert identical, "async/serial stream diverged from compress_tiled"

    # served-read layer: repeated query hits the decoded-unit cache
    analysis.query.unit_cache.clear()
    k = analysis.track_summaries(blob_a)[0]["track_id"]
    cold = analysis.decode_for_track(blob_a, k)
    warm = analysis.decode_for_track(blob_a, k)
    assert warm.range_reads < cold.range_reads, \
        "second track query did not hit the decoded-unit cache"

    out = {
        "field": f"advected_turbulence {T}x{H}x{W}",
        "predictor": "mop", "backend": "xla",
        "MB": round(mb, 2),
        "n_units": stats_t["n_units"],
        "frame_latency_s": frame_latency,
        "t_encode_serial": round(min(t_ser), 3),
        "t_encode_async": round(min(t_asy), 3),
        "MBps_encode_serial": _mbps(mb, min(t_ser)),
        "MBps_encode_async": _mbps(mb, min(t_asy)),
        "speedup": round(min(t_ser) / max(min(t_asy), 1e-9), 3),
        "t_encode_serial_unpaced": round(min(t_ser0), 3),
        "t_encode_async_unpaced": round(min(t_asy0), 3),
        "speedup_unpaced": round(min(t_ser0) / max(min(t_asy0), 1e-9), 3),
        "bit_identical": identical,
        "track_query_reads_cold": cold.range_reads,
        "track_query_reads_warm": warm.range_reads,
    }
    log(f"[bench] async-vs-serial stream {T}x{H}x{W} "
        f"({stats_t['n_units']} units, {frame_latency * 1e3:.0f} ms/frame "
        f"producer): {out['MBps_encode_serial']} -> "
        f"{out['MBps_encode_async']} MB/s ({out['speedup']}x paced, "
        f"{out['speedup_unpaced']}x unpaced), bit_identical={identical}, "
        f"track reads {cold.range_reads} -> {warm.range_reads}")
    return out


def _bench_entropy(eb, shape, repeat, log, n_units=16):
    """Stage-level host-vs-device entropy coder A/B (core/entropy.py).

    Collects genuine residual streams by running the fused pipeline on
    ``n_units`` same-shape time slabs of one field, then times the two
    entropy-stage shapes over the SAME streams -- exactly the host-loop
    vs batched-call gap the device codec exists to close:

    * host: per-unit ``encode.to_symbols`` + ``encode.huffman_encode``
      loop (the reference host entropy coder: symbolize, heap-built
      canonical table, bit-pack -- one pass per unit per stream)
    * device: ONE batched ``entropy.encode_streams`` call over the
      stacked units (all 2*n_units streams through shared
      symbolize/histogram/table/bit-pack passes)

    ``bytes_equal`` asserts decode parity: every device bitstream
    decodes (``entropy.decode_symbols``) to the exact symbol array the
    host coder consumed, and the escape arrays match element-wise."""
    from repro.core import encode, entropy, fixedpoint, pipeline
    from repro.core.compressor import _abs_eb, _as_fields
    from repro.data import synthetic

    T, H, W = shape
    u, v = synthetic.advected_turbulence(T=T * n_units, H=H, W=W)
    cfg = CompressionConfig(eb=eb, mode="rel", predictor="mop",
                            backend="xla", verify=True, fused=True)
    units = []
    for i in range(n_units):
        uu, vv = _as_fields(u[i * T:(i + 1) * T], v[i * T:(i + 1) * T])
        eb_abs = _abs_eb(uu, vv, cfg)
        scale, ufp, vfp = fixedpoint.to_fixed(uu, vv, cfg.fixed_bits)
        plan = pipeline.plan_from_cfg(cfg, "xla", scale, eb_abs, "fused")
        enc = pipeline.compress_field(
            pipeline.PlanExecutor(plan), uu, vv, ufp, vfp)
        units.append((np.asarray(enc.res_u), np.asarray(enc.res_v)))
    # rate basis: the float32 u+v field bytes the streams encode
    mb = n_units * T * H * W * 2 * 4 / 2**20
    ru = np.stack([x[0] for x in units])
    rv = np.stack([x[1] for x in units])

    def host_arm():
        out = []
        for res_u, res_v in units:
            su, eu = encode.to_symbols(res_u)
            sv, ev = encode.to_symbols(res_v)
            out.append((encode.huffman_encode(su),
                        encode.huffman_encode(sv), eu, ev))
        return out

    def device_arm():
        return entropy.encode_streams(ru, rv)

    _warmup(host_arm, device_arm)
    th, td = [], []
    host_out = dev_out = None
    for _ in range(max(repeat, 2)):
        host_out, dt = _span_time("bench.entropy_host", host_arm)
        th.append(dt)
        dev_out, dt = _span_time("bench.entropy_device", device_arm)
        td.append(dt)

    equal = True
    for (hu, hv, eu, ev), frag in zip(host_out, dev_out):
        for host_enc, esc, key, ekey in ((hu, eu, "sym_u", "esc_u"),
                                         (hv, ev, "sym_v", "esc_v")):
            sec = frag[key]
            dec = entropy.decode_symbols(sec.lengths, sec.data, sec.n)
            h_sym = encode.huffman_decode(*host_enc)
            equal = equal and np.array_equal(dec, h_sym)
            equal = equal and np.array_equal(
                np.asarray(frag[ekey]), np.asarray(esc))
    assert equal, "device entropy streams diverged from host decode"

    host_bytes = sum(len(h[0][1]) + len(h[1][1]) for h in host_out)
    dev_bytes = sum(len(f["sym_u"].data) + len(f["sym_v"].data)
                    for f in dev_out)
    out = {
        "field": f"advected_turbulence {T * n_units}x{H}x{W}",
        "n_units": n_units,
        "unit_shape": [T, H, W],
        "backend": "xla",
        "MB": round(mb, 2),
        "host_bytes": host_bytes,
        "device_bytes": dev_bytes,
        "t_encode_host": round(min(th), 4),
        "t_encode_device": round(min(td), 4),
        "MBps_host": _mbps(mb, min(th)),
        "MBps_device": _mbps(mb, min(td)),
        "speedup": round(min(th) / max(min(td), 1e-9), 3),
        "bytes_equal": bool(equal),
    }
    log(f"[bench] entropy_stage {n_units}x{T}x{H}x{W}: host "
        f"{out['MBps_host']} -> device {out['MBps_device']} MB/s "
        f"({out['speedup']}x), bytes_equal={equal}")
    return out


def _bench_recovery(eb, shape, log):
    """Crash-recovery + salvage cost (core/stream_engine.py journal,
    encode.salvage_container -- DESIGN.md #12).

    * ``overhead_pct``: wall-time cost of journaling + fsync relative
      to the pre-journal streaming path (stream-to-BytesIO, which
      never journals), on the same frames.
    * crash-and-resume: a fault kills the run at ~2/3 of the stream;
      ``byte_identical`` asserts the resumed container equals the
      uninterrupted one (the tentpole guarantee, gated in CI).
    * ``salvage_MBps``: directory-rebuild throughput on a footerless
      archive, with every intact unit recovered and the salvaged
      container decoding clean in degraded mode.
    """
    import io
    import os
    import tempfile

    from repro.core import TileGrid, compress_stream, encode
    from repro.core import faults as faults_mod
    from repro.data import synthetic

    T, H, W = shape
    u, v = synthetic.advected_turbulence(T=T, H=H, W=W)
    mb = (u.nbytes + v.nbytes) / 2**20
    grid = TileGrid(tile_h=max(H // 2, 1), tile_w=max(W // 2, 1),
                    window_t=max(T // 4, 1))
    cfg = CompressionConfig(eb=eb, mode="rel", predictor="mop",
                            backend="xla", verify=True, fused=True,
                            track_index=True)
    vr = (float(min(u.min(), v.min())), float(max(u.max(), v.max())))
    pairs = list(zip(u, v))

    def feed(t0):
        return iter(pairs[t0:])

    with tempfile.TemporaryDirectory() as td:
        # overhead_pct measures journal+fsync cost, not compile time, so
        # both the journaled (file-sink) and unjournaled (BytesIO) arms
        # warm before the clock starts
        _warmup(
            lambda: compress_stream(feed, cfg, grid, value_range=vr,
                                    sink=io.BytesIO()),
            lambda: compress_stream(feed, cfg, grid, value_range=vr,
                                    sink=os.path.join(td, "warm.cptt")))
        ref_path = os.path.join(td, "ref.cptt")
        _, t_journaled = _span_time(
            "bench.stream_journaled", lambda: compress_stream(
                feed, cfg, grid, value_range=vr, sink=ref_path))
        with open(ref_path, "rb") as f:
            ref = f.read()
        _, t_plain = _span_time(
            "bench.stream_unjournaled", lambda: compress_stream(
                feed, cfg, grid, value_range=vr, sink=io.BytesIO()))

        crash_path = os.path.join(td, "crash.cptt")
        plan = faults_mod.FaultPlan().io_error("stream.compute",
                                               nth=max(2 * T // 3, 2))
        t_crashed = time.perf_counter()
        try:
            compress_stream(feed, cfg, grid, value_range=vr,
                            sink=crash_path, faults=plan)
            raise SystemExit("recovery bench: fault did not fire")
        except faults_mod.InjectedFault:
            t_crashed = time.perf_counter() - t_crashed
        from repro.core import stream_engine

        info = stream_engine.resume_info(crash_path)
        (_, stats), t_resume = _span_time(
            "bench.stream_resume", lambda: compress_stream(
                feed, cfg, grid, value_range=vr, sink=crash_path,
                resume=True))
        with open(crash_path, "rb") as f:
            identical = f.read() == ref
        assert identical, "resumed container diverged from uninterrupted"

        # salvage throughput on a footerless archive
        hdr = encode.tiled_header(ref)
        last = max(hdr["units"], key=lambda e: e["off"])
        cut = ref[: last["off"] + last["len"]]
        (blob, rep), t_salvage = _span_time(
            "bench.salvage", lambda: encode.salvage_container(cut))
        assert rep["units_recovered"] == len(hdr["units"]), \
            "salvage lost intact units"
        from repro.core import tiling as tiling_mod

        _, _, drep = tiling_mod.decompress_tiled(blob, degraded=True)
        assert drep.complete, "salvaged container failed degraded decode"

    out = {
        "field": f"advected_turbulence {T}x{H}x{W}",
        "predictor": "mop", "backend": "xla",
        "MB": round(mb, 2),
        "n_units": len(hdr["units"]),
        "t_encode_unjournaled": round(t_plain, 3),
        "t_encode_journaled": round(t_journaled, 3),
        "overhead_pct": round(100.0 * (t_journaled - t_plain)
                              / max(t_plain, 1e-9), 2),
        "resume_from": int(info["resume_from"]),
        "t_crashed_run": round(t_crashed, 3),
        "t_resume": round(t_resume, 3),
        "resumed_units": int(stats["n_units"]),
        "byte_identical": bool(identical),
        "salvage_bytes": len(cut),
        "t_salvage": round(t_salvage, 4),
        "salvage_MBps": _mbps(len(cut) / 2**20, t_salvage),
        "salvage_units_recovered": int(rep["units_recovered"]),
        "salvaged_degraded_complete": bool(drep.complete),
    }
    log(f"[bench] recovery {T}x{H}x{W} ({out['n_units']} units): "
        f"journal overhead {out['overhead_pct']}%, resume from frame "
        f"{out['resume_from']} in {out['t_resume']}s, byte_identical="
        f"{identical}, salvage {out['salvage_MBps']} MB/s")
    return out


def _bench_trajectory_analysis(eb, shape, log, field="turbulence"):
    """Track-level metric rows: ours vs the non-trajectory-preserving
    baselines (broken vs preserved tracks), with per-type CP counts,
    false-case counts, and the analysis-phase throughput (extraction
    MB/s on the decoded field).  The turbulence ensemble is the field
    where generic compressors actually break tracks (many
    near-degenerate crossings); cpsz-like preserves slices only, so
    FC_s > 0 and tracks merge/split across slabs."""
    from repro import analysis
    from repro.baselines import REGISTRY
    from repro.core import fixedpoint, trajectory
    from repro.data import synthetic

    T, H, W = shape
    u, v = synthetic.DATASETS[field](T=T, H=H, W=W)
    mb = (u.nbytes + v.nbytes) / 2**20
    scale, uo, vo = fixedpoint.to_fixed(u, v)
    # one predicate pass per field, threaded through FC and extraction
    p0 = trajectory.face_predicate_tables(uo, vo)
    ref = analysis.extract(uo, vo, tables=p0)

    def row(name, ur, vr):
        ufp, vfp = fixedpoint.refix(ur, vr, scale)

        def arm():
            p1 = trajectory.face_predicate_tables(ufp, vfp)
            return p1, analysis.extract(ufp, vfp, tables=p1)

        _warmup(arm)
        (p1, ts), dt = _span_time("bench.analysis_extract", arm,
                                  method=name)
        fc = trajectory.false_cases_from_tables(p0, p1)
        out = {
            "method": name,
            "n_tracks": ts.n_tracks,
            "n_tracks_orig": ref.n_tracks,
            "tracks_preserved": ts.n_tracks == ref.n_tracks
            and fc["FC_t"] == 0 and fc["FC_s"] == 0,
            "FC_t": fc["FC_t"],
            "FC_s": fc["FC_s"],
            "type_counts": ts.type_counts(),
            "t_analysis": round(dt, 4),
            "MBps_analysis": _mbps(mb, dt),
        }
        log(f"[bench] trajectory_analysis {name:10s} "
            f"tracks {ts.n_tracks}/{ref.n_tracks} "
            f"FC_t {fc['FC_t']} FC_s {fc['FC_s']} "
            f"({out['MBps_analysis']} MB/s analysis)")
        return out

    rows = []
    cfg = CompressionConfig(eb=eb, mode="rel", predictor="mop",
                            backend="xla")
    blob, _ = compress(u, v, cfg)
    ur, vr = decompress(blob)
    rows.append(row("ours-mop", ur, vr))
    for bname in ("sz3-like", "cpsz-like"):
        res = REGISTRY[bname](u, v, eb=eb, mode="rel")
        rows.append(row(bname, res["u_rec"], res["v_rec"]))
    return {"field": f"{field} {T}x{H}x{W}", "eb": eb, "rows": rows}


def _bench_obs_overhead(eb, shape, repeat, log):
    """Cost of the observability layer on the mop encode (the run the
    ``obs_overhead`` schema gate bounds).

    * ``enabled_pct``: measured best-of A/B -- same compress with
      REPRO_OBS tracing off vs on (clamped at 0; on small fields the
      difference is inside timer noise).
    * ``disabled_pct``: the disabled path is too cheap to resolve by
      A/B timing on any field small enough for CI, so it is computed
      synthetically: (measured ns per no-op instrumentation call) x
      (the number of trace events the SAME workload emits when
      enabled) / (the obs-off wall time).  That deliberately
      overestimates -- every disabled call is priced at the full
      span-construction cost."""
    from repro.data import synthetic

    T, H, W = shape
    u, v = synthetic.advected_turbulence(T=T, H=H, W=W)
    cfg = CompressionConfig(eb=eb, mode="rel", predictor="mop",
                            backend="xla", verify=True, fused=True)
    was_enabled = obs.enabled()
    # earlier bench sections leave ~1e5 events in the trace buffer;
    # gen-2 GC walking that list mid-run bills milliseconds to whatever
    # arm it fires in.  Measure the layer's own cost from a clean slate.
    obs.reset()
    gc.collect()
    _warmup(lambda: compress(u, v, cfg))
    n_rep = max(repeat, 5)
    try:
        obs.disable()
        t_off = min(_time_ours(u, v, cfg)[2] for _ in range(n_rep))
        obs.enable()
        n_ev0 = len(obs.trace_events())
        t_on = min(_time_ours(u, v, cfg)[2] for _ in range(n_rep))
        # events of ONE enabled run (the n_rep runs all emit the same
        # workload; dividing keeps the estimate per-compress)
        n_events = max((len(obs.trace_events()) - n_ev0) // n_rep, 1)
    finally:
        obs.enable() if was_enabled else obs.disable()

    # price every would-be event at the cost of a full disabled
    # span-construction + enter/exit round trip
    obs.disable()
    n_loop = 100_000
    t0 = time.perf_counter_ns()
    for _ in range(n_loop):
        with obs.span("noop", x=1):
            pass
    noop_ns = (time.perf_counter_ns() - t0) / n_loop
    if was_enabled:
        obs.enable()

    enabled_pct = max(0.0, 100.0 * (t_on - t_off) / max(t_off, 1e-9))
    disabled_pct = 100.0 * (n_events * noop_ns) / max(t_off * 1e9, 1.0)
    out = {
        "field": f"advected_turbulence {T}x{H}x{W}",
        "predictor": "mop", "backend": "xla",
        "t_encode_obs_off": round(t_off, 4),
        "t_encode_obs_on": round(t_on, 4),
        "trace_events_per_encode": int(n_events),
        "noop_call_ns": round(noop_ns, 1),
        "disabled_pct": round(disabled_pct, 4),
        "enabled_pct": round(enabled_pct, 2),
    }
    log(f"[bench] obs_overhead {T}x{H}x{W}: off {t_off:.3f}s -> on "
        f"{t_on:.3f}s (enabled {out['enabled_pct']}%, disabled "
        f"{out['disabled_pct']}% over {n_events} events at "
        f"{noop_ns:.0f} ns/noop)")
    return out


def _measure_autotune_arms(shape, arms, run, repeat, model, default,
                           mb, log, scenario, ingest_s=0.0):
    """Shared exhaustive-vs-autotuned protocol for one scenario: measure
    every arm (warmup + best-of-``repeat``), then let the searcher rank
    the SAME arms with the calibrated model and measure-verify its top-3
    picks against the already-collected measurements -- so autotuned,
    exhaustive-best and default are timed by identical runs."""
    from repro import autotune as at

    times = {}
    for cand in arms:
        _warmup(lambda: run(cand))
        t = []
        for _ in range(repeat):
            _, dt = _span_time("bench.autotune_arm", lambda: run(cand),
                               plan=cand.describe(), scenario=scenario)
            t.append(dt)
        times[cand.key] = min(t)
    best = min(arms, key=lambda c: (times[c.key], c.key))
    ranked = at.search(shape, model=model, candidates=arms, top_k=3,
                       stream=any(c.async_engine for c in arms),
                       measure=lambda c: times[c.key], ingest_s=ingest_s)
    chosen = ranked[0].cand
    row = {
        "scenario": scenario,
        "shape": list(shape), "MB": round(mb, 2),
        "arms": [{"plan": c.describe(),
                  "t_encode": round(times[c.key], 4),
                  "MBps": _mbps(mb, times[c.key])} for c in arms],
        "default_plan": default.describe(),
        "MBps_default": _mbps(mb, times[default.key]),
        "best_plan": best.describe(),
        "MBps_best": _mbps(mb, times[best.key]),
        "chosen_plan": chosen.describe(),
        "MBps_autotuned": _mbps(mb, times[chosen.key]),
        "ratio_vs_best": round(times[best.key] / times[chosen.key], 3),
        "ratio_vs_default": round(
            times[default.key] / times[chosen.key], 3),
    }
    T, H, W = shape
    log(f"[bench] autotune {scenario} {T}x{H}x{W}: chose "
        f"{row['chosen_plan']} ({row['MBps_autotuned']} MB/s; best "
        f"{row['best_plan']} {row['MBps_best']} MB/s, default "
        f"{row['default_plan']} {row['MBps_default']} MB/s) "
        f"ratio_vs_best={row['ratio_vs_best']} "
        f"ratio_vs_default={row['ratio_vs_default']}")
    return row


def _bench_autotune(eb, shapes, repeat, log, stream_shape=(8, 32, 32),
                    frame_latency=0.06):
    """Cost-model plan auto-tuning vs exhaustive search vs the default
    plan (repro.autotune, DESIGN.md #15).  Two scenarios:

    * *in-memory*: per shape, a fixed plan grid (mono/tiled x backend x
      codec) is measured exhaustively and the autotuner (calibrated
      in-process from obs spans) must land within 10% of the true best
      -- ``ratio_vs_best`` >= 0.9, gated on every row.
    * *stream*: frames arrive from a paced producer (the paper's
      archive-while-simulating use case).  The default plan a
      non-tuning caller gets is the serial engine with the hand-set
      halving grid every bench section uses; the search space adds
      async on/off and queue bounds, where overlap genuinely beats the
      default -- ``ratio_vs_default`` >= 1.1, gated on at least one
      row.
    """
    from repro import autotune as at
    from repro.core import compress_stream, compress_tiled
    from repro.data import synthetic

    table = at.calibrate(backends=("xla", "numpy"), eb=eb, save=False)
    model = at.CostModel(coeffs=table.coeffs, kind=table.device_kind)
    rows = []
    base = CompressionConfig(eb=eb, mode="rel", predictor="mop",
                             verify=True, fused=True, track_index=False)
    for shape in shapes:
        T, H, W = shape
        u, v = synthetic.advected_turbulence(T=T, H=H, W=W)
        mb = (u.nbytes + v.nbytes) / 2**20
        arms = [at.PlanCandidate(grid=None, backend=be)
                for be in ("xla", "numpy")]
        g = (max(H // 2, 8), max(W // 2, 8), max(T // 2, 2))
        for be in ("xla", "numpy"):
            for codec in ("host", "device"):
                arms.append(at.PlanCandidate(grid=g, backend=be,
                                             codec=codec))

        def run(cand, u=u, v=v):
            c = at.apply(base, cand)
            if c.tiling is None:
                return compress(u, v, c)
            return compress_tiled(u, v, c, c.tiling)

        rows.append(_measure_autotune_arms(
            shape, arms, run, repeat, model,
            at.PlanCandidate(grid=None, backend="xla"), mb, log,
            "in-memory"))

    if stream_shape is not None:
        import dataclasses as _dc

        T, H, W = stream_shape
        u, v = synthetic.advected_turbulence(T=T, H=H, W=W)
        mb = (u.nbytes + v.nbytes) / 2**20
        vr = (float(min(u.min(), v.min())), float(max(u.max(), v.max())))
        g = (max(H // 2, 8), max(W // 2, 8), max(T // 4, 2))
        tpw = 4  # 2x2 spatial tiles per window under the halving grid
        serial = at.PlanCandidate(grid=g, backend="xla", codec="host")
        arms = [
            serial,
            _dc.replace(serial, codec="device"),
            _dc.replace(serial, async_engine=True,
                        q_in_frames=max(g[2], 2), q_out_units=2 * tpw),
            _dc.replace(serial, async_engine=True, codec="device",
                        q_in_frames=max(g[2], 2), q_out_units=2 * tpw),
        ]

        def run_stream(cand, u=u, v=v, vr=vr):
            c = at.apply(base, cand)

            def frames():
                for t in range(u.shape[0]):
                    time.sleep(frame_latency)   # paced producer
                    yield u[t], v[t]

            return compress_stream(frames(), c, c.tiling, value_range=vr,
                                   async_engine=cand.async_engine)

        rows.append(_measure_autotune_arms(
            stream_shape, arms, run_stream, repeat, model, serial, mb,
            log, "stream", ingest_s=T * frame_latency))

    return {"device_kind": table.device_kind,
            "calibrated": bool(table.coeffs),
            "n_coeffs": len(table.coeffs),
            "frame_latency_s": frame_latency,
            "shapes": rows}


def _bench_rate_accounting(eb, shape, log):
    """Where the container bytes go (obs.run_report): disjoint byte
    ranges by section kind -- gated to sum EXACTLY to the container
    size -- plus achieved bits-per-symbol vs the zero-order Shannon
    bound, for both unit-frame codecs."""
    import dataclasses as _dc

    from repro.core import TileGrid, compress_tiled
    from repro.data import synthetic

    T, H, W = shape
    u, v = synthetic.advected_turbulence(T=T, H=H, W=W)
    grid = TileGrid(tile_h=max(H // 2, 1), tile_w=max(W // 2, 1),
                    window_t=max(T // 2, 1))
    base = CompressionConfig(eb=eb, mode="rel", predictor="mop",
                             backend="xla", verify=True, fused=True,
                             track_index=False)
    codecs = {}
    for codec in ("host", "device"):
        cfg = _dc.replace(base, codec=codec)
        blob, _ = compress_tiled(u, v, cfg, grid)
        rep = obs.run_report(blob)
        assert rep["kind_bytes_total"] == rep["container_bytes"], \
            f"{codec}: byte kinds do not sum to container size"
        n_sym = sum(r["n_symbols"] for r in rep["units"])
        ach = sum(r["achieved_bits"] for r in rep["units"])
        sh = sum(r["shannon_bits"] for r in rep["units"])
        row = {
            "codec": rep["codec"],
            "container_bytes": rep["container_bytes"],
            "n_units": rep["n_units"],
            "bytes_by_kind": rep["bytes_by_kind"],
            "kind_bytes_total": rep["kind_bytes_total"],
            "n_symbols": int(n_sym),
            "achieved_bps": round(ach / max(n_sym, 1), 4),
            "shannon_bps": round(sh / max(n_sym, 1), 4),
            "units": rep["units"],
        }
        if "payload_bytes_by_kind" in rep:
            row["payload_bytes_by_kind"] = rep["payload_bytes_by_kind"]
        codecs[codec] = row
        log(f"[bench] rate_accounting {codec:6s} {T}x{H}x{W}: "
            f"{rep['container_bytes']} B over {rep['n_units']} units, "
            f"{row['achieved_bps']} bits/sym achieved vs "
            f"{row['shannon_bps']} Shannon")
    return {"field": f"advected_turbulence {T}x{H}x{W}", "eb": eb,
            "codecs": codecs}


def _bench_adaptive_rate(eb, shape, log):
    """Adaptive per-unit bounds vs the uniform scalar bound at equal
    feature fidelity (DESIGN.md #16): a track-aware policy keeps
    trajectory-covering units at the tight bound and relaxes the rest,
    so the ratio must come out strictly higher than uniform-tight while
    FC stays 0 and the track set is preserved exactly.  Also exercises
    the ``compress(..., target_ratio=...)`` search end to end."""
    import dataclasses as _dc

    from repro import analysis
    from repro.core import ebpolicy, fixedpoint, trajectory
    from repro.data import synthetic

    T, H, W = shape
    u, v = synthetic.double_gyre(T=T, H=H, W=W)
    tight, relaxed = 1e-3, 2e-1
    uni_cfg = CompressionConfig(eb=tight, mode="abs", predictor="mop",
                                backend="xla", verify=True, fused=True)
    blob_u, st_u = compress(u, v, uni_cfg)

    wt = min(max(T // 2, 1), 4)
    th = min(H, max(8, H // 8))
    tw = min(W, max(8, W // 8))
    pol = analysis.track_aware_policy(u, v, tight=tight, relaxed=relaxed,
                                      window_t=wt, tile_h=th, tile_w=tw)
    ad_cfg = _dc.replace(uni_cfg, eb_policy=pol,
                         n_levels=ebpolicy.levels_for(pol,
                                                      uni_cfg.n_levels))
    blob_a, st_a = compress(u, v, ad_cfg)
    ur, vr = decompress(blob_a)
    fc = trajectory.false_cases(u, v, ur, vr, st_a["scale"])

    def track_set(uu, vv):
        _, ufp, vfp = fixedpoint.to_fixed(uu, vv)
        traj = analysis.extract(ufp, vfp, classify=False)
        return (len(traj.tracks),
                sum(len(t.nodes) for t in traj.tracks))

    nt0, nn0 = track_set(u, v)
    nt1, nn1 = track_set(ur, vr)

    from repro.autotune import compress_with_target

    target = round(st_u["ratio"] * 1.1, 3)
    _, st_t = compress_with_target(u, v, uni_cfg, target, max_iters=4)
    rt = st_t["rate_target"]

    sec = {
        "field": f"double_gyre {T}x{H}x{W}",
        "tight": tight, "relaxed": relaxed,
        "policy_grid": [wt, th, tw],
        "n_protected_units": len(pol.values),
        "n_levels": ad_cfg.n_levels,
        "ratio_uniform": round(st_u["ratio"], 3),
        "ratio_adaptive": round(st_a["ratio"], 3),
        "adaptive_higher": bool(st_a["ratio"] > st_u["ratio"]),
        "FC_t": fc["FC_t"], "FC_s": fc["FC_s"],
        "tracks_orig": nt0, "tracks_rec": nt1,
        "nodes_orig": nn0, "nodes_rec": nn1,
        "tracks_preserved": bool(nt0 == nt1 and nn0 == nn1),
        "target_search": {
            "target_ratio": rt["target_ratio"],
            "achieved_ratio": round(rt["achieved_ratio"], 3),
            "met": rt["met"],
            "relax": rt["relax"],
            "rungs_tried": rt.get("rungs_tried", []),
        },
    }
    log(f"[bench] adaptive_rate {T}x{H}x{W}: uniform "
        f"{sec['ratio_uniform']} -> adaptive {sec['ratio_adaptive']} "
        f"(FC_t={fc['FC_t']} FC_s={fc['FC_s']}, tracks "
        f"{nt0}->{nt1}); target {target} "
        f"{'met' if rt['met'] else 'MISSED'} at relax {rt['relax']}")
    return sec


def bench_compress(small=True, eb=1e-2, backends=("xla",),
                   predictors=("lorenzo", "sl", "mop"),
                   speedup_shape=(64, 256, 256), repeat=2, log=print,
                   data=None, tiled_shape=(64, 256, 256),
                   analysis_shape=(16, 48, 48),
                   batched_shape=(16, 64, 64),
                   async_shape=(32, 64, 64),
                   recovery_shape=(24, 64, 64),
                   entropy_shape=(2, 16, 16),
                   obs_shape=(16, 64, 64),
                   rate_shape=(16, 64, 64),
                   adaptive_shape=(8, 64, 64),
                   autotune_shapes=((8, 32, 32), (16, 64, 64))):
    """Emit the BENCH_compress.json payload.

    Each (dataset, predictor, backend) cell reports best-of-``repeat``
    encode/decode wall time and MB/s (first call pays jit compilation;
    best-of captures the steady state the roadmap cares about).

    The whole emit runs with obs tracing ENABLED (the section timings
    derive from obs spans); ``_bench_obs_overhead`` toggles it per arm
    to measure its own cost.
    """
    from repro.data import synthetic

    obs.enable()
    rows = []
    if data is None:
        data = datasets.load_all(small)
    for name, (u, v, meta) in data.items():
        mb = (u.nbytes + v.nbytes) / 2**20
        for pred in predictors:
            for be in backends:
                cfg = CompressionConfig(eb=eb, mode="rel", predictor=pred,
                                        backend=be, **meta)
                _warmup(lambda: decompress(compress(u, v, cfg)[0]))
                tcs, tds = [], []
                for _ in range(repeat):
                    blob, stats, tc, td = _time_ours(u, v, cfg)
                    tcs.append(tc)
                    tds.append(td)
                rows.append({
                    "dataset": name, "predictor": pred, "backend": be,
                    "MB": round(mb, 2),
                    "t_encode": round(min(tcs), 4),
                    "t_decode": round(min(tds), 4),
                    "MBps_encode": _mbps(mb, min(tcs)),
                    "MBps_decode": _mbps(mb, min(tds)),
                    "ratio": round(stats["ratio"], 3),
                    "verify_rounds": stats["verify_rounds"],
                })
                log(f"[bench] {name} {pred:8s} {be:6s} "
                    f"enc {rows[-1]['MBps_encode']:8.2f} MB/s  "
                    f"dec {rows[-1]['MBps_decode']:8.2f} MB/s  "
                    f"ratio {rows[-1]['ratio']}")

    comparison = None
    if speedup_shape is not None:
        T, H, W = speedup_shape
        u, v = synthetic.advected_turbulence(T=T, H=H, W=W)
        mb = (u.nbytes + v.nbytes) / 2**20
        base = CompressionConfig(eb=eb, mode="rel", predictor="mop",
                                 backend="xla", verify=True, fused=False)
        opt = CompressionConfig(eb=eb, mode="rel", predictor="mop",
                                backend="xla", verify=True, fused=True)
        _warmup(lambda: compress(u, v, base),
                lambda: compress(u, v, opt))
        t_seed = min(_time_ours(u, v, base)[2] for _ in range(repeat))
        t_fused = min(_time_ours(u, v, opt)[2] for _ in range(repeat))
        comparison = {
            "field": f"advected_turbulence {T}x{H}x{W}",
            "predictor": "mop", "backend": "xla", "verify": True,
            "MB": round(mb, 2),
            "t_encode_seed": round(t_seed, 3),
            "t_encode_fused": round(t_fused, 3),
            "speedup": round(t_seed / max(t_fused, 1e-9), 3),
        }
        log(f"[bench] seed-vs-fused mop {T}x{H}x{W}: "
            f"{t_seed:.2f}s -> {t_fused:.2f}s "
            f"({comparison['speedup']:.2f}x)")

    tiled = None
    if tiled_shape is not None:
        tiled = _bench_tiled(eb, tiled_shape, repeat, log)
    batched = None
    if batched_shape is not None:
        batched = _bench_batched(eb, batched_shape, repeat, log)
    async_section = None
    if async_shape is not None:
        async_section = _bench_async(eb, async_shape, repeat, log)
    recovery = None
    if recovery_shape is not None:
        recovery = _bench_recovery(eb, recovery_shape, log)
    entropy_stage = None
    if entropy_shape is not None:
        entropy_stage = _bench_entropy(eb, entropy_shape, repeat, log)
    traj = None
    if analysis_shape is not None:
        traj = _bench_trajectory_analysis(eb, analysis_shape, log)
    obs_overhead = None
    if obs_shape is not None:
        obs_overhead = _bench_obs_overhead(eb, obs_shape, repeat, log)
    rate_accounting = None
    if rate_shape is not None:
        rate_accounting = _bench_rate_accounting(eb, rate_shape, log)
    adaptive_rate = None
    if adaptive_shape is not None:
        adaptive_rate = _bench_adaptive_rate(eb, adaptive_shape, log)
    autotune = None
    if autotune_shapes is not None:
        autotune = _bench_autotune(eb, autotune_shapes, repeat, log)
    return {"rows": rows, "seed_vs_fused": comparison,
            "tiled_vs_monolithic": tiled,
            "batched_vs_sequential": batched,
            "async_vs_serial": async_section,
            "recovery": recovery,
            "entropy_stage": entropy_stage,
            "trajectory_analysis": traj,
            "obs_overhead": obs_overhead,
            "rate_accounting": rate_accounting,
            "adaptive_rate": adaptive_rate,
            "autotune": autotune,
            "eb": eb, "small": small}


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI run (one tiny field, tiny A/B shape)")
    ap.add_argument("--large", action="store_true",
                    help="use the large dataset variants")
    ap.add_argument("--backends", default="xla",
                    help="comma-separated: xla,pallas,numpy")
    ap.add_argument("--out", default="BENCH_compress.json")
    ap.add_argument("--eb", type=float, default=1e-2)
    ap.add_argument("--legacy-table", action="store_true",
                    help="also emit the paper-style baseline table")
    args = ap.parse_args()

    backends = tuple(args.backends.split(","))
    if args.smoke:
        from repro.data import synthetic

        tiny = {"DG-tiny": (*synthetic.double_gyre(T=6, H=24, W=32),
                            dict(dt=0.1, dx=2.0 / 31, dy=1.0 / 23))}
        payload = bench_compress(
            eb=args.eb, backends=backends, data=tiny,
            predictors=("mop",), speedup_shape=(6, 32, 32), repeat=1,
            tiled_shape=(6, 32, 32), analysis_shape=(6, 24, 24),
            batched_shape=(6, 32, 32), async_shape=(8, 32, 32),
            recovery_shape=(9, 32, 32), entropy_shape=(2, 16, 16),
            obs_shape=(6, 32, 32), rate_shape=(6, 32, 32),
            adaptive_shape=(8, 64, 64),
            autotune_shapes=((6, 32, 32),))
    else:
        payload = bench_compress(
            small=not args.large, eb=args.eb, backends=backends,
            repeat=2)
    if args.legacy_table:
        payload["paper_table"] = main(small=not args.large, eb=args.eb)
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"wrote {args.out}")
