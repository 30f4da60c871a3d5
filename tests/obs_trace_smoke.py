"""Observability smoke: the ISSUE acceptance run, end to end.

Run as:  REPRO_OBS=1 PYTHONPATH=src python tests/obs_trace_smoke.py

With observability enabled, one streamed compression (async engine,
filesystem sink so the journal is live) plus one track query must
produce:

  * a valid Chrome-trace JSON (loads as ``{"traceEvents": [...]}``,
    Perfetto-compatible) containing spans for all three engine stages
    on distinct threads, with queue-depth counter events for both
    handoff queues, and the track query's stage spans (open, fetch,
    unpack, decode sections, decode fields, rebuild) sharing its qid;
  * a registry snapshot covering pipeline, engine, journal, cache and
    retry metrics;
  * a container byte-identical to an obs-off run of the same input.

The in-suite tests (tests/test_obs.py) cover each piece in isolation;
this leg proves they compose in one process the way the README's
Perfetto walkthrough describes.
"""
import json
import os
import sys
import tempfile

import numpy as np

FAILURES = []


def need(cond, msg):
    if not cond:
        FAILURES.append(msg)
        print(f"obs_trace_smoke: FAIL: {msg}", file=sys.stderr)


def main() -> int:
    from repro import analysis, obs
    from repro.core import CompressionConfig, TileGrid, compress_tiled
    from repro.core import faults as faults_mod
    from repro.core.tiling import compress_stream
    from repro.data import synthetic
    from repro.obs import trace

    T, H, W = 10, 24, 32
    u, v = synthetic.double_gyre(T=T, H=H, W=W)
    vr = (float(min(u.min(), v.min())), float(max(u.max(), v.max())))
    cfg = CompressionConfig(track_index=True)
    grid = TileGrid(tile_h=8, tile_w=12, window_t=3)

    # reference container with observability hard-off
    obs.disable()
    ref, _ = compress_tiled(u, v, cfg, grid)

    obs.enable()
    trace.reset()
    with tempfile.TemporaryDirectory() as td:
        sink = os.path.join(td, "smoke.cptt")

        # one streamed compression on the async engine, journal live
        _, stats = compress_stream(list(zip(u, v)), cfg, grid,
                                   value_range=vr, sink=sink,
                                   async_engine=True)
        with open(sink, "rb") as f:
            got = f.read()
        need(got == ref,
             f"streamed obs-on container differs from obs-off run "
             f"({len(got)} vs {len(ref)} bytes)")

        # one track query, cold then warm (cache miss then hit)
        snap0 = obs.snapshot()
        res_cold = analysis.decode_for_track(sink, 0)
        res_warm = analysis.decode_for_track(sink, 0)
        need(res_cold.units_read >= 1, "track query decoded no units")
        need(res_warm.cache_hits > 0,
             "warm repeat of the track query missed the unit cache")

        # a recovered transient failure at a real retry site
        plan = faults_mod.FaultPlan().io_error("source.read", nth=1,
                                               transient=1)
        with analysis.ContainerSource(sink, faults=plan,
                                      retries=2) as src:
            src.read(0, 8)
            need(src.retried >= 1,
                 "transient fault was not retried/recovered")

        # ---- trace export: Chrome trace-event JSON ----
        trace_path = os.path.join(td, "trace.json")
        n = obs.export_trace(trace_path)
        need(n > 0, "export_trace wrote no events")
        with open(trace_path) as f:
            payload = json.load(f)
        need(set(payload) == {"traceEvents", "displayTimeUnit"},
             f"trace top-level keys wrong: {sorted(payload)}")
        evs = payload["traceEvents"]
        by_name = {}
        for e in evs:
            by_name.setdefault(e["name"], []).append(e)

        stage_tids = {}
        for stage in ("engine.ingest", "engine.compute", "engine.write"):
            spans = [e for e in by_name.get(stage, ())
                     if e["ph"] == "X"]
            need(spans, f"no {stage} spans in trace")
            stage_tids[stage] = {e["tid"] for e in spans}
        if all(stage_tids.get(s) for s in stage_tids):
            need(stage_tids["engine.ingest"].isdisjoint(
                     stage_tids["engine.compute"]),
                 "ingest and compute spans share a thread")
            need(stage_tids["engine.write"].isdisjoint(
                     stage_tids["engine.compute"]),
                 "write and compute spans share a thread")
        for qname in ("engine.q_in", "engine.q_out"):
            counters = [e for e in by_name.get(qname, ())
                        if e["ph"] == "C"]
            need(counters, f"no {qname} queue-depth counter events")
            need(all(e["args"]["depth"] >= 0 for e in counters),
                 f"{qname} counter event missing depth arg")
        need(len([e for e in by_name.get("engine.ingest", ())
                  if e["ph"] == "X"]) == T,
             "ingest span count != frame count")
        need(len([e for e in by_name.get("engine.write", ())
                  if e["ph"] == "X"]) == stats["n_units"],
             "write span count != unit count")
        bad = [e for e in evs
               if e["ph"] == "X" and "stack_corrupt" in e.get("args", {})]
        need(not bad, f"corrupt span stacks in trace: {bad[:3]}")
        need({"engine.ingest", "engine.writer", "engine.compute"} <=
             {e["args"]["name"] for e in evs if e["ph"] == "M"},
             "engine threads did not self-label")
        need(by_name.get("query.decode_for_track"),
             "no query.decode_for_track span")
        # the cold query's stages, sharing its qid
        qids = {e["args"].get("qid")
                for e in by_name.get("query.decode_for_track", ())}
        for stage in ("query.open", "query.fetch_units", "query.unpack",
                      "pipeline.decode_sections", "pipeline.decode_fields",
                      "query.rebuild"):
            spans = by_name.get(stage, ())
            need(spans, f"no {stage} span under the track query")
            need(all(e["args"].get("qid") in qids for e in spans
                     if e["name"].startswith("query.")),
                 f"{stage} spans do not carry their query's qid")

        # ---- registry snapshot: all five metric families ----
        snap = obs.snapshot()
        for name in ("engine.units_emitted", "engine.frames_ingested",
                     "engine.units_written", "journal.fsync",
                     "journal.checkpoints", "cache.hits", "cache.misses",
                     "query.range_reads", "query.bytes_fetched",
                     "query.units_decoded", "query.decode_dup",
                     "query.decode_joined",
                     "faults.retry.source.read.attempts",
                     "faults.retry.source.read.retries"):
            need(name in snap, f"snapshot missing {name}")
        need(any(k.startswith("pipeline.") for k in snap),
             "snapshot has no pipeline.* metrics")
        need(snap.get("journal.fsync", {}).get("value", 0) > 0,
             "journal fsyncs not counted on a sink-path run")
        need(snap.get("cache.misses", {}).get("value", 0)
             > snap0.get("cache.misses", {}).get("value", 0),
             "cold track query did not miss the unit cache")
        need(snap.get("faults.retry.source.read.retries", {})
             .get("value", 0) >= 1,
             "recovered retry invisible in the registry")
        st = faults_mod.retry_stats("source.read")
        need(st.get("last_outcome") == "ok",
             f"retry site outcome not ok: {st}")

    if not FAILURES:
        print(f"obs_trace_smoke: trace ok ({n} events), snapshot "
              f"covers pipeline/engine/journal/cache/retry, container "
              f"byte-identical ({len(ref)} bytes, "
              f"{stats['n_units']} units)")
    return 1 if FAILURES else 0


if __name__ == "__main__":
    sys.exit(main())
