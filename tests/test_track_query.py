"""CPTT1 track index: query roundtrip + footer forward-compat.

The acceptance bar: ``decode_for_track`` on a >= 8-unit tiled blob must
decode STRICTLY FEWER units than the full field and return a polyline
bit-identical (node coordinates, connectivity, types) to extraction
from a monolithic full decode; and blobs written with the index must
keep decoding identically on readers that ignore the new footer
section (old-reader simulation).
"""
import copy

import numpy as np
import pytest

from repro import analysis
from repro.core import (
    CompressionConfig,
    TileGrid,
    compress_stream,
    compress_tiled,
    decompress_tiled,
    encode,
    fixedpoint,
)
from repro.data import synthetic


def _make_blob(track_index=True, predictor="mop"):
    u, v = synthetic.double_gyre(T=8, H=20, W=28)
    cfg = CompressionConfig(eb=1e-2, mode="rel", predictor=predictor,
                            fused=True, track_index=track_index,
                            dt=0.1, dx=2.0 / 27, dy=1.0 / 19)
    grid = TileGrid(tile_h=10, tile_w=14, window_t=4)
    blob, stats = compress_tiled(u, v, cfg, grid)
    return u, v, blob, stats


@pytest.fixture(scope="module")
def indexed():
    return _make_blob(track_index=True)


def test_query_roundtrip_bit_identical(indexed):
    """decode_for_track == full-decode extraction, track by track."""
    u, v, blob, stats = indexed
    assert stats["n_units"] >= 8
    ur, vr = decompress_tiled(blob)
    ufp, vfp = fixedpoint.refix(ur, vr, stats["scale"])
    full = analysis.extract(ufp, vfp)
    assert full.n_tracks == len(analysis.track_summaries(blob))
    for k in range(full.n_tracks):
        res = analysis.decode_for_track(blob, k)
        ref = full.track(k)
        assert res.units_read < res.units_total, \
            "feature decode read the whole field"
        assert np.array_equal(res.track.face_ids, ref.face_ids)
        assert np.array_equal(res.track.nodes, ref.nodes)  # bitwise
        assert np.array_equal(res.track.types, ref.types)
        assert res.track.is_loop == ref.is_loop


def test_read_plan_matches_decode(indexed):
    _, _, blob, _ = indexed
    hdr = encode.tiled_header(blob)
    for s in analysis.track_summaries(blob):
        k = s["track_id"]
        plan = analysis.track_read_plan(blob, k)
        res = analysis.decode_for_track(blob, k)
        assert plan == res.entries
        assert 0 < len(plan) < len(hdr["units"])
        assert res.bytes_read == sum(e["len"] for e in plan)
        assert res.bytes_read < len(blob)


def test_query_filters(indexed):
    _, _, blob, _ = indexed
    T, H, W = 8, 20, 28
    allt = analysis.track_summaries(blob)
    centers = analysis.query_tracks(blob, cp_type="center")
    saddles = analysis.query_tracks(blob, cp_type="saddle")
    assert {s["track_id"] for s in centers} \
        | {s["track_id"] for s in saddles} \
        == {s["track_id"] for s in allt}
    assert len(centers) == 2 and len(saddles) == 2
    # spatial filter: the left gyre core only
    left = analysis.query_tracks(blob, bbox=(5, H - 6, 0, W / 2 - 3),
                                 cp_type="center")
    assert len(left) == 1
    # time filter: everything lives through the whole window
    assert len(analysis.query_tracks(blob, trange=(0, 1))) == len(allt)
    assert analysis.query_tracks(blob, trange=(T + 5, T + 9)) == []
    with pytest.raises(ValueError, match="unknown cp_type"):
        analysis.query_tracks(blob, cp_type="vortexx")


def test_streaming_blob_carries_same_index(indexed):
    u, v, blob, _ = indexed
    cfg = CompressionConfig(eb=1e-2, mode="rel", predictor="mop",
                            fused=True, dt=0.1, dx=2.0 / 27, dy=1.0 / 19)
    grid = TileGrid(tile_h=10, tile_w=14, window_t=4)
    vr = (float(min(u.min(), v.min())), float(max(u.max(), v.max())))
    blob_s, _ = compress_stream(
        ((u[t], v[t]) for t in range(u.shape[0])), cfg, grid,
        value_range=vr)
    assert blob_s == blob  # bytes, index included


def test_no_index_is_a_clear_error():
    _, _, blob, _ = _make_blob(track_index=False)
    with pytest.raises(ValueError, match="no track index"):
        analysis.track_summaries(blob)
    with pytest.raises(ValueError, match="no track index"):
        analysis.decode_for_track(blob, 0)


def test_index_does_not_perturb_units_or_decode():
    """The sidecar index must be purely additive: same unit bytes, same
    directory offsets, same decoded field as an index-less blob."""
    _, _, blob_on, _ = _make_blob(track_index=True)
    _, _, blob_off, _ = _make_blob(track_index=False)
    h_on = encode.tiled_header(blob_on)
    h_off = encode.tiled_header(blob_off)
    assert h_on["units"] == h_off["units"]       # offsets + lengths
    last = max(e["off"] + e["len"] for e in h_on["units"])
    assert blob_on[:last] == blob_off[:last]     # unit bytes identical
    a = decompress_tiled(blob_on)
    b = decompress_tiled(blob_off)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])


def test_old_reader_skips_footer_section(indexed):
    """Simulate a pre-index reader: strip the unknown footer key and
    re-pack the footer -- the decode must be unchanged, proving no
    decode path depends on the new section."""
    _, _, blob, _ = indexed
    hdr = encode.tiled_header(blob)
    assert encode.TRACK_INDEX_KEY in hdr
    stripped = copy.deepcopy(hdr)
    units = stripped.pop("units")
    stripped.pop(encode.TRACK_INDEX_KEY)
    # rebuild a footer without the index on top of the same unit bytes
    import msgpack
    import struct
    import zlib
    stripped["units"] = units
    last = max(e["off"] + e["len"] for e in units)
    raw = zlib.compress(msgpack.packb(stripped, use_bin_type=True), 6)
    doctored = blob[:last] + raw + struct.pack("<I", len(raw)) \
        + encode.MAGIC_TILED
    a = decompress_tiled(blob)
    b = decompress_tiled(doctored)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])


def test_future_index_version_refused(indexed):
    _, _, blob, _ = indexed
    hdr = encode.tiled_header(blob)
    section = copy.deepcopy(hdr[encode.TRACK_INDEX_KEY])
    section["version"] = 99
    with pytest.raises(ValueError, match="track index version 99"):
        analysis.TrackIndex(section)


def test_path_source_uses_range_reads(tmp_path, indexed):
    """A path source must answer queries with seek-based range reads
    (footer + covering units), matching the bytes-source results."""
    _, _, blob, _ = indexed
    p = tmp_path / "field.cptt1"
    p.write_bytes(blob)
    assert analysis.track_summaries(str(p)) == analysis.track_summaries(blob)
    k = analysis.track_summaries(blob)[0]["track_id"]
    assert analysis.track_read_plan(str(p), k) == \
        analysis.track_read_plan(blob, k)
    a = analysis.decode_for_track(str(p), k)
    b = analysis.decode_for_track(blob, k)
    assert np.array_equal(a.track.nodes, b.track.nodes)
    assert a.bytes_read == b.bytes_read < len(blob)


def test_repeated_query_hits_unit_cache(tmp_path, indexed):
    """Acceptance: the second identical query is served from the
    decoded-unit cache -- STRICTLY fewer range reads (only the three
    footer reads), every covering unit a cache hit, same polyline."""
    from repro.analysis import query as query_mod

    _, _, blob, _ = indexed
    p = tmp_path / "field.cptt1"
    p.write_bytes(blob)
    query_mod.unit_cache.clear()
    cold = analysis.decode_for_track(str(p), 0)
    warm = analysis.decode_for_track(str(p), 0)
    assert warm.range_reads < cold.range_reads
    assert warm.bytes_fetched < cold.bytes_fetched
    assert cold.cache_hits == 0
    assert warm.cache_hits == warm.units_read > 0
    # the logical plan accounting is unchanged by caching
    assert warm.bytes_read == cold.bytes_read
    assert warm.entries == cold.entries
    assert np.array_equal(warm.track.nodes, cold.track.nodes)
    # the cache is content-addressed: the same container as BYTES hits
    # the entries populated through the path source
    from_bytes = analysis.decode_for_track(blob, 0)
    assert from_bytes.cache_hits == from_bytes.units_read


def test_overlapping_queries_share_units(indexed):
    """Tracks with overlapping covering sets re-decode nothing for the
    shared units."""
    from repro.analysis import query as query_mod

    _, _, blob, _ = indexed
    query_mod.unit_cache.clear()
    plans = {s["track_id"]: analysis.track_read_plan(blob, s["track_id"])
             for s in analysis.track_summaries(blob)}
    ids = sorted(plans)
    offs = [{e["off"] for e in plans[k]} for k in ids]
    shared = offs[0].intersection(*offs[1:]) if len(offs) > 1 else set()
    seen = set()
    for k in ids:
        res = analysis.decode_for_track(blob, k)
        expected_hits = len({e["off"] for e in plans[k]} & seen)
        assert res.cache_hits == expected_hits
        seen |= {e["off"] for e in plans[k]}
    if shared:  # double-gyre tracks do share covering units
        assert any(res.cache_hits for k in ids[1:]
                   for res in [analysis.decode_for_track(blob, k)])


def test_unit_cache_bounded_and_disablable(indexed):
    from repro.analysis import query as query_mod

    _, _, blob, _ = indexed
    cache = query_mod.configure_unit_cache(0)     # disabled
    try:
        a = analysis.decode_for_track(blob, 0)
        b = analysis.decode_for_track(blob, 0)
        assert a.cache_hits == 0 and b.cache_hits == 0
        assert cache.stats()["entries"] == 0
        # tiny budget: the cache must stay within max_bytes
        query_mod.configure_unit_cache(0.02)      # ~20 KB
        analysis.decode_for_track(blob, 0)
        st = cache.stats()
        assert st["bytes"] <= st["max_bytes"]
    finally:
        query_mod.configure_unit_cache(256)


def test_region_decode_uses_cache(indexed):
    """decompress_region stops re-reading/re-decoding covering units on
    repeated queries (served through the same unit cache)."""
    from repro.core import decompress_region

    from repro.analysis import query as query_mod

    _, _, blob, _ = indexed
    query_mod.unit_cache.clear()
    region = (0, 2, 0, 8, 0, 8)
    r1 = decompress_region(blob, region)
    s1 = query_mod.unit_cache.stats()
    r2 = decompress_region(blob, region)
    s2 = query_mod.unit_cache.stats()
    assert s2["misses"] == s1["misses"]       # nothing re-decoded
    assert s2["hits"] > s1["hits"]
    assert np.array_equal(r1[0], r2[0]) and np.array_equal(r1[1], r2[1])


def test_lorenzo_predictor_roundtrip():
    """Same guarantee under the pure-Lorenzo predictor."""
    u, v, blob, stats = _make_blob(predictor="lorenzo")
    ur, vr = decompress_tiled(blob)
    ufp, vfp = fixedpoint.refix(ur, vr, stats["scale"])
    full = analysis.extract(ufp, vfp)
    for k in range(full.n_tracks):
        res = analysis.decode_for_track(blob, k)
        assert np.array_equal(res.track.nodes, full.track(k).nodes)
        assert res.units_read < res.units_total


# ----------------------------------------------------------------------
# read-path spans and the duplicate-decode counter
# ----------------------------------------------------------------------

READ_PATH_STAGES = ("query.open", "query.fetch_units", "query.unpack",
                    "pipeline.decode_sections", "pipeline.decode_fields",
                    "query.rebuild")


@pytest.fixture
def traced():
    """Observability on for one test, restored (and the trace buffer
    cleared) afterwards."""
    from repro import obs
    from repro.obs import trace

    was = obs.enabled()
    obs.enable()
    trace.reset()
    yield obs
    (obs.enable if was else obs.disable)()
    trace.reset()


def _self_ns(span, spans):
    """Duration of ``span`` less the union of the spans nested in it on
    its thread."""
    s, e = span["ts"], span["ts"] + span["dur"]
    kids = sorted((k["ts"], k["ts"] + k["dur"]) for k in spans
                  if k is not span and k["tid"] == span["tid"]
                  and k["ts"] >= s and k["ts"] + k["dur"] <= e)
    covered, reach = 0.0, s
    for ks, ke in kids:
        ks = max(ks, reach)
        if ke > ks:
            covered += ke - ks
            reach = ke
    return span["dur"] - covered


def test_decode_for_track_stage_spans(indexed, traced):
    """A cold track query yields the six stage spans, nested in
    ``query.decode_for_track`` on its thread and sharing its qid; the
    self times of the stages and of the parent add up to the parent's
    duration."""
    from repro.analysis import query as query_mod

    _, _, blob, _ = indexed
    query_mod.unit_cache.clear()
    res = analysis.decode_for_track(blob, 0)
    assert res.cache_hits == 0 and res.units_read > 0
    spans = [e for e in traced.trace_events() if e["ph"] == "X"]
    (parent,) = [e for e in spans if e["name"] == "query.decode_for_track"]
    qid = parent["args"]["qid"]
    kids = [e for e in spans if e["name"] in READ_PATH_STAGES]
    assert {e["name"] for e in kids} == set(READ_PATH_STAGES)
    n_units = res.units_read
    for name, n in [("query.open", 1), ("query.fetch_units", 1),
                    ("query.rebuild", 1), ("query.unpack", n_units),
                    ("pipeline.decode_sections", n_units),
                    ("pipeline.decode_fields", n_units)]:
        assert sum(e["name"] == name for e in kids) == n, name
    end = parent["ts"] + parent["dur"]
    for e in kids:
        assert e["tid"] == parent["tid"]
        assert e["args"]["qid"] == qid
        assert parent["ts"] <= e["ts"] and e["ts"] + e["dur"] <= end + 1e-3
        assert "stack_corrupt" not in e["args"]
    for e in kids:
        if e["name"] == "pipeline.decode_fields":
            assert e["args"]["sl_frames"] >= 0
    total = sum(_self_ns(e, spans) for e in kids + [parent])
    assert total == pytest.approx(parent["dur"], abs=1e-3 * len(kids))

    # a second query takes the next qid
    analysis.decode_for_track(blob, 0)
    qids = [e["args"]["qid"] for e in traced.trace_events()
            if e["name"] == "query.decode_for_track"]
    assert len(set(qids)) == 2


def _held_decode(monkeypatch, fail=None):
    """Make every unit decode wait, once it has started, until the
    returned ``release`` event is set; ``arrived(n)`` waits until n
    decodes have started, and ``started[0]`` counts them.  With
    ``fail`` given, the first decode raises it once released."""
    import threading

    from repro.core import pipeline

    cond = threading.Condition()
    started = [0]
    release = threading.Event()
    orig = pipeline.PlanExecutor.decode_unit

    def decode_unit(self, unit_header, sections, **placement):
        with cond:
            started[0] += 1
            first = started[0] == 1
            cond.notify_all()
        assert release.wait(60)
        if fail is not None and first:
            raise fail
        return orig(self, unit_header, sections, **placement)

    def arrived(n):
        with cond:
            return cond.wait_for(lambda: started[0] >= n, timeout=60)

    monkeypatch.setattr(pipeline.PlanExecutor, "decode_unit", decode_unit)
    return arrived, release, started


def _claims(monkeypatch):
    """Record what each registry claim returns, as (led, joined) counts;
    ``claimed(n)`` waits until n claims have returned."""
    import threading

    from repro.analysis import query as query_mod

    cond = threading.Condition()
    got = []
    orig = query_mod._InFlight.claim

    def claim(self, cid, entries):
        hits, led, joined = orig(self, cid, entries)
        with cond:
            got.append((len(led), len(joined)))
            cond.notify_all()
        return hits, led, joined

    def claimed(n):
        with cond:
            return cond.wait_for(lambda: len(got) >= n, timeout=60)

    monkeypatch.setattr(query_mod._InFlight, "claim", claim)
    return claimed, got


def _one_unit_missing(blob, k):
    """Reference decode of track ``k``, then every covering unit but the
    first put in the cache: each query of ``k`` misses one unit."""
    from repro.analysis import query as query_mod
    from repro.core import pipeline

    query_mod.unit_cache.clear()
    ref = analysis.decode_for_track(blob, k)
    query_mod.unit_cache.clear()
    source, hdr, _ = query_mod.load_track_index(blob)
    with source:
        query_mod.fetch_decoded_units(
            source, pipeline.executor_from_header(hdr), ref.entries[1:])
    return ref


def _same_track(res, ref):
    assert np.array_equal(res.track.face_ids, ref.track.face_ids)
    assert np.array_equal(res.track.nodes, ref.track.nodes)
    assert np.array_equal(res.track.types, ref.track.types)


def _race(first, second, arrived, claimed, release):
    """Run ``first`` on a thread until its first unit decode is under
    way, then ``second`` on another until it has claimed its units, then
    let the decode go on.  Returns the two results (or the exceptions
    raised) and the number of units in flight at that moment."""
    import threading

    from repro.analysis import query as query_mod

    out = [None, None]

    def run(i, fn):
        try:
            out[i] = fn()
        except Exception as exc:   # noqa: BLE001 -- reported to the test
            out[i] = exc

    threads = [threading.Thread(target=run, args=(i, fn))
               for i, fn in enumerate((first, second))]
    threads[0].start()
    assert arrived(1)
    threads[1].start()
    assert claimed(2)
    in_flight = len(query_mod._inflight)
    release.set()
    for t in threads:
        t.join(60)
        assert not t.is_alive()
    return out, in_flight


@pytest.mark.parametrize("obs_on", [True, False])
def test_concurrent_miss_decodes_once(indexed, monkeypatch, obs_on):
    """Two threads that miss the same uncached unit while one decode of
    it is under way: the second waits for that decode and takes its
    patch, tracing on or off.  The unit decodes once; with tracing on
    ``query.decode_joined`` rises by one and ``query.decode_dup`` stays;
    both answers are bit-identical to the reference."""
    from repro import obs
    from repro.analysis import query as query_mod

    _, _, blob, _ = indexed
    k = 0
    ref = _one_unit_missing(blob, k)

    was = obs.enabled()
    (obs.enable if obs_on else obs.disable)()
    try:
        dup0 = obs.counter("query.decode_dup").value
        joined0 = obs.counter("query.decode_joined").value
        arrived, release, started = _held_decode(monkeypatch)
        claimed, claims = _claims(monkeypatch)
        answers, held = _race(lambda: analysis.decode_for_track(blob, k),
                              lambda: analysis.decode_for_track(blob, k),
                              arrived, claimed, release)
        dup = obs.counter("query.decode_dup").value - dup0
        joined = obs.counter("query.decode_joined").value - joined0
    finally:
        (obs.enable if was else obs.disable)()
    assert started[0] == 1
    assert claims == [(1, 0), (0, 1)]
    assert held == 1
    assert (joined, dup) == ((1, 0) if obs_on else (0, 0))
    assert len(query_mod._inflight) == 0
    for res in answers:
        assert res.cache_hits == len(ref.entries) - 1
        _same_track(res, ref)


@pytest.mark.parametrize("degraded", [False, True])
def test_joiner_shares_leaders_container_error(indexed, monkeypatch,
                                               degraded):
    """A leader whose decode finds the unit corrupt: the thread that
    joined it gets the same ContainerError -- raised in strict mode,
    the unit reported missing in degraded mode -- without a decode of
    its own."""
    from repro.analysis import query as query_mod

    _, _, blob, _ = indexed
    k = 0
    ref = _one_unit_missing(blob, k)
    bad = encode.ContainerError("planted corrupt unit")
    arrived, release, started = _held_decode(monkeypatch, fail=bad)
    claimed, _ = _claims(monkeypatch)

    def ask():
        return analysis.decode_for_track(blob, k, degraded=degraded)

    answers, _ = _race(ask, ask, arrived, claimed, release)
    assert started[0] == 1
    assert len(query_mod._inflight) == 0
    for res in answers:
        if not degraded:
            assert res is bad
            continue
        assert [m["key"] for m in res.missing_units] \
            == [tuple(ref.entries[0]["key"])]
        assert res.missing_units[0]["error"] == str(bad)


def test_joiner_redoes_after_leaders_transient_fault(indexed,
                                                     monkeypatch):
    """A leader that fails with an OSError (a transient fault its
    retries gave up on): the joiner does not inherit it, but decodes
    the unit itself and answers correctly."""
    from repro.analysis import query as query_mod

    _, _, blob, _ = indexed
    k = 0
    ref = _one_unit_missing(blob, k)
    fault = OSError("planted transient fault")
    arrived, release, started = _held_decode(monkeypatch, fail=fault)
    claimed, claims = _claims(monkeypatch)
    answers, _ = _race(lambda: analysis.decode_for_track(blob, k),
                       lambda: analysis.decode_for_track(blob, k),
                       arrived, claimed, release)
    assert answers[0] is fault
    assert claims == [(1, 0), (0, 1), (1, 0)]    # the joiner led a redo
    assert started[0] == 2
    assert len(query_mod._inflight) == 0
    assert answers[1].cache_hits == len(ref.entries) - 1
    _same_track(answers[1], ref)


def test_opposite_order_misses_finish(indexed, monkeypatch):
    """Two threads that need the same two uncached units, in opposite
    order, while the first is decoding: both finish, with two decodes
    in all.  A thread claims its units at once and decodes the ones it
    leads before it waits on any other, so no two threads wait on each
    other."""
    from repro.analysis import query as query_mod
    from repro.core import pipeline

    _, _, blob, _ = indexed
    query_mod.unit_cache.clear()
    source, hdr, _ = query_mod.load_track_index(blob)
    ex = pipeline.executor_from_header(hdr)
    a, b = hdr["units"][:2]
    arrived, release, started = _held_decode(monkeypatch)
    claimed, claims = _claims(monkeypatch)
    with source:
        got, _ = _race(
            lambda: query_mod.fetch_decoded_units(source, ex, [a, b]),
            lambda: query_mod.fetch_decoded_units(source, ex, [b, a]),
            arrived, claimed, release)
    assert started[0] == 2
    assert claims == [(2, 0), (0, 2)]
    assert len(query_mod._inflight) == 0
    (pa, pb), hits0 = got[0]
    (qb, qa), hits1 = got[1]
    assert hits0 == hits1 == 0
    assert pa is qa and pb is qb          # the very same patches
    assert pa[0] == tuple(a["box"]) and pb[0] == tuple(b["box"])


def test_concurrent_miss_decodes_once_uncached(indexed, monkeypatch):
    """With the cache disabled the joiner still takes the leader's
    patches, from the in-flight record: every covering unit decodes
    once."""
    from repro.analysis import query as query_mod

    _, _, blob, _ = indexed
    k = 0
    query_mod.unit_cache.clear()
    ref = analysis.decode_for_track(blob, k)
    cache = query_mod.configure_unit_cache(0)
    try:
        arrived, release, started = _held_decode(monkeypatch)
        claimed, claims = _claims(monkeypatch)
        answers, _ = _race(lambda: analysis.decode_for_track(blob, k),
                           lambda: analysis.decode_for_track(blob, k),
                           arrived, claimed, release)
        assert cache.stats()["entries"] == 0
    finally:
        query_mod.configure_unit_cache(256)
    n = len(ref.entries)
    assert started[0] == n
    assert claims == [(n, 0), (0, n)]
    assert len(query_mod._inflight) == 0
    for res in answers:
        assert res.cache_hits == 0
        _same_track(res, ref)


def test_region_decode_and_track_query_share_decode(indexed, monkeypatch):
    """A region decode over a track's uncached covering unit and a
    query of that track at the same moment decode the unit once; both
    answers are right."""
    from repro.analysis import query as query_mod
    from repro.core import decompress_region

    _, _, blob, _ = indexed
    k = 0
    ref = _one_unit_missing(blob, k)
    box = tuple(ref.entries[0]["box"])
    ur, vr = decompress_tiled(blob)
    want = [x[box[0]:box[1], box[2]:box[3], box[4]:box[5]]
            for x in (ur, vr)]
    arrived, release, started = _held_decode(monkeypatch)
    claimed, claims = _claims(monkeypatch)
    (region, res), _ = _race(lambda: decompress_region(blob, box),
                             lambda: analysis.decode_for_track(blob, k),
                             arrived, claimed, release)
    assert started[0] == 1
    assert claims == [(1, 0), (0, 1)]
    assert len(query_mod._inflight) == 0
    assert np.array_equal(region[0], want[0])
    assert np.array_equal(region[1], want[1])
    assert res.cache_hits == len(ref.entries) - 1
    _same_track(res, ref)
