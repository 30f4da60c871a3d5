"""Backend-dispatch parity (core/backend.py determinism contract).

The two integer hot ops (fused dualquant+Lorenzo residual, SoS face
predicate) must be bit-identical across pallas-interpret / xla / numpy;
full pipeline runs must produce identical residual streams, lossless
masks and blockmaps on synthetic fields; and the verify loop must be
backend-invariant (same round counts, FC_t = FC_s = 0 everywhere).
"""
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core as core
from repro.core import backend as backend_mod
from repro.core import compressor, encode, predictors, quantize
from repro.data import synthetic

BACKENDS = ("pallas", "xla", "numpy")


# ------------------------------------------------------------- op level

@pytest.mark.parametrize("shape", [(3, 64, 64), (2, 40, 72)])
@pytest.mark.parametrize("tau", [100, 2**20])
def test_lorenzo_residual_op_parity(shape, tau):
    rng = np.random.default_rng(0)
    dfp = jnp.asarray(rng.integers(-(2**29), 2**29, shape).astype(np.int64))
    xi_unit, n_levels = quantize.ladder(tau)
    eb = jnp.asarray(rng.integers(0, tau + 1, shape).astype(np.int64))
    k, lossless = quantize.quantize_eb(eb, xi_unit, n_levels)
    outs = {
        be: np.asarray(backend_mod.lorenzo_residual(
            dfp, k, lossless, xi_unit, 16, be))
        for be in BACKENDS
    }
    assert (outs["xla"] == outs["numpy"]).all()
    assert (outs["xla"] == outs["pallas"]).all()


@pytest.mark.parametrize("n", [5, 300])
def test_face_crossed_op_parity(n):
    rng = np.random.default_rng(n)
    u = rng.integers(-(2**29), 2**29, (n, 3)).astype(np.int64)
    v = rng.integers(-(2**29), 2**29, (n, 3)).astype(np.int64)
    u[:: max(n // 5, 1)] = 0   # degeneracies
    idx = np.arange(3 * n, dtype=np.int64).reshape(n, 3)
    outs = {   # slot-major (3, n) operands
        be: np.asarray(backend_mod.face_crossed(
            jnp.asarray(u.T), jnp.asarray(v.T), jnp.asarray(idx.T),
            backend=be, n_verts=3 * n))
        for be in BACKENDS
    }
    assert (outs["xla"] == outs["numpy"]).all()
    assert (outs["xla"] == outs["pallas"]).all()


def test_sl_stepper_shared_executable():
    """The same stepper instance is returned for identical params (the
    structural-consistency requirement), and its integer outputs agree
    with the xla reference on aligned frames."""
    s1 = backend_mod.sl_stepper("xla", 0.5, 0.5, 2.0, 8)
    s2 = backend_mod.sl_stepper("xla", 0.5, 0.5, 2.0, 8)
    assert s1 is s2
    rng = np.random.default_rng(2)
    xu = jnp.asarray(rng.integers(-500, 500, (32, 48)).astype(np.int64))
    xv = jnp.asarray(rng.integers(-500, 500, (32, 48)).astype(np.int64))
    pu, pv = s1(xu, xv, 0.01)
    want = predictors.sl_predict_frame(xu, xv, 0.01, 0.5, 0.5, 2.0, 8)
    assert (np.asarray(pu) == np.asarray(want[0])).all()
    assert (np.asarray(pv) == np.asarray(want[1])).all()


# -------------------------------------------------------- stream level

def _sections(u, v, cfg):
    blob, stats = core.compress(u, v, cfg)
    header, sections = encode.unpack(blob)
    return header, sections, stats


@pytest.mark.parametrize("predictor", ["lorenzo", "sl", "mop"])
def test_stream_parity_across_backends(predictor):
    # H = 32 keeps the pallas SL kernel row-tile aligned
    u, v = synthetic.vortex_street(T=6, H=32, W=48)
    meta = dict(dt=0.05, dx=2.0 / 47, dy=1.0 / 31)
    ref = None
    for be in BACKENDS:
        cfg = core.CompressionConfig(eb=1e-3, predictor=predictor,
                                     backend=be, **meta)
        header, sections, stats = _sections(u, v, cfg)
        if ref is None:
            ref = (sections, stats)
            continue
        for name in ref[0]:
            assert np.array_equal(sections[name], ref[0][name]), (
                f"{predictor}/{be}: section {name} differs")
        assert stats["verify_rounds"] == ref[1]["verify_rounds"]


def test_stream_parity_random_field():
    rng = np.random.default_rng(11)
    u = rng.normal(0, 1, (5, 32, 40)).astype(np.float32)
    v = rng.normal(0, 1, (5, 32, 40)).astype(np.float32)
    ref = None
    for be in BACKENDS:
        cfg = core.CompressionConfig(eb=1e-2, predictor="mop", backend=be)
        _, sections, _ = _sections(u, v, cfg)
        if ref is None:
            ref = sections
            continue
        for name in ref:
            assert np.array_equal(sections[name], ref[name]), (
                f"{be}: section {name} differs")


def test_fused_matches_legacy_streams():
    """The fused device-resident pipeline and the seed (legacy) pipeline
    must produce identical residual streams, lossless sets and blockmaps
    -- the restructure is a pure perf transformation.

    For the integer-only lorenzo predictor this equality is guaranteed
    and asserted byte-for-byte.  SL-containing streams additionally rely
    on the legacy in-scan predictor and the fused stepper executable
    rounding f64 identically, which holds on a fixed stack but is not
    contractual (DESIGN.md #4); there we assert the invariant parts
    (lossless set, round counts) plus full end-to-end guarantees.
    """
    from repro.core import trajectory

    u, v = synthetic.double_gyre(T=5, H=24, W=40)
    meta = dict(dt=0.1, dx=2.0 / 39, dy=1.0 / 23)
    for predictor in ("lorenzo", "sl", "mop"):
        cfg_f = core.CompressionConfig(eb=2e-3, predictor=predictor,
                                       backend="xla", fused=True, **meta)
        cfg_l = core.CompressionConfig(eb=2e-3, predictor=predictor,
                                       fused=False, **meta)
        _, sec_f, st_f = _sections(u, v, cfg_f)
        _, sec_l, st_l = _sections(u, v, cfg_l)
        if predictor == "lorenzo":
            for name in sec_f:
                assert np.array_equal(sec_f[name], sec_l[name]), (
                    f"{predictor}: section {name} differs fused vs legacy")
        else:
            assert np.array_equal(sec_f["lossless"], sec_l["lossless"])
            assert np.array_equal(sec_f["bm_shape"], sec_l["bm_shape"])
        assert st_f["verify_rounds"] == st_l["verify_rounds"]
        assert st_f["verify_bad_counts"] == st_l["verify_bad_counts"]
        for cfg in (cfg_f, cfg_l):
            blob, stats = core.compress(u, v, cfg)
            ur, vr = core.decompress(blob)
            assert np.abs(ur.astype(np.float64) - u).max() <= stats["eb_abs"]
            fc = trajectory.false_cases(u, v, ur, vr, stats["scale"])
            assert fc["FC_t"] == 0 and fc["FC_s"] == 0


# ------------------------------------------------- verify-loop behavior

def _large_magnitude_field():
    """f32 output rounding competes with the bound -> pointwise verify
    rounds actually fire (verify_bad_counts[0] > 0)."""
    rng = np.random.default_rng(3)
    T, H, W = 4, 16, 16
    base = 1.0e8
    u = (base + rng.normal(0, 100.0, (T, H, W))).astype(np.float32)
    v = (base + rng.normal(0, 100.0, (T, H, W))).astype(np.float32)
    return u, v


@pytest.mark.parametrize("be", BACKENDS)
def test_verify_convergence_backend_invariant(be):
    u, v = _large_magnitude_field()
    cfg = core.CompressionConfig(eb=6.0, mode="abs", predictor="mop",
                                 backend=be)
    blob, stats = core.compress(u, v, cfg)
    assert stats["verify_rounds"] >= 1          # the loop actually fired
    assert stats["verify_bad_counts"][0] > 0
    assert stats["verify_bad_counts"][-1] == 0  # ... and converged
    ur, vr = core.decompress(blob)
    assert np.abs(ur.astype(np.float64) - u).max() <= stats["eb_abs"]
    assert np.abs(vr.astype(np.float64) - v).max() <= stats["eb_abs"]
    from repro.core import trajectory
    fc = trajectory.false_cases(u, v, ur, vr, stats["scale"])
    assert fc["FC_t"] == 0 and fc["FC_s"] == 0


def test_verify_round_counts_equal_across_backends():
    u, v = _large_magnitude_field()
    counts = {}
    for be in BACKENDS:
        cfg = core.CompressionConfig(eb=6.0, mode="abs", predictor="mop",
                                     backend=be)
        _, stats = core.compress(u, v, cfg)
        counts[be] = (stats["verify_rounds"], tuple(stats["verify_bad_counts"]))
    assert counts["xla"] == counts["numpy"] == counts["pallas"], counts


def test_incremental_face_check_matches_full():
    """The incremental subset predicate evaluation must agree with a
    full re-evaluation at the touched faces (gather/id bookkeeping)."""
    u, v = synthetic.double_gyre(T=4, H=20, W=24)
    T, H, W = u.shape
    from repro.core import ebound, fixedpoint

    scale, ufp, vfp = fixedpoint.to_fixed(u, v)
    fns = compressor._fused_fns((T, H, W), 16, 1, "mop", "xla")
    full_slice, full_slab = ebound.all_face_predicates(
        jnp.asarray(ufp), jnp.asarray(vfp))
    rng = np.random.default_rng(0)
    delta = rng.random((T, H, W)) < 0.01
    verts, (ts, fs), (tb, fb) = compressor._touched_faces(delta, T, H, W)
    assert len(verts)
    crossed = np.asarray(fns.face_subset(
        jnp.asarray(ufp.reshape(-1)), jnp.asarray(vfp.reshape(-1)),
        jnp.asarray(verts.T)))
    want = np.concatenate([np.asarray(full_slice)[ts, fs],
                           np.asarray(full_slab)[tb, fb]])
    assert (crossed == want).all()


def test_decode_parallel_matches_stepper_reference():
    """Prefix-sum (parallel-in-time) decode == a per-frame reference
    loop through the SAME stepper executable, on a mixed Lorenzo/SL
    blockmap.  This pins the segment re-basing algebra exactly without
    depending on cross-executable float rounding."""
    from repro.core import predictors

    rng = np.random.default_rng(5)
    T, H, W = 8, 32, 32
    block = 16
    res_u = jnp.asarray(rng.integers(-3, 4, (T, H, W)).astype(np.int64))
    res_v = jnp.asarray(rng.integers(-3, 4, (T, H, W)).astype(np.int64))
    bm = np.zeros((T, 2, 2), dtype=bool)
    bm[3] = True          # one SL frame mid-run
    bm[6, 0, 1] = True    # one mixed frame
    scale, xi_unit = 1024.0, 4
    g2f = (2.0 * xi_unit) / scale
    stepper = backend_mod.sl_stepper("xla", 0.5, 0.5, 2.0, 8)
    xu_p, xv_p = compressor._decode_fields_parallel(
        res_u, res_v, bm, scale, xi_unit, block, stepper)

    # reference: strictly sequential frame loop, same stepper
    mask = np.repeat(np.repeat(bm, block, 1), block, 2)[:, :H, :W]
    xu = [predictors.c2_block(res_u[0], block)]
    xv = [predictors.c2_block(res_v[0], block)]
    for t in range(1, T):
        pu, pv = stepper(xu[-1], xv[-1], g2f)
        m = jnp.asarray(mask[t])
        xu.append(jnp.where(m, res_u[t] + pu,
                            xu[-1] + predictors.c2_block(res_u[t], block)))
        xv.append(jnp.where(m, res_v[t] + pv,
                            xv[-1] + predictors.c2_block(res_v[t], block)))
    assert (np.asarray(xu_p) == np.asarray(jnp.stack(xu))).all()
    assert (np.asarray(xv_p) == np.asarray(jnp.stack(xv))).all()


def test_decode_parallel_pure_lorenzo_matches_scan():
    """With no SL frames both decoders are integer-exact, so the cumsum
    path must equal the sequential scan bit-for-bit."""
    rng = np.random.default_rng(6)
    T, H, W = 6, 32, 32
    res_u = jnp.asarray(rng.integers(-5, 6, (T, H, W)).astype(np.int64))
    res_v = jnp.asarray(rng.integers(-5, 6, (T, H, W)).astype(np.int64))
    bm = np.zeros((T, 2, 2), dtype=bool)
    stepper = backend_mod.sl_stepper("xla", 0.5, 0.5, 2.0, 8)
    xu_p, xv_p = compressor._decode_fields_parallel(
        res_u, res_v, bm, 1024.0, 4, 16, stepper)
    xu_s, xv_s = compressor._decode_fields(
        res_u, res_v, jnp.asarray(bm), 1024.0, 4, 16, 0.5, 0.5, 2.0, 8)
    assert (np.asarray(xu_p) == np.asarray(xu_s)).all()
    assert (np.asarray(xv_p) == np.asarray(xv_s)).all()


def test_no_python_loop_in_faces_to_vertex_mask():
    """Acceptance guard: _faces_to_vertex_mask is a vectorized scatter
    (no `for` over frames) and still marks exactly the right vertices."""
    import inspect

    src = inspect.getsource(compressor._faces_to_vertex_mask)
    assert "for t in range" not in src
    T, H, W = 3, 6, 7
    from repro.core import grid
    Fs = len(grid.slab_faces(H, W)["slice0"])
    from repro.core import ebound
    Fb = len(ebound.slab_face_table(H, W))
    bad_slice = np.zeros((T, Fs), bool)
    bad_slab = np.zeros((T - 1, Fb), bool)
    bad_slice[1, 5] = True
    bad_slab[0, Fb - 1] = True
    mask = compressor._faces_to_vertex_mask(bad_slice, bad_slab, T, H, W)
    want = np.zeros(T * H * W, bool)
    want[grid.slab_faces(H, W)["slice0"][5].astype(np.int64) + H * W] = True
    want[ebound.slab_face_table(H, W)[Fb - 1].astype(np.int64)] = True
    assert (mask.reshape(-1) == want).all()
