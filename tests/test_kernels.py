"""Per-kernel validation: interpret-mode pallas vs pure-jnp oracle.

Integer kernels assert exact equality; the f32 SL kernel asserts
allclose at f32 tolerances.  Shapes sweep non-aligned sizes to exercise
the padding paths.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import repro.core  # noqa: F401 (x64 on; kernels must be dtype-explicit)
from repro.core import predictors, quantize
from repro.kernels.cptest import ops as cp_ops
from repro.kernels.cptest import ref as cp_ref
from repro.kernels.lorenzo import ops as lz_ops
from repro.kernels.semilagrange import ops as sl_ops
from repro.kernels.semilagrange import ref as sl_ref


# ------------------------------------------------------------- lorenzo

@pytest.mark.parametrize("shape", [(2, 128, 128), (3, 128, 256), (2, 130, 140)])
@pytest.mark.parametrize("tau", [100, 10_000, 2**24])
def test_lorenzo_kernel_matches_core(shape, tau):
    rng = np.random.default_rng(0)
    T, H, W = shape
    dfp = rng.integers(-(2**29), 2**29, shape).astype(np.int64)
    xi_unit, n_levels = quantize.ladder(tau)
    eb = jnp.asarray(
        rng.integers(0, tau + 1, shape).astype(np.int64))
    k, lossless = quantize.quantize_eb(eb, xi_unit, n_levels)

    # core pipeline result
    x = quantize.dual_quantize(jnp.asarray(dfp), k, lossless, xi_unit)
    want = predictors.lorenzo_encode(x, 16).astype(jnp.int32)

    got = lz_ops.dualquant_lorenzo_residual(
        jnp.asarray(dfp), k, lossless, xi_unit)
    assert (np.asarray(got) == np.asarray(want)).all()


def test_lorenzo_kernel_interpret_path_runs():
    """Aligned shape goes through pallas interpret, not the ref loop."""
    T, H, W = 2, 128, 128
    dfp = jnp.asarray(np.arange(T * H * W).reshape(T, H, W) % 1000,
                      dtype=jnp.int64)
    k = jnp.zeros((T, H, W), jnp.int32)
    ll = jnp.zeros((T, H, W), bool)
    out = lz_ops.dualquant_lorenzo_residual(dfp, k, ll, 8)
    assert out.shape == (T, H, W) and out.dtype == jnp.int32


# ------------------------------------------------------------- cptest

ints30 = st.integers(min_value=-(2**30) + 1, max_value=2**30 - 1)


@given(st.lists(st.tuples(ints30, ints30), min_size=3, max_size=3))
@settings(max_examples=200, deadline=None)
def test_cptest_limb_sign_exact(vals):
    """int32-limb det sign == int64 ground truth (random + boundary)."""
    from repro.kernels.cptest.kernel import _sign_det_exact

    (au, av), (bu, bv), _ = vals
    want = int(np.sign(np.int64(au) * np.int64(bv)
                       - np.int64(av) * np.int64(bu)))
    got = int(_sign_det_exact(jnp.int32(au), jnp.int32(av),
                              jnp.int32(bu), jnp.int32(bv)))
    assert got == want


@pytest.mark.parametrize("n", [1, 7, 128, 1000, 1025])
def test_cptest_kernel_matches_sos(n):
    rng = np.random.default_rng(n)
    u = rng.integers(-(2**29), 2**29, (n, 3)).astype(np.int64)
    v = rng.integers(-(2**29), 2**29, (n, 3)).astype(np.int64)
    # plant degeneracies: zeros and duplicated vertices
    u[:: max(n // 7, 1)] = 0
    if n > 3:
        v[3, 1] = v[3, 0]
        u[3, 1] = u[3, 0]
    idx = np.arange(3 * n).reshape(n, 3)
    want = np.asarray(cp_ref.face_crossed(
        jnp.asarray(u), jnp.asarray(v), jnp.asarray(idx)))
    got = np.asarray(cp_ops.face_crossed_batch(u.T, v.T, idx.T))
    assert (got == want).all()


def test_cptest_small_values_near_zero():
    """Dense sweep of tiny configurations around the origin."""
    vals = np.array(
        [[a, b, c] for a in (-1, 0, 1) for b in (-1, 0, 1)
         for c in (-1, 0, 1)], dtype=np.int64)
    n = len(vals)
    u = vals
    v = np.roll(vals, 1, axis=0)
    idx = np.arange(3 * n).reshape(n, 3)
    want = np.asarray(cp_ref.face_crossed(
        jnp.asarray(u), jnp.asarray(v), jnp.asarray(idx)))
    got = np.asarray(cp_ops.face_crossed_batch(u.T, v.T, idx.T))
    assert (got == want).all()


# ------------------------------------------------------------- semilagrange

@pytest.mark.parametrize("shape", [(16, 128), (32, 64), (8, 200)])
@pytest.mark.parametrize("speed", [0.3, 5.0])
def test_sl_kernel_matches_ref(shape, speed):
    rng = np.random.default_rng(1)
    H, W = shape
    u = (rng.normal(0, speed, (H, W))).astype(np.float32)
    v = (rng.normal(0, speed, (H, W))).astype(np.float32)
    pu_ref, pv_ref = sl_ref.sl_predict(jnp.asarray(u), jnp.asarray(v),
                                       1.0, 1.0)
    pu, pv = sl_ops.sl_predict(u, v, 1.0, 1.0)
    # f32 rounding differs between compilation contexts (fusion changes
    # op roundings) and the iterative backtrace amplifies it by the
    # velocity gradient; the substepping regime (speed > d_max) needs
    # the looser bound.  Exact end-to-end consistency is structural
    # (shared stepper executable, core/backend.py), not numerical.
    tol = 1e-5 if speed <= 2.0 else 1e-3
    np.testing.assert_allclose(np.asarray(pu), np.asarray(pu_ref),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(pv), np.asarray(pv_ref),
                               rtol=tol, atol=tol)


def test_sl_kernel_uniform_translation_exact():
    H, W = 16, 128
    u = np.full((H, W), 2.0, np.float32)   # exactly 2 px in j
    v = np.zeros((H, W), np.float32)
    pu, pv = sl_ops.sl_predict(u, v, 1.0, 1.0)
    np.testing.assert_allclose(np.asarray(pu), 2.0, atol=1e-6)


def test_sl_batched_kernel_matches_per_frame():
    """The (B, rows)-grid encoder batch kernel computes the same tiles
    as B per-frame launches (same math, frame-parallel grid)."""
    from repro.kernels.semilagrange import kernel as sl_kernel

    rng = np.random.default_rng(4)
    B, H, W = 3, 16, 64
    u = rng.normal(0, 1.5, (B, H, W)).astype(np.float32)
    v = rng.normal(0, 1.5, (B, H, W)).astype(np.float32)
    pu_b, pv_b = sl_kernel.sl_predict_batched_pallas(
        jnp.asarray(u), jnp.asarray(v), 1.0, 1.0, 2.0, 8)
    for b in range(B):
        pu, pv = sl_kernel.sl_predict_pallas(
            jnp.asarray(u[b]), jnp.asarray(v[b]), 1.0, 1.0, 2.0, 8)
        np.testing.assert_allclose(np.asarray(pu_b[b]), np.asarray(pu),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(pv_b[b]), np.asarray(pv),
                                   rtol=1e-5, atol=1e-5)
