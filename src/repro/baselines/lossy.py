"""Lossy baselines (paper Tables II-V comparison rows).

zfp-like   -- fixed-accuracy 4x4 orthonormal block transform (DCT-II)
              per frame, coefficient quantization, zstd backend.  A
              faithful-in-spirit stand-in for ZFP's decorrelating
              transform (labelled "-like" everywhere).
sz3-like   -- our dual-quantized block-local 3D-Lorenzo pipeline with a
              *uniform* error bound and NO critical-point constraints:
              exactly what a generic SZ-style compressor does.
cpsz-like  -- per-time-slice CP preservation only (slice faces constrain
              the error bound; cross-time slab faces are ignored), the
              paper's characterization of cpSZ(SoS): FC_t = 0 but
              trajectories may still break inside slabs.
"""
from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from ..core import ebound, encode, fixedpoint, predictors, quantize
from ..core.compressor import (
    CompressionConfig, _decode_fields_jit, _reconstruct, _faces_to_vertex_mask,
)
import jax

_DCT4 = None


def _dct4():
    global _DCT4
    if _DCT4 is None:
        k = np.arange(4)[:, None]
        n = np.arange(4)[None, :]
        m = np.cos(np.pi * (2 * n + 1) * k / 8.0) * np.sqrt(2.0 / 4.0)
        m[0] /= np.sqrt(2.0)
        _DCT4 = m
    return _DCT4


def zfp_like(u, v, eb=1e-2, mode="rel", level=12, **kw):
    t0 = time.perf_counter()
    u = np.asarray(u, np.float32)
    v = np.asarray(v, np.float32)
    rng = float(max(u.max(), v.max()) - min(u.min(), v.min()))
    eb_abs = eb * rng if mode == "rel" else eb
    T, H, W = u.shape
    Hp, Wp = -(-H // 4) * 4, -(-W // 4) * 4
    m = _dct4()

    def fwd(x):
        xp = np.zeros((T, Hp, Wp), np.float32)
        xp[:, :H, :W] = x
        xp[:, H:, :W] = xp[:, H - 1 : H, :W]
        xp[:, :, W:] = xp[:, :, W - 1 : W]
        b = xp.reshape(T, Hp // 4, 4, Wp // 4, 4).transpose(0, 1, 3, 2, 4)
        c = np.einsum("ij,tbkjl,ml->tbkim", m, b.astype(np.float64), m)
        q = np.round(c / eb_abs).astype(np.int32)
        return q

    def inv(q):
        c = q.astype(np.float64) * eb_abs
        b = np.einsum("ji,tbkjl,lm->tbkim", m, c, m)
        xp = b.transpose(0, 1, 3, 2, 4).reshape(T, Hp, Wp)
        return xp[:, :H, :W].astype(np.float32)

    qu, qv = fwd(u), fwd(v)
    payload = qu.astype(np.int16).tobytes() + qv.astype(np.int16).tobytes()
    over = np.concatenate([qu[np.abs(qu) > 32000], qv[np.abs(qv) > 32000]])
    blob = encode.codec_compress(payload, level)
    tc = time.perf_counter() - t0
    t0 = time.perf_counter()
    ur, vr = inv(np.clip(qu, -32000, 32000)), inv(np.clip(qv, -32000, 32000))
    td = time.perf_counter() - t0
    n = u.nbytes + v.nbytes
    return {
        "name": "zfp-like", "lossless": False, "eb_abs": eb_abs,
        "orig_bytes": n, "comp_bytes": len(blob) + over.nbytes,
        "ratio": n / (len(blob) + over.nbytes),
        "t_compress": tc, "t_decompress": td,
        "u_rec": ur, "v_rec": vr,
    }


def _pack_like_ours(res_u, res_v, lossless, u_ll, v_ll, bm_shape, level):
    sym_u, esc_u = encode.to_symbols(np.asarray(res_u))
    sym_v, esc_v = encode.to_symbols(np.asarray(res_v))
    sections = {
        "sym_u": sym_u, "sym_v": sym_v, "esc_u": esc_u, "esc_v": esc_v,
        "lossless": np.packbits(lossless),
        "u_ll": u_ll, "v_ll": v_ll,
        "blockmap": np.packbits(np.zeros(bm_shape, bool)),
        "bm_shape": np.asarray(bm_shape, np.int32),
    }
    return encode.pack({"v": 1}, sections, level)


def sz3_like(u, v, eb=1e-2, mode="rel", level=12, block=16, **kw):
    """Uniform-eb Lorenzo pipeline, no CP constraints, no verify."""
    t0 = time.perf_counter()
    u = np.asarray(u, np.float32)
    v = np.asarray(v, np.float32)
    T, H, W = u.shape
    rng = float(max(u.max(), v.max()) - min(u.min(), v.min()))
    eb_abs = eb * rng if mode == "rel" else eb
    scale, ufp, vfp = fixedpoint.to_fixed(u, v)
    tau = max(int(np.floor(eb_abs * scale)), 1)
    xi_unit = max(tau, 1)  # SZ semantics: quantum 2*eb, max err <= eb
    k = jnp.zeros((T, H, W), jnp.int32)
    ll = jnp.zeros((T, H, W), bool)
    xu = quantize.dual_quantize(jnp.asarray(ufp), k, ll, xi_unit)
    xv = quantize.dual_quantize(jnp.asarray(vfp), k, ll, xi_unit)
    res_u = predictors.lorenzo_encode(xu, block)
    res_v = predictors.lorenzo_encode(xv, block)
    bm_shape = (T, -(-H // block), -(-W // block))
    blob = _pack_like_ours(res_u, res_v, np.zeros((T, H, W), bool),
                           np.zeros(0, np.float32), np.zeros(0, np.float32),
                           bm_shape, level)
    tc = time.perf_counter() - t0

    t0 = time.perf_counter()
    xu_d, xv_d = _decode_fields_jit(
        res_u, res_v, jnp.zeros(bm_shape, bool), scale, xi_unit, block,
        1.0, 1.0, 2.0, 32)
    ur, vr = _reconstruct(xu_d, xv_d, scale, xi_unit, ll,
                          jnp.asarray(u), jnp.asarray(v))
    td = time.perf_counter() - t0
    n = u.nbytes + v.nbytes
    return {
        "name": "sz3-like", "lossless": False, "eb_abs": eb_abs,
        "orig_bytes": n, "comp_bytes": len(blob), "ratio": n / len(blob),
        "t_compress": tc, "t_decompress": td,
        "u_rec": np.asarray(ur), "v_rec": np.asarray(vr),
    }


def cpsz_like(u, v, eb=1e-2, mode="rel", level=12, block=16, **kw):
    """Per-slice CP preservation only (no slab faces, no slab verify)."""
    t0 = time.perf_counter()
    u = np.asarray(u, np.float32)
    v = np.asarray(v, np.float32)
    T, H, W = u.shape
    rng = float(max(u.max(), v.max()) - min(u.min(), v.min()))
    eb_abs = eb * rng if mode == "rel" else eb
    scale, ufp, vfp = fixedpoint.to_fixed(u, v)
    tau = max(int(np.floor(eb_abs * scale)), 1)
    xi_unit, n_levels = quantize.ladder(tau)

    ufp_j, vfp_j = jnp.asarray(ufp), jnp.asarray(vfp)
    # slice faces only: run the full derivation, then lift the slab
    # constraints by re-deriving with slab contributions ignored.
    eb_slice = _slice_only_eb(ufp_j, vfp_j, tau)

    lossless_extra = jnp.zeros((T, H, W), bool)
    for _ in range(8):
        k, lossless = quantize.quantize_eb(eb_slice, xi_unit, n_levels)
        lossless = jnp.logical_or(lossless, lossless_extra)
        xu = quantize.dual_quantize(ufp_j, k, lossless, xi_unit)
        xv = quantize.dual_quantize(vfp_j, k, lossless, xi_unit)
        res_u = predictors.lorenzo_encode(xu, block)
        res_v = predictors.lorenzo_encode(xv, block)
        bm_shape = (T, -(-H // block), -(-W // block))
        xu_d, xv_d = _decode_fields_jit(
            res_u, res_v, jnp.zeros(bm_shape, bool), scale, xi_unit, block,
            1.0, 1.0, 2.0, 32)
        ur, vr = _reconstruct(xu_d, xv_d, scale, xi_unit, lossless,
                              jnp.asarray(u), jnp.asarray(v))
        # verify SLICE predicates only (the cpSZ guarantee)
        ur_fp, vr_fp = fixedpoint.refix(np.asarray(ur), np.asarray(vr), scale)
        s0, _ = ebound.all_face_predicates(ufp_j, vfp_j)
        s1, _ = ebound.all_face_predicates(jnp.asarray(ur_fp), jnp.asarray(vr_fp))
        bad = np.asarray(s0 ^ s1)
        err = np.maximum(np.abs(np.asarray(ur, np.float64) - u),
                         np.abs(np.asarray(vr, np.float64) - v))
        bad_pt = err > eb_abs
        if bad.sum() == 0 and bad_pt.sum() == 0:
            break
        extra = np.asarray(lossless_extra) | bad_pt
        extra |= _faces_to_vertex_mask(
            bad, np.zeros((T - 1, 1), bool), T, H, W)
        lossless_extra = jnp.asarray(extra)

    lossless_np = np.asarray(lossless)
    blob = _pack_like_ours(res_u, res_v, lossless_np,
                           u[lossless_np], v[lossless_np], bm_shape, level)
    tc = time.perf_counter() - t0
    n = u.nbytes + v.nbytes
    return {
        "name": "cpsz-like", "lossless": False, "eb_abs": eb_abs,
        "orig_bytes": n, "comp_bytes": len(blob), "ratio": n / len(blob),
        "t_compress": tc, "t_decompress": 0.0,
        "u_rec": np.asarray(ur), "v_rec": np.asarray(vr),
    }


def _slice_only_eb(ufp, vfp, tau):
    """Per-vertex bound from time-slice faces only (cpSZ semantics)."""
    from ..core import grid, sos
    from ..core.ebound import _faces_eb_update, _incidence_rows, face_rows

    T, H, W = ufp.shape
    HW = H * W
    slice_tab = jnp.asarray(face_rows(grid.slab_faces(H, W)["slice0"]))
    slice_inc = jnp.asarray(_incidence_rows(H, W, "slice"))
    u2 = ufp.reshape(T, HW)
    v2 = vfp.reshape(T, HW)

    def body(carry, x):
        t, u_t, v_t = x
        eb, _ = _faces_eb_update(u_t, v_t, t * HW, slice_tab, tau, HW,
                                 slice_inc)
        return carry, eb

    _, ebs = jax.lax.scan(
        body, 0, (jnp.arange(T, dtype=jnp.int64), u2, v2))
    return ebs.reshape(T, H, W)
