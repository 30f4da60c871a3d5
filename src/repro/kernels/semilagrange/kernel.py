"""Pallas kernel: semi-Lagrangian backtrace + bilinear sampling.

The previous frame's (u, v) planes are held whole in VMEM (two
f32[H, W] buffers); the grid tiles the *output* rows, so the irregular
reads of the backtrace stay on-chip and each output element is written
once.  RK2 midpoint for small displacements, clamped Euler substeps
otherwise (paper Eqs. 4-9), f32 arithmetic.  The wrappers pad H to the
row tile; the kernel clamps every read to the true H.

Not on the compression path, and not compilable for the TPU: the
bilinear taps ``f[i0, j0]`` are per-element 2D gathers from VMEM, and
Mosaic lowers only take_along_axis-shaped gathers along one axis
(JAX 0.9.0 refuses this kernel for TPU v5e with "Unsupported gather").
The SL op therefore binds to the XLA stepper on every backend
(core/backend.py BINDINGS).  The kernel runs in interpret mode only,
where tests pin it against the f32 oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_H = 8


def _bilinear(f, fi, fj, H, W):
    i0 = jnp.clip(jnp.floor(fi), 0, H - 1)
    j0 = jnp.clip(jnp.floor(fj), 0, W - 1)
    a = fi - i0
    b = fj - j0
    i0 = i0.astype(jnp.int32)
    j0 = j0.astype(jnp.int32)
    i1 = jnp.minimum(i0 + 1, H - 1)
    j1 = jnp.minimum(j0 + 1, W - 1)
    f00 = f[i0, j0]
    f01 = f[i0, j1]
    f10 = f[i1, j0]
    f11 = f[i1, j1]
    return ((1 - a) * (1 - b) * f00 + (1 - a) * b * f01
            + a * (1 - b) * f10 + a * b * f11)


def _sl_tile(u, v, u0, v0, r, H, W, cfl_x, cfl_y, d_max, n_max):
    """Backtrace + sample one (TILE_H, W) output row tile of one frame;
    u0/v0 are the tile's own rows of u/v."""
    ii = (r * TILE_H
          + jax.lax.broadcasted_iota(jnp.int32, (TILE_H, W), 0)
          ).astype(jnp.float32)
    jj = jax.lax.broadcasted_iota(jnp.int32, (TILE_H, W), 1).astype(
        jnp.float32)
    d_inf = jnp.maximum(jnp.abs(u0) * cfl_x, jnp.abs(v0) * cfl_y)

    # RK2 midpoint
    i_h = jnp.clip(ii - 0.5 * v0 * cfl_y, 0.0, H - 1.0)
    j_h = jnp.clip(jj - 0.5 * u0 * cfl_x, 0.0, W - 1.0)
    u_h = _bilinear(u, i_h, j_h, H, W)
    v_h = _bilinear(v, i_h, j_h, H, W)
    i_rk = ii - v_h * cfl_y
    j_rk = jj - u_h * cfl_x

    # clamped Euler substeps
    n_sub = jnp.clip(jnp.ceil(d_inf / d_max), 1.0, float(n_max))
    pi, pj = ii, jj
    for s in range(n_max):
        us = _bilinear(u, pi, pj, H, W)
        vs = _bilinear(v, pi, pj, H, W)
        active = s < n_sub
        pi = jnp.where(active,
                       jnp.clip(pi - vs * cfl_y / n_sub, 0.0, H - 1.0), pi)
        pj = jnp.where(active,
                       jnp.clip(pj - us * cfl_x / n_sub, 0.0, W - 1.0), pj)

    use_rk = d_inf <= d_max
    i_s = jnp.clip(jnp.where(use_rk, i_rk, pi), 0.0, H - 1.0)
    j_s = jnp.clip(jnp.where(use_rk, j_rk, pj), 0.0, W - 1.0)
    return _bilinear(u, i_s, j_s, H, W), _bilinear(v, i_s, j_s, H, W)


def _pad_rows(x):
    """Zero-pad the row axis (-2) to a multiple of TILE_H."""
    ph = (-x.shape[-2]) % TILE_H
    if not ph:
        return x
    pad = [(0, 0)] * x.ndim
    pad[-2] = (0, ph)
    return jnp.pad(x, pad)


def _make_kernel(H, W, cfl_x, cfl_y, d_max, n_max):
    def kernel(u_ref, v_ref, pu_ref, pv_ref):
        r = pl.program_id(0)
        rows = pl.ds(pl.multiple_of(r * TILE_H, TILE_H), TILE_H)
        pu, pv = _sl_tile(u_ref[...], v_ref[...], u_ref[rows, :],
                          v_ref[rows, :], r, H, W,
                          cfl_x, cfl_y, d_max, n_max)
        pu_ref[...] = pu
        pv_ref[...] = pv

    return kernel


def _make_batched_kernel(H, W, cfl_x, cfl_y, d_max, n_max):
    def kernel(u_ref, v_ref, pu_ref, pv_ref):
        r = pl.program_id(1)
        rows = pl.ds(pl.multiple_of(r * TILE_H, TILE_H), TILE_H)
        pu, pv = _sl_tile(u_ref[0], v_ref[0], u_ref[0, rows, :],
                          v_ref[0, rows, :], r, H, W,
                          cfl_x, cfl_y, d_max, n_max)
        pu_ref[0] = pu
        pv_ref[0] = pv

    return kernel


@functools.partial(
    jax.jit, static_argnames=("cfl_x", "cfl_y", "d_max", "n_max", "interpret")
)
def sl_predict_pallas(u_prev, v_prev, cfl_x, cfl_y, d_max=2.0, n_max=8,
                      interpret=True):
    """u_prev, v_prev: f32 (H, W)."""
    H, W = u_prev.shape
    Hp = H + (-H) % TILE_H
    kern = _make_kernel(H, W, float(cfl_x), float(cfl_y), float(d_max),
                        int(n_max))
    full = pl.BlockSpec((Hp, W), lambda r: (0, 0))
    tile = pl.BlockSpec((TILE_H, W), lambda r: (r, 0))
    pu, pv = pl.pallas_call(
        kern,
        grid=(Hp // TILE_H,),
        in_specs=[full, full],
        out_specs=[tile, tile],
        out_shape=[jax.ShapeDtypeStruct((Hp, W), jnp.float32)] * 2,
        interpret=interpret,
    )(_pad_rows(u_prev.astype(jnp.float32)),
      _pad_rows(v_prev.astype(jnp.float32)))
    return pu[:H], pv[:H]


@functools.partial(
    jax.jit, static_argnames=("cfl_x", "cfl_y", "d_max", "n_max", "interpret")
)
def sl_predict_batched_pallas(u_prev, v_prev, cfl_x, cfl_y, d_max=2.0,
                              n_max=8, interpret=True):
    """Frame-batched variant: u_prev, v_prev f32 (B, H, W) stacks of
    previous frames.  One pallas_call over a (B, rows) grid; each
    program holds its frame's two planes whole in VMEM and writes one
    output row tile (same math as sl_predict_pallas); tests pin it
    against the per-frame kernel at f32 tolerance."""
    B, H, W = u_prev.shape
    Hp = H + (-H) % TILE_H
    kern = _make_batched_kernel(H, W, float(cfl_x), float(cfl_y),
                                float(d_max), int(n_max))
    full = pl.BlockSpec((1, Hp, W), lambda b, r: (b, 0, 0))
    tile = pl.BlockSpec((1, TILE_H, W), lambda b, r: (b, r, 0))
    pu, pv = pl.pallas_call(
        kern,
        grid=(B, Hp // TILE_H),
        in_specs=[full, full],
        out_specs=[tile, tile],
        out_shape=[jax.ShapeDtypeStruct((B, Hp, W), jnp.float32)] * 2,
        interpret=interpret,
    )(_pad_rows(u_prev.astype(jnp.float32)),
      _pad_rows(v_prev.astype(jnp.float32)))
    return pu[:, :H], pv[:, :H]
