"""Pallas TPU kernel: fused dual-quantization + block-local 3D Lorenzo.

One pass over the fixed-point field produces the residual stream: load a
(1, TH, TW) tile of frames t and t-1 (+ eb-level and lossless maps),
quantize onto the base grid, apply the tile-local 2D difference and the
temporal difference -- 1 store per element, pure VPU integer work.

Because the Lorenzo context is *block-local* (16 x 16, DESIGN.md #3.2)
and the VMEM tile (default 128 x 128) is a multiple of it, the kernel
needs NO halo: every 16-tile is fully contained in one VMEM tile.  The
MXU is untouched; the kernel is bandwidth-bound by design (it exists to
fuse 5 HBM round-trips -- quantize, context, two diffs, temporal -- into
one).

Preconditions: |dfp| < 2^30 (fixedpoint.py guarantees), int32 domain.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LBLOCK = 16          # Lorenzo tile (matches core.predictors.DEFAULT_BLOCK)
TILE_H = 128         # VMEM tile (8x sublane, 128-lane aligned)
TILE_W = 128


def _round_div(d, g, k):
    q_half = (g << k) >> 1
    mag = ((jnp.abs(d) + q_half) >> k) // g
    return jnp.sign(d) * mag


def _dual_quant(dfp, k, lossless, g):
    kk = jnp.maximum(k, 0)
    x = _round_div(dfp, g, kk) << kk
    x0 = _round_div(dfp, g, jnp.zeros_like(kk))
    return jnp.where(lossless, x0, x)


def _d2_block(x):
    """Tile-local 2D first-order difference (within-VMEM, no halo).

    The neighbours come from rotations: the wrapped-around row/column
    lands only on block-boundary positions, which the masks zero.
    Constants stay int32 (with x64 on, bare Python ints would widen to
    int64, which Mosaic cannot lower).
    """
    H, W = x.shape
    ii = jax.lax.broadcasted_iota(jnp.int32, (H, W), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (H, W), 1)
    lb = jnp.int32(LBLOCK - 1)
    mi = ((ii & lb) != 0).astype(x.dtype)
    mj = ((jj & lb) != 0).astype(x.dtype)
    one = jnp.int32(1)
    x_up = pltpu.roll(x, one, 0)
    xi = x_up * mi
    xj = pltpu.roll(x, one, 1) * mj
    xij = pltpu.roll(x_up, one, 1) * (mi * mj)
    return x - xi - xj + xij


def _kernel(dfp_t_ref, dfp_p_ref, k_t_ref, k_p_ref, ll_t_ref, ll_p_ref,
            meta_ref, out_ref):
    t = pl.program_id(0)
    g = meta_ref[0]
    x_t = _dual_quant(dfp_t_ref[0], k_t_ref[0], ll_t_ref[0] != 0, g)
    x_p = _dual_quant(dfp_p_ref[0], k_p_ref[0], ll_p_ref[0] != 0, g)
    d2_t = _d2_block(x_t)
    d2_p = _d2_block(x_p)
    out_ref[0] = jnp.where(t == 0, d2_t, d2_t - d2_p)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dualquant_lorenzo_residual_pallas(dfp, k, lossless, xi_unit,
                                      interpret=True):
    """dfp (T, H, W) int32; k (T, H, W) int32; lossless bool.

    Returns residual (T, H, W) int32.  H, W must be multiples of the
    VMEM tile (the ops wrapper pads).
    """
    T, H, W = dfp.shape
    grid = (T, H // TILE_H, W // TILE_W)

    def idx_t(t, i, j):
        return (t, i, j)

    def idx_p(t, i, j):
        return (jnp.maximum(t - 1, 0), i, j)

    def idx_meta(t, i, j):
        return (jnp.int32(0),)   # int32: x64 would make a bare 0 int64

    tile = (1, TILE_H, TILE_W)
    in_specs = [
        pl.BlockSpec(tile, idx_t),                     # dfp_t
        pl.BlockSpec(tile, idx_p),                     # dfp_{t-1}
        pl.BlockSpec(tile, idx_t),                     # k_t
        pl.BlockSpec(tile, idx_p),                     # k_{t-1}
        pl.BlockSpec(tile, idx_t),                     # lossless_t
        pl.BlockSpec(tile, idx_p),                     # lossless_{t-1}
        pl.BlockSpec((1,), idx_meta,                   # meta: 2*xi_unit
                     memory_space=pltpu.SMEM),
    ]
    meta = (2 * jnp.asarray(xi_unit, dtype=jnp.int32)).reshape(1)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(tile, idx_t),
        out_shape=jax.ShapeDtypeStruct((T, H, W), jnp.int32),
        interpret=interpret,
    )(dfp, dfp, k.astype(jnp.int32), k.astype(jnp.int32),
      lossless.astype(jnp.int32), lossless.astype(jnp.int32), meta)
