"""Wrapper: pad to the VMEM tile and run the kernel (compiled on TPU,
interpret mode elsewhere)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import kernel


def _pad_to(x, mh, mw, value=0):
    T, H, W = x.shape
    ph = (-H) % mh
    pw = (-W) % mw
    if ph or pw:
        x = jnp.pad(x, ((0, 0), (0, ph), (0, pw)), constant_values=value)
    return x


def dualquant_lorenzo_residual(dfp, k, lossless, xi_unit):
    """Fused dual-quantization + block-local (LBLOCK) Lorenzo residual.

    dfp int32/int64 (T, H, W); k int32 (-1 where lossless); lossless
    bool.  Returns int32 residual (T, H, W); core.quantize +
    core.predictors are the oracle.
    """
    T, H, W = dfp.shape
    dfp32 = _pad_to(dfp.astype(jnp.int32), kernel.TILE_H, kernel.TILE_W)
    k32 = _pad_to(k.astype(jnp.int32), kernel.TILE_H, kernel.TILE_W)
    ll = _pad_to(lossless, kernel.TILE_H, kernel.TILE_W)
    out = kernel.dualquant_lorenzo_residual_pallas(
        dfp32, k32, ll, xi_unit, interpret=jax.default_backend() != "tpu"
    )
    return out[:, :H, :W]
