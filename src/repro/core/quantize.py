"""Error-bound quantization + dual-quantization onto a base integer grid.

TPU adaptation of the paper's (eb-quantize, predict, quantize) stages --
see DESIGN.md #3.1.  The per-vertex bound xi_v (ebound.py) is rounded
*down* onto a power-of-two ladder

    xi_k = xi_unit * 2^k,   k in [0, n_levels),  xi_unit = max(1, tau >> (K-1))

and each fixed-point value is rounded half-away-from-zero to the nearest
multiple of q_k = 2 * xi_k, expressed on the base grid g = 2 * xi_unit:

    X_v = round(d_v / q_k) << k          (integer, multiple of 2^k)
    recon_v = X_v * g,   |recon_v - d_v| <= xi_k <= xi_v

Crucially the decoder never needs k_v: X is self-contained.  The paper's
per-vertex eb code stream Q_xi disappears from the format entirely (a
strict rate improvement), and reconstruction is a single parallel
multiply.  Vertices with xi_v < xi_unit are stored losslessly (mask +
raw values); their X entry carries the k=0 rounding of the original so
that predictors see a well-defined context on both sides.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

# Ladder depth. The paper uses a multi-level eb quantization (Q_xi); with
# the dual-quantized PARALLEL coder the multi-level ladder expresses
# residuals on the finest grid, inflating symbols at coarse-eb vertices
# (they escape entropy coding entirely). A single level + lossless
# fallback measured strictly better at every tested (dataset, eb):
# e.g. advected turbulence 6.97x -> 41.78x, SCF 7.6x -> 12.8x
# (EXPERIMENTS.md #Perf, iteration C1). The ladder stays available via
# CompressionConfig(n_levels=...).
DEFAULT_LEVELS = 1


def ladder(tau: int, n_levels: int = DEFAULT_LEVELS):
    """Returns (xi_unit, n_usable_levels).  xi_unit >= 1."""
    tau = int(tau)
    if tau < 1:
        return 1, 0
    xi_unit = max(1, tau >> (n_levels - 1))
    # largest k with xi_unit * 2^k <= tau
    kmax = int(np.floor(np.log2(tau / xi_unit))) if tau >= xi_unit else -1
    return xi_unit, kmax + 1


def quantize_eb(eb, xi_unit, n_levels: int):
    """Map per-vertex integer bounds onto the ladder.

    Returns (k (int32, -1 where lossless), lossless mask).  xi_unit may
    be a python int or a traced scalar (the fused pipeline passes it as
    a jit argument so eb sweeps reuse one compiled round).
    """
    eb = jnp.asarray(eb)
    xi = jnp.asarray(xi_unit, jnp.int64)
    lossless = eb < xi
    ratio = (jnp.maximum(eb, xi).astype(jnp.float64)
             / xi.astype(jnp.float64))
    k = jnp.floor(jnp.log2(ratio)).astype(jnp.int32)
    k = jnp.clip(k, 0, max(n_levels - 1, 0))
    k = jnp.where(lossless, -1, k)
    return k, lossless


def round_half_away_div(d, q):
    """sign(d) * ((|d| + q//2) // q) for int64 d, even int64 q > 0.

    The quotient is taken in float64 and corrected one step either way
    by the exact int64 remainder, which is exact while |d| < 2^51
    (fixed-point values stay below 2^30): for |d| < q/2 the float
    quotient is below 1 and the result 0, otherwise every operand is
    below 2^52.  TPUs have no 64-bit integer divide, and XLA's emulated
    one costs ~30 s of compile time per use at frame sizes; this form
    compiles in about a second.
    """
    n = jnp.abs(d) + (q >> 1)
    mag = jnp.floor(n.astype(jnp.float64) / q.astype(jnp.float64)
                    ).astype(jnp.int64)
    r = n - mag * q
    mag = mag - (r < 0) + (r >= q)
    return jnp.sign(d) * mag


def dual_quantize(dfp, k, lossless, xi_unit):
    """Round fixed-point values to the base grid with per-vertex granularity.

    dfp: int64; k: int32 (>=0 where coded); lossless: bool.
    Returns X int64 with recon = X * g, g = 2 * xi_unit.
    """
    g = 2 * jnp.asarray(xi_unit, jnp.int64)
    kk = jnp.maximum(k, 0).astype(jnp.int64)
    q = g << kk
    x = round_half_away_div(dfp, q) << kk
    x0 = round_half_away_div(dfp, g)  # k = 0 rounding for lossless context
    return jnp.where(lossless, x0, x)


def recon_fixed(x, xi_unit):
    return x * (2 * jnp.asarray(xi_unit, jnp.int64))
