"""Pallas TPU kernel: batched 256-bin symbol histogram.

The device entropy stage (core/entropy.py) needs one histogram per
symbol row of a (B, n) uint8 stack -- the only data the host ever sees
before bit-packing (the canonical code tables are built from it).  TPUs
have no scatter-add fast path, so the kernel takes the compare-and-sum
form instead: each grid step loads a (1, CHUNK) slice of one row,
compares it against a (NBINS, CHUNK) sublane iota and sums along the
lanes -- pure VPU integer work, exact by construction.  The n axis is
the inner grid dimension, so partial counts accumulate into the same
output block across sequential grid steps.

Layout: the TPU tiling wants the last two block dims divisible by
(8, 128) or equal to the array's.  A row therefore travels as a
(1, n) plane of a (B, 1, n) array (block (1, 1, CHUNK)), and its counts
come back as a (NBINS, 1) column of a (B, NBINS, 1) array -- the
lane reduction yields a column, so no relayout is needed.  Padding B
to 8 rows per step would compute the same compares with up to 8x
wasted rows for the small B of a unit batch.

Symbols arrive as int32 (the ops wrapper widens uint8) to keep VMEM
tiling on the friendly (8, 128) int32 granularity rather than the
(32, 128) int8 one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NBINS = 256
CHUNK = 512          # n-axis slice per grid step (multiple of 128 lanes)


def _kernel(sym_ref, out_ref):
    j = pl.program_id(1)
    s = sym_ref[0]                                   # (1, CHUNK) int32
    bins = jax.lax.broadcasted_iota(jnp.int32, (NBINS, s.shape[1]), 0)
    counts = jnp.sum((s == bins).astype(jnp.int32), axis=1, keepdims=True,
                     dtype=jnp.int32)                # (NBINS, 1)

    @pl.when(j == 0)
    def _init():
        out_ref[0] = counts

    @pl.when(j != 0)
    def _acc():
        out_ref[0] = out_ref[0] + counts


# index maps: int32 literals, since with x64 on a bare 0 index would be
# int64, which Mosaic cannot return from an index map
def _row_chunk(i, j):
    return (i, jnp.int32(0), j)


def _row_block(i, j):
    zero = jnp.int32(0)
    return (i, zero, zero)


@functools.partial(jax.jit, static_argnames=("interpret",))
def symbol_histogram_pallas(sym, interpret=True):
    """sym (B, n) int32 with values in [0, 255]; n a multiple of CHUNK
    (the ops wrapper zero-pads and corrects bin 0).  Returns (B, 256)
    int32 counts."""
    B, n = sym.shape
    grid = (B, n // CHUNK)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, 1, CHUNK), _row_chunk)],
        out_specs=pl.BlockSpec((1, NBINS, 1), _row_block),
        out_shape=jax.ShapeDtypeStruct((B, NBINS, 1), jnp.int32),
        interpret=interpret,
    )(sym.reshape(B, 1, n))
    return out.reshape(B, NBINS)
