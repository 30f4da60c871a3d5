"""Pallas TPU kernel: exact SoS face-crossing predicate in int32 limbs.

TPU has no int64 vector unit, but the SoS determinant test needs the
EXACT sign of au*bv - av*bu for |values| < 2^30 -- a 61-bit quantity.
We decompose each operand into three 10-bit limbs (a = a2*2^20 + a1*2^10
+ a0); every partial-product limb is then a sum of <= 3 terms of < 2^20,
so the 5-limb product difference stays below 2^23 in int32.  A single
carry-normalization pass canonicalizes limbs 0..3 into [0, 2^10) leaving
the sign in limb 4 + a nonneg remainder:

    sign = +1  if L4 > 0 or (L4 == 0 and rest > 0)
            0  if L4 == 0 and rest == 0
           -1  otherwise

The SoS tie-break cascade (core/sos.py) runs on top of the exact signs.
This is the TPU-native replacement for the paper's int64 CPU predicate
-- the hardware-adaptation note in DESIGN.md #3.4/#7.

Layout: faces arrive slot-major, as three (R, C) int32 planes per
operand (a minor dimension of 3 would leave every vector tile 3/128
full); the grid walks (8, 128) VMEM tiles; pure VPU integer MACs, no
MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_R = 8
TILE_C = 128
_B = 10                     # limb bits
_MASK = (1 << _B) - 1


def _limbs(x):
    """int32 -> three 10-bit limbs (floor semantics for negatives)."""
    a0 = x & _MASK
    x1 = x >> _B
    a1 = x1 & _MASK
    a2 = x1 >> _B
    return a2, a1, a0


def _sign_det_exact(au, av, bu, bv):
    """Exact sign of au*bv - av*bu via limb arithmetic (all int32)."""
    p2, p1, p0 = _limbs(au)
    q2, q1, q0 = _limbs(bv)
    r2, r1, r0 = _limbs(av)
    s2, s1, s0 = _limbs(bu)
    # product limbs of au*bv minus av*bu, positions 0..4 (base 2^10)
    l0 = p0 * q0 - r0 * s0
    l1 = p0 * q1 + p1 * q0 - r0 * s1 - r1 * s0
    l2 = p0 * q2 + p1 * q1 + p2 * q0 - r0 * s2 - r1 * s1 - r2 * s0
    l3 = p1 * q2 + p2 * q1 - r1 * s2 - r2 * s1
    l4 = p2 * q2 - r2 * s2
    # carry-normalize limbs 0..3 into [0, 2^10)
    c = l0 >> _B
    l0 = l0 & _MASK
    l1 = l1 + c
    c = l1 >> _B
    l1 = l1 & _MASK
    l2 = l2 + c
    c = l2 >> _B
    l2 = l2 & _MASK
    l3 = l3 + c
    c = l3 >> _B
    l3 = l3 & _MASK
    l4 = l4 + c
    rest = ((l3 << _B | l2) != 0) | ((l1 << _B | l0) != 0)
    pos = (l4 > 0) | ((l4 == 0) & rest)
    neg = l4 < 0
    # int32 constants: with x64 on, bare Python ints would widen the
    # select to int64, which Mosaic cannot lower
    one = jnp.ones_like(l4)
    return jnp.where(pos, one, jnp.where(neg, -one, jnp.zeros_like(l4)))


def _sos_cascade(au, av, bu, bv):
    s = _sign_det_exact(au, av, bu, bv)
    s = jnp.where(s != 0, s, jnp.sign(bv))
    s = jnp.where(s != 0, s, jnp.sign(-bu))
    s = jnp.where(s != 0, s, jnp.sign(-av))
    s = jnp.where(s != 0, s, jnp.sign(au))
    return jnp.where(s != 0, s, -jnp.ones_like(s))


def _sign_det_sos(au, av, ma, bu, bv, mb):
    fwd = _sos_cascade(au, av, bu, bv)
    rev = _sos_cascade(bu, bv, au, av)
    return jnp.where(ma < mb, fwd, -rev)


def _kernel(u0, v0, u1, v1, u2, v2, m0, m1, m2, out):
    a_u, a_v, i_a = u0[...], v0[...], m0[...]
    b_u, b_v, i_b = u1[...], v1[...], m1[...]
    c_u, c_v, i_c = u2[...], v2[...], m2[...]
    s1 = _sign_det_sos(a_u, a_v, i_a, b_u, b_v, i_b)
    s2 = _sign_det_sos(b_u, b_v, i_b, c_u, c_v, i_c)
    s3 = _sign_det_sos(c_u, c_v, i_c, a_u, a_v, i_a)
    out[...] = ((s1 == s2) & (s2 == s3)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def face_crossed_pallas(u, v, idx, interpret=True):
    """u, v, idx: (3, R, C) int32 slot planes (R % 8 == 0, C % 128 == 0).

    Returns (R, C) int32 (1 = crossed).
    """
    _, R, C = u.shape
    grid = (R // TILE_R, C // TILE_C)
    tile = (TILE_R, TILE_C)

    args = [u[0], v[0], u[1], v[1], u[2], v[2], idx[0], idx[1], idx[2]]
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(tile, lambda i, j: (i, j)) for _ in range(9)],
        out_specs=pl.BlockSpec(tile, lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((R, C), jnp.int32),
        interpret=interpret,
    )(*args)
