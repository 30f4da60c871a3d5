"""Per-vertex error-bound derivation (paper Alg. 2 + Alg. 4).

For every triangular face of the space-time mesh we evaluate Alg. 2 once
per vertex rotation (the algorithm is asymmetric: it bounds the
perturbation of the vertex in slot 2 with the other two fixed), zero the
bound on faces already crossed by the zero set (so their vertices are
stored losslessly and the crossing geometry is exact), and scatter-min
into the per-vertex bound array.  Faces are processed slab-by-slab with
``lax.scan``; the face tables (grid.py) are static constants.

Alg. 2's sufficiency is for a single moving vertex; the compressor's
verify-and-correct loop (compressor.py) upgrades this to an unconditional
guarantee under simultaneous perturbation -- see DESIGN.md #3.5.

Tile locality: everything here depends on vertex VALUES plus the
relative ORDER of vertex ids (the SoS tie-break compares ids, it never
uses their magnitude).  A halo-extended sub-box of the grid preserves
the global id order under its own row-major local ids
(grid.box_vertex_ids), so ``derive_vertex_eb`` evaluated on a tile is
bit-identical to the global evaluation restricted to that tile; min-
reducing per-tile bounds across every tile that sees a vertex
reconstructs the global per-vertex bound exactly (core/tiling.py,
DESIGN.md #6).

All bounds are integers in fixed-point units.  Divisions run in float64
with a conservative down-rounding (relative margin 2^-40, then -1), which
keeps every returned bound strictly below the exact real-valued bound.
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from . import grid, sos

_MARGIN = 1.0 - 2.0 ** -40


def _alg2_eb(xp, u0, u1, u2, v0, v1, v2):
    """Alg. 2: max perturbation of (u2, v2) that cannot flip the face
    predicate, with (u0,v0), (u1,v1) held fixed.  int64 in, int64 out.

    Reference formulation; the production path is face_rotation_ebs /
    _rotation_ebs_from_dets, which shares the pairwise determinants
    across the three rotations (bit-equal, see
    tests/test_grid_ebound.py::test_rotation_ebs_match_per_rotation_reference).
    """
    m0 = u2 * v0 - u0 * v2
    m1 = u1 * v2 - u2 * v1
    m2 = u0 * v1 - u1 * v0
    m = m0 + m1 + m2

    f = jnp.float64 if xp is jnp else np.float64
    absm = xp.abs(m).astype(f)
    den0 = (xp.abs(u1 - u0) + xp.abs(v0 - v1)).astype(f)
    den1 = (xp.abs(u1) + xp.abs(v1)).astype(f)
    den2 = (xp.abs(u0) + xp.abs(v0)).astype(f)

    big = xp.asarray(2.0**62, dtype=f)
    eb = xp.where(den0 > 0, absm / xp.maximum(den0, 1.0), big)
    eb = xp.minimum(eb, xp.abs(m1).astype(f) / xp.maximum(den1, 1.0))
    eb = xp.minimum(eb, xp.abs(m0).astype(f) / xp.maximum(den2, 1.0))

    # same-sign relaxation: if all u (resp. v) share a strict sign the
    # face can never be crossed while each vertex keeps its own sign, so
    # |u2| - 1 is a safe integer bound for this vertex.
    su0, su1, su2 = xp.sign(u0), xp.sign(u1), xp.sign(u2)
    sv0, sv1, sv2 = xp.sign(v0), xp.sign(v1), xp.sign(v2)
    same_u = (su0 == su1) & (su1 == su2) & (su2 != 0)
    same_v = (sv0 == sv1) & (sv1 == sv2) & (sv2 != 0)
    eb = xp.where(same_u, xp.maximum(eb, (xp.abs(u2) - 1).astype(f)), eb)
    eb = xp.where(same_v, xp.maximum(eb, (xp.abs(v2) - 1).astype(f)), eb)

    eb_int = xp.floor(eb * _MARGIN).astype(xp.int64) - 1
    # paper early-outs: degenerate face (M == 0) or a fixed vertex exactly
    # at the origin -> lossless.
    zero = (m == 0) | (den1 == 0) | (den2 == 0)
    eb_int = xp.where(zero, xp.zeros_like(eb_int), eb_int)
    return xp.maximum(eb_int, 0)


def face_rotation_ebs(xp, fu, fv, crossed):
    """Alg. 2 for the three rotations of each face.

    fu, fv: (..., 3) int64 values;  crossed: (...,) bool.
    Returns (..., 3) int64 bounds aligned with the face's vertex slots.
    Every rotation permutes the SAME three pairwise determinants, so
    they are computed once and shared (bit-identical to the per-rotation
    evaluation: integer dets, identical float division operands).
    """
    us = tuple(fu[..., s] for s in range(3))
    vs = tuple(fv[..., s] for s in range(3))
    a_u, b_u, c_u = us
    a_v, b_v, c_v = vs
    d_ab = a_u * b_v - a_v * b_u
    d_bc = b_u * c_v - b_v * c_u
    d_ca = c_u * a_v - c_v * a_u
    return xp.stack(_rotation_ebs_from_dets(
        xp, us, vs, crossed, d_ab, d_bc, d_ca), axis=-1)


def _rotation_ebs_from_dets(xp, us, vs, crossed, d_ab, d_bc, d_ca):
    """Per-slot bounds (eb_a, eb_b, eb_c) from the slot components
    ``us = (a_u, b_u, c_u)``, ``vs = (a_v, b_v, c_v)``; zero on crossed
    faces."""
    a_u, b_u, c_u = us
    a_v, b_v, c_v = vs
    f = jnp.float64 if xp is jnp else np.float64
    m = d_ca + d_bc + d_ab
    absm = xp.abs(m).astype(f)
    big = xp.asarray(2.0**62, dtype=f)

    # same-sign relaxation is a property of the whole face
    su0, su1, su2 = xp.sign(a_u), xp.sign(b_u), xp.sign(c_u)
    sv0, sv1, sv2 = xp.sign(a_v), xp.sign(b_v), xp.sign(c_v)
    same_u = (su0 == su1) & (su1 == su2) & (su2 != 0)
    same_v = (sv0 == sv1) & (sv1 == sv2) & (sv2 != 0)

    def rot_eb(m0, m1, pu, pv, qu, qv, su, sv):
        """Perturb vertex s with (p, q) fixed; m0 = det(s,p), m1 = det(q,s)."""
        den0 = (xp.abs(qu - pu) + xp.abs(pv - qv)).astype(f)
        den1 = (xp.abs(qu) + xp.abs(qv)).astype(f)
        den2 = (xp.abs(pu) + xp.abs(pv)).astype(f)
        eb = xp.where(den0 > 0, absm / xp.maximum(den0, 1.0), big)
        eb = xp.minimum(eb, xp.abs(m1).astype(f) / xp.maximum(den1, 1.0))
        eb = xp.minimum(eb, xp.abs(m0).astype(f) / xp.maximum(den2, 1.0))
        eb = xp.where(same_u, xp.maximum(eb, (xp.abs(su) - 1).astype(f)), eb)
        eb = xp.where(same_v, xp.maximum(eb, (xp.abs(sv) - 1).astype(f)), eb)
        eb_int = xp.floor(eb * _MARGIN).astype(xp.int64) - 1
        zero = (m == 0) | (den1 == 0) | (den2 == 0) | crossed
        eb_int = xp.where(zero, xp.zeros_like(eb_int), eb_int)
        return xp.maximum(eb_int, 0)

    eb_c = rot_eb(d_ca, d_bc, a_u, a_v, b_u, b_v, c_u, c_v)
    eb_a = rot_eb(d_ab, d_ca, b_u, b_v, c_u, c_v, a_u, a_v)
    eb_b = rot_eb(d_bc, d_ab, c_u, c_v, a_u, a_v, b_u, b_v)
    return eb_a, eb_b, eb_c


@lru_cache(maxsize=32)
def _incidence_table(H: int, W: int, kind: str) -> np.ndarray:
    """Static vertex -> incident (face, slot) flat-index table.

    Entry [v, k] indexes into ``ebs.reshape(-1)`` (layout f*3 + slot);
    rows are padded with the out-of-range sentinel F*3.  Lets the eb
    reduction run as a vectorized gather-min instead of a scatter-min
    (XLA scatters serialize on CPU and dominate derivation time).
    """
    if kind == "slice":
        tab = grid.slab_faces(H, W)["slice0"]
        n_verts = H * W
    else:
        tab = slab_face_table(H, W)
        n_verts = 2 * H * W
    F = len(tab)
    vert = tab.reshape(-1).astype(np.int64)
    order = np.argsort(vert, kind="stable")
    sv = vert[order]
    si = order.astype(np.int64)          # flat index f*3 + slot
    counts = np.bincount(sv, minlength=n_verts)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    pos = np.arange(len(sv)) - starts[sv]
    out = np.full((n_verts, int(counts.max())), F * 3, dtype=np.int64)
    out[sv, pos] = si
    return out


@lru_cache(maxsize=32)
def _incidence_rows(H: int, W: int, kind: str) -> np.ndarray:
    """``_incidence_table`` transposed to (K, n_verts) rows of
    slot-major flat indices (slot * F + f; sentinel 3F), the layout the
    device reduction gathers with."""
    inc = _incidence_table(H, W, kind)
    F = (grid.slab_faces(H, W)["slice0"] if kind == "slice"
         else slab_face_table(H, W)).shape[0]
    rows = np.where(inc < 3 * F, (inc % 3) * F + inc // 3, 3 * F)
    return np.ascontiguousarray(rows.T)


def face_rows(tab: np.ndarray) -> np.ndarray:
    """(F, 3) face table -> (3, F) slot rows (device layout)."""
    return np.ascontiguousarray(np.asarray(tab).T)


def _faces_eb_update(u_flat, v_flat, idx_base, faces, tau, n_verts, inc):
    """Per-face ebs gather-min'd into a fresh (n_verts,) array.

    u_flat/v_flat: (n_verts,) int64 values of the vertex planes involved;
    idx_base: scalar global id of local vertex 0 (for SoS indices);
    faces: (3, F) int32 slot rows (face_rows); inc: (K, n_verts)
    incidence rows (_incidence_rows).  The three pairwise determinants
    are shared between the crossed test and all Alg. 2 rotations.

    Every array stays 1-D per slot: a minor dimension of 3 (or K)
    makes the TPU compiler's time grow with the face count -- minutes
    per 500 x 500 slab -- where 1-D gathers compile in about a second.
    """
    us = tuple(u_flat[faces[s]] for s in range(3))
    vs = tuple(v_flat[faces[s]] for s in range(3))
    ids = tuple(faces[s].astype(jnp.int64) + idx_base for s in range(3))
    a_u, b_u, c_u = us
    a_v, b_v, c_v = vs
    d_ab = a_u * b_v - a_v * b_u
    d_bc = b_u * c_v - b_v * c_u
    d_ca = c_u * a_v - c_v * a_u
    crossed = sos.face_crossed(
        jnp, a_u, a_v, ids[0], b_u, b_v, ids[1], c_u, c_v, ids[2],
        d_ab=d_ab, d_bc=d_bc, d_ca=d_ca,
    )
    ebs = _rotation_ebs_from_dets(jnp, us, vs, crossed, d_ab, d_bc, d_ca)
    big = jnp.asarray([2**62], dtype=jnp.int64)
    ebs_flat = jnp.concatenate([*ebs, big])
    out = ebs_flat[inc[0]]
    for k in range(1, inc.shape[0]):
        out = jnp.minimum(out, ebs_flat[inc[k]])
    return jnp.minimum(out, jnp.asarray(tau, jnp.int64)), crossed


def derive_vertex_eb(ufp, vfp, tau: int):
    """Per-vertex error bounds over the full space-time mesh.

    ufp, vfp: (T, H, W) int64.  Returns (eb (T, H, W) int64,
    slice_crossed (T, Fs) bool, slab_crossed (T-1, Fb) bool).
    """
    T, H, W = ufp.shape
    HW = H * W
    slice_tab = jnp.asarray(face_rows(grid.slab_faces(H, W)["slice0"]))
    slab_tab = jnp.asarray(face_rows(slab_face_table(H, W)))
    slice_inc = jnp.asarray(_incidence_rows(H, W, "slice"))
    slab_inc = jnp.asarray(_incidence_rows(H, W, "slab"))

    u2 = ufp.reshape(T, HW)
    v2 = vfp.reshape(T, HW)

    def slice_body(t, uv):
        u_t, v_t = uv
        eb, crossed = _faces_eb_update(
            u_t, v_t, t * HW, slice_tab, tau, HW, slice_inc)
        return eb, crossed

    def slice_scan(carry, x):
        t, u_t, v_t = x
        eb, crossed = slice_body(t, (u_t, v_t))
        return carry, (eb, crossed)

    _, (eb_slice, slice_crossed) = jax.lax.scan(
        slice_scan, 0, (jnp.arange(T, dtype=jnp.int64), u2, v2)
    )

    def slab_scan(carry, x):
        t, u_pair, v_pair = x
        eb, crossed = _faces_eb_update(
            u_pair.reshape(-1), v_pair.reshape(-1), t * HW, slab_tab, tau,
            2 * HW, slab_inc
        )
        return carry, (eb.reshape(2, HW), crossed)

    pairs_u = jnp.stack([u2[:-1], u2[1:]], axis=1)  # (T-1, 2, HW)
    pairs_v = jnp.stack([v2[:-1], v2[1:]], axis=1)
    _, (eb_slab2, slab_crossed) = jax.lax.scan(
        slab_scan, 0, (jnp.arange(T - 1, dtype=jnp.int64), pairs_u, pairs_v)
    )

    # slab [t, t+1] contributes its plane-0 bounds to time t and its
    # plane-1 bounds to time t+1 (shifted elementwise mins, no scatter)
    none = jnp.full((1, HW), 2**62, jnp.int64)
    eb = jnp.minimum(eb_slice, jnp.concatenate([eb_slab2[:, 0], none]))
    eb = jnp.minimum(eb, jnp.concatenate([none, eb_slab2[:, 1]]))
    return eb.reshape(T, H, W), slice_crossed, slab_crossed


# jitted entry point shared by the monolithic compressor and the tiled
# pipeline (one compiled executable per (shape, tau) class)
derive_vertex_eb_jit = jax.jit(derive_vertex_eb, static_argnums=2)


def all_face_predicates(ufp, vfp, be: str = "xla"):
    """SoS predicates for every face, via the dispatched predicate op
    (core/backend.py).  Returns (slice (T, Fs), slab (T-1, Fb))."""
    from . import backend as _backend

    T, H, W = ufp.shape
    HW = H * W
    n_verts = T * HW
    slice_rows = face_rows(grid.slab_faces(H, W)["slice0"])
    slab_rows = face_rows(slab_face_table(H, W))

    if be == "numpy":
        u2 = np.asarray(ufp).reshape(T, HW)
        v2 = np.asarray(vfp).reshape(T, HW)
        toff = (np.arange(T, dtype=np.int64) * HW)[None, :, None]
        st = slice_rows.astype(np.int64)
        idx = st[:, None, :] + toff                      # (3, T, Fs)
        slice_pred = _backend.face_crossed(
            np.stack([u2[:, r] for r in st]),
            np.stack([v2[:, r] for r in st]), idx, backend=be,
            n_verts=n_verts)
        bt = slab_rows.astype(np.int64)
        pair_u = np.concatenate([u2[:-1], u2[1:]], axis=1)
        pair_v = np.concatenate([v2[:-1], v2[1:]], axis=1)
        idx = bt[:, None, :] + toff[:, :-1]              # (3, T-1, Fb)
        slab_pred = _backend.face_crossed(
            np.stack([pair_u[:, r] for r in bt]),
            np.stack([pair_v[:, r] for r in bt]), idx, backend=be,
            n_verts=n_verts)
        return slice_pred, slab_pred

    slice_tab = jnp.asarray(slice_rows)
    slab_tab = jnp.asarray(slab_rows)
    u2 = ufp.reshape(T, HW)
    v2 = vfp.reshape(T, HW)

    def slice_scan(carry, x):
        t, u_t, v_t = x
        fu, fv = u_t[slice_tab], v_t[slice_tab]
        fidx = slice_tab.astype(jnp.int64) + t * HW
        return carry, _backend.face_crossed(fu, fv, fidx, backend=be,
                                            n_verts=n_verts)

    _, slice_pred = jax.lax.scan(
        slice_scan, 0, (jnp.arange(T, dtype=jnp.int64), u2, v2)
    )

    def slab_scan(carry, x):
        t, u_pair, v_pair = x
        uf = u_pair.reshape(-1)[slab_tab]
        vf = v_pair.reshape(-1)[slab_tab]
        fidx = slab_tab.astype(jnp.int64) + t * HW
        return carry, _backend.face_crossed(uf, vf, fidx, backend=be,
                                            n_verts=n_verts)

    pairs_u = jnp.stack([u2[:-1], u2[1:]], axis=1)
    pairs_v = jnp.stack([v2[:-1], v2[1:]], axis=1)
    _, slab_pred = jax.lax.scan(
        slab_scan, 0, (jnp.arange(T - 1, dtype=jnp.int64), pairs_u, pairs_v)
    )
    return slice_pred, slab_pred


@lru_cache(maxsize=32)
def slab_face_table(H, W):
    """(Fb, 3) int32 side+internal face table (local 2-plane ids).

    Cached: the concatenation is rebuilt for every verify round and every
    tile geometry otherwise (the table is static per (H, W))."""
    sf = grid.slab_faces(H, W)
    return np.concatenate([sf["side"], sf["internal"]], axis=0)
