"""Plain reference of the compressor's stated guarantees.

Written from the paper's definitions (arXiv 2510.25143, Sec. III-B,
Eq. 1-2, Alg. 3-4) and the container's documented face numbering,
with numpy only: it imports nothing of the program under test.

* Fixed point (Alg. 3, lines 1-2): ``x -> round(x * S)`` in int64 with
  the power-of-two scale ``S = 2**(floor(bits - log2(max|x|)) - 1)``,
  ``bits = 30``, taken from the stream's stated value range.
* Mesh: every grid cell (i, j)-(i+1, j+1) splits into the triangles
  ``tri1 = (i,j),(i+1,j),(i+1,j+1)`` and ``tri2 = (i,j),(i,j+1),
  (i+1,j+1)``; each prism over a slab [t, t+1] splits into three
  tetrahedra by the global vertex order (Kuhn/Freudenthal).  Vertex
  ids are ``t*H*W + i*W + j``; within every face family below the
  three vertices are listed in ascending id, so the id order that
  simulation of simplicity (SoS) needs is structural.
* Predicate (Eq. 1 with SoS): a face (a, b, c) is crossed by the zero
  set iff ``S(a,b) == S(b,c) == -S(a,c)``, where ``S(x, y)`` for
  id(x) < id(y) is the sign of ``det(x, y) = xu*yv - xv*yu`` and, when
  that is 0, the first nonzero of ``yv, -yu, -xv, xu``, else -1 (the
  expansion of the perturbation ``eps**(4**m), eps**(2*4**m)``).
* Tracks: crossed faces are nodes, the two crossed faces of a
  tetrahedron form a segment (Lemma 1: 0 or 2 per tetrahedron),
  connected components are tracks, numbered by ascending smallest
  global face id, with nodes at the barycentric zero of each face
  (Eq. 2) and typed by the eigenvalues of the interpolated Jacobian.

Everything works on one block of frames at a time with array slices,
not gathers, so a 25 x 500 x 500 block takes seconds.
"""
from __future__ import annotations

import math

import numpy as np
from scipy.sparse import coo_matrix
from scipy.sparse.csgraph import connected_components

FIXED_BITS = 30
CP_TYPES = ("saddle", "source", "sink", "spiral_in", "spiral_out",
            "center", "degenerate")


class Lemma1Error(RuntimeError):
    """A tetrahedron with a crossed-face count outside {0, 2}."""


def fixed_scale(lo: float, hi: float, bits: int = FIXED_BITS) -> float:
    max_abs = max(abs(float(lo)), abs(float(hi)), 1e-300)
    return float(2.0 ** (math.floor(bits - math.log2(max_abs)) - 1))


def to_fixed(x: np.ndarray, scale: float) -> np.ndarray:
    return np.round(np.asarray(x, np.float64) * scale).astype(np.int64)


# ----------------------------------------------------------------------
# SoS predicates by face family
# ----------------------------------------------------------------------

def _sos(xu, xv, yu, yv):
    """SoS sign of det(x, y) for id(x) < id(y), as int8."""
    s = np.sign(xu * yv - xv * yu).astype(np.int8)
    z = s == 0
    if z.any():
        t = np.sign(yv[z]).astype(np.int8)
        for nxt in (-yu[z], -xv[z], xu[z]):
            t = np.where(t != 0, t, np.sign(nxt).astype(np.int8))
        s[z] = np.where(t != 0, t, np.int8(-1))
    return s


def _crossed(a, b, c):
    """Face predicate for vertex value pairs a, b, c in ascending id."""
    (au, av), (bu, bv), (cu, cv) = a, b, c
    s_ab = _sos(au, av, bu, bv)
    s_bc = _sos(bu, bv, cu, cv)
    s_ac = _sos(au, av, cu, cv)
    return (s_ab == s_bc) & (s_bc == -s_ac)


def _corners(P):
    """Cell-corner views of one frame pair component (H, W)."""
    return P[:-1, :-1], P[:-1, 1:], P[1:, :-1], P[1:, 1:]


def slice_predicates(U, V):
    """Crossed flags of the two triangles of every cell of one frame.
    Returns (tri1, tri2), each (H-1, W-1) bool."""
    u00, u01, u10, u11 = _corners(U)
    v00, v01, v10, v11 = _corners(V)
    tri1 = _crossed((u00, v00), (u10, v10), (u11, v11))
    tri2 = _crossed((u00, v00), (u01, v01), (u11, v11))
    return tri1, tri2


def _edges(P):
    """(p, q) endpoint views of the horizontal, vertical and diagonal
    spatial edges, p < q in id."""
    return {"h": (P[:, :-1], P[:, 1:]),
            "v": (P[:-1, :], P[1:, :]),
            "d": (P[:-1, :-1], P[1:, 1:])}


def slab_predicates(U0, V0, U1, V1):
    """Crossed flags of the faces that span the slab between two frames.

    Returns a dict: ``side1[e]`` = (p0, q0, q1), ``side2[e]`` =
    (p0, p1, q1) for edge kinds e in h/v/d, and ``int1[k]`` =
    (a0, b0, c1), ``int2[k]`` = (a0, b1, c1) for triangle kinds k in
    1/2 (tri1/tri2 as in :func:`slice_predicates`)."""
    eu0, ev0, eu1, ev1 = _edges(U0), _edges(V0), _edges(U1), _edges(V1)
    out = {"side1": {}, "side2": {}, "int1": {}, "int2": {}}
    for e in ("h", "v", "d"):
        (pu0, qu0), (pv0, qv0) = eu0[e], ev0[e]
        (pu1, qu1), (pv1, qv1) = eu1[e], ev1[e]
        out["side1"][e] = _crossed((pu0, pv0), (qu0, qv0), (qu1, qv1))
        out["side2"][e] = _crossed((pu0, pv0), (pu1, pv1), (qu1, qv1))
    a0 = (U0[:-1, :-1], V0[:-1, :-1])
    c1 = (U1[1:, 1:], V1[1:, 1:])
    b0 = {1: (U0[1:, :-1], V0[1:, :-1]), 2: (U0[:-1, 1:], V0[:-1, 1:])}
    b1 = {1: (U1[1:, :-1], V1[1:, :-1]), 2: (U1[:-1, 1:], V1[:-1, 1:])}
    for k in (1, 2):
        out["int1"][k] = _crossed(a0, b0[k], c1)
        out["int2"][k] = _crossed(a0, b1[k], c1)
    return out


def _flat_slab(sp):
    return np.concatenate(
        [sp[f][e].ravel() for f in ("side1", "side2") for e in "hvd"]
        + [sp[f][k].ravel() for f in ("int1", "int2") for k in (1, 2)])


def false_cases(U0, V0, U1, V1):
    """Faces whose predicate differs between two fixed-point blocks of
    frames (T, H, W): returns dict FC_t (time-slice faces), FC_s (faces
    spanning a slab) and the crossed counts of both."""
    T = U0.shape[0]
    out = {"FC_t": 0, "FC_s": 0, "crossed_a": 0, "crossed_b": 0,
           "faces": 0}
    for t in range(T):
        a = np.concatenate([p.ravel() for p in slice_predicates(U0[t], V0[t])])
        b = np.concatenate([p.ravel() for p in slice_predicates(U1[t], V1[t])])
        out["FC_t"] += int((a != b).sum())
        out["crossed_a"] += int(a.sum())
        out["crossed_b"] += int(b.sum())
        out["faces"] += a.size
    for t in range(T - 1):
        a = _flat_slab(slab_predicates(U0[t], V0[t], U0[t + 1], V0[t + 1]))
        b = _flat_slab(slab_predicates(U1[t], V1[t], U1[t + 1], V1[t + 1]))
        out["FC_s"] += int((a != b).sum())
        out["crossed_a"] += int(a.sum())
        out["crossed_b"] += int(b.sum())
        out["faces"] += a.size
    return out


# ----------------------------------------------------------------------
# global face ids (the container's documented numbering)
# ----------------------------------------------------------------------

class FaceIds:
    """Global face ids of an (H, W) grid, independent of T:

        slice faces  t * F + f         f over [tri1 cells, tri2 cells]
        slab faces   t * F + Fs + f    f over [side1 h|v|d, side2 h|v|d,
                                              int1 tri1|tri2, int2 tri1|tri2]

    with cells and edges in row-major order, Fs = 2 (H-1)(W-1) and
    F = Fs + Fb."""

    def __init__(self, H, W):
        self.H, self.W = H, W
        nc = (H - 1) * (W - 1)
        self.n_edges = {"h": H * (W - 1), "v": (H - 1) * W, "d": nc}
        self.Fs = 2 * nc
        E = sum(self.n_edges.values())
        self.Fb = 2 * E + 4 * nc
        self.F = self.Fs + self.Fb
        off = {}
        o = 0
        for e in "hvd":
            off["side1", e] = o
            o += self.n_edges[e]
        for e in "hvd":
            off["side2", e] = o
            o += self.n_edges[e]
        for fam in ("int1", "int2"):
            for k in (1, 2):
                off[fam, k] = o
                o += nc
        self.slab_off = off
        self.nc = nc

    def slice_id(self, t, k, flat):
        """Slice triangle of kind k (1/2) at flat cell index ``flat``."""
        return t * self.F + (k - 1) * self.nc + flat

    def slab_id(self, t, fam, key, flat):
        return t * self.F + self.Fs + self.slab_off[fam, key] + flat

    def vertices(self, fid):
        """(N, 3, 3) int64 (t, i, j) of the faces' vertices, in
        ascending id order."""
        H, W = self.H, self.W
        fid = np.asarray(fid, np.int64)
        t = fid // self.F
        r = fid % self.F
        out = np.empty((len(fid), 3, 3), np.int64)
        sl = r < self.Fs
        if sl.any():
            k = r[sl] // self.nc
            c = r[sl] % self.nc
            i, j = c // (W - 1), c % (W - 1)
            tt = t[sl]
            # tri1: (i,j),(i+1,j),(i+1,j+1); tri2: (i,j),(i,j+1),(i+1,j+1)
            b_i = np.where(k == 0, i + 1, i)
            b_j = np.where(k == 0, j, j + 1)
            out[sl] = np.stack([
                np.stack([tt, i, j], -1),
                np.stack([tt, b_i, b_j], -1),
                np.stack([tt, i + 1, j + 1], -1)], 1)
        sb = ~sl
        if sb.any():
            out[sb] = self._slab_vertices(t[sb], r[sb] - self.Fs)
        return out

    def _slab_vertices(self, t, r):
        H, W = self.H, self.W
        out = np.empty((len(t), 3, 3), np.int64)
        for (fam, key), o in self.slab_off.items():
            n = self.n_edges[key] if fam.startswith("side") else self.nc
            m = (r >= o) & (r < o + n)
            if not m.any():
                continue
            f = r[m] - o
            tt = t[m]
            if fam.startswith("side"):
                ncol = W - 1 if key in "hd" else W
                i, j = f // ncol, f % ncol
                di, dj = {"h": (0, 1), "v": (1, 0), "d": (1, 1)}[key]
                p = (i, j)
                q = (i + di, j + dj)
                if fam == "side1":      # (p0, q0, q1)
                    vs = [(tt, *p), (tt, *q), (tt + 1, *q)]
                else:                   # (p0, p1, q1)
                    vs = [(tt, *p), (tt + 1, *p), (tt + 1, *q)]
            else:
                i, j = f // (W - 1), f % (W - 1)
                b = (i + 1, j) if key == 1 else (i, j + 1)
                if fam == "int1":       # (a0, b0, c1)
                    vs = [(tt, i, j), (tt, *b), (tt + 1, i + 1, j + 1)]
                else:                   # (a0, b1, c1)
                    vs = [(tt, i, j), (tt + 1, *b), (tt + 1, i + 1, j + 1)]
            out[m] = np.stack([np.stack(v, -1) for v in vs], 1)
        return out


# ----------------------------------------------------------------------
# track extraction
# ----------------------------------------------------------------------

def _slab_segments(ids, t, S0, S1, SP):
    """Segment edges (E, 2) of global face ids in slab t.

    S0/S1: slice (tri1, tri2) flags at t and t+1; SP: slab_predicates.
    Tetrahedra per triangle (a, b, c) and their faces:
        tau1 (a0,b0,c0,c1): slice_t, int1, side1[ac], side1[bc]
        tau2 (a0,b0,b1,c1): side1[ab], int1, int2, side2[bc]
        tau3 (a0,a1,b1,c1): side2[ab], side2[ac], int2, slice_t+1
    tri1 (a=(i,j), b=(i+1,j), c=(i+1,j+1)): ab vertical at (i,j),
    bc horizontal at (i+1,j), ac diagonal at (i,j).  tri2 (a=(i,j),
    b=(i,j+1), c=(i+1,j+1)): ab horizontal at (i,j), bc vertical at
    (i,j+1), ac diagonal at (i,j).
    """
    H, W = ids.H, ids.W
    ci, cj = np.meshgrid(np.arange(H - 1), np.arange(W - 1), indexing="ij")
    cell = ci * (W - 1) + cj

    def side(fam, e, di, dj):
        flag = SP[fam][e]
        ii, jj = ci + di, cj + dj
        ncol = flag.shape[1]
        return flag[ii, jj], ids.slab_id(t, fam, e, ii * ncol + jj)

    parts = []
    for k in (1, 2):
        if k == 1:
            ab, bc, ac = ("v", 0, 0), ("h", 1, 0), ("d", 0, 0)
        else:
            ab, bc, ac = ("h", 0, 0), ("v", 0, 1), ("d", 0, 0)
        f_slice0 = (S0[k - 1], ids.slice_id(t, k, cell))
        f_slice1 = (S1[k - 1], ids.slice_id(t + 1, k, cell))
        f_int1 = (SP["int1"][k], ids.slab_id(t, "int1", k, cell))
        f_int2 = (SP["int2"][k], ids.slab_id(t, "int2", k, cell))
        tets = (
            (f_slice0, f_int1, side("side1", *ac), side("side1", *bc)),
            (side("side1", *ab), f_int1, f_int2, side("side2", *bc)),
            (side("side2", *ab), side("side2", *ac), f_int2, f_slice1),
        )
        for faces in tets:
            flags = np.stack([f for f, _ in faces], -1)      # (H-1, W-1, 4)
            n = flags.sum(-1)
            if ((n != 0) & (n != 2)).any():
                raise Lemma1Error(
                    f"slab {t}: {int(((n != 0) & (n != 2)).sum())} "
                    f"tetrahedra with a crossed-face count not in {{0, 2}}")
            sel = n == 2
            if not sel.any():
                continue
            fids = np.stack([np.broadcast_to(f, sel.shape)[sel]
                             for _, f in faces], -1)         # (M, 4)
            fl = flags[sel]
            parts.append(fids[fl].reshape(-1, 2))
    return parts


def _order(keys, edges):
    """Canonical node order of one component: an open path starts at
    the endpoint with the smaller key; a loop starts at its smallest
    key and steps first to its smaller-keyed neighbour."""
    n = len(keys)
    if n == 1:
        return np.zeros(1, np.int64)
    adj = [[] for _ in range(n)]
    for a, b in edges:
        adj[a].append(b)
        adj[b].append(a)
    deg = np.array([len(x) for x in adj])
    if (deg > 2).any():
        raise Lemma1Error("a crossing node has more than two segments")
    ends = np.nonzero(deg == 1)[0]
    if len(ends):
        start = int(ends[np.argmin(keys[ends])])
        nxt = adj[start][0]
    else:
        start = int(np.argmin(keys))
        nxt = min(adj[start], key=lambda x: keys[x])
    order, prev, cur = [start], start, nxt
    while cur != start:
        order.append(cur)
        step = [x for x in adj[cur] if x != prev]
        if not step:
            break
        prev, cur = cur, step[0]
    if len(order) != n:
        raise Lemma1Error("a track is not a single path or loop")
    return np.asarray(order, np.int64)


def positions(ids, fid, U, V):
    """(N, 3) float64 (t, y, x) barycentric zero of each face (Eq. 2).
    U, V: fixed-point int64 fields (T, H, W) indexed in global time."""
    vt = ids.vertices(fid)                              # (N, 3, 3)
    u = U[vt[..., 0], vt[..., 1], vt[..., 2]].astype(np.float64)
    v = V[vt[..., 0], vt[..., 1], vt[..., 2]].astype(np.float64)
    d_ab = u[:, 0] * v[:, 1] - v[:, 0] * u[:, 1]
    d_bc = u[:, 1] * v[:, 2] - v[:, 1] * u[:, 2]
    d_ca = u[:, 2] * v[:, 0] - v[:, 2] * u[:, 0]
    df = d_ab + d_bc + d_ca
    df = np.where(df == 0.0, 1.0, df)
    w = np.stack([d_bc / df, d_ca / df, d_ab / df], -1)  # weights of a, b, c
    return np.einsum("nk,nkd->nd", w, vt.astype(np.float64))


def classify(U, V, pos, spiral_tol):
    """Critical-point type codes from the Jacobian of the interpolant
    that is bilinear in space and linear in time, at each node."""
    T, H, W = U.shape
    t, y, x = pos[:, 0], pos[:, 1], pos[:, 2]
    t0 = np.clip(np.floor(t), 0, T - 2).astype(np.int64)
    i0 = np.clip(np.floor(y), 0, H - 2).astype(np.int64)
    j0 = np.clip(np.floor(x), 0, W - 2).astype(np.int64)
    at, ay, ax = t - t0, y - i0, x - j0

    def grads(F):
        g = {}
        for di in (0, 1):
            for dj in (0, 1):
                f0 = F[t0, i0 + di, j0 + dj].astype(np.float64)
                f1 = F[t0 + 1, i0 + di, j0 + dj].astype(np.float64)
                g[di, dj] = (1 - at) * f0 + at * f1
        ddx = (1 - ay) * (g[0, 1] - g[0, 0]) + ay * (g[1, 1] - g[1, 0])
        ddy = (1 - ax) * (g[1, 0] - g[0, 0]) + ax * (g[1, 1] - g[0, 1])
        return ddx, ddy

    ux, uy = grads(U)
    vx, vy = grads(V)
    tr = ux + vy
    det = ux * vy - uy * vx
    disc = tr * tr - 4.0 * det
    code = {n: i for i, n in enumerate(CP_TYPES)}
    out = np.full(len(pos), code["degenerate"], np.int8)
    out[det < 0] = code["saddle"]
    node = (det > 0) & (disc >= 0)
    spiral = (det > 0) & (disc < 0)
    out[node & (tr > 0)] = code["source"]
    out[node & (tr <= 0)] = code["sink"]
    out[spiral & (tr > 0)] = code["spiral_out"]
    out[spiral & (tr <= 0)] = code["spiral_in"]
    out[spiral & (np.abs(tr) <= spiral_tol * np.sqrt(np.maximum(det, 0)))] = \
        code["center"]
    return out


def extract_tracks(U, V, spiral_tol=0.05, geometry=True):
    """All tracks of fixed-point fields (T, H, W).

    Returns a list of dicts with ``face_ids`` (polyline order),
    ``is_loop`` and, with ``geometry``, ``nodes`` (N, 3) and ``types``;
    list index = track id (ascending smallest face id)."""
    T, H, W = U.shape
    ids = FaceIds(H, W)
    S = [slice_predicates(U[t], V[t]) for t in range(T)]
    parts = []
    for t in range(T - 1):
        SP = slab_predicates(U[t], V[t], U[t + 1], V[t + 1])
        parts += _slab_segments(ids, t, S[t], S[t + 1], SP)
    seg = np.concatenate(parts) if parts else np.empty((0, 2), np.int64)
    node_fid, inv = np.unique(seg, return_inverse=True)
    edges = inv.reshape(-1, 2)
    n = len(node_fid)
    if n == 0:
        return []
    g = coo_matrix((np.ones(len(edges)), (edges[:, 0], edges[:, 1])),
                   shape=(n, n))
    _, lab = connected_components(g, directed=False)
    # number components by ascending smallest face id (node_fid sorted)
    first = np.full(lab.max() + 1, n, np.int64)
    np.minimum.at(first, lab, np.arange(n))
    rank = np.empty_like(first)
    rank[np.argsort(first, kind="stable")] = np.arange(len(first))
    track_of = rank[lab]
    if geometry:
        pos = positions(ids, node_fid, U, V)
        types = classify(U, V, pos, spiral_tol)
    order_nodes = np.argsort(track_of, kind="stable")
    node_ptr = np.searchsorted(track_of[order_nodes],
                               np.arange(track_of.max() + 2))
    etrack = track_of[edges[:, 0]]
    eorder = np.argsort(etrack, kind="stable")
    edge_ptr = np.searchsorted(etrack[eorder], np.arange(track_of.max() + 2))
    deg = np.bincount(edges.ravel(), minlength=n)
    tracks = []
    for k in range(track_of.max() + 1):
        sel = order_nodes[node_ptr[k]:node_ptr[k + 1]]
        e = np.searchsorted(sel, edges[eorder[edge_ptr[k]:edge_ptr[k + 1]]])
        idx = sel[_order(node_fid[sel], e.tolist())]
        tr = {"face_ids": node_fid[idx],
              "is_loop": bool(len(sel) > 1 and (deg[sel] == 2).all())}
        if geometry:
            tr["nodes"] = pos[idx]
            tr["types"] = types[idx]
        tracks.append(tr)
    return tracks
