"""Tiled streaming compression with halo-exact trajectory preservation.

The monolithic pipeline (compressor.py) holds the full (T, H, W) field
device-resident.  This module splits the field into spatial tiles x
temporal windows, compresses every (tile, window) as an independent unit
through the same fused stages, and packs the units into a random-access
container (encode.TiledWriter) -- while keeping the decoded output
BIT-IDENTICAL to the monolithic fused pipeline.  Why that is possible:

1.  *Order isomorphism.*  The SoS predicate (sos.py) reads vertex ids
    only through ``<`` comparisons, and a sub-box's row-major local ids
    preserve the global id order (grid.box_vertex_ids).  So predicates
    and Alg.-2 bounds evaluated on a halo-extended tile are bit-equal to
    the global evaluation restricted to that tile.

2.  *Halo-exact eb reduction.*  Each tile derives per-vertex error
    bounds over its one-cell/one-frame halo extension; the global bound
    is the MIN across every tile that sees a vertex.  Every face lies
    inside at least one extension, and a tile missing some of a vertex's
    incident faces only ever reports a LARGER bound, so the reduction
    reconstructs the global per-vertex eb exactly -- seam vertices get
    the same bound on both sides.

3.  *Pointwise X.*  Dual-quantization is pointwise in (value, eb,
    forced-mask), and integer residual decode is an exact inverse of
    residual encode, so the reconstructed integer field X -- and hence
    the float32 output -- is fully determined by (eb, forced mask,
    xi_unit) regardless of how residuals are blocked into units.  Units
    may therefore reset the temporal predictor at window starts and run
    the semi-Lagrangian predictor tile-locally (full random access)
    without changing a single output bit.

4.  *Seam-agreed verify.*  The verify-and-correct loop runs per tile on
    the halo extension; every face is checked by every tile that sees
    it, with identical values and order-isomorphic ids, so all tiles
    reach the same forced/not decision and the per-round union of
    forced vertices equals the monolithic round's forced set.  By
    induction the fixpoint -- and the output -- is bit-identical.

Entry points:

    blob, stats = compress_tiled(u, v, cfg, TileGrid(...))
    blob, stats = compress_stream(frame_pairs, cfg, grid,
                                  value_range=(lo, hi))   # bounded memory
    u, v = decompress_tiled(blob)                         # full field
    u, v = decompress_region(blob, (t0, t1, i0, i1, j0, j1))
    plan = read_plan(blob, region)    # directory entries a decode touches

``compress_stream`` consumes an iterable of per-frame ``(u_t, v_t)``
planes and holds only ~2 windows of frames in memory; units are written
to the sink as soon as their window's verify fixpoint can no longer be
affected by future frames.  A verify cascade that would force a vertex
in an already-emitted window raises StreamingCascadeError (enlarge
``window_t`` or use compress_tiled); forcing cascades that long have not
been observed on any test field.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import backend as backend_mod
from . import compressor, ebound, ebpolicy, encode, fixedpoint, pipeline, sos
from . import grid as mesh
from .. import obs

# v4: prologue frame + per-frame "CPUN"/"CPPR" preambles (walkable body,
# salvageable without a footer) + per-unit CRC in the directory.
# Version-3 and older archives stay readable: the directory-driven read
# path never looks between frames and checksum verification keys off
# the entry's ``crc`` field (tests/test_container_golden.py pins this
# against a checked-in v3 blob).
TILED_FORMAT_VERSION = 4
# v5: unit frames may be CPTH1 (device entropy stage, core/entropy.py)
# instead of CPTZ1/CPTL1.  Host-codec archives keep writing v4 -- the
# bump applies only where an old reader would actually fail.
TILED_FORMAT_VERSION_DEVICE = 5
# v6: adaptive eb policy (core/ebpolicy.py): the container header
# records the policy spec and every unit frame records its own base
# bound ("eb_base", self-describing msgpack extras a v<=5 reader skips).
# Uniform-policy archives keep writing v4/v5, so the goldens and old
# readers are unaffected (DESIGN.md #16).
TILED_FORMAT_VERSION_ADAPTIVE = 6
_EB_BIG = np.int64(2**62)
# batched unit execution: cap the stacked batch (with pow2 padding this
# bounds both peak memory and the number of compiled batch sizes).
# The per-run value is a searched scheduling knob
# (pipeline.PLAN_KNOBS["batch_cap"], carried on _State); chunking by
# signature group keeps the bytes identical for every cap value.
_BATCH_CAP = pipeline.PLAN_DEFAULTS["batch_cap"]


class StreamingCascadeError(RuntimeError):
    """A verify-and-correct cascade crossed the emitted-window frontier."""


# ----------------------------------------------------------------------
# tile planning
# ----------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TileGrid:
    """Tiling geometry: spatial tiles x temporal windows + halo widths."""

    tile_h: int = 128
    tile_w: int = 128
    window_t: int = 32
    halo: int = 1       # spatial halo (cells); >= 1 for halo-exact eb
    thalo: int = 1      # temporal halo (frames); >= 1

    def validate(self):
        # real raises, not asserts: geometry validation must hold under
        # python -O (a halo=0 grid silently breaks eb exactness)
        if self.tile_h < 1 or self.tile_w < 1 or self.window_t < 1:
            raise ValueError(f"tile/window sizes must be >= 1: {self}")
        if self.halo < 1:
            raise ValueError("spatial halo must cover incident faces "
                             "(halo >= 1)")
        if self.thalo < 1:
            raise ValueError("temporal halo must cover incident slabs "
                             "(thalo >= 1)")


@dataclasses.dataclass(frozen=True)
class TileSpec:
    """One (window, tile) unit: owned + halo-extended half-open boxes."""

    wi: int
    ti: int
    tj: int
    t0: int; t1: int; i0: int; i1: int; j0: int; j1: int
    et0: int; et1: int; ei0: int; ei1: int; ej0: int; ej1: int

    @property
    def key(self):
        return (self.wi, self.ti, self.tj)

    @property
    def owned_box(self):
        return (self.t0, self.t1, self.i0, self.i1, self.j0, self.j1)

    @property
    def ext_box(self):
        return (self.et0, self.et1, self.ei0, self.ei1, self.ej0, self.ej1)

    @property
    def owned_shape(self):
        return (self.t1 - self.t0, self.i1 - self.i0, self.j1 - self.j0)

    @property
    def ext_shape(self):
        return (self.et1 - self.et0, self.ei1 - self.ei0,
                self.ej1 - self.ej0)

    @property
    def owned_in_ext(self):
        return (slice(self.t0 - self.et0, self.t1 - self.et0),
                slice(self.i0 - self.ei0, self.i1 - self.ei0),
                slice(self.j0 - self.ej0, self.j1 - self.ej0))


def window_specs(wi: int, t0: int, t1: int, H: int, W: int, et1: int,
                 grid: TileGrid):
    """Tile specs of one temporal window (et1 = clamped extended end)."""
    et0 = max(t0 - grid.thalo, 0)
    nti = -(-H // grid.tile_h)
    ntj = -(-W // grid.tile_w)
    specs = []
    for ti in range(nti):
        i0 = ti * grid.tile_h
        i1 = min(i0 + grid.tile_h, H)
        ei0 = max(i0 - grid.halo, 0)
        ei1 = min(i1 + grid.halo, H)
        for tj in range(ntj):
            j0 = tj * grid.tile_w
            j1 = min(j0 + grid.tile_w, W)
            ej0 = max(j0 - grid.halo, 0)
            ej1 = min(j1 + grid.halo, W)
            specs.append(TileSpec(wi, ti, tj, t0, t1, i0, i1, j0, j1,
                                  et0, et1, ei0, ei1, ej0, ej1))
    return specs


def plan(shape, grid: TileGrid):
    """All TileSpecs for a full (T, H, W) field."""
    grid.validate()
    T, H, W = shape
    specs = []
    for wi in range(-(-T // grid.window_t)):
        t0 = wi * grid.window_t
        t1 = min(t0 + grid.window_t, T)
        et1 = min(t1 + grid.thalo, T)
        specs.extend(window_specs(wi, t0, t1, H, W, et1, grid))
    return specs


# ----------------------------------------------------------------------
# sliding per-frame plane storage (bounded memory for streaming)
# ----------------------------------------------------------------------

class _Planes:
    """Dict-of-frames (H, W) numpy storage with box accessors."""

    def __init__(self, H, W, dtype, fill):
        self.H, self.W = H, W
        self.dtype = dtype
        self.fill = fill
        self.p = {}

    def ensure(self, t):
        if t not in self.p:
            self.p[t] = np.full((self.H, self.W), self.fill, self.dtype)
        return self.p[t]

    def put(self, t, arr):
        self.p[t] = np.asarray(arr, self.dtype)

    def box(self, b):
        t0, t1, i0, i1, j0, j1 = b
        return np.stack([self.ensure(t)[i0:i1, j0:j1]
                         for t in range(t0, t1)])

    def min_box(self, b, vals):
        t0, t1, i0, i1, j0, j1 = b
        for k, t in enumerate(range(t0, t1)):
            sl = self.ensure(t)[i0:i1, j0:j1]
            np.minimum(sl, vals[k], out=sl)

    def or_box(self, b, vals):
        t0, t1, i0, i1, j0, j1 = b
        for k, t in enumerate(range(t0, t1)):
            self.ensure(t)[i0:i1, j0:j1] |= vals[k]

    def drop_below(self, t):
        for k in [k for k in self.p if k < t]:
            del self.p[k]


# ----------------------------------------------------------------------
# shared state + jitted batch deriver
# ----------------------------------------------------------------------

@dataclasses.dataclass
class _State:
    cfg: object
    grid: TileGrid
    ex: object                      # pipeline.PlanExecutor (stage impls)
    be: str
    H: int
    W: int
    scale: float
    eb_abs: float
    tau: int
    xi_unit: int
    n_usable: int
    g2f: float
    stepper: object
    u: _Planes
    v: _Planes
    ufp: _Planes
    vfp: _Planes
    eb: _Planes
    forced: _Planes
    preds: dict = dataclasses.field(default_factory=dict)
    seen: dict = dataclasses.field(default_factory=dict)
    writer: object = None
    prologue: dict = None           # global decode params (v4 prologue)
    tindex: object = None           # analysis.index.TrackIndexBuilder | None
    n_frames: int = 0
    bad_counts: list = dataclasses.field(default_factory=list)
    rounds: int = 0
    n_ll: int = 0
    n_sl_blocks: int = 0
    n_blocks: int = 0
    n_verts: int = 0
    n_units: int = 0
    batch_cap: int = _BATCH_CAP     # searched scheduling knob (never
                                    # changes bytes; pipeline.PLAN_KNOBS)
    policy: object = None           # normalized ebpolicy.TilePolicy |
                                    # None (uniform scalar path)
    ebf: object = None              # adaptive only: float64 _Planes of
                                    # resolved per-vertex ABSOLUTE base
                                    # bounds (verify + eb_base headers)
    eb_factor: float = 1.0          # cfg.eb-units -> absolute (1.0 for
                                    # abs mode, the f32 range for rel)


def _init_state(cfg, grid: TileGrid, H, W, vrange, sink):
    """Global stream parameters from the (exact) global value range.

    Mirrors the monolithic derivation bit-for-bit: same eb_abs, fixed-
    point scale, tau and xi_unit, so every downstream integer matches.
    """
    grid.validate()
    be = backend_mod.resolve(cfg.backend)
    lo, hi = float(vrange[0]), float(vrange[1])
    pol = ebpolicy.normalize(getattr(cfg, "eb_policy", None))
    if cfg.mode == "abs":
        eb_factor = 1.0
    else:
        # the value range is reduced in float32 exactly like the
        # monolithic _abs_eb (fields are float32, so lo/hi are exactly
        # representable and only the SUBTRACTION rounding matters --
        # a f64 subtract here once cost a off-by-one tau at 64x256x256)
        rng = float(np.float32(hi) - np.float32(lo))
        ebpolicy.check_relative_range(rng, max(abs(lo), abs(hi)))
        eb_factor = max(rng, 1e-30)
    # the global plan derives from the policy's LOOSEST bound; adaptive
    # per-vertex caps only clamp down from it (core/ebpolicy.py)
    eb_abs = float(cfg.eb if pol is None
                   else ebpolicy.max_bound(pol)) * eb_factor
    max_abs = max(abs(lo), abs(hi), 1e-300)
    scale = fixedpoint.compute_scale(max_abs, cfg.fixed_bits)
    plan = pipeline.plan_from_cfg(cfg, be, scale, eb_abs, name="tiled")
    ex = pipeline.PlanExecutor(plan)
    all_ll = plan.tau < 1 or plan.n_usable < 1
    tindex = None
    if getattr(cfg, "track_index", True):
        from ..analysis.index import TrackIndexBuilder

        tindex = TrackIndexBuilder(grid, be)
    st = _State(
        tindex=tindex,
        cfg=cfg, grid=grid, ex=ex, be=be, H=H, W=W,
        scale=plan.scale, eb_abs=plan.eb_abs, tau=plan.tau,
        xi_unit=plan.xi_unit, n_usable=plan.n_usable, g2f=plan.g2f,
        batch_cap=max(int(pipeline.resolve_knobs(cfg)["batch_cap"]), 1),
        stepper=ex.stepper,
        u=_Planes(H, W, np.float32, 0.0),
        v=_Planes(H, W, np.float32, 0.0),
        ufp=_Planes(H, W, np.int64, 0),
        vfp=_Planes(H, W, np.int64, 0),
        eb=_Planes(H, W, np.int64, _EB_BIG),
        forced=_Planes(H, W, bool, all_ll),
        policy=pol,
        ebf=(None if pol is None
             else _Planes(H, W, np.float64, np.inf)),
        eb_factor=eb_factor,
    )
    # v4 prologue: the global decode parameters, written up front so a
    # footerless (crashed/truncated) archive remains self-describing
    # for encode.salvage_container.  shape[0] is 0 here -- the true T
    # is only known at finish time; salvage recovers it from unit boxes.
    prologue = _container_header(st, 0)
    prologue["prologue"] = True
    st.prologue = prologue
    st.writer = encode.TiledWriter(sink, cfg.zstd_level, prologue=prologue)
    return st


def _add_frame(st: _State, t, u_t, v_t, ufp_t=None, vfp_t=None):
    """Insert one frame; ``ufp_t``/``vfp_t`` accept the fixed-point
    planes precomputed off-thread (the async engine's ingest stage --
    np.round(x64 * scale) is deterministic, so who computes it cannot
    change a bit)."""
    u_t = np.asarray(u_t, np.float32)
    v_t = np.asarray(v_t, np.float32)
    if u_t.shape != (st.H, st.W) or v_t.shape != (st.H, st.W):
        raise ValueError(
            f"frame {t} shape {u_t.shape}/{v_t.shape} != ({st.H}, {st.W})")
    st.n_frames = max(st.n_frames, t + 1)
    st.u.put(t, u_t)
    st.v.put(t, v_t)
    if ufp_t is None:
        ufp_t = np.round(u_t.astype(np.float64) * st.scale)
    if vfp_t is None:
        vfp_t = np.round(v_t.astype(np.float64) * st.scale)
    st.ufp.put(t, ufp_t)
    st.vfp.put(t, vfp_t)


def _pick_fns(st: _State, shape):
    # one keyed registry for every path (pipeline.unit_fns); the pallas
    # int32-headroom demotion rule lives in the plan
    return st.ex.fns(shape)


def _sig(spec: TileSpec):
    """Batching signature: units sharing it stack through one vmapped
    executable set (pipeline.BatchFns)."""
    return pipeline.unit_signature(
        spec.ext_shape, spec.owned_shape,
        (spec.t0 - spec.et0, spec.i0 - spec.ei0, spec.j0 - spec.ej0))


@functools.lru_cache(maxsize=8)
def _batch_deriver(tau: int):
    """Jitted, device-parallel per-vertex eb derivation over a stacked
    batch of same-shape tile extensions (parallel/sharding.py mesh)."""
    from ..parallel import sharding

    def one(uu, vv):
        return ebound.derive_vertex_eb(uu, vv, tau)

    return jax.jit(lambda us, vs: sharding.map_tiles(one, us, vs))


def _derive_window(st: _State, w):
    """Phase 1 for one window: per-tile eb + original face predicates,
    min-reduced into the global per-vertex bound planes."""
    run = _batch_deriver(int(max(st.tau, 1)))
    groups = {}
    for spec in w.specs:
        groups.setdefault(spec.ext_shape, []).append(spec)
    with obs.span("tiling.derive_window", window=int(w.wi),
                  units=len(w.specs)):
        for specs in groups.values():
            us = np.stack([st.ufp.box(s.ext_box) for s in specs])
            vs = np.stack([st.vfp.box(s.ext_box) for s in specs])
            ebs, slice_c, slab_c = run(us, vs)
            # np.asarray of the device results is the host fetch -- the
            # stage's device-sync point
            ebs = np.asarray(ebs)
            slice_c = np.asarray(slice_c)
            slab_c = np.asarray(slab_c)
            for k, spec in enumerate(specs):
                st.eb.min_box(spec.ext_box, ebs[k])
                st.preds[spec.key] = (slice_c[k], slab_c[k])
    if st.policy is not None:
        # adaptive policy: min the resolved per-vertex caps into the
        # derived bound planes (idempotent, so thalo overlap between
        # windows and journaled re-derivation after resume are safe);
        # the float64 bound planes feed verify and the eb_base headers
        et0 = min(s.et0 for s in w.specs)
        for t in range(et0, w.et1):
            boundf = ebpolicy.frame_bounds(st.policy, t, st.H, st.W,
                                           st.eb_factor)
            cap = np.floor(boundf * st.scale).astype(np.int64)
            np.minimum(st.eb.ensure(t), cap, out=st.eb.ensure(t))
            np.minimum(st.ebf.ensure(t), boundf, out=st.ebf.ensure(t))
    w.derived = True


# ----------------------------------------------------------------------
# per-tile encode + verify round
# ----------------------------------------------------------------------

def _quant_and_streams(st: _State, spec: TileSpec):
    """Quantize the halo extension + build the unit's residual streams
    (sequential per-unit emission path; the batched path is
    _encode_group).  Returns only what emission reads."""
    _, _, ll_e, res_u, res_v, bm = st.ex.encode_unit(
        st.ufp.box(spec.ext_box), st.vfp.box(spec.ext_box),
        st.eb.box(spec.ext_box), st.forced.box(spec.ext_box),
        spec.owned_in_ext)
    return ll_e, res_u, res_v, bm


def _tile_round(st: _State, spec: TileSpec, delta):
    """One verify round on one tile's halo extension.

    ``delta`` is None for the initial (sign-stability-screened) full
    check, else the ext-shaped bool mask of vertices forced since this
    tile last checked (only incident faces are re-evaluated).  Returns
    (forced_ext bool, n_bad) with decisions bit-equal to the monolithic
    round restricted to this extension.
    """
    # bind the extension boxes on device once; encode_unit and the
    # checks below reuse them (jnp.asarray of a device array is free)
    ufp_e = jnp.asarray(st.ufp.box(spec.ext_box))
    vfp_e = jnp.asarray(st.vfp.box(spec.ext_box))
    extra_e = jnp.asarray(st.forced.box(spec.ext_box))
    xu_e, xv_e, ll_e, res_u, res_v, bm = st.ex.encode_unit(
        ufp_e, vfp_e, st.eb.box(spec.ext_box), extra_e, spec.owned_in_ext)
    fns_e = _pick_fns(st, spec.ext_shape)
    o = spec.owned_in_ext
    # simulate the unit's exact decode, paste into the extension
    xu_d, xv_d = st.ex.decode_fields(res_u, res_v, bm)
    xu_sim = jnp.asarray(xu_e).at[o].set(xu_d)
    xv_sim = jnp.asarray(xv_e).at[o].set(xv_d)
    u_e = jnp.asarray(st.u.box(spec.ext_box))
    v_e = jnp.asarray(st.v.box(spec.ext_box))
    forced, n_pt, ur_fp, vr_fp = fns_e.check_pt(
        xu_sim, xv_sim, ll_e, extra_e, u_e, v_e,
        st.scale, st.xi_unit,
        # uniform passes the exact scalar (pre-policy trace); adaptive
        # passes the resolved per-vertex absolute bounds, which the
        # pointwise check broadcasts elementwise
        st.eb_abs if st.policy is None
        else jnp.asarray(st.ebf.box(spec.ext_box)))
    n_bad = int(n_pt)
    forced_np = np.asarray(forced)
    add, nf = pipeline.check_faces(
        fns_e, spec.ext_shape, ufp_e, vfp_e, ur_fp, vr_fp,
        st.preds[spec.key], delta)
    n_bad += nf
    if add is not None:
        forced_np = forced_np | add
    return forced_np, n_bad


# ----------------------------------------------------------------------
# batched same-signature unit execution (pipeline.BatchFns)
# ----------------------------------------------------------------------

def _stack_boxes(st: _State, specs, planes):
    return np.stack([planes.box(s.ext_box) for s in specs])


def _encode_group(st: _State, specs):
    """Batched encode of one same-signature spec group.  Returns
    per-spec (xu_e, xv_e, ll_e, res_u, res_v, bm) tuples, byte-equal to
    the sequential _quant_and_streams outputs (pipeline module doc)."""
    sig = _sig(specs[0])
    xu_e, xv_e, ll_e, res_u, res_v, bms = st.ex.encode_units(
        sig, _stack_boxes(st, specs, st.ufp),
        _stack_boxes(st, specs, st.vfp),
        _stack_boxes(st, specs, st.eb),
        _stack_boxes(st, specs, st.forced))
    return [(xu_e[b], xv_e[b], ll_e[b], res_u[b], res_v[b], bms[b])
            for b in range(len(specs))]


def _round_group(st: _State, specs, deltas):
    """Batched verify round over one same-signature spec group; the
    face re-checks (variable-size selections) stay per-unit.  Returns
    per-spec (forced_ext np bool, n_bad) -- decisions bit-equal to the
    sequential _tile_round (pipeline module doc).

    Each extension box is stacked and uploaded exactly ONCE per round;
    encode, decode-sim, pointwise check and screen all reuse the bound
    device stacks (the sequential path's no-re-upload rule, batched).
    """
    ex = st.ex
    sig = _sig(specs[0])
    bf = ex.batch_fns(sig)
    ufp_es = jnp.asarray(_stack_boxes(st, specs, st.ufp))
    vfp_es = jnp.asarray(_stack_boxes(st, specs, st.vfp))
    extra_es = jnp.asarray(_stack_boxes(st, specs, st.forced))
    xu_e, xv_e, ll_e, res_u, res_v, bms = ex.encode_units(
        sig, ufp_es, vfp_es, _stack_boxes(st, specs, st.eb), extra_es)
    xu_d, xv_d = ex.decode_units(bf, res_u, res_v, bms)
    xu_sim, xv_sim = bf.paste(xu_e, xv_e, xu_d, xv_d)
    u_es = jnp.asarray(_stack_boxes(st, specs, st.u))
    v_es = jnp.asarray(_stack_boxes(st, specs, st.v))
    (xu_p, xv_p, ll_p, ex_p, u_p, v_p), _ = pipeline._pad_pow2(
        [xu_sim, xv_sim, ll_e, extra_es, u_es, v_es])
    pb = xu_p.shape[0]
    scales = jnp.full((pb,), st.scale, jnp.float64)
    xis = jnp.full((pb,), st.xi_unit, jnp.int64)
    if st.policy is None:
        ebs = jnp.full((pb,), st.eb_abs, jnp.float64)
    else:
        # per-vertex bound stacks ride the same vmapped check: the
        # mapped axis stays 0, the inner broadcast turns elementwise
        (ebs,), _ = pipeline._pad_pow2(
            [jnp.asarray(_stack_boxes(st, specs, st.ebf))])
    forced_b, n_pt_b, ur_b, vr_b = bf.check_pt(
        xu_p, xv_p, ll_p, ex_p, u_p, v_p, scales, xis, ebs)

    screened = all(d is None for d in deltas)
    if screened:
        (ufp_p, vfp_p), _ = pipeline._pad_pow2([ufp_es, vfp_es])
        unsafe_sl_b, unsafe_sb_b = bf.screen(ufp_p, vfp_p, ur_b, vr_b)

    Te, he, we = specs[0].ext_shape
    fns_e = _pick_fns(st, specs[0].ext_shape)
    out = []
    for b, (spec, delta) in enumerate(zip(specs, deltas)):
        n_bad = int(n_pt_b[b])
        forced_np = np.asarray(forced_b[b])
        if delta is None:
            selection = pipeline.screen_selection_from(
                unsafe_sl_b[b], unsafe_sb_b[b], he, we)
        else:
            selection = pipeline._touched_faces(delta, Te, he, we)
        add, nf = pipeline.face_recheck(
            fns_e, spec.ext_shape, ur_b[b], vr_b[b], st.preds[spec.key],
            selection)
        n_bad += nf
        if add is not None:
            forced_np = forced_np | add
        out.append((forced_np, n_bad))
    return out


def _round_work(st: _State, work):
    """Run one verify round over ``work`` = [(spec, delta)]: batched by
    signature when the plan allows, per-unit otherwise.  Returns
    [(spec, forced_ext, n_bad)]."""
    if not st.ex.plan.batch_units:
        return [(spec, *_tile_round(st, spec, delta))
                for spec, delta in work]
    groups = {}
    for spec, delta in work:
        groups.setdefault((_sig(spec), delta is None), []).append(
            (spec, delta))
    out = []
    for items in groups.values():
        for lo in range(0, len(items), st.batch_cap):
            chunk = items[lo:lo + st.batch_cap]
            obs.observe("pipeline.batch_group_size", len(chunk))
            if len(chunk) == 1:
                # a 1-unit batch would just compile a second executable
                # set for the same work; the per-unit path is bit-equal
                spec, delta = chunk[0]
                out.append((spec, *_tile_round(st, spec, delta)))
                continue
            specs = [s for s, _ in chunk]
            deltas = [d for _, d in chunk]
            for spec, (forced_np, nb) in zip(
                    specs, _round_group(st, specs, deltas)):
                out.append((spec, forced_np, nb))
    return out


# ----------------------------------------------------------------------
# verify-and-correct fixpoint over a set of windows
# ----------------------------------------------------------------------

def _fixpoint(st: _State, windows, frontier: int = 0):
    """Run the seam-agreed verify loop over ``windows``' tiles.

    Per round every participating tile evaluates its extension exactly
    as the monolithic round would (screen on first contact, incident
    faces of newly-forced vertices afterwards); the per-round union of
    forced vertices is applied globally so both sides of every seam
    agree before the next round.  Raises StreamingCascadeError if an
    addition lands below ``frontier`` (an already-emitted frame).
    """
    cfg = st.cfg
    specs = [s for w in windows for s in w.specs]
    work = []
    for spec in specs:
        if spec.key not in st.seen:
            work.append((spec, None))
        else:
            delta = st.forced.box(spec.ext_box) & ~st.seen[spec.key]
            if delta.any():
                work.append((spec, delta))
    rounds = 0
    while work:
        additions = {}
        n_bad = 0
        with obs.span("tiling.verify_round", round=rounds,
                      units=len(work)):
            round_out = _round_work(st, work)
        for spec, forced_ext, nb in round_out:
            n_bad += nb
            new = forced_ext & ~st.forced.box(spec.ext_box)
            if new.any():
                t0 = spec.et0
                for k in range(new.shape[0]):
                    if new[k].any():
                        acc = additions.setdefault(
                            t0 + k, np.zeros((st.H, st.W), bool))
                        acc[spec.ei0:spec.ei1, spec.ej0:spec.ej1] |= new[k]
        st.bad_counts.append(n_bad)
        if not additions or rounds >= cfg.max_rounds:
            break
        if min(additions) < frontier:
            raise StreamingCascadeError(
                f"verify cascade reached emitted frame {min(additions)} "
                f"(< frontier {frontier}); increase window_t or use "
                f"compress_tiled")
        for t, mask in additions.items():
            st.forced.ensure(t)
            st.forced.p[t] |= mask
        rounds += 1
        st.rounds = max(st.rounds, rounds)
        work = []
        for spec in specs:
            t0, t1, i0, i1, j0, j1 = spec.ext_box
            delta = np.stack([
                additions[t][i0:i1, j0:j1] if t in additions
                else np.zeros((i1 - i0, j1 - j0), bool)
                for t in range(t0, t1)
            ])
            if delta.any():
                work.append((spec, delta))
    obs.count("tiling.verify_rounds", rounds)
    for spec in specs:
        st.seen[spec.key] = st.forced.box(spec.ext_box)
    for w in windows:
        w.screened = True


# ----------------------------------------------------------------------
# per-unit trajectory-segment extraction (sidecar track index)
# ----------------------------------------------------------------------
#
# Every unit owns the tets anchored in its owned box (slabs
# [t0, min(t1, T-1)), cells [i0, min(i1, H-1)) x [j0, min(j1, W-1)) --
# a partition of all tets).  The crossed-state of those tets' faces is
# evaluated on the halo extension with tile-local vertex ids
# (order-isomorphic to global ids => bit-identical SoS predicates),
# batched per extension-geometry group and shard_mapped over the
# ("tiles",) mesh like the eb derivation.  The sparse host pass then
# converts crossings to GLOBAL face ids / anchor cells and records the
# unit's segments + crossing nodes into the TrackIndexBuilder; global
# stitching happens once at finish time (analysis/index.py).


class _PlanesView:
    """(T, H, W) fancy-indexing facade over _Planes frame storage.

    Lets analysis.node_positions / classify gather from the sliding
    per-frame planes without materializing the full field (streaming
    holds only ~2 windows of frames).
    """

    def __init__(self, planes: _Planes, T: int):
        self.planes = planes
        self.shape = (T, planes.H, planes.W)

    def __getitem__(self, idx):
        t, i, j = (np.asarray(x) for x in idx)
        t, i, j = np.broadcast_arrays(t, i, j)
        out = np.empty(t.shape, dtype=self.planes.dtype)
        for tt in np.unique(t):
            m = t == tt
            assert int(tt) in self.planes.p, \
                f"frame {int(tt)} not resident (dropped or not yet seen)"
            out[m] = self.planes.p[int(tt)][i[m], j[m]]
        return out


@functools.lru_cache(maxsize=64)
def _local_tet_faces(key):
    """Static (n_slabs * Ntl, 4, 3) tet-face vertex ids, local to the
    extension box, for the tets a unit owns.  Mirrors the grid.py
    enumeration order (tau1|tau2|tau3 over tri1|tri2 over row-major
    cells) so local tet index -> global tet index is pure arithmetic.
    """
    Te, he, we, dt0, di0, dj0, nsl, nci, ncj = key
    if nsl <= 0 or nci <= 0 or ncj <= 0:
        return None
    P = he * we
    ii, jj = np.meshgrid(np.arange(nci), np.arange(ncj), indexing="ij")

    def sid(i, j):
        return ((di0 + i) * we + (dj0 + j)).ravel().astype(np.int64)

    v00 = sid(ii, jj)
    v10 = sid(ii, jj + 1)
    v01 = sid(ii + 1, jj)
    v11 = sid(ii + 1, jj + 1)
    tri1 = np.stack([v00, v01, v11], 1)
    tri2 = np.stack([v00, v10, v11], 1)
    tris = np.concatenate([tri1, tri2], 0)
    a, b, c = tris[:, 0], tris[:, 1], tris[:, 2]
    tau1 = np.stack([a, b, c, c + P], 1)
    tau2 = np.stack([a, b, b + P, c + P], 1)
    tau3 = np.stack([a, a + P, b + P, c + P], 1)
    tets = np.concatenate([tau1, tau2, tau3], 0)
    faces = tets[:, mesh.TET_FACES]               # (Ntl, 4, 3)
    out = faces[None] + ((dt0 + np.arange(nsl, dtype=np.int64)) * P
                         )[:, None, None, None]
    return np.ascontiguousarray(out.reshape(-1, 4, 3))


@functools.lru_cache(maxsize=64)
def _batch_seg_fn(key, be: str):
    """Batched crossed-face evaluator for one extension geometry:
    ``run(us, vs)`` -> (B, nsl, 4, Ntl) bool, slab by slab.

    Local ids are order-isomorphic to global ids, so the SoS predicate
    is bit-identical to the global evaluation (the integer op contract:
    all backends agree, so jnp is used on-device and numpy on host).
    One slab's face table serves every slab of the unit -- slab s is
    the table shifted by s planes, which changes no id order -- so the
    per-vertex gather indices and SoS id-order bools (pre-split on the
    host, sos.face_crossed_ordered) are (4, Ntl) rows, not an
    (nsl * Ntl, 4, 3) jit constant: XLA constant-folded slices of that
    for >30 s per geometry, and a minor dimension of 4 padded the TPU
    gathers 32-fold past the chip's memory at 250 x 250 tiles.
    """
    fidx_np = _local_tet_faces(key)
    if fidx_np is None:
        return None
    nsl = key[6]
    P = key[1] * key[2]
    slab0 = fidx_np[: len(fidx_np) // nsl]        # (Ntl, 4, 3), slab 0
    if be == "numpy":
        def run_np(us, vs):
            out = []
            for u, v in zip(us, vs):
                u, v = np.asarray(u).reshape(-1), np.asarray(v).reshape(-1)
                out.append([sos.face_crossed_vals(
                    np, u[slab0 + s * P], v[slab0 + s * P],
                    slab0 + s * P).T for s in range(nsl)])
            return np.asarray(out)
        return run_np

    from ..parallel import sharding

    rows = slab0.transpose(2, 1, 0)                # (3, 4, Ntl)
    f0, f1, f2 = (jnp.asarray(r.astype(np.int32)) for r in rows)
    lt_ab = jnp.asarray(rows[0] < rows[1])
    lt_bc = jnp.asarray(rows[1] < rows[2])
    lt_ca = jnp.asarray(rows[2] < rows[0])

    def one(uu, vv):
        uf = uu.reshape(-1)
        vf = vv.reshape(-1)

        def slab(s):
            o = s * P
            return sos.face_crossed_ordered(
                jnp, uf[f0 + o], vf[f0 + o], uf[f1 + o], vf[f1 + o],
                uf[f2 + o], vf[f2 + o], lt_ab, lt_bc, lt_ca)
        return jax.lax.map(slab, jnp.arange(nsl, dtype=jnp.int32))

    return jax.jit(lambda us, vs: sharding.map_tiles(one, us, vs))


def _unit_segment_records(st: _State, spec: TileSpec, crossed, key):
    """Host conversion: local crossings -> global segments + nodes."""
    from ..analysis import classify as classify_mod
    from ..analysis import extraction

    (_, _, _, _, _, _, nsl, nci, ncj) = key
    H, W = st.H, st.W
    ncc = nci * ncj
    Ntl = 6 * ncc
    # (nsl, 4, Ntl) slot-major -> (nsl * Ntl, 4)
    crossed = np.asarray(crossed).transpose(0, 2, 1).reshape(nsl * Ntl, 4)
    from . import trajectory
    trajectory.check_lemma1(crossed.reshape(nsl, Ntl, 4), t_lo=spec.t0)

    j = np.nonzero(crossed.sum(axis=1) == 2)[0]
    if len(j) == 0:
        e = np.empty
        return (e((0, 2), np.int64), e((0, 3), np.int32), e(0, np.int64),
                e((0, 3), np.float64), e(0, np.int8))
    rows = crossed[j]
    _, slots = np.nonzero(rows)
    slots = slots.reshape(-1, 2)
    rt = j // Ntl
    r = j % Ntl
    k = r // (2 * ncc)
    rq = r % (2 * ncc)
    q = rq // ncc
    cc = rq % ncc
    gi = spec.i0 + cc // ncj
    gj = spec.j0 + cc % ncj
    ts = spec.t0 + rt
    Nc = (H - 1) * (W - 1)
    gtet = (k * 2 + q) * Nc + gi * (W - 1) + gj
    family, index = mesh.tet_face_map(H, W)
    seg_fid = mesh.tet_face_fids(
        family[gtet[:, None], slots], index[gtet[:, None], slots],
        ts[:, None], H, W)
    seg_cell = np.stack([ts, gi, gj], axis=1).astype(np.int32)

    node_fid = np.unique(seg_fid)
    uview = _PlanesView(st.ufp, st.n_frames)
    vview = _PlanesView(st.vfp, st.n_frames)
    node_pos = extraction.node_positions(
        node_fid, uview, vview, uview.shape)
    node_type = classify_mod.classify_nodes(
        uview, vview, node_pos, spiral_tol=st.tindex.spiral_tol)
    return seg_fid, seg_cell, node_fid, node_pos, node_type


def _window_segment_records(st: _State, w) -> dict:
    """Batched per-tile segment extraction for one window's units."""
    T = st.n_frames
    groups = {}
    for spec in w.specs:
        key = (spec.ext_shape + (
            spec.t0 - spec.et0, spec.i0 - spec.ei0, spec.j0 - spec.ej0,
            min(spec.t1, T - 1) - spec.t0,
            min(spec.i1, st.H - 1) - spec.i0,
            min(spec.j1, st.W - 1) - spec.j0))
        groups.setdefault(key, []).append(spec)
    records = {}
    for key, specs in groups.items():
        run = _batch_seg_fn(key, st.be)
        if run is None:
            e = np.empty
            for spec in specs:
                records[spec.key] = (
                    e((0, 2), np.int64), e((0, 3), np.int32),
                    e(0, np.int64), e((0, 3), np.float64), e(0, np.int8))
            continue
        us = np.stack([st.ufp.box(s.ext_box) for s in specs])
        vs = np.stack([st.vfp.box(s.ext_box) for s in specs])
        crossed = np.asarray(run(jnp.asarray(us), jnp.asarray(vs))
                             if st.be != "numpy" else run(us, vs))
        for b, spec in enumerate(specs):
            records[spec.key] = _unit_segment_records(
                st, spec, crossed[b], key)
    return records


# ----------------------------------------------------------------------
# unit emission
# ----------------------------------------------------------------------

@dataclasses.dataclass
class _UnitPayload:
    """Everything the CPU-side write stage needs for ONE unit -- no
    reference back into the sliding plane storage, so the scheduler may
    drop frames the moment payloads exist (the async engine hands these
    across a thread boundary)."""

    key: tuple
    box: tuple
    ll: object          # owned lossless mask (np bool)
    u_ll: object        # raw values at lossless vertices (np f32)
    v_ll: object
    res_u: object       # residual streams (device or host arrays)
    res_v: object
    bm: object          # blockmap (np bool)
    seg: object         # segment records tuple | None
    frag: object = None  # device-codec entropy fragment (HuffSections +
                        # escapes, core/entropy.py); res_u/res_v are
                        # released once it exists
    eb_base: object = None  # adaptive only: the unit's loosest resolved
                        # absolute base bound (self-describing per-unit
                        # header extra); computed here because the
                        # async writer thread has no plane access


def _unit_payloads(st: _State, w):
    """Span-wrapped entry for :func:`_unit_payloads_impl` (the device
    half of window emission; the async engine times this stage per
    window through the same span)."""
    with obs.span("tiling.unit_payloads", window=int(w.wi),
                  units=len(w.specs)):
        return _unit_payloads_impl(st, w)


def _unit_payloads_impl(st: _State, w):
    """Device/plane-reading half of window emission.

    Runs the final-mask encode (batched by signature when the plan
    allows) and snapshots per-unit payloads in the window's spec order
    -- the order the serial writer emits, which the async engine
    preserves through its handoff queue, keeping the container bytes
    identical.  Re-quantizes at the final mask rather than caching the
    last verify round's streams: a cache would hold every pending
    tile's residual field (2x the raw f32 footprint) alive until
    emission, defeating the bounded-memory point of tiling for one
    redundant encode pass.
    """
    seg_records = _window_segment_records(st, w) \
        if st.tindex is not None else None
    streams = {}
    if st.ex.plan.batch_units:
        groups = {}
        for spec in w.specs:
            groups.setdefault(_sig(spec), []).append(spec)
        for specs in groups.values():
            for lo in range(0, len(specs), st.batch_cap):
                chunk = specs[lo:lo + st.batch_cap]
                if len(chunk) == 1:
                    continue          # per-unit path below is bit-equal
                for spec, enc in zip(chunk, _encode_group(st, chunk)):
                    # keep only what emission reads -- pinning the
                    # extension X fields of a whole window would break
                    # the streaming path's bounded-memory contract
                    streams[spec.key] = enc[2:]
    payloads = []
    for spec in w.specs:
        if spec.key in streams:
            ll_e, res_u, res_v, bm = streams.pop(spec.key)
        else:
            ll_e, res_u, res_v, bm = _quant_and_streams(st, spec)
        o = spec.owned_in_ext
        ll_o = np.asarray(ll_e[o])
        u_o = st.u.box(spec.owned_box)
        v_o = st.v.box(spec.owned_box)
        payloads.append(_UnitPayload(
            key=spec.key, box=spec.owned_box, ll=ll_o,
            u_ll=u_o[ll_o], v_ll=v_o[ll_o],
            res_u=res_u, res_v=res_v, bm=bm,
            seg=None if seg_records is None else seg_records[spec.key],
            eb_base=(None if st.policy is None else
                     float(st.ebf.box(spec.owned_box).max()))))
        # original-predicate tables and seam snapshots are dead now
        st.preds.pop(spec.key, None)
        st.seen.pop(spec.key, None)
    if st.ex.codec == "device":
        _attach_entropy_fragments(st, payloads)
    w.emitted = True
    return payloads


def _attach_entropy_fragments(st: _State, payloads):
    """Device entropy stage over one window's payloads: stack the
    residual streams by owned shape and entropy-encode each stack in
    one batched device pass (per-unit tables keep the bytes independent
    of the grouping -- pipeline module doc).  The raw residual arrays
    are dropped once their fragment exists, so the async writer thread
    hands off pre-packed bitstreams instead of full streams."""
    stack = np.stack if st.ex.plan.backend == "numpy" else jnp.stack
    groups = {}
    for i, p in enumerate(payloads):
        groups.setdefault(tuple(p.res_u.shape), []).append(i)
    with obs.span("tiling.entropy_fragments", units=len(payloads),
                  groups=len(groups)):
        for idxs in groups.values():
            obs.observe("pipeline.batch_group_size", len(idxs))
            frags = st.ex.entropy_fragments(
                stack([payloads[i].res_u for i in idxs]),
                stack([payloads[i].res_v for i in idxs]))
            for i, frag in zip(idxs, frags):
                payloads[i].frag = frag
                payloads[i].res_u = payloads[i].res_v = None


def _write_unit(st: _State, p: _UnitPayload):
    """CPU half of unit emission: symbolize + pack + directory/index
    bookkeeping.  Pure host work on payload data only -- the async
    engine runs this on its writer thread while the device encodes the
    next window."""
    header = {"box": [int(x) for x in p.box]}
    if p.eb_base is not None:
        # self-describing per-unit base bound (adaptive policy); v<=5
        # readers skip unknown msgpack keys, so only obs/report tooling
        # needs to know it exists
        header["eb_base"] = float(p.eb_base)
    if p.frag is not None:
        from . import entropy
        sections = entropy.merge_sections(
            p.frag, p.ll, p.u_ll, p.v_ll, p.bm)
    else:
        sections = encode.field_sections(
            p.res_u, p.res_v, p.ll, p.u_ll, p.v_ll, p.bm)
    st.writer.add_unit(p.key, p.box, header, sections)
    if p.seg is not None:
        st.tindex.add_unit(p.key, *p.seg)
    bm = np.asarray(p.bm)
    obs.count("tiling.units_written", 1)
    st.n_units += 1
    st.n_ll += int(p.ll.sum())
    st.n_verts += p.ll.size
    st.n_sl_blocks += int(bm.sum())
    st.n_blocks += bm.size


def _emit_window(st: _State, w):
    payloads = _unit_payloads(st, w)
    with obs.span("tiling.write_units", window=int(w.wi),
                  units=len(payloads)):
        for p in payloads:
            _write_unit(st, p)


def _finish_header(st: _State, T: int):
    """Container header + the optional track-index footer section.

    The index rides as an EXTRA msgpack key (encode.TRACK_INDEX_KEY):
    readers that do not know it skip it without parsing, so the
    container version stays unchanged.
    """
    header = _container_header(st, T)
    if st.tindex is not None:
        header[encode.TRACK_INDEX_KEY] = st.tindex.finalize(
            (T, st.H, st.W))
    return header


def _container_header(st: _State, T: int):
    cfg = st.cfg
    # device-codec containers hold CPTH1 unit frames an older reader
    # cannot parse, so only THEY bump to v5; host-codec containers stay
    # at v4 (old readers keep working, and the v4 golden pin in
    # tests/test_container_golden.py stays exact).  An adaptive eb
    # policy bumps to v6 regardless of codec -- its bytes depend on the
    # policy, so it can never alias a uniform container.
    if st.policy is not None:
        version = TILED_FORMAT_VERSION_ADAPTIVE
    elif st.ex.codec == "device":
        version = TILED_FORMAT_VERSION_DEVICE
    else:
        version = TILED_FORMAT_VERSION
    header = {
        "version": version,
        "pipeline": "tiled",
        "predictor": cfg.predictor,
        "sl_backend": st.ex.plan.op_bindings["semilagrange"],
        "shape": [int(T), int(st.H), int(st.W)],
        "scale": float(st.scale),
        "xi_unit": int(st.xi_unit),
        "block": int(cfg.block),
        "cfl_x": float(cfg.dt / cfg.dx),
        "cfl_y": float(cfg.dt / cfg.dy),
        "d_max": float(cfg.d_max),
        "n_max": int(cfg.n_max),
        "eb_abs": float(st.eb_abs),
        "tiling": dataclasses.asdict(st.grid),
    }
    if st.policy is not None:
        header["eb_policy"] = ebpolicy.policy_spec(st.policy)
    return header


def _stats(st: _State, T, blob, t0):
    """Stream stats (monolithic keys + tiled extras).  Note
    verify_bad_counts sums PER-TILE counts: a bad seam face or halo
    vertex is counted once per tile that sees it, so the numbers are
    inflated relative to the monolithic pipeline's same-named stat
    (the forced-vertex SETS are identical; only the counting differs)."""
    orig_bytes = T * st.H * st.W * 4 * 2
    comp_bytes = len(blob) if blob is not None else st.writer.bytes_written
    return {
        "orig_bytes": orig_bytes,
        "comp_bytes": comp_bytes,
        "ratio": orig_bytes / max(comp_bytes, 1),
        "lossless_frac": st.n_ll / max(st.n_verts, 1),
        "sl_block_frac": st.n_sl_blocks / max(st.n_blocks, 1),
        "verify_rounds": st.rounds,
        "verify_bad_counts": st.bad_counts,
        "eb_abs": st.eb_abs,
        "scale": st.scale,
        "tau": st.tau,
        "xi_unit": st.xi_unit,
        "seconds": time.perf_counter() - t0,
        "backend": st.be,
        "bindings": st.ex.plan.op_bindings,
        "pipeline": "tiled",
        "n_units": st.n_units,
        "tiling": dataclasses.asdict(st.grid),
        "batch_units": st.ex.plan.batch_units,
    }


class _Window:
    def __init__(self, wi, t0, t1, specs):
        self.wi, self.t0, self.t1 = wi, t0, t1
        self.specs = specs
        self.et1 = max(s.et1 for s in specs)
        self.derived = False
        self.screened = False
        self.emitted = False


# ----------------------------------------------------------------------
# public entry points: in-memory tiled + streaming
# ----------------------------------------------------------------------

def _prepare(u, v, cfg, grid: TileGrid, sink=None):
    """Load an in-memory field into stream state + derive every window
    (phase 1).  Split out so tests can drive the fixpoint directly."""
    u, v = compressor._as_fields(u, v)
    T, H, W = u.shape
    vrange = (float(min(u.min(), v.min())), float(max(u.max(), v.max())))
    st = _init_state(cfg, grid, H, W, vrange, sink)
    for t in range(T):
        _add_frame(st, t, u[t], v[t])
    windows = []
    for wi in range(-(-T // grid.window_t)):
        t0 = wi * grid.window_t
        t1 = min(t0 + grid.window_t, T)
        et1 = min(t1 + grid.thalo, T)
        windows.append(_Window(wi, t0, t1,
                               window_specs(wi, t0, t1, H, W, et1, grid)))
    for w in windows:
        _derive_window(st, w)
    return st, windows, T


def compress_tiled(u, v, cfg=None, grid: Optional[TileGrid] = None,
                   sink=None):
    """Tiled compression of an in-memory field; bit-identical output to
    the monolithic fused pipeline (global verify fixpoint across all
    units).  Returns (blob, stats) -- blob is None when ``sink`` given.
    """
    cfg = cfg or compressor.CompressionConfig()
    grid = grid or getattr(cfg, "tiling", None) or TileGrid()
    grid.validate()
    t_start = time.perf_counter()
    with obs.span("tiling.compress_tiled", codec=None) as _sp:
        st, windows, T = _prepare(u, v, cfg, grid, sink)
        _sp.set(codec=st.ex.codec, n_windows=len(windows),
                shape=[int(T), int(st.H), int(st.W)])
        if cfg.verify:
            with obs.span("tiling.fixpoint", n_windows=len(windows)):
                _fixpoint(st, windows, frontier=0)
        for w in windows:
            _emit_window(st, w)
        blob = st.writer.finish(_finish_header(st, T))
    return blob, _stats(st, T, blob, t_start)


def compress_stream(pairs, cfg=None, grid: Optional[TileGrid] = None,
                    value_range=None, sink=None, async_engine=False,
                    resume=False, faults=None, stage_timeout=None,
                    autotune=False, n_frames_hint=None):
    """Streaming tiled compression of an iterable of (u_t, v_t) frames.

    ``value_range=(lo, hi)`` must be the exact global min/max over both
    components (it fixes the fixed-point scale and the relative error
    bound before the stream starts); without it the stream is
    materialized and delegated to compress_tiled.  Holds ~2 windows of
    frames; emits each unit as soon as later frames can no longer
    change its verify outcome.  Returns (blob, stats); blob is None
    when writing to ``sink``.

    ``async_engine=True`` runs the out-of-core concurrent engine
    (core/stream_engine.py): frame ingestion, device encode/verify and
    CPU symbolize/pack overlap on three stages, producing bytes
    IDENTICAL to the serial path (and to compress_tiled) -- only the
    scheduling changes, never the emission order or the packed streams.

    Crash recovery: when ``sink`` is a filesystem path the run keeps a
    write-ahead journal at ``<sink>.journal`` (fsync'd at window
    boundaries).  After a crash, rerunning with ``resume=True``
    restarts from the last durable checkpoint: already-final container
    bytes are kept, the scheduler state is restored, and only frames
    from the journal's ``resume_from`` onward are consumed from
    ``pairs`` -- the finished container is byte-identical to an
    uninterrupted run (DESIGN.md #12).  ``pairs`` may be a callable
    ``pairs(t_start) -> iterable`` so a source can seek instead of
    replaying (a plain iterable is skipped forward).

    ``faults`` (core/faults.py FaultPlan) and ``stage_timeout``
    (seconds; also REPRO_STAGE_TIMEOUT) are the fault-injection /
    watchdog hooks of the async engine -- test and benchmark plumbing,
    inert in production use.

    ``autotune=True`` picks grid/backend/codec/scheduling via the cost
    model (repro.autotune, model-only: a stream cannot be rerun per
    candidate) before any frame is compressed; ``n_frames_hint`` bounds
    the workload estimate when ``pairs`` has no ``len``.  Incompatible
    with ``resume`` -- a resumed run must replay the original plan
    bit-for-bit, not search for a new one.
    """
    cfg = cfg or compressor.CompressionConfig()
    if autotune:
        if resume:
            raise ValueError(
                "autotune=True cannot be combined with resume=True: a "
                "resumed run must replay the journaled plan exactly; "
                "rerun with the original grid/config")
        from .. import autotune as autotune_mod

        src = pairs(0) if callable(pairs) else pairs
        n_frames = None
        try:
            n_frames = len(src)
        except TypeError:
            pass
        it = iter(src)
        try:
            first = next(it)
        except StopIteration:
            raise ValueError("autotune=True needs at least one frame")
        H, W = np.asarray(first[0]).shape
        cfg, cand = autotune_mod.tune_stream(
            (n_frames or n_frames_hint or 64, H, W), cfg)
        grid = cfg.tiling
        async_engine = cand.async_engine
        pairs = itertools.chain([first], it)
    grid = grid or getattr(cfg, "tiling", None) or TileGrid()
    grid.validate()
    from . import stream_engine

    if value_range is None:
        if resume:
            raise ValueError(
                "resume=True needs an explicit value_range: the range "
                "fixes the fixed-point scale, and a resumed run must "
                "derive bit-identical parameters without re-reading "
                "already-compressed frames")
        # the stream must be materialized to learn the global range;
        # with the async engine requested, derive the exact range and
        # still run the engine (same bytes either way) rather than
        # silently downgrading to the serial in-memory path
        src = pairs(0) if callable(pairs) else pairs
        frames = [(np.asarray(uf, np.float32), np.asarray(vf, np.float32))
                  for uf, vf in src]
        if not async_engine:
            u = np.stack([f[0] for f in frames])
            v = np.stack([f[1] for f in frames])
            return compress_tiled(u, v, cfg, grid, sink=sink)
        lo = min(min(float(uf.min()), float(vf.min())) for uf, vf in frames)
        hi = max(max(float(uf.max()), float(vf.max())) for uf, vf in frames)
        pairs = frames
        value_range = (lo, hi)

    return stream_engine.run(pairs, cfg, grid, value_range, sink,
                             async_engine=async_engine, resume=resume,
                             faults=faults, stage_timeout=stage_timeout)


# ----------------------------------------------------------------------
# decode: full, region, read planning
# ----------------------------------------------------------------------

def _overlaps(box, region):
    t0, t1, i0, i1, j0, j1 = box
    rt0, rt1, ri0, ri1, rj0, rj1 = region
    return t0 < rt1 and rt0 < t1 and i0 < ri1 and ri0 < i1 \
        and j0 < rj1 and rj0 < j1


def _source_of(src):
    """ContainerSource over bytes or a path (persistent handle + typed
    short-read errors + decoded-unit cache id; analysis/query.py)."""
    from ..analysis import query as query_mod

    return query_mod.ContainerSource(src)


def _plan_entries(hdr: dict, region=None):
    """Directory entries overlapping ``region`` -- the ONE place the
    coverage rule lives (read planning and region decode must never
    diverge on which units a region touches)."""
    if region is None:
        return list(hdr["units"])
    return [e for e in hdr["units"] if _overlaps(e["box"], region)]


def read_plan(src, region=None):
    """Directory entries a region decode touches -- and nothing else.
    ``src`` is container bytes or a filesystem path."""
    with _source_of(src) as source:
        hdr = source.header()
    return _plan_entries(hdr, region)


@dataclasses.dataclass
class DecodeReport:
    """What a degraded-mode decode could and could not recover.

    ``missing_units`` lists one dict per unit that failed its checksum
    or could not be read ({"key", "box", "error"}); the corresponding
    output voxels are holes (left at 0).  A report with no missing
    units is a complete decode.

    ``retries`` is the per-site :func:`faults.retry_stats` snapshot
    taken when the decode finished -- a decode that only succeeded
    because the source retried transient read errors is visible here
    instead of looking identical to a clean one."""

    n_units: int = 0                 # units the region plan touched
    n_decoded: int = 0
    missing_units: list = dataclasses.field(default_factory=list)
    retries: dict = dataclasses.field(default_factory=dict)

    @property
    def complete(self) -> bool:
        return not self.missing_units

    def hole_mask(self, region):
        """(T, H, W)-of-region bool mask of voxels lost to missing
        units (True = hole)."""
        rt0, rt1, ri0, ri1, rj0, rj1 = region
        mask = np.zeros((rt1 - rt0, ri1 - ri0, rj1 - rj0), dtype=bool)
        for m in self.missing_units:
            t0, t1, i0, i1, j0, j1 = m["box"]
            mask[max(t0, rt0) - rt0: max(min(t1, rt1) - rt0, 0),
                 max(i0, ri0) - ri0: max(min(i1, ri1) - ri0, 0),
                 max(j0, rj0) - rj0: max(min(j1, rj1) - rj0, 0)] = True
        return mask


def decompress_tiled(src, region=None, backend=None, degraded=False):
    """Decode a tiled container (whole field, or just ``region``).

    ``src`` is container bytes or a filesystem path (range reads only).
    Only the units whose owned boxes overlap the region are read
    (byte slices at directory offsets) and decoded -- and repeated or
    overlapping decodes are served from the process-wide decoded-unit
    cache (analysis/query.py) instead of re-reading and re-decoding
    covering units.

    ``degraded=True`` turns per-unit damage (checksum mismatch, short
    read) from a raise into a report: the return becomes
    ``(u, v, DecodeReport)``, damaged units' voxels are holes (0) and
    ``report.missing_units`` says exactly which and where.  Structural
    damage (corrupt footer/directory) still raises -- there is nothing
    to partially decode without a directory; run
    ``encode.salvage_container`` first.
    """
    from ..analysis import query as query_mod

    report = DecodeReport()
    with _source_of(src) as source:
        hdr = source.header()
        version = hdr.get("version", 1)
        if version > TILED_FORMAT_VERSION_ADAPTIVE:
            raise ValueError(
                f"container format version {version} is newer than this "
                f"decoder (supports <= {TILED_FORMAT_VERSION_ADAPTIVE})")
        T, H, W = hdr["shape"]
        if region is None:
            region = (0, T, 0, H, 0, W)
        rt0, rt1, ri0, ri1, rj0, rj1 = region
        if not (0 <= rt0 < rt1 <= T and 0 <= ri0 < ri1 <= H
                and 0 <= rj0 < rj1 <= W):
            raise ValueError(f"region {region} outside field "
                             f"({T}, {H}, {W})")
        ex = pipeline.executor_from_header(hdr, backend)
        u_out = np.zeros((rt1 - rt0, ri1 - ri0, rj1 - rj0),
                         dtype=np.float32)
        v_out = np.zeros_like(u_out)
        entries = _plan_entries(hdr, region)
        report.n_units = len(entries)
        failures = [] if degraded else None
        full = (rt0, rt1, ri0, ri1, rj0, rj1) == (0, T, 0, H, 0, W)
        if full:
            # full-field decode: stream unit-at-a-time (one compressed
            # frame resident at a time) and leave the unit cache alone
            # -- pinning a whole field of patches would evict every
            # entry with real reuse probability for zero future hits
            def decoded_iter():
                for entry in entries:
                    try:
                        uh, secs = source.unit(entry)
                        u_rec, v_rec = ex.decode_unit(uh, secs)
                    except encode.ContainerError as e:
                        if failures is None:
                            raise
                        failures.append((entry, e))
                        continue
                    yield tuple(uh["box"]), u_rec, v_rec
            decoded = decoded_iter()
        else:
            decoded, _ = query_mod.fetch_decoded_units(
                source, ex, entries, failures=failures)
        for box, u_rec, v_rec in decoded:
            t0, t1, i0, i1, j0, j1 = box
            ct0, ct1 = max(t0, rt0), min(t1, rt1)
            ci0, ci1 = max(i0, ri0), min(i1, ri1)
            cj0, cj1 = max(j0, rj0), min(j1, rj1)
            u_src = (slice(ct0 - t0, ct1 - t0), slice(ci0 - i0, ci1 - i0),
                     slice(cj0 - j0, cj1 - j0))
            dst = (slice(ct0 - rt0, ct1 - rt0),
                   slice(ci0 - ri0, ci1 - ri0),
                   slice(cj0 - rj0, cj1 - rj0))
            u_out[dst] = u_rec[u_src]
            v_out[dst] = v_rec[u_src]
            report.n_decoded += 1
        if failures:
            report.missing_units = [
                {"key": tuple(e["key"]), "box": tuple(e["box"]),
                 "error": str(err)} for e, err in failures]
    if degraded:
        from . import faults as faults_mod

        report.retries = faults_mod.retry_stats()
        return u_out, v_out, report
    return u_out, v_out


def decompress_region(src, region, backend=None, degraded=False):
    """Random-access decode of (t0, t1, i0, i1, j0, j1) -- reads only
    the units covering the region (cached across repeated queries).
    ``degraded=True`` reports damaged units instead of raising (see
    decompress_tiled)."""
    return decompress_tiled(src, region=region, backend=backend,
                            degraded=degraded)
