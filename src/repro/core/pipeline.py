"""Declarative pipeline plan + shared unit executor (DESIGN.md #10).

Every compression path in this repo -- monolithic fused, legacy (seed),
tiled and streaming -- runs the same stage graph

    fixedpoint -> eb-derive -> quantize -> predict -> verify-fixpoint
               -> symbolize -> pack

over *units* (a unit is a (field view, forced mask, eb, predicate
snapshot) tuple; the monolithic pipelines are the single-unit special
case).  This module owns:

* ``PipelinePlan``: the frozen description of one pipeline configuration
  -- global stream parameters (scale, tau, xi_unit, CFL, ...) plus the
  per-stage *bindings* that select a stage implementation.  The legacy
  seed pipeline is just the alternate binding set (``LEGACY_BINDINGS``:
  full predicate re-evaluation + sequential scan decode); the fused and
  tiled paths share ``FUSED_BINDINGS``.

* ``PlanExecutor``: binds a plan to executables -- the per-shape
  ``UnitFns`` stage registry, the shared SL stepper, and the batched
  ``BatchFns`` registry -- and exposes the stage entry points the
  drivers (core/compressor.py, core/tiling.py) orchestrate.

* Batched unit execution: same-signature units (one (ext_shape,
  owned_shape, owned offset) triple -- all interior tiles of a window
  share it) are stacked on a leading axis and run through vmapped
  encode/verify stages, shard_mapped over the ``("tiles",)`` mesh
  (parallel/sharding.py).  Why batched == sequential BITWISE:

    - quantize, Lorenzo residuals, MoP assembly, the decode cumsum, and
      every predicate/screen op are exact integer/boolean arithmetic --
      identical under any batching or backend (the DESIGN.md #4
      contract).
    - the reconstruction/pointwise checks are elementwise IEEE f64 ops
      (no reductions), bit-stable under vmap.
    - the two float-sensitive stages go through ONE executable in both
      modes by construction: SL prediction steps each unit through the
      same per-frame ``sl_stepper`` executable the sequential path (and
      the decoder) uses, and the MoP rate model runs the per-owned-shape
      ``UnitFns.mop_select`` executable per unit.

  So the residual streams, blockmaps and lossless masks -- hence the
  container bytes -- are byte-equal between ``batch_units=True`` and
  ``False`` (asserted in tests/test_pipeline_executor.py and
  benchmarks/timing.py's ``batched_vs_sequential`` section).

Compiled-stage registries (``unit_fns`` / ``batch_fns``) are explicit
keyed dicts, NOT an LRU: unit-shape churn (tile geometry sweeps, many
fields in one process) can never silently evict a live entry and
recompile every verify round.  Entries are keyed by the full static
signature and live for the process; ``clear_registries()`` resets them.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from . import backend as backend_mod
from . import ebound, ebpolicy, encode, fixedpoint, grid, mop, predictors, \
    quantize

jax.config.update("jax_enable_x64", True)

FORMAT_VERSION = 2
# written only by adaptive (non-uniform eb policy) monolithic encodes:
# the header additionally records the policy spec.  Uniform containers
# stay at FORMAT_VERSION, so pre-policy readers (and the goldens) are
# unaffected (DESIGN.md #16).
FORMAT_VERSION_ADAPTIVE = 3

STAGES = ("fixedpoint", "eb_derive", "quantize", "predict",
          "verify_fixpoint", "symbolize", "pack")

# stage bindings: (stage, variant) pairs; the variant names select the
# implementations below.  Stages not listed are shared by every plan.
FUSED_BINDINGS = (("encode", "fused"), ("decode", "parallel"),
                  ("verify", "screened"))
LEGACY_BINDINGS = (("encode", "legacy"), ("decode", "scan"),
                   ("verify", "full"))
# alternate symbolize/pack binding: the device-resident batched entropy
# stage (core/entropy.py) -- per-unit canonical Huffman bitstreams
# packed on the accelerator, emitted as self-describing CPTH1 frames.
# The default host binding keeps the zstd/zlib whole-payload codecs.
DEVICE_ENTROPY_BINDINGS = (("symbolize", "device"), ("pack", "device"))
HOST_ENTROPY_BINDINGS = (("symbolize", "host"), ("pack", "host"))
CODECS = ("host", "device")


def _codec_bindings(base: tuple, codec: str) -> tuple:
    if codec not in CODECS:
        raise ValueError(f"unknown codec {codec!r}; expected one of {CODECS}")
    return base + (DEVICE_ENTROPY_BINDINGS if codec == "device"
                   else HOST_ENTROPY_BINDINGS)


# Tunable plan/execution knobs: the single declarative source for every
# configurable default that plan construction and the tiled/streaming
# executors read.  ``plan_from_cfg`` and the execution paths resolve
# each knob through ``resolve_knobs`` (no scattered hand-set getattr
# defaults), and ``repro.autotune`` derives its search space from the
# same rows -- adding a knob here is the one step that exposes it to
# both.  Rows are (name, default); scheduling knobs (batch_cap, queue
# bounds) never reach the PipelinePlan and can never change container
# bytes -- only how fast a fixed plan executes.
PLAN_KNOBS = (
    ("predictor", "mop"),
    ("block", predictors.DEFAULT_BLOCK),
    ("n_levels", quantize.DEFAULT_LEVELS),
    ("zstd_level", 12),
    ("verify", True),
    ("max_rounds", 12),
    ("batch_units", True),       # stack same-signature units (vmapped)
    ("codec", "host"),           # entropy stage: host | device
    ("batch_cap", 8),            # tiled: max units per stacked batch
    ("q_in_frames", None),       # async engine: ingest queue bound
                                 # (None -> max(window_t, 2))
    ("q_out_units", None),       # async engine: handoff queue bound
                                 # (None -> max(2 * tiles_per_window, 2))
    ("eb_policy", None),         # BYTE-CHANGING plan knob: per-unit
                                 # base-bound policy (core/ebpolicy.py);
                                 # None/uniform -> the scalar path
)
PLAN_DEFAULTS = dict(PLAN_KNOBS)


def resolve_knobs(cfg) -> dict:
    """Every PLAN_KNOBS value for ``cfg``, falling back to the declared
    defaults for knobs the config object does not carry."""
    return {name: getattr(cfg, name, default)
            for name, default in PLAN_KNOBS}


@dataclasses.dataclass(frozen=True)
class PipelinePlan:
    """One pipeline configuration: global stream parameters + bindings.

    ``name`` is the container's ``pipeline`` tag ("fused" | "legacy" |
    "tiled"); "tiled" shares the fused bindings.
    """

    name: str
    predictor: str
    backend: str
    block: int
    n_levels: int
    scale: float
    eb_abs: float
    tau: int
    xi_unit: int
    n_usable: int
    cfl_x: float
    cfl_y: float
    d_max: float
    n_max: int
    zstd_level: int = 12
    verify: bool = True
    max_rounds: int = 12
    batch_units: bool = True
    codec: str = "host"
    # canonical spec tuple of the eb policy (ebpolicy.policy_spec);
    # None for uniform.  A PLAN knob, not a scheduling knob: it changes
    # container bytes, so it lives on the plan and in the header.
    eb_policy: object = None
    bindings: tuple = FUSED_BINDINGS + HOST_ENTROPY_BINDINGS

    @property
    def g2f(self) -> float:
        return (2.0 * self.xi_unit) / self.scale

    @property
    def op_bindings(self) -> dict:
        """op -> implementation actually run (backend.op_bindings)."""
        return backend_mod.op_bindings(self.backend, self.block,
                                       self.xi_unit)


def plan_from_cfg(cfg, be: str, scale: float, eb_abs: float,
                  name: str = "fused") -> PipelinePlan:
    """Plan from a CompressionConfig + the field-derived stream params.

    Every configurable default routes through PLAN_KNOBS/resolve_knobs
    -- plan construction is fully data-driven, so autotune's searched
    configs and hand-written ones resolve through the same table.
    """
    knobs = resolve_knobs(cfg)
    tau = max(int(np.floor(eb_abs * scale)), 0)
    xi_unit, n_usable = quantize.ladder(tau, knobs["n_levels"])
    return PipelinePlan(
        name=name,
        predictor=knobs["predictor"],
        backend=be,
        block=knobs["block"],
        n_levels=knobs["n_levels"],
        scale=scale,
        eb_abs=eb_abs,
        tau=tau,
        xi_unit=xi_unit,
        n_usable=n_usable,
        cfl_x=cfg.dt / cfg.dx,
        cfl_y=cfg.dt / cfg.dy,
        d_max=cfg.d_max,
        n_max=cfg.n_max,
        zstd_level=knobs["zstd_level"],
        verify=knobs["verify"],
        max_rounds=knobs["max_rounds"],
        batch_units=knobs["batch_units"],
        codec=knobs["codec"],
        eb_policy=ebpolicy.policy_spec(
            ebpolicy.normalize(knobs["eb_policy"])),
        bindings=_codec_bindings(
            LEGACY_BINDINGS if name == "legacy" else FUSED_BINDINGS,
            knobs["codec"]),
    )


def plan_from_header(header: dict, backend: Optional[str] = None
                     ) -> PipelinePlan:
    """Decode-side plan.  The fused/tiled decoder replays the SL stepper
    backend recorded in the header (``sl_backend``); the legacy decoder
    uses the pure-XLA scan."""
    name = header.get("pipeline", "legacy")
    if name == "legacy":
        be = "xla"
    else:
        be = backend_mod.resolve(backend or header.get("sl_backend"))
    xi_unit = int(header["xi_unit"])
    return PipelinePlan(
        name=name,
        predictor=header.get("predictor", "mop"),
        backend=be,
        block=int(header["block"]),
        n_levels=1,
        scale=float(header["scale"]),
        eb_abs=float(header.get("eb_abs", 0.0)),
        tau=0,
        xi_unit=xi_unit,
        n_usable=1,
        cfl_x=float(header["cfl_x"]),
        cfl_y=float(header["cfl_y"]),
        d_max=float(header["d_max"]),
        n_max=int(header["n_max"]),
        # decode is host-side either way (the section ``enc`` tags carry
        # the per-section codec); record which entropy stage encoded it
        codec="device" if header.get("codec") == "huffman" else "host",
        bindings=_codec_bindings(
            LEGACY_BINDINGS if name == "legacy" else FUSED_BINDINGS,
            "device" if header.get("codec") == "huffman" else "host"),
    )


# ----------------------------------------------------------------------
# shared static face tables (cached -- rebuilt per verify round before)
# ----------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def _face_tables(H: int, W: int):
    """Host (slice_tab, slab_tab) pair used by every verify round."""
    return grid.slab_faces(H, W)["slice0"], ebound.slab_face_table(H, W)


def _faces_to_vertex_mask(bad_slice, bad_slab, T, H, W):
    """Mark all vertices of violated faces (vectorized scatter)."""
    HW = H * W
    mask = np.zeros(T * HW, dtype=bool)
    slice_tab, slab_tab = _face_tables(H, W)
    t_ids, f_ids = np.nonzero(np.asarray(bad_slice))
    if len(t_ids):
        ids = slice_tab[f_ids].astype(np.int64) + t_ids[:, None] * HW
        mask[ids.reshape(-1)] = True
    t_ids, f_ids = np.nonzero(np.asarray(bad_slab))
    if len(t_ids):
        ids = slab_tab[f_ids].astype(np.int64) + t_ids[:, None] * HW
        mask[ids.reshape(-1)] = True
    return mask.reshape(T, H, W)


def _face_verts(ts, fs, tb, fb, H, W):
    """Global vertex-id triples for explicit (slice, slab) face indices."""
    HW = H * W
    slice_tab, slab_tab = _face_tables(H, W)
    return np.concatenate([
        slice_tab[fs].astype(np.int64) + ts[:, None] * HW,
        slab_tab[fb].astype(np.int64) + tb[:, None] * HW,
    ], axis=0)


def _touched_faces(delta_np, T, H, W):
    """Faces incident to newly-forced vertices -> (verts (N,3) global
    ids, slice_sel, slab_sel index arrays)."""
    HW = H * W
    slice_tab, slab_tab = _face_tables(H, W)
    d2 = delta_np.reshape(T, HW)
    t_slice = (d2[:, slice_tab[:, 0]] | d2[:, slice_tab[:, 1]]
               | d2[:, slice_tab[:, 2]])
    pair = np.concatenate([d2[:-1], d2[1:]], axis=1)
    t_slab = (pair[:, slab_tab[:, 0]] | pair[:, slab_tab[:, 1]]
              | pair[:, slab_tab[:, 2]])
    ts, fs = np.nonzero(t_slice)
    tb, fb = np.nonzero(t_slab)
    return _face_verts(ts, fs, tb, fb, H, W), (ts, fs), (tb, fb)


# ----------------------------------------------------------------------
# shared jitted stage pieces
# ----------------------------------------------------------------------

def _reconstruct(xu, xv, scale, xi_unit, lossless, u_raw, v_raw):
    g = 2.0 * xi_unit
    u_rec = (xu.astype(jnp.float64) * (g / scale)).astype(jnp.float32)
    v_rec = (xv.astype(jnp.float64) * (g / scale)).astype(jnp.float32)
    u_rec = jnp.where(lossless, u_raw, u_rec)
    v_rec = jnp.where(lossless, v_raw, v_rec)
    return u_rec, v_rec


def _recon_refix(xu_d, xv_d, lossless, u_raw, v_raw, scale, xi_unit,
                 eb_abs):
    """Reconstruct, re-fix and flag pointwise-bound violations."""
    u_rec, v_rec = _reconstruct(xu_d, xv_d, scale, xi_unit, lossless,
                                u_raw, v_raw)
    ur_fp = jnp.round(u_rec.astype(jnp.float64) * scale).astype(jnp.int64)
    vr_fp = jnp.round(v_rec.astype(jnp.float64) * scale).astype(jnp.int64)
    err = jnp.maximum(
        jnp.abs(u_rec.astype(jnp.float64) - u_raw.astype(jnp.float64)),
        jnp.abs(v_rec.astype(jnp.float64) - v_raw.astype(jnp.float64)),
    )
    bad_pt = err > eb_abs
    return ur_fp, vr_fp, bad_pt


def _quantize_core(ufp, vfp, eb_vertex, lossless_extra, xi_unit, n_levels):
    """eb -> (X_u, X_v, k, lossless); the ONE quantize-stage body every
    binding (sequential, batched, legacy) runs -- divergence here would
    break the batched == sequential byte-equality guarantee."""
    k, lossless = quantize.quantize_eb(eb_vertex, xi_unit, n_levels)
    lossless = jnp.logical_or(lossless, lossless_extra)
    k = jnp.where(lossless_extra, -1, k)
    xu = quantize.dual_quantize(ufp, k, lossless, xi_unit)
    xv = quantize.dual_quantize(vfp, k, lossless, xi_unit)
    return xu, xv, k, lossless


def _check_pt_core(xu_d, xv_d, lossless, lossless_extra, u_raw, v_raw,
                   scale, xi_unit, eb_abs):
    ur_fp, vr_fp, bad_pt = _recon_refix(
        xu_d, xv_d, lossless, u_raw, v_raw, scale, xi_unit, eb_abs)
    forced = lossless_extra | bad_pt
    return forced, jnp.asarray(bad_pt).sum(), ur_fp, vr_fp


def _screen_unsafe_core(shape, slice_tab, slab_tab, ufp, vfp, ur_fp, vr_fp):
    """Faces whose predicate COULD have flipped (sound screen).

    ``slice_tab``/``slab_tab`` are (3, F) slot rows (ebound.face_rows).

    A face all of whose u-components (or all of whose v-components)
    keep one strict sign in BOTH the original and the reconstruction
    cannot be crossed in either (the convex hull stays off the
    origin, SoS included), so its predicate is provably unchanged.
    Only the remaining faces -- a thin band around the zero set --
    need the exact SoS evaluation.  Pure boolean gathers: no int64
    products.
    """
    T, H, W = shape
    HW = H * W
    masks = []
    for o, r in ((ufp, ur_fp), (vfp, vr_fp)):
        masks.append(((o > 0) & (r > 0)).reshape(T, HW))
        masks.append(((o < 0) & (r < 0)).reshape(T, HW))
    masks = jnp.stack(masks, axis=1)                 # (T, 4, HW)

    def unsafe(tab):
        # per frame (lax.map): 1-D gathers keep the TPU compile time
        # independent of T
        def one(m4):
            pu, nu, pv, nv = (m[tab[0]] & m[tab[1]] & m[tab[2]]
                              for m in m4)
            return ~(pu | nu | pv | nv)
        return one

    unsafe_slice = jax.lax.map(unsafe(slice_tab), masks)
    pair = jnp.concatenate([masks[:-1], masks[1:]], axis=2)
    unsafe_slab = jax.lax.map(unsafe(slab_tab), pair)
    return unsafe_slice, unsafe_slab


# ----------------------------------------------------------------------
# per-shape unit stage functions (the keyed registry, DESIGN.md #10)
# ----------------------------------------------------------------------

class UnitFns:
    """Jitted stages of the fused pipeline for one static configuration
    (shape x block x n_levels x predictor x backend); registered once in
    the keyed ``unit_fns`` registry and shared by every path.

    ``be_lorenzo`` routes only the Lorenzo-residual op: the plan's
    ``op_bindings`` bind it to xla where the pallas kernel cannot
    compute it (block size, int32 headroom; backend.op_bindings).
    """

    def __init__(self, shape, block, n_levels, predictor, be,
                 be_lorenzo=None):
        self.shape = shape
        self.block = block
        self.n_levels = n_levels
        self.predictor = predictor
        self.be = be
        self.be_lorenzo = be if be_lorenzo is None else be_lorenzo
        T, H, W = shape
        self.nb = (-(-H // block), -(-W // block))
        slice_tab, slab_tab = _face_tables(H, W)
        self._slice_tab = jnp.asarray(ebound.face_rows(slice_tab))
        self._slab_tab = jnp.asarray(ebound.face_rows(slab_tab))
        jit = (lambda f, **kw: f) if be == "numpy" else jax.jit

        self.lorenzo_stage = jit(self._lorenzo_stage)
        self.quant_stage = jit(self._quant_stage)
        self.sl_stage = jit(self._sl_stage)
        self.mop_stage = jit(self._mop_stage)
        self.screen_unsafe = jit(self._screen_unsafe)
        self.check_pt = jit(self._check_pt)
        self.face_subset = jit(self._face_subset)
        # mop_select is ALWAYS jitted -- even on the numpy backend -- so
        # the float rate model runs through one executable per owned
        # shape in every mode (sequential, batched, any backend):
        # executable identity is what makes the blockmap -- hence the
        # container bytes -- mode-independent (module doc).
        self.mop_select = jax.jit(self._mop_select)
        self.mop_assemble = jax.jit(self._mop_assemble)

    # ---- encode stages

    def _quant_stage(self, ufp, vfp, eb_vertex, lossless_extra, xi_unit):
        return _quantize_core(ufp, vfp, eb_vertex, lossless_extra,
                              xi_unit, self.n_levels)

    def _lorenzo_stage(self, ufp, vfp, eb_vertex, lossless_extra, xi_unit):
        """Pure-Lorenzo encode: the fused dualquant+residual op, no X
        materialization."""
        k, lossless = quantize.quantize_eb(eb_vertex, xi_unit, self.n_levels)
        lossless = jnp.logical_or(lossless, lossless_extra)
        k = jnp.where(lossless_extra, -1, k)
        res_u = backend_mod.lorenzo_residual(
            ufp, k, lossless, xi_unit, self.block, self.be_lorenzo)
        res_v = backend_mod.lorenzo_residual(
            vfp, k, lossless, xi_unit, self.block, self.be_lorenzo)
        return res_u, res_v, lossless

    def _sl_stage(self, xu, xv, pu, pv):
        res_u = jnp.concatenate(
            [predictors.d2_block(xu[:1], self.block), xu[1:] - pu], axis=0)
        res_v = jnp.concatenate(
            [predictors.d2_block(xv[:1], self.block), xv[1:] - pv], axis=0)
        return res_u, res_v

    def _mop_stage(self, ufp, vfp, k, lossless, xu, xv, pu, pv, xi_unit):
        res3_u, res3_v, ressl_u, ressl_v = self._mop_residuals(
            ufp, vfp, k, lossless, xu, xv, pu, pv, xi_unit)
        bm = mop.select(res3_u, res3_v, ressl_u, ressl_v, self.block)
        res_u = mop.assemble(res3_u, ressl_u, bm, self.block)
        res_v = mop.assemble(res3_v, ressl_v, bm, self.block)
        return res_u, res_v, bm

    def _mop_residuals(self, ufp, vfp, k, lossless, xu, xv, pu, pv,
                       xi_unit):
        """MoP candidate residuals only; selection runs separately
        through the shared ``mop_select`` executable (unit paths)."""
        res3_u = backend_mod.lorenzo_residual(
            ufp, k, lossless, xi_unit, self.block, self.be_lorenzo, x=xu)
        res3_v = backend_mod.lorenzo_residual(
            vfp, k, lossless, xi_unit, self.block, self.be_lorenzo, x=xv)
        zero = jnp.zeros_like(xu[:1])
        ressl_u = jnp.concatenate([zero, xu[1:] - pu], axis=0)
        ressl_v = jnp.concatenate([zero, xv[1:] - pv], axis=0)
        return (jnp.asarray(res3_u), jnp.asarray(res3_v),
                ressl_u, ressl_v)

    def _mop_select(self, res3_u, res3_v, ressl_u, ressl_v):
        return mop.select(res3_u, res3_v, ressl_u, ressl_v, self.block)

    def _mop_assemble(self, res3_u, res3_v, ressl_u, ressl_v, bm):
        return (mop.assemble(res3_u, ressl_u, bm, self.block),
                mop.assemble(res3_v, ressl_v, bm, self.block))

    # ---- verify stages

    def _screen_unsafe(self, ufp, vfp, ur_fp, vr_fp):
        return _screen_unsafe_core(self.shape, self._slice_tab,
                                   self._slab_tab, ufp, vfp, ur_fp, vr_fp)

    def _check_pt(self, xu_d, xv_d, lossless, lossless_extra, u_raw, v_raw,
                  scale, xi_unit, eb_abs):
        return _check_pt_core(xu_d, xv_d, lossless, lossless_extra,
                              u_raw, v_raw, scale, xi_unit, eb_abs)

    def _face_subset(self, ur_flat, vr_flat, verts):
        """Predicates for an explicit face subset (incremental rounds);
        ``verts`` (3, B) global vertex ids, slot-major."""
        T, H, W = self.shape
        return backend_mod.face_crossed(
            ur_flat[verts], vr_flat[verts], verts.astype(jnp.int64),
            backend=self.be, n_verts=T * H * W)


# explicit keyed registries (no LRU: shape churn can never evict a live
# entry and silently recompile every verify round).  Guarded by a lock:
# the async stream engine and the served-read layer (analysis/query.py)
# may build executors from worker threads, and an unguarded get-or-create
# could construct the same UnitFns twice concurrently.
_UNIT_FNS: dict = {}
_BATCH_FNS: dict = {}
_BATCH_STAGES: dict = {}
_REGISTRY_LOCK = threading.Lock()


def unit_fns(shape, block, n_levels, predictor, be, be_lorenzo=None
             ) -> UnitFns:
    key = (tuple(shape), block, n_levels, predictor, be, be_lorenzo)
    with _REGISTRY_LOCK:
        fns = _UNIT_FNS.get(key)
        if fns is None:
            # registry miss = a fresh jit trace per stage; the retrace
            # counter is how shape churn shows up in obs.snapshot()
            obs.counter("pipeline.registry_miss.unit_fns").add(1)
            fns = _UNIT_FNS[key] = UnitFns(shape, block, n_levels,
                                           predictor, be, be_lorenzo)
    return fns


def clear_registries():
    from . import entropy
    with _REGISTRY_LOCK:
        _UNIT_FNS.clear()
        _BATCH_FNS.clear()
        _BATCH_STAGES.clear()
    entropy.clear_registry()


# ----------------------------------------------------------------------
# batched unit stage functions (one signature = one stacked batch)
# ----------------------------------------------------------------------

def unit_signature(ext_shape, owned_shape, owned_offset):
    """Batching signature: units sharing it can be stacked and run
    through one vmapped executable set."""
    return (tuple(ext_shape), tuple(owned_shape), tuple(owned_offset))


class _BatchStages:
    """The signature-offset-INDEPENDENT stage executables of BatchFns.

    Every stage here depends only on (ext_shape, block, n_levels) --
    NOT on the owned box -- so units whose signatures differ only in
    owned shape/offset (e.g. the four corner tiles of a window, or
    interior vs edge tiles) share ONE compiled executable set instead
    of recompiling identical programs per signature.  Only ``paste``
    (BatchFns) closes over the owned slice.
    """

    def __init__(self, ext_shape, block, n_levels):
        from ..parallel import sharding

        Te, he, we = ext_shape
        slice_tab, slab_tab = _face_tables(he, we)
        slice_tab = jnp.asarray(ebound.face_rows(slice_tab))
        slab_tab = jnp.asarray(ebound.face_rows(slab_tab))
        blk = block

        def _quant1(u, v, eb, extra, xi):
            return _quantize_core(u, v, eb, extra, xi, n_levels)

        def _res_lorenzo1(xu, xv):
            return (predictors.lorenzo_encode(xu, blk),
                    predictors.lorenzo_encode(xv, blk))

        def _res_sl1(xu, xv, pu, pv):
            ru = jnp.concatenate(
                [predictors.d2_block(xu[:1], blk), xu[1:] - pu], axis=0)
            rv = jnp.concatenate(
                [predictors.d2_block(xv[:1], blk), xv[1:] - pv], axis=0)
            return ru, rv

        def _res_mop1(xu, xv, pu, pv):
            r3u = predictors.lorenzo_encode(xu, blk)
            r3v = predictors.lorenzo_encode(xv, blk)
            zero = jnp.zeros_like(xu[:1])
            rsu = jnp.concatenate([zero, xu[1:] - pu], axis=0)
            rsv = jnp.concatenate([zero, xv[1:] - pv], axis=0)
            return r3u, r3v, rsu, rsv

        def _assemble1(r3u, r3v, rsu, rsv, bm):
            return (mop.assemble(r3u, rsu, bm, blk),
                    mop.assemble(r3v, rsv, bm, blk))

        def _decode_cumsum1(ru, rv):
            return (jnp.cumsum(predictors.c2_block(ru, blk), axis=0),
                    jnp.cumsum(predictors.c2_block(rv, blk), axis=0))

        def _check_pt1(xu_d, xv_d, ll, extra, u, v, scale, xi, eb_abs):
            return _check_pt_core(xu_d, xv_d, ll, extra, u, v,
                                  scale, xi, eb_abs)

        def _screen1(ufp, vfp, ur, vr):
            return _screen_unsafe_core((Te, he, we), slice_tab, slab_tab,
                                       ufp, vfp, ur, vr)

        def mt(fn):
            return jax.jit(lambda *b: sharding.map_tiles(fn, *b))

        self.quant = mt(_quant1)
        self.res_lorenzo = mt(_res_lorenzo1)
        self.res_sl = mt(_res_sl1)
        self.res_mop = mt(_res_mop1)
        self.assemble = mt(_assemble1)
        self.decode_cumsum = mt(_decode_cumsum1)
        self.check_pt = mt(_check_pt1)
        self.screen = mt(_screen1)


class BatchFns:
    """Vmapped + tiles-mesh-sharded stages for one unit signature.

    Per-unit scalars (xi_unit, scale, eb_abs) travel as (B,) arrays so
    one compiled executable serves every plan with this geometry.  Only
    exact integer/boolean and elementwise-f64 work lives here; the SL
    predictor and the MoP rate model are routed through the same
    executables as the sequential path (module doc).  All stages except
    ``paste`` are borrowed from the shared per-ext-shape _BatchStages
    entry (same registry lifetime), so same-geometry signatures never
    compile twice.
    """

    def __init__(self, sig, block, n_levels, stages: _BatchStages):
        (Te, he, we), (To, ho, wo), (dt0, di0, dj0) = sig
        self.sig = sig
        self.block = block
        self.n_levels = n_levels
        self.ext_shape = (Te, he, we)
        self.owned_shape = (To, ho, wo)
        self.owned = (slice(dt0, dt0 + To), slice(di0, di0 + ho),
                      slice(dj0, dj0 + wo))
        self.quant = stages.quant
        self.res_lorenzo = stages.res_lorenzo
        self.res_sl = stages.res_sl
        self.res_mop = stages.res_mop
        self.assemble = stages.assemble
        self.decode_cumsum = stages.decode_cumsum
        self.check_pt = stages.check_pt
        self.screen = stages.screen
        o = (slice(None),) + self.owned
        self.paste = jax.jit(
            lambda xe, ve, xd, vd: (xe.at[o].set(xd), ve.at[o].set(vd)))


def batch_fns(sig, block, n_levels) -> BatchFns:
    key = (sig, block, n_levels)
    with _REGISTRY_LOCK:
        fns = _BATCH_FNS.get(key)
        if fns is None:
            obs.counter("pipeline.registry_miss.batch_fns").add(1)
            skey = (sig[0], block, n_levels)
            stages = _BATCH_STAGES.get(skey)
            if stages is None:
                obs.counter("pipeline.registry_miss.batch_stages").add(1)
                stages = _BATCH_STAGES[skey] = _BatchStages(
                    sig[0], block, n_levels)
            fns = _BATCH_FNS[key] = BatchFns(sig, block, n_levels, stages)
    return fns


def _pad_pow2(arrays):
    """Pad each array's leading axis to the next power of two (repeating
    the last row) so jitted batched stages compile for O(log) distinct
    batch sizes instead of one per group size.  Returns (padded, n)."""
    n = int(arrays[0].shape[0])
    m = 1 << max(n - 1, 0).bit_length()
    if m == n:
        return [jnp.asarray(a) for a in arrays], n
    out = []
    for a in arrays:
        a = jnp.asarray(a)
        out.append(jnp.concatenate(
            [a, jnp.repeat(a[-1:], m - n, axis=0)], axis=0))
    return out, n


# ----------------------------------------------------------------------
# legacy (seed) stage implementations -- the alternate binding
# ----------------------------------------------------------------------

_predicates_jit = jax.jit(lambda ufp, vfp: ebound.all_face_predicates(
    ufp, vfp))


def legacy_quantize(ufp, vfp, eb, xi_unit, n_levels, lossless_extra):
    """Seed quantize stage: the shared core, k discarded."""
    xu, xv, _, lossless = _quantize_core(ufp, vfp, eb, lossless_extra,
                                         xi_unit, n_levels)
    return xu, xv, lossless


def legacy_residuals(xu, xv, scale, xi_unit, predictor, block,
                     cfl_x, cfl_y, d_max, n_max):
    """Seed predict stage: full residual stacks, no fused ops."""
    g2f = (2.0 * xi_unit) / scale
    T = xu.shape[0]
    nbi = -(-xu.shape[1] // block)
    nbj = -(-xu.shape[2] // block)
    if predictor == "lorenzo":
        res3_u = predictors.lorenzo_encode(xu, block)
        res3_v = predictors.lorenzo_encode(xv, block)
        bm = jnp.zeros((T, nbi, nbj), dtype=bool)
        return res3_u, res3_v, bm
    ressl_u, ressl_v = predictors.sl_encode(
        xu, xv, g2f, cfl_x, cfl_y, d_max, n_max)
    if predictor == "sl":
        # only frame 0 consumes a Lorenzo (spatial-only) residual; skip
        # the full 3DL stack the seed computed here
        res_u = ressl_u.at[0].set(predictors.d2_block(xu[0], block))
        res_v = ressl_v.at[0].set(predictors.d2_block(xv[0], block))
        bm = jnp.ones((T, nbi, nbj), dtype=bool).at[0].set(False)
        return res_u, res_v, bm
    res3_u = predictors.lorenzo_encode(xu, block)
    res3_v = predictors.lorenzo_encode(xv, block)
    bm = mop.select(res3_u, res3_v, ressl_u, ressl_v, block)
    res_u = mop.assemble(res3_u, ressl_u, bm, block)
    res_v = mop.assemble(res3_v, ressl_v, bm, block)
    return res_u, res_v, bm


def _decode_fields(res_u, res_v, blockmap, scale, xi_unit, block,
                   cfl_x, cfl_y, d_max, n_max):
    """Legacy decode: sequential scan over frames (seed pipeline)."""
    g2f = (2.0 * xi_unit) / scale
    T, H, W = res_u.shape

    def frame0(res_u0, res_v0):
        xu = predictors.c2_block(res_u0, block)
        xv = predictors.c2_block(res_v0, block)
        return xu, xv

    def step(carry, inp):
        xu_p, xv_p = carry
        ru, rv, bm = inp
        xu3 = predictors.lorenzo_decode_frame(xu_p, ru, block)
        xv3 = predictors.lorenzo_decode_frame(xv_p, rv, block)
        pu, pv = predictors.sl_predict_frame(
            xu_p, xv_p, g2f, cfl_x, cfl_y, d_max, n_max
        )
        xus = ru + pu
        xvs = rv + pv
        mask = jnp.repeat(jnp.repeat(bm, block, axis=0), block, axis=1)[:H, :W]
        xu = jnp.where(mask, xus, xu3)
        xv = jnp.where(mask, xvs, xv3)
        return (xu, xv), (xu, xv)

    xu0, xv0 = frame0(res_u[0], res_v[0])
    (_, _), (xu_rest, xv_rest) = jax.lax.scan(
        step, (xu0, xv0), (res_u[1:], res_v[1:], blockmap[1:])
    )
    xu = jnp.concatenate([xu0[None], xu_rest], axis=0)
    xv = jnp.concatenate([xv0[None], xv_rest], axis=0)
    return xu, xv


_decode_fields_jit = jax.jit(
    _decode_fields, static_argnums=(5, 8, 9), static_argnames=()
)


# ----------------------------------------------------------------------
# fused decode: parallel-in-time, shared by verify-sim and decompress
# ----------------------------------------------------------------------

def _decode_fields_parallel(res_u, res_v, blockmap, scale, xi_unit, block,
                            stepper):
    """Parallel-in-time decode shared by the verify simulation and
    decompress (one implementation => bitwise-consistent guarantees).

    ``blockmap`` is a HOST bool array (T, nbi, nbj): maximal runs of
    frames with no SL tile satisfy X_t = X_{t-1} + C2(res_t), a prefix
    sum decoded with one cumsum over time; only frames containing SL
    tiles step through the shared SL ``stepper`` executable.
    """
    res_u = jnp.asarray(res_u)
    res_v = jnp.asarray(res_v)
    bm = np.asarray(blockmap)
    T, H, W = res_u.shape
    g2f = (2.0 * xi_unit) / scale
    c2u = predictors.c2_block(res_u, block)   # every frame, in parallel
    c2v = predictors.c2_block(res_v, block)
    any_sl = bm.reshape(T, -1).any(axis=1)
    any_sl[0] = False                          # frame 0 is spatial-only
    if not any_sl.any():
        return jnp.cumsum(c2u, axis=0), jnp.cumsum(c2v, axis=0)
    Su = jnp.cumsum(c2u, axis=0)
    Sv = jnp.cumsum(c2v, axis=0)
    mask_rep = np.repeat(np.repeat(bm, block, axis=1), block, axis=2)[:, :H, :W]

    us, vs = [], []
    prev_u = prev_v = None
    cur = 0
    for t in np.flatnonzero(any_sl):
        t = int(t)
        if t > cur:
            if cur == 0:
                seg_u, seg_v = Su[:t], Sv[:t]
            else:
                seg_u = (prev_u - Su[cur - 1])[None] + Su[cur:t]
                seg_v = (prev_v - Sv[cur - 1])[None] + Sv[cur:t]
            us.append(seg_u)
            vs.append(seg_v)
            prev_u, prev_v = seg_u[-1], seg_v[-1]
        pu, pv = stepper(prev_u, prev_v, g2f)
        m = jnp.asarray(mask_rep[t])
        xu_t = jnp.where(m, res_u[t] + pu, prev_u + c2u[t])
        xv_t = jnp.where(m, res_v[t] + pv, prev_v + c2v[t])
        us.append(xu_t[None])
        vs.append(xv_t[None])
        prev_u, prev_v = xu_t, xv_t
        cur = t + 1
    if cur < T:
        us.append((prev_u - Su[cur - 1])[None] + Su[cur:])
        vs.append((prev_v - Sv[cur - 1])[None] + Sv[cur:])
    return jnp.concatenate(us, axis=0), jnp.concatenate(vs, axis=0)


# ----------------------------------------------------------------------
# face re-verification shared by monolithic and tiled rounds
# ----------------------------------------------------------------------

def screen_selection_from(unsafe_sl, unsafe_sb, H, W):
    """Host face selection from (already computed) screen masks."""
    ts, fs = np.nonzero(np.asarray(unsafe_sl))
    tb, fb = np.nonzero(np.asarray(unsafe_sb))
    return _face_verts(ts, fs, tb, fb, H, W), (ts, fs), (tb, fb)


def face_recheck(fns: UnitFns, shape, ur_fp, vr_fp, preds, selection):
    """Exact SoS re-evaluation of an explicit face selection against the
    original-predicate snapshot ``preds = (slice0, slab0)``.

    Returns (forced-additions bool array of ``shape`` or None, n_bad).
    """
    verts, (ts, fs), (tb, fb) = selection
    if not len(verts):
        return None, 0
    slice0, slab0 = preds
    orig = np.concatenate([slice0[ts, fs], slab0[tb, fb]])
    B = max(8, 1 << (len(verts) - 1).bit_length())
    verts_p = np.concatenate([
        verts,
        np.tile(np.array([[0, 1, 2]], np.int64), (B - len(verts), 1)),
    ], axis=0)
    crossed = np.asarray(fns.face_subset(
        ur_fp.reshape(-1), vr_fp.reshape(-1),
        jnp.asarray(np.ascontiguousarray(verts_p.T))))[: len(verts)]
    bad = crossed != orig
    if not bad.any():
        return None, 0
    T, H, W = shape
    add = np.zeros(T * H * W, dtype=bool)
    add[verts[bad].reshape(-1)] = True
    return add.reshape(shape), int(bad.sum())


def check_faces(fns: UnitFns, shape, ufp_j, vfp_j, ur_fp, vr_fp, preds,
                delta):
    """Face re-verification where predicates could have changed:
    ``delta is None`` -> the sign-stability screen (first contact);
    else only faces incident to newly-forced ``delta`` vertices."""
    T, H, W = shape
    if delta is None:
        unsafe_sl, unsafe_sb = fns.screen_unsafe(ufp_j, vfp_j, ur_fp, vr_fp)
        selection = screen_selection_from(unsafe_sl, unsafe_sb, H, W)
    else:
        selection = _touched_faces(delta, T, H, W)
    return face_recheck(fns, shape, ur_fp, vr_fp, preds, selection)


def _on_device(device):
    """Make ``device`` the calling thread's default device (a no-op for
    None), so that the arrays a decode makes inside (the SL frames'
    masks) land beside the unit's committed arrays instead of being
    copied over from the process's default device."""
    if device is None:
        return contextlib.nullcontext()
    return jax.default_device(device)


# ----------------------------------------------------------------------
# executor
# ----------------------------------------------------------------------

class PlanExecutor:
    """Binds a PipelinePlan to its executables and exposes the stage
    entry points (full-field and per-unit) that every driver routes
    through."""

    def __init__(self, plan: PipelinePlan):
        self.plan = plan
        self._impl = dict(plan.bindings)
        self.stepper = backend_mod.sl_stepper(
            plan.op_bindings["semilagrange"], plan.cfl_x, plan.cfl_y,
            plan.d_max, plan.n_max)

    @property
    def g2f(self):
        return self.plan.g2f

    def fns(self, shape) -> UnitFns:
        p = self.plan
        return unit_fns(shape, p.block, p.n_levels, p.predictor,
                        p.backend, p.op_bindings["lorenzo"])

    def batch_fns(self, sig) -> BatchFns:
        return batch_fns(sig, self.plan.block, self.plan.n_levels)

    # ---- eb-derive stage ------------------------------------------------

    def derive_eb(self, ufp_j, vfp_j):
        """Per-vertex bounds + original predicates (one pass: the
        crossed-face zeroing evaluates every SoS predicate anyway)."""
        return ebound.derive_vertex_eb_jit(
            ufp_j, vfp_j, int(max(self.plan.tau, 1)))

    # ---- decode stage ---------------------------------------------------

    def decode_fields(self, res_u, res_v, bm):
        p = self.plan
        if self._impl["decode"] == "scan":
            return _decode_fields_jit(
                jnp.asarray(res_u), jnp.asarray(res_v), jnp.asarray(bm),
                p.scale, p.xi_unit, p.block, p.cfl_x, p.cfl_y,
                p.d_max, p.n_max)
        return _decode_fields_parallel(
            res_u, res_v, np.asarray(bm), p.scale, p.xi_unit, p.block,
            self.stepper)

    def _sl_frames(self, bm) -> int:
        """Frames of a payload with blockmap ``bm`` that step through
        the SL stepper in ``decode_fields``."""
        bm = np.asarray(bm)
        if len(bm) < 2:
            return 0                   # frame 0 is spatial-only
        if self._impl["decode"] == "scan":
            return len(bm) - 1
        return int(bm[1:].reshape(len(bm) - 1, -1).any(axis=1).sum())

    def decode_payload(self, shape, sections, device=None, chip=None):
        """sections -> reconstructed (u, v) float32 numpy arrays.  One
        implementation for monolithic blobs and tiled container units.

        ``device``: the JAX device that runs the decode.  The unit's
        arrays are committed to it and every program of the decode (SL
        stepper, fusions, reconstruction) runs there; ``None`` leaves
        everything on the default device.  ``chip`` is the device's
        index in the caller's device set, recorded as the
        ``pipeline.decode_fields`` span's ``device`` arg.

        ``pipeline.decode_sections`` times the host work (section parse,
        lossless scatter), ``pipeline.decode_fields`` the device decode
        up to the arrays on the host: the closing ``np.asarray`` waits
        for the device, so that span needs no ``obs.device_sync``."""
        p = self.plan
        with obs.span("pipeline.decode_sections"):
            res_u, res_v, bm, ll = encode.parse_field_sections(sections,
                                                               shape)
            u_raw = np.zeros(shape, dtype=np.float32)
            v_raw = np.zeros(shape, dtype=np.float32)
            u_raw[ll] = sections["u_ll"]
            v_raw[ll] = sections["v_ll"]
        with obs.span("pipeline.decode_fields") as sp, \
                _on_device(device):
            if obs.enabled():
                sp.set(sl_frames=self._sl_frames(bm))
                if chip is not None:
                    sp.set(device=chip)
            if device is not None:
                res_u, res_v, ll, u_raw, v_raw = jax.device_put(
                    (res_u, res_v, ll, u_raw, v_raw), device)
            xu, xv = self.decode_fields(res_u, res_v, bm)
            u_rec, v_rec = _reconstruct(
                xu, xv, p.scale, p.xi_unit,
                jnp.asarray(ll), jnp.asarray(u_raw), jnp.asarray(v_raw))
            return np.asarray(u_rec), np.asarray(v_rec)

    def decode_unit(self, unit_header, sections, device=None, chip=None):
        t0, t1, i0, i1, j0, j1 = unit_header["box"]
        return self.decode_payload((t1 - t0, i1 - i0, j1 - j0), sections,
                                   device=device, chip=chip)

    # ---- symbolize/pack stage (host codec vs device entropy stage) ------

    @property
    def codec(self) -> str:
        return self._impl.get("symbolize", "host")

    def encode_sections(self, res_u, res_v, ll, u_ll, v_ll, bm) -> dict:
        """One unit's streams -> container section dict, routed through
        the plan's symbolize/pack binding: the host codec symbolizes on
        CPU (encode.field_sections), the device codec entropy-encodes
        the residual streams on the accelerator (core/entropy.py)."""
        if self.codec == "device":
            from . import entropy
            return entropy.field_sections_device(
                res_u, res_v, np.asarray(ll), u_ll, v_ll, np.asarray(bm),
                self.plan.backend)
        return encode.field_sections(res_u, res_v, np.asarray(ll),
                                     u_ll, v_ll, np.asarray(bm))

    def entropy_fragments(self, res_u_stack, res_v_stack) -> list:
        """Batched device entropy encode of stacked same-shape residual
        streams; returns one section fragment per unit (device codec
        only -- callers gate on ``codec``)."""
        from . import entropy
        return entropy.encode_streams(res_u_stack, res_v_stack,
                                      self.plan.backend)

    # ---- per-unit encode (tiled paths; ext-quantize + owned streams) ----

    def encode_unit(self, ufp_e, vfp_e, eb_e, extra_e, owned):
        """Sequential unit encode: quantize the halo extension, build
        the owned box's residual streams.  Returns (xu_e, xv_e, ll_e,
        res_u, res_v, bm(np))."""
        p = self.plan
        ext_shape = tuple(int(s) for s in ufp_e.shape)
        fns_e = self.fns(ext_shape)
        # bind the device copies once: every later use (quant, owned
        # slicing) reuses them instead of re-uploading the boxes
        ufp_j = jnp.asarray(ufp_e)
        vfp_j = jnp.asarray(vfp_e)
        xu_e, xv_e, k_e, ll_e = fns_e.quant_stage(
            ufp_j, vfp_j, jnp.asarray(eb_e), jnp.asarray(extra_e),
            p.xi_unit)
        o = owned
        owned_shape = tuple(
            int(s.stop - s.start) for s in o)
        fns_o = self.fns(owned_shape)
        res_u, res_v, bm = self._unit_streams(
            fns_o, ufp_j[o], vfp_j[o],
            k_e[o], ll_e[o], xu_e[o], xv_e[o])
        return xu_e, xv_e, ll_e, res_u, res_v, bm

    def _unit_streams(self, fns_o, ufp_o, vfp_o, k_o, ll_o, xu_o, xv_o):
        """Residual streams of one unit (the bytes that get stored).

        The temporal predictor restarts at the unit's first frame and
        the SL backtrace runs on the unit's own planes (tile-local), so
        decode of a unit touches nothing outside it.  Residual blocking
        cannot change the decoded X (exact integer inverses), so this
        stays bit-compatible with the monolithic output.
        """
        p = self.plan
        To, ho, wo = xu_o.shape
        nbi, nbj = fns_o.nb
        if p.predictor == "lorenzo":
            res_u = backend_mod.lorenzo_residual(
                ufp_o, k_o, ll_o, p.xi_unit, p.block, fns_o.be_lorenzo,
                x=xu_o)
            res_v = backend_mod.lorenzo_residual(
                vfp_o, k_o, ll_o, p.xi_unit, p.block, fns_o.be_lorenzo,
                x=xv_o)
            return res_u, res_v, np.zeros((To, nbi, nbj), dtype=bool)
        if To > 1:
            pu, pv = backend_mod.sl_predictions(xu_o, xv_o, self.g2f,
                                                self.stepper)
        else:
            pu = pv = jnp.zeros((0, ho, wo), jnp.int64)
        if p.predictor == "sl":
            res_u, res_v = fns_o.sl_stage(xu_o, xv_o, pu, pv)
            bm = np.ones((To, nbi, nbj), dtype=bool)
            bm[0] = False
            return res_u, res_v, bm
        r3u, r3v, rsu, rsv = fns_o._mop_residuals(
            ufp_o, vfp_o, k_o, ll_o, xu_o, xv_o, pu, pv, p.xi_unit)
        bm = fns_o.mop_select(r3u, r3v, rsu, rsv)
        res_u, res_v = fns_o.mop_assemble(r3u, r3v, rsu, rsv, bm)
        return res_u, res_v, np.asarray(bm)

    # ---- batched unit encode -------------------------------------------

    def encode_units(self, sig, ufp_es, vfp_es, eb_es, extra_es):
        """Batched encode of same-signature units stacked on axis 0.

        Integer stages run vmapped over the ("tiles",) mesh; SL goes
        per-unit through the shared stepper; MoP selection per-unit
        through the shared ``mop_select`` executable -- so the result is
        byte-equal to ``encode_unit`` per unit (module doc).
        Returns (xu_e, xv_e, ll_e, res_u, res_v, bms(np (B, ...))).
        """
        p = self.plan
        bf = self.batch_fns(sig)
        B = int(ufp_es.shape[0])
        (padded, _) = _pad_pow2([ufp_es, vfp_es, eb_es, extra_es])
        xis = jnp.full((padded[0].shape[0],), p.xi_unit, jnp.int64)
        xu_e, xv_e, k_e, ll_e = bf.quant(*padded, xis)
        ob = (slice(None),) + bf.owned
        xu_o, xv_o = xu_e[ob], xv_e[ob]
        To, ho, wo = bf.owned_shape
        nbi = -(-ho // p.block)
        nbj = -(-wo // p.block)
        if p.predictor == "lorenzo":
            res_u, res_v = bf.res_lorenzo(xu_o, xv_o)
            bms = np.zeros((B, To, nbi, nbj), dtype=bool)
            return xu_e[:B], xv_e[:B], ll_e[:B], res_u[:B], res_v[:B], bms
        if To > 1:
            # SL steps only the live rows (the padding rows repeat the
            # last unit; their predictions are re-padded to match)
            pu, pv = backend_mod.sl_predictions_batched(
                xu_o[:B], xv_o[:B], self.g2f, self.stepper)
            (pu, pv), _ = _pad_pow2([pu, pv])
        else:
            pu = pv = jnp.zeros((xu_o.shape[0], 0, ho, wo), jnp.int64)
        if p.predictor == "sl":
            res_u, res_v = bf.res_sl(xu_o, xv_o, pu, pv)
            bms = np.ones((B, To, nbi, nbj), dtype=bool)
            bms[:, 0] = False
            return xu_e[:B], xv_e[:B], ll_e[:B], res_u[:B], res_v[:B], bms
        r3u, r3v, rsu, rsv = bf.res_mop(xu_o, xv_o, pu, pv)
        fns_o = self.fns(bf.owned_shape)
        bms_dev = [fns_o.mop_select(r3u[b], r3v[b], rsu[b], rsv[b])
                   for b in range(B)]
        bms_j = jnp.stack(bms_dev)
        bms = np.asarray(bms_j)
        (bm_p,), _ = _pad_pow2([bms_j])
        res_u, res_v = bf.assemble(r3u, r3v, rsu, rsv, bm_p)
        return xu_e[:B], xv_e[:B], ll_e[:B], res_u[:B], res_v[:B], bms

    def decode_units(self, bf: BatchFns, res_u, res_v, bms):
        """Decode-sim of a unit batch: one batched cumsum when no unit
        contains an SL frame (exact integers), else the shared per-unit
        parallel decode."""
        if not bms[:, 1:].any():
            (ru_p, rv_p), n = _pad_pow2([res_u, res_v])
            xu, xv = bf.decode_cumsum(ru_p, rv_p)
            return xu[:n], xv[:n]
        p = self.plan
        xus, xvs = [], []
        for b in range(len(bms)):
            xu, xv = _decode_fields_parallel(
                res_u[b], res_v[b], bms[b], p.scale, p.xi_unit, p.block,
                self.stepper)
            xus.append(xu)
            xvs.append(xv)
        return jnp.stack(xus), jnp.stack(xvs)


def executor_from_header(header: dict, backend: Optional[str] = None
                         ) -> PlanExecutor:
    return PlanExecutor(plan_from_header(header, backend))


# ----------------------------------------------------------------------
# full-field drivers (quantize -> predict -> verify-fixpoint)
# ----------------------------------------------------------------------

@dataclasses.dataclass
class FieldEncode:
    """compress_field result: streams + masks + verify accounting."""

    res_u: object
    res_v: object
    bm: object
    lossless: object
    rounds: int
    bad_counts: list


class _ScreenedCtx:
    """Fused verify-loop state: original predicates (host copies fetched
    lazily) + the previous round's forced set (incremental rechecks)."""

    def __init__(self, slice0, slab0):
        self._dev = (slice0, slab0)
        self._np = None
        self.prev_extra = None

    def preds_np(self):
        if self._np is None:
            self._np = (np.asarray(self._dev[0]), np.asarray(self._dev[1]))
        return self._np


def _encode_field(ex: PlanExecutor, variant, ufp_j, vfp_j, eb_vertex,
                  lossless_extra, shape):
    """Quantize + predict stages on the full field -> (res_u, res_v,
    bm, lossless)."""
    p = ex.plan
    T, H, W = shape
    if variant == "legacy":
        xu, xv, lossless = legacy_quantize(
            ufp_j, vfp_j, eb_vertex, p.xi_unit, p.n_levels, lossless_extra)
        res_u, res_v, bm = legacy_residuals(
            xu, xv, p.scale, p.xi_unit, p.predictor, p.block,
            p.cfl_x, p.cfl_y, p.d_max, p.n_max)
        return res_u, res_v, bm, lossless
    fns = ex.fns(shape)
    nbi, nbj = fns.nb
    if p.predictor == "lorenzo":
        res_u, res_v, lossless = fns.lorenzo_stage(
            ufp_j, vfp_j, eb_vertex, lossless_extra, p.xi_unit)
        bm = np.zeros((T, nbi, nbj), dtype=bool)
        return res_u, res_v, bm, lossless
    xu, xv, k, lossless = fns.quant_stage(
        ufp_j, vfp_j, eb_vertex, lossless_extra, p.xi_unit)
    pu, pv = backend_mod.sl_predictions(xu, xv, ex.g2f, ex.stepper)
    if p.predictor == "sl":
        res_u, res_v = fns.sl_stage(xu, xv, pu, pv)
        bm = np.ones((T, nbi, nbj), dtype=bool)
        bm[0] = False
        return res_u, res_v, bm, lossless
    res_u, res_v, bm_dev = fns.mop_stage(
        ufp_j, vfp_j, k, lossless, xu, xv, pu, pv, p.xi_unit)
    return res_u, res_v, np.asarray(bm_dev), lossless


def _verify_screened(ex, ctx: _ScreenedCtx, shape, ufp_j, vfp_j, u_j, v_j,
                     xu_d, xv_d, lossless, lossless_extra,
                     eb_bound=None):
    """Fused verify round: device-resident pointwise check + screened /
    incremental face re-verification (DESIGN.md #3.5).

    ``eb_bound``: per-vertex absolute base bounds (adaptive policy);
    None keeps the plan's scalar -- the exact pre-policy trace."""
    p = ex.plan
    fns = ex.fns(shape)
    forced, n_pt, ur_fp, vr_fp = fns.check_pt(
        xu_d, xv_d, lossless, lossless_extra, u_j, v_j,
        p.scale, p.xi_unit,
        p.eb_abs if eb_bound is None else jnp.asarray(eb_bound))
    n_bad = int(n_pt)
    delta = None if ctx.prev_extra is None else np.asarray(
        lossless_extra ^ ctx.prev_extra)
    add, nf = check_faces(fns, shape, ufp_j, vfp_j, ur_fp, vr_fp,
                          ctx.preds_np(), delta)
    n_bad += nf
    if add is not None:
        forced = forced | jnp.asarray(add)
    return forced, n_bad


def _verify_full(ex, ctx: _ScreenedCtx, shape, u, v, xu_d, xv_d, lossless,
                 lossless_extra, eb_bound=None):
    """Legacy verify round: full predicate re-evaluation + host
    transfers (seed pipeline, kept for A/B benchmarking)."""
    p = ex.plan
    T, H, W = shape
    slice_pred0, slab_pred0 = ctx._dev
    u_rec, v_rec = _reconstruct(
        xu_d, xv_d, p.scale, p.xi_unit, lossless,
        jnp.asarray(u), jnp.asarray(v))
    ur_fp, vr_fp = fixedpoint.refix(np.asarray(u_rec), np.asarray(v_rec),
                                    p.scale)
    slice_pred1, slab_pred1 = _predicates_jit(
        jnp.asarray(ur_fp), jnp.asarray(vr_fp))
    bad_slice = np.asarray(slice_pred0 ^ slice_pred1)
    bad_slab = np.asarray(slab_pred0 ^ slab_pred1)
    err = np.maximum(
        np.abs(np.asarray(u_rec, dtype=np.float64) - u.astype(np.float64)),
        np.abs(np.asarray(v_rec, dtype=np.float64) - v.astype(np.float64)),
    )
    bad_pt = err > (p.eb_abs if eb_bound is None
                    else np.asarray(eb_bound))
    n_bad = int(bad_slice.sum()) + int(bad_slab.sum()) + int(bad_pt.sum())
    extra = np.asarray(lossless_extra).copy()
    extra |= bad_pt
    extra |= _faces_to_vertex_mask(bad_slice, bad_slab, T, H, W)
    return jnp.asarray(extra), n_bad


def compress_field(ex: PlanExecutor, u, v, ufp, vfp,
                   eb_cap=None, eb_bound=None) -> FieldEncode:
    """Full-field quantize -> predict -> verify-fixpoint driver; the
    monolithic pipelines are this single-unit loop (the tiled fixpoint
    in core/tiling.py runs the same stages per unit).

    ``eb_cap`` / ``eb_bound``: per-vertex int64 caps and float64
    absolute bounds of an adaptive eb policy; both None on the uniform
    path, which then runs the exact pre-policy traces."""
    p = ex.plan
    T, H, W = u.shape
    shape = (T, H, W)
    ufp_j = jnp.asarray(ufp)
    vfp_j = jnp.asarray(vfp)
    u_j = jnp.asarray(u)
    v_j = jnp.asarray(v)
    # eb derivation evaluates every face's SoS predicate along the way
    # (the crossed-face zeroing); reuse those instead of a second full
    # predicate pass over the original field (the seed paid it twice)
    with obs.span("pipeline.derive_eb", shape=list(shape)):
        eb_vertex, slice_pred0, slab_pred0 = ex.derive_eb(ufp_j, vfp_j)
        if eb_cap is not None:
            # adaptive policy: clamp the derived bounds DOWN to the
            # per-vertex caps -- min composes with the derivation's own
            # tau clamp, so ordering cannot matter
            eb_vertex = jnp.minimum(eb_vertex, jnp.asarray(eb_cap))
        obs.device_sync(eb_vertex)
    lossless_extra = jnp.zeros(shape, dtype=bool)
    if p.tau < 1 or p.n_usable < 1:
        lossless_extra = jnp.ones(shape, dtype=bool)

    enc_variant = ex._impl["encode"]
    verify_variant = ex._impl["verify"]
    ctx = _ScreenedCtx(slice_pred0, slab_pred0)
    rounds = 0
    bad_counts = []
    while True:
        with obs.span("pipeline.quantize_predict", round=rounds):
            res_u, res_v, bm, lossless = _encode_field(
                ex, enc_variant, ufp_j, vfp_j, eb_vertex, lossless_extra,
                shape)
            obs.device_sync(res_u)
        if not p.verify:
            break
        # simulate the exact decode (same code as decompress)
        with obs.span("pipeline.verify_round", round=rounds) as _vs:
            xu_d, xv_d = ex.decode_fields(res_u, res_v, bm)
            if verify_variant == "full":
                new_extra, n_bad = _verify_full(
                    ex, ctx, shape, u, v, xu_d, xv_d, lossless,
                    lossless_extra, eb_bound=eb_bound)
            else:
                new_extra, n_bad = _verify_screened(
                    ex, ctx, shape, ufp_j, vfp_j, u_j, v_j, xu_d, xv_d,
                    lossless, lossless_extra, eb_bound=eb_bound)
            _vs.set(n_bad=n_bad)
        bad_counts.append(n_bad)
        if n_bad == 0 or rounds >= p.max_rounds:
            break
        ctx.prev_extra = lossless_extra
        lossless_extra = new_extra
        rounds += 1
    obs.count("pipeline.verify_rounds", rounds)
    return FieldEncode(res_u, res_v, bm, lossless, rounds, bad_counts)


# ----------------------------------------------------------------------
# symbolize + pack + stats (shared assembly, all paths)
# ----------------------------------------------------------------------

def field_header(plan: PipelinePlan, shape) -> dict:
    T, H, W = shape
    header = {
        # the version only moves when the policy does: uniform
        # containers are byte-identical to pre-policy output
        "version": (FORMAT_VERSION_ADAPTIVE if plan.eb_policy
                    else FORMAT_VERSION),
        "pipeline": plan.name,
        "predictor": plan.predictor,
    }
    if plan.eb_policy:
        header["eb_policy"] = plan.eb_policy
    if plan.name != "legacy":
        header["sl_backend"] = plan.op_bindings["semilagrange"]
    header.update({
        "shape": [int(T), int(H), int(W)],
        "scale": float(plan.scale),
        "xi_unit": int(plan.xi_unit),
        "block": int(plan.block),
        "cfl_x": float(plan.cfl_x),
        "cfl_y": float(plan.cfl_y),
        "d_max": float(plan.d_max),
        "n_max": int(plan.n_max),
        "eb_abs": float(plan.eb_abs),
    })
    return header


def pack_field(ex: PlanExecutor, u, v, enc: FieldEncode, t0: float):
    """Symbolize + pack + stats for a full-field encode."""
    p = ex.plan
    lossless_np = np.asarray(enc.lossless)
    bm_np = np.asarray(enc.bm)
    with obs.span("pipeline.symbolize", codec=ex.codec):
        sections = ex.encode_sections(
            enc.res_u, enc.res_v, lossless_np, u[lossless_np],
            v[lossless_np], bm_np)
    with obs.span("pipeline.pack") as _ps:
        blob = encode.pack(field_header(p, u.shape), sections,
                           p.zstd_level)
        _ps.set(bytes=len(blob))
    t1 = time.perf_counter()
    orig_bytes = u.nbytes + v.nbytes
    stats = {
        "orig_bytes": orig_bytes,
        "comp_bytes": len(blob),
        "ratio": orig_bytes / max(len(blob), 1),
        "lossless_frac": float(lossless_np.mean()),
        "sl_block_frac": float(bm_np.mean()),
        "verify_rounds": enc.rounds,
        "verify_bad_counts": enc.bad_counts,
        "eb_abs": p.eb_abs,
        "scale": p.scale,
        "tau": p.tau,
        "xi_unit": p.xi_unit,
        "seconds": t1 - t0,
        "backend": p.backend,
        "bindings": p.op_bindings,
        "pipeline": p.name,
    }
    return blob, stats


def decode_field_blob(ex: PlanExecutor, header: dict, sections: dict):
    T, H, W = header["shape"]
    return ex.decode_payload((T, H, W), sections)
