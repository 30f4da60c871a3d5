"""Critical-point-trajectory-preserving compressor (paper Alg. 3).

Public API:

    blob, stats = compress(u, v, CompressionConfig(eb=...))
    u_rec, v_rec = decompress(blob)

Pipeline (encode):
  1. fixed-point conversion (fixedpoint.py)
  2. face predicates + per-vertex error bounds (ebound.py, Alg. 2/4)
  3. eb log-quantization + dual-quantization -> integer field X
  4. predictors: block-local 3D Lorenzo and/or semi-Lagrangian + MoP,
     routed through the kernel-dispatch backend (backend.py: pallas /
     xla / numpy implementations of the three hot ops)
  5. verify-and-correct: simulate the *exact* decode (including the
     float32 output rounding), re-evaluate SoS face predicates on the
     reconstruction, force the vertices of any violated face (or any
     vertex breaking the pointwise bound) to lossless, and repeat.  The
     loop is monotone (the lossless set only grows) and terminates; on
     exit FC_t = FC_s = 0 *by construction* -- an end-to-end guarantee
     rather than a derivation-time one (DESIGN.md #3.5).
  6. escape-coded symbol streams + lossless side channels -> zstd (or
     zlib-fallback) container (encode.py)

Since the pipeline-plan refactor (DESIGN.md #10) this module is a thin
driver: the stage graph lives in core/pipeline.py as a ``PipelinePlan``
executed by a ``PlanExecutor``, and the SAME stage implementations serve
the monolithic fused path, the legacy seed path (``cfg.fused=False`` /
``REPRO_FUSED=0`` -- just the alternate stage binding, kept so
benchmarks/timing.py can measure the fused speedup under identical
accounting) and the tiled/streaming paths (core/tiling.py).  Names like
``_decode_fields_parallel`` are re-exported here for backward
compatibility (tests, baselines, benchmarks).
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Optional

import jax
import numpy as np

from .. import perfflags
from . import backend as backend_mod
from . import ebound, ebpolicy, encode, fixedpoint, pipeline, predictors, \
    quantize
from .ebpolicy import DegenerateRangeError, TilePolicy, UniformPolicy

jax.config.update("jax_enable_x64", True)
perfflags.configure_compile_cache()

FORMAT_VERSION = pipeline.FORMAT_VERSION
FORMAT_VERSION_ADAPTIVE = pipeline.FORMAT_VERSION_ADAPTIVE


@dataclasses.dataclass
class CompressionConfig:
    eb: float = 1e-2                  # error bound
    mode: str = "rel"                 # 'abs' or 'rel' (relative to value range)
    predictor: str = "mop"            # 'mop' | 'lorenzo' | 'sl'
    block: int = predictors.DEFAULT_BLOCK
    n_levels: int = quantize.DEFAULT_LEVELS
    fixed_bits: int = fixedpoint.DEFAULT_BITS
    dt: float = 1.0
    dx: float = 1.0
    dy: float = 1.0
    d_max: float = 2.0
    n_max: int = 32
    zstd_level: int = 12
    verify: bool = True
    max_rounds: int = 12
    backend: Optional[str] = None     # 'pallas' | 'xla' | 'numpy' | None=auto
    fused: Optional[bool] = None      # None -> perfflags.fused_default()
    tiling: Optional[object] = None   # tiling.TileGrid -> tiled pipeline
    track_index: bool = True          # tiled: write the CPTT1 sidecar
                                      # track index (repro.analysis)
    batch_units: bool = True          # tiled: stack same-signature units
                                      # through the vmapped batched stages
                                      # (pipeline.py; False = per-unit loop)
    codec: str = "host"               # entropy stage: 'host' (per-unit
                                      # CPU Huffman + zstd/zlib) |
                                      # 'device' (batched accelerator
                                      # entropy stage, core/entropy.py)
    # execution-scheduling knobs (pipeline.PLAN_KNOBS): these change how
    # fast a fixed plan runs, NEVER the container bytes it produces --
    # repro.autotune searches over them alongside the plan knobs above
    batch_cap: int = 8                # tiled: max units per stacked batch
    q_in_frames: Optional[int] = None   # async engine ingest queue bound
                                        # (None -> max(window_t, 2))
    q_out_units: Optional[int] = None   # async engine handoff queue bound
                                        # (None -> 2 * tiles per window)
    # byte-changing plan knob (NOT a scheduling knob): per-(window,
    # tile) base-bound policy (core/ebpolicy.py).  None / "uniform" /
    # UniformPolicy() -> the scalar cfg.eb path, byte-identical to a
    # config predating the knob; a TilePolicy resolves into a
    # per-vertex base-bound field before the derive stage and bumps
    # the container version (DESIGN.md #16)
    eb_policy: Optional[object] = None


def _as_fields(u, v):
    u = np.asarray(u)
    v = np.asarray(v)
    # real raises (not asserts): input validation must hold under -O
    if u.shape != v.shape or u.ndim != 3:
        raise ValueError(
            f"expect (T, H, W) u and v, got {u.shape} and {v.shape}")
    if min(u.shape) < 2:
        raise ValueError(
            f"need at least a 2x2x2 space-time grid, got {u.shape}")
    return u.astype(np.float32), v.astype(np.float32)


def _eb_factor(u, v, cfg):
    """The mode factor turning a bound in ``cfg.eb`` units absolute:
    1.0 for ``abs``, the value range for ``rel``.  Raises
    :class:`DegenerateRangeError` on (near-)constant relative-mode
    fields, where the range carries no signal to scale with."""
    if cfg.mode == "abs":
        return 1.0
    lo = min(u.min(), v.min())
    hi = max(u.max(), v.max())
    # the subtraction stays in the fields' float32 (bit-compatibility
    # with the pre-policy scalar path)
    rng = float(hi - lo)
    ebpolicy.check_relative_range(rng, max(abs(float(lo)),
                                           abs(float(hi))))
    return max(rng, 1e-30)


def _abs_eb(u, v, cfg):
    return float(cfg.eb) * _eb_factor(u, v, cfg)


# ----------------------------------------------------------------------
# backward-compatible re-exports (implementations live in pipeline.py)
# ----------------------------------------------------------------------

_derive_eb_jit = ebound.derive_vertex_eb_jit
_predicates = pipeline._predicates_jit
_decode_fields = pipeline._decode_fields
_decode_fields_jit = pipeline._decode_fields_jit
_decode_fields_parallel = pipeline._decode_fields_parallel
_reconstruct = pipeline._reconstruct
_faces_to_vertex_mask = pipeline._faces_to_vertex_mask
_face_verts = pipeline._face_verts
_touched_faces = pipeline._touched_faces
_FusedFns = pipeline.UnitFns
_fused_fns = pipeline.unit_fns


def _encode_stage(ufp, vfp, eb, xi_unit, n_levels, lossless_extra,
                  cfg: CompressionConfig):
    """eb -> X fields (legacy quantize binding; eb is precomputed)."""
    return pipeline.legacy_quantize(ufp, vfp, eb, xi_unit, n_levels,
                                    lossless_extra)


def _residuals(xu, xv, scale, xi_unit, cfg: CompressionConfig):
    """Legacy predict binding (full residual stacks)."""
    return pipeline.legacy_residuals(
        xu, xv, scale, xi_unit, cfg.predictor, cfg.block,
        cfg.dt / cfg.dx, cfg.dt / cfg.dy, cfg.d_max, cfg.n_max)


# ----------------------------------------------------------------------
# public API
# ----------------------------------------------------------------------

def compress(u, v, cfg: Optional[CompressionConfig] = None,
             autotune: bool = False, target_ratio: Optional[float] = None):
    # default is constructed per call: a module-level default instance
    # would be shared (and mutable) across every caller
    if cfg is None:
        cfg = CompressionConfig()
    if target_ratio is not None:
        # rate-distortion mode: search per-unit base bounds (an eb
        # policy) until the container hits the target ratio, keeping
        # track-covering units at cfg.eb (repro.autotune.rate)
        from ..autotune import rate as rate_mod

        return rate_mod.compress_with_target(u, v, cfg,
                                             float(target_ratio))
    if autotune:
        # pick the fastest searched config for this input (calibrated
        # cost model + top-k measurement, repro.autotune); the chosen
        # config may set cfg.tiling, switch backend/codec etc. -- but
        # for the plan it picks, the bytes are identical to a
        # hand-configured run with that same plan
        from .. import autotune as autotune_mod

        cfg = autotune_mod.tune_config(u, v, cfg)
    if cfg.tiling is not None:
        from . import tiling
        return tiling.compress_tiled(u, v, cfg, cfg.tiling)
    fused = perfflags.fused_default() if cfg.fused is None else cfg.fused
    name = "fused" if fused else "legacy"
    be = backend_mod.resolve(cfg.backend) if fused else "xla"

    t0 = time.perf_counter()
    u, v = _as_fields(u, v)
    pol = ebpolicy.normalize(cfg.eb_policy)
    factor = _eb_factor(u, v, cfg)
    # the plan's global (tau, xi_unit) derive from the policy's LOOSEST
    # bound; per-vertex caps only ever clamp down from there, so the
    # quantization grid stays global and decode is unchanged
    eb_abs = float(cfg.eb if pol is None else
                   ebpolicy.max_bound(pol)) * factor
    scale, ufp, vfp = fixedpoint.to_fixed(u, v, cfg.fixed_bits)
    plan = pipeline.plan_from_cfg(cfg, be, scale, eb_abs, name)
    ex = pipeline.PlanExecutor(plan)
    if pol is None:
        enc = pipeline.compress_field(ex, u, v, ufp, vfp)
    else:
        enc = pipeline.compress_field(
            ex, u, v, ufp, vfp,
            eb_cap=ebpolicy.field_caps(pol, u.shape, factor, scale),
            eb_bound=ebpolicy.field_bounds(pol, u.shape, factor))
    return pipeline.pack_field(ex, u, v, enc, t0)


def decompress(blob, backend: Optional[str] = None):
    """Decode a container given as bytes or as a path to its file (a
    tiled container file is range-read, not loaded whole)."""
    from . import tiling

    if isinstance(blob, (str, os.PathLike)):
        with open(blob, "rb") as f:
            head = f.read(len(encode.MAGIC_TILED))
            if encode.is_tiled(head):
                return tiling.decompress_tiled(blob, backend=backend)
            blob = head + f.read()
    if encode.is_tiled(blob):
        return tiling.decompress_tiled(blob, backend=backend)
    header, sections = encode.unpack(blob)
    version = header.get("version", 1)
    if version > FORMAT_VERSION_ADAPTIVE:
        raise ValueError(
            f"container format version {version} is newer than this "
            f"decoder (supports <= {FORMAT_VERSION_ADAPTIVE})")
    ex = pipeline.executor_from_header(header, backend)
    return pipeline.decode_field_blob(ex, header, sections)
