"""Kernel-dispatch backend for the compression hot path (DESIGN.md #4).

The four hot ops of the pipeline -- fused dual-quantize + block-Lorenzo
residual, semi-Lagrangian prediction, the SoS face predicate and the
device entropy stage's symbol histogram -- are routed through one of
three backend names:

  ``pallas``  the Pallas TPU kernels under ``repro.kernels`` (compiled
              on TPU, ``interpret=True`` elsewhere) -- the production
              device path;
  ``xla``     the pure-jnp implementations in core (default off-TPU);
  ``numpy``   host reference implementations.

A backend name does not bind every op to its own implementation:
``BINDINGS`` is the one per-op table, and ``op_bindings`` adds the
per-plan rules (Lorenzo block size, int32 headroom).  Every dispatch
below follows the table; an op asked to run on a binding it cannot
honour raises instead of quietly running something else.

Determinism contract (DESIGN.md #4):

* The INTEGER ops (Lorenzo residual, SoS predicate, histogram) are
  exact and bit-identical across all three backends;
  tests/test_backend_parity.py enforces this on residual streams,
  lossless masks and blockmaps.
* The SL predictor is float and float arithmetic is not bit-stable
  across different XLA compilation contexts, so encoder, verify loop
  and decoder all call the SAME per-frame executable returned by
  ``sl_stepper`` -- consistency is structural, not numerical.  The
  blob header records which stepper produced the SL predictions
  (``sl_backend``: the op's binding, ``xla`` or ``numpy``) and
  decompress replays it.  Both steppers share f64 math.

Backend selection: explicit argument > ``REPRO_BACKEND`` env var
(perfflags) > auto (``pallas`` on TPU, ``xla`` elsewhere).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import perfflags
from ..kernels.cptest import ops as _cp_ops
from ..kernels.entropy import ops as _ent_ops
from ..kernels.lorenzo import ops as _lz_ops
from . import predictors, quantize, sos

BACKENDS = ("pallas", "xla", "numpy")
OPS = ("lorenzo", "semilagrange", "cptest", "entropy")

# op -> implementation for each backend name.  On ``pallas`` the SL op
# binds to the XLA stepper: its backtrace samples the previous frame at
# arbitrary per-element (row, col) positions, and Mosaic lowers only
# gathers shaped like take_along_axis along one axis (JAX 0.9.0 refuses
# the 2D VMEM gathers of kernels/semilagrange for TPU v5e), so that
# kernel cannot be compiled for the chip.
BINDINGS = {
    "pallas": {"lorenzo": "pallas", "semilagrange": "xla",
               "cptest": "pallas", "entropy": "pallas"},
    "xla": {op: "xla" for op in OPS},
    "numpy": {op: "numpy" for op in OPS},
}


def resolve(name: str | None = None) -> str:
    """Resolve a backend name (None -> env override -> hardware auto)."""
    name = name or perfflags.backend_override()
    if name is None:
        name = "pallas" if jax.default_backend() == "tpu" else "xla"
    if name not in BACKENDS:
        raise ValueError(f"unknown backend {name!r}; expected one of {BACKENDS}")
    return name


def op_bindings(be: str, block: int = predictors.DEFAULT_BLOCK,
                xi_unit: int = 4) -> dict:
    """The op -> implementation table of one plan.

    Beyond ``BINDINGS``, the pallas Lorenzo kernel binds only where it
    can compute the op: its tile-local context is the fixed
    ``LBLOCK`` (16) block, and it computes in int32, where at
    xi_unit < 4 a worst-case residual (8 * 2^29 / xi_unit) could wrap.
    Elsewhere the op binds to xla, and the plan says so.
    """
    table = dict(BINDINGS[be])
    if table["lorenzo"] == "pallas" and (
            block != _lz_ops.kernel.LBLOCK or xi_unit < 4):
        table["lorenzo"] = "xla"
    return table


# ----------------------------------------------------------------------
# op 1: fused dual-quantization + block-local 3D Lorenzo residual
# ----------------------------------------------------------------------

def _lorenzo_residual_np(dfp, k, lossless, xi_unit, block):
    dfp = np.asarray(dfp, np.int64)
    k = np.asarray(k)
    ll = np.asarray(lossless)
    g = np.int64(2 * int(xi_unit))
    kk = np.maximum(k, 0).astype(np.int64)
    q = g << kk
    x = (np.sign(dfp) * ((np.abs(dfp) + (q >> 1)) // q)) << kk
    x0 = np.sign(dfp) * ((np.abs(dfp) + (g >> 1)) // g)
    x = np.where(ll, x0, x)
    T, H, W = x.shape
    mi = ((np.arange(H) % block) != 0).astype(np.int64)[:, None]
    mj = ((np.arange(W) % block) != 0).astype(np.int64)[None, :]
    xi = np.zeros_like(x)
    xi[:, 1:, :] = x[:, :-1, :]
    xj = np.zeros_like(x)
    xj[:, :, 1:] = x[:, :, :-1]
    xij = np.zeros_like(x)
    xij[:, 1:, 1:] = x[:, :-1, :-1]
    d2 = x - xi * mi - xj * mj + xij * (mi * mj)
    res = d2.copy()
    res[1:] -= d2[:-1]
    return res


def lorenzo_residual(dfp, k, lossless, xi_unit,
                     block=predictors.DEFAULT_BLOCK, backend="xla", x=None):
    """Fused eb-quantize + dual-quantize + 3D-Lorenzo residual.

    dfp (T, H, W) int64 fixed-point; k int32 eb levels (-1 lossless);
    lossless bool.  Returns int64 residuals, identical across backends.
    ``x`` optionally passes the already-materialized dual-quantized
    field (the mop path computes it anyway for SL): the xla backend
    then skips the in-op re-quantization -- XLA cannot CSE across jit
    boundaries -- while the pallas kernel re-fuses it from dfp by
    design (one HBM pass) and the numpy reference stays self-contained.
    """
    if backend == "pallas":
        if block != _lz_ops.kernel.LBLOCK:
            raise ValueError(
                f"the pallas Lorenzo kernel computes {_lz_ops.kernel.LBLOCK}"
                f"-blocks, not {block}; bind the op with op_bindings()")
        out = _lz_ops.dualquant_lorenzo_residual(dfp, k, lossless, xi_unit)
        return out.astype(jnp.int64)
    if backend == "numpy":
        return _lorenzo_residual_np(dfp, k, lossless, xi_unit, block)
    if x is None:
        x = quantize.dual_quantize(dfp, k, lossless, xi_unit)
    return predictors.lorenzo_encode(x, block)


# ----------------------------------------------------------------------
# op 2: semi-Lagrangian prediction (canonical f32, predictors.py)
# ----------------------------------------------------------------------

def _bilinear_np(f, fi, fj):
    H, W = f.shape[-2], f.shape[-1]
    i0 = np.clip(np.floor(fi), 0, H - 1)
    j0 = np.clip(np.floor(fj), 0, W - 1)
    a = fi - i0
    b = fj - j0
    i0 = i0.astype(np.int32)
    j0 = j0.astype(np.int32)
    i1 = np.minimum(i0 + 1, H - 1)
    j1 = np.minimum(j0 + 1, W - 1)
    f00 = f[..., i0, j0]
    f01 = f[..., i0, j1]
    f10 = f[..., i1, j0]
    f11 = f[..., i1, j1]
    return (
        (1 - a) * (1 - b) * f00
        + (1 - a) * b * f01
        + a * (1 - b) * f10
        + a * b * f11
    )


def _sl_predict_frame_np(xu_prev, xv_prev, g2f, cfl_x, cfl_y, d_max, n_max):
    """numpy transcription of predictors.sl_predict_frame (f64 math)."""
    f64 = np.float64
    g2 = f64(g2f)
    u = np.asarray(xu_prev).astype(f64) * g2
    v = np.asarray(xv_prev).astype(f64) * g2
    H, W = u.shape
    cx = f64(cfl_x)
    cy = f64(cfl_y)
    ii, jj = np.meshgrid(np.arange(H, dtype=f64), np.arange(W, dtype=f64),
                         indexing="ij")
    d_inf = np.maximum(np.abs(u) * cx, np.abs(v) * cy)

    i_h = np.clip(ii - 0.5 * v * cy, 0.0, H - 1.0)
    j_h = np.clip(jj - 0.5 * u * cx, 0.0, W - 1.0)
    u_h = _bilinear_np(u, i_h, j_h)
    v_h = _bilinear_np(v, i_h, j_h)
    i_rk = ii - v_h * cy
    j_rk = jj - u_h * cx

    n_sub = np.clip(np.ceil(d_inf / d_max), 1.0, float(n_max))
    n_hi = float(n_sub.max())
    pi, pj = ii.copy(), jj.copy()
    s = 0
    while s < n_hi:
        us = _bilinear_np(u, pi, pj)
        vs = _bilinear_np(v, pi, pj)
        active = s < n_sub
        pi = np.where(active, np.clip(pi - vs * cy / n_sub, 0.0, H - 1.0), pi)
        pj = np.where(active, np.clip(pj - us * cx / n_sub, 0.0, W - 1.0), pj)
        s += 1

    use_rk = d_inf <= d_max
    i_s = np.clip(np.where(use_rk, i_rk, pi), 0.0, H - 1.0)
    j_s = np.clip(np.where(use_rk, j_rk, pj), 0.0, W - 1.0)
    pu = _bilinear_np(u, i_s, j_s) / g2
    pv = _bilinear_np(v, i_s, j_s) / g2
    return (np.rint(pu).astype(np.int64), np.rint(pv).astype(np.int64))


@functools.lru_cache(maxsize=64)
def sl_stepper(binding, cfl_x, cfl_y, d_max, n_max):
    """The per-frame SL prediction executable F(xu_prev, xv_prev, g2f).

    ``binding`` is the SL op's implementation (``xla`` or ``numpy``;
    see ``BINDINGS``).  F maps frame t-1's base-grid integer planes to
    frame t's integer predictions.  The SAME returned callable (one
    jitted executable per (binding, CFL, d_max, n_max)) is used by the
    encoder's residual pass, the verify loop's decode simulation, and
    decompress -- which is what makes the float prediction consistent
    end-to-end (module doc).  g2f stays a traced argument so eb sweeps
    don't recompile.
    """
    if binding == "numpy":
        def step_np(xu_prev, xv_prev, g2f):
            return _sl_predict_frame_np(
                np.asarray(xu_prev), np.asarray(xv_prev), float(g2f),
                cfl_x, cfl_y, d_max, n_max)
        return step_np
    if binding != "xla":
        raise ValueError(f"no SL stepper is bound to {binding!r}")

    @jax.jit
    def step_xla(xu_prev, xv_prev, g2f):
        return predictors.sl_predict_frame(
            xu_prev, xv_prev, g2f, cfl_x, cfl_y, d_max, n_max,
            early_exit=True)
    return step_xla


def sl_predictions(xu, xv, g2f, stepper):
    """Encoder-side predictions for frames 1..T-1 via T-1 calls of the
    shared stepper (dispatches pipeline asynchronously on device; the
    loop is over frames of ONE executable, not a fresh trace)."""
    pus, pvs = [], []
    for t in range(1, xu.shape[0]):
        pu, pv = stepper(xu[t - 1], xv[t - 1], g2f)
        pus.append(pu)
        pvs.append(pv)
    return jnp.stack(pus), jnp.stack(pvs)


def sl_predictions_batched(xus, xvs, g2f, stepper):
    """Predictions for a (B, T, H, W) batch of units, T >= 2.

    Deliberately NOT a vmap: float arithmetic is not bit-stable across
    compilation contexts (module doc), so every (unit, frame) steps
    through the SAME per-frame executable the sequential encode path and
    the decoder use -- batched output is bit-identical to per-unit
    output by construction.  All B * (T-1) dispatches are asynchronous.
    """
    pus, pvs = [], []
    for b in range(int(xus.shape[0])):
        pu, pv = sl_predictions(xus[b], xvs[b], g2f, stepper)
        pus.append(pu)
        pvs.append(pv)
    return jnp.stack(pus), jnp.stack(pvs)


# ----------------------------------------------------------------------
# op: batched connected-component labeling (trajectory stitching)
# ----------------------------------------------------------------------

_CCL_MAX_ROUNDS = 64


# module-level jits: defining these inside connected_labels would give
# every call fresh function objects and re-compile both executables
@jax.jit
def _ccl_hook_jnp(p, a, b):
    pa, pb = p[a], p[b]
    lo = jnp.minimum(pa, pb)
    hi = jnp.maximum(pa, pb)
    return p.at[hi].min(lo)


@jax.jit
def _ccl_jump_jnp(p):
    return p[p]


def _ccl_rounds(parent, ea, eb, hook, compress, all_equal):
    """Shared hook + pointer-jump driver (generic over array backend).

    Each round min-hooks every edge's endpoint labels and then pointer-
    jumps ``parent`` to its own fixpoint (full path compression), so
    label information spreads at a doubling rate along tracks.  The loop
    stops when a hook round changes nothing.  Labels only ever decrease
    and only toward ids inside the same component, so the fixpoint is
    exactly label[i] = min(component(i)) -- deterministic, identical
    across backends, and independent of edge order.
    """
    for _ in range(_CCL_MAX_ROUNDS):
        nxt = hook(parent, ea, eb)
        while True:
            jumped = compress(nxt)
            if all_equal(jumped, nxt):
                break
            nxt = jumped
        if all_equal(nxt, parent):
            return parent
        parent = nxt
    raise RuntimeError("connected_labels did not converge "
                       f"in {_CCL_MAX_ROUNDS} rounds")


def connected_labels(n: int, edges, backend="xla"):
    """Connected components of an undirected graph on nodes [0, n).

    edges: (E, 2) integer array.  Returns int64 labels with
    label[i] = min node id of i's component -- the device-resident
    replacement for the host union-find over trajectory crossing nodes
    (iterated min-hook + pointer jumping).  The integer op is exact, so
    all three backends return identical labels; ``pallas`` routes to the
    xla implementation (the op is pure gather/scatter, which XLA already
    emits as memory-bound kernels -- there is no compute to fuse).
    """
    edges = np.asarray(edges) if backend == "numpy" else jnp.asarray(edges)
    if n == 0:
        return np.empty(0, np.int64) if backend == "numpy" \
            else jnp.empty(0, jnp.int64)
    if edges.size == 0:
        return np.arange(n, dtype=np.int64) if backend == "numpy" \
            else jnp.arange(n, dtype=jnp.int64)

    if backend == "numpy":
        ea = np.asarray(edges[:, 0], np.int64)
        eb = np.asarray(edges[:, 1], np.int64)

        def hook(p, a, b):
            p = p.copy()
            pa, pb = p[a], p[b]
            lo = np.minimum(pa, pb)
            hi = np.maximum(pa, pb)
            np.minimum.at(p, hi, lo)
            return p

        return _ccl_rounds(np.arange(n, dtype=np.int64), ea, eb, hook,
                           lambda p: p[p], np.array_equal)

    ea = jnp.asarray(edges[:, 0], jnp.int64)
    eb = jnp.asarray(edges[:, 1], jnp.int64)
    return _ccl_rounds(
        jnp.arange(n, dtype=jnp.int64), ea, eb, _ccl_hook_jnp,
        _ccl_jump_jnp, lambda a, b: bool(jnp.array_equal(a, b)))


# ----------------------------------------------------------------------
# op 3: SoS face-crossing predicate
# ----------------------------------------------------------------------

def face_crossed(fu, fv, fidx, backend="xla", n_verts=None):
    """Exact SoS predicate on batched faces given slot-major: fu, fv,
    fidx (3, ...) -- row s holds every face's slot-s vertex.

    ``n_verts`` (static total space-time vertex count) guards the pallas
    int32-limb kernel's id-width precondition.
    """
    if backend == "pallas":
        if n_verts is not None and n_verts >= 2**31:
            raise ValueError(
                f"{n_verts} space-time vertices overflow the pallas "
                "predicate kernel's int32 vertex ids; tile the field")
        shape = fu.shape[1:]
        n = int(np.prod(shape)) if shape else 1
        out = _cp_ops.face_crossed_batch(
            jnp.reshape(fu, (3, n)), jnp.reshape(fv, (3, n)),
            jnp.reshape(fidx, (3, n)),
        )
        return jnp.reshape(out, shape)
    xp = jnp
    if backend == "numpy":
        xp = np
        fu, fv, fidx = np.asarray(fu), np.asarray(fv), np.asarray(fidx)
    return sos.face_crossed(xp, fu[0], fv[0], fidx[0], fu[1], fv[1],
                            fidx[1], fu[2], fv[2], fidx[2])


# ----------------------------------------------------------------------
# op 4: batched symbol histogram (device entropy stage, core/entropy.py)
# ----------------------------------------------------------------------

def _symbol_histogram_np(sym):
    # one flat bincount over row-offset keys (row i -> bins [256i, 256i+256))
    # instead of a per-row loop: one C pass regardless of B
    sym = np.asarray(sym)
    B, n = sym.shape
    keys = sym.astype(np.int32) + (np.arange(B, dtype=np.int32)[:, None] << 8)
    counts = np.bincount(keys.reshape(-1), minlength=B * 256)
    return counts.reshape(B, 256).astype(np.int32)


def symbol_histogram(sym, backend="xla"):
    """Per-row 256-bin histogram of a (B, n) uint8 symbol stack.

    Integer counts: exact and bit-identical across all three backends.
    The pallas path routes through kernels/entropy (compare-and-sum
    kernel on TPU, interpret mode elsewhere); xla uses the vmapped
    scatter-add reference; numpy is the host bincount loop.
    """
    if backend == "numpy":
        return _symbol_histogram_np(sym)
    if backend == "pallas":
        return _ent_ops.symbol_histogram(sym, force_pallas=True)
    return _ent_ops.symbol_histogram(sym, force_ref=True)
