"""Logical-axis sharding rules for params, optimizer state, activations.

Mesh axes:
  * ``model`` (tp): tensor parallel -- attention heads / ffn hidden /
    vocab / experts (EP).
  * ``data``  (dp + fsdp): batch sharding *and* the FSDP dimension of
    every weight matrix.
  * ``pod``   (multi-pod only): pure data parallelism across pods;
    gradients cross pods once per step (optionally compressed --
    train/grad_compress.py).  FSDP stays *within* a pod so parameter
    all-gathers never cross the inter-pod links.

Model code never names mesh axes: it calls ``act(x, kind)`` which applies
``with_sharding_constraint`` when rules are active (dry-run/production)
and is a no-op otherwise (CPU unit tests).

Param specs are assigned by leaf-path pattern matching; stacked-layer
leading dims are unsharded.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import re
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    dp: Tuple[str, ...] = ("data",)    # batch axes (includes 'pod' if present)
    fsdp: Optional[str] = "data"       # weight-shard axis (within-pod)
    tp: Optional[str] = "model"
    tp_size: int = 1
    dp_size: int = 1


_RULES: Optional[ShardingRules] = None


def rules_for_mesh(mesh: Mesh) -> ShardingRules:
    names = mesh.axis_names
    dp = tuple(a for a in ("pod", "data") if a in names)
    dp = dp or (names[0],)
    tp = "model" if "model" in names else None
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    return ShardingRules(
        dp=dp,
        fsdp="data" if "data" in names else None,
        tp=tp,
        tp_size=mesh.shape[tp] if tp else 1,
        dp_size=dp_size,
    )


@contextlib.contextmanager
def use_rules(rules: Optional[ShardingRules]):
    global _RULES
    prev = _RULES
    _RULES = rules
    try:
        yield
    finally:
        _RULES = prev


def current_rules() -> Optional[ShardingRules]:
    return _RULES


# ------------------------------------------------------------- activations

def act(x, kind: str):
    """Sharding constraint on an activation; no-op without active rules."""
    r = _RULES
    if r is None:
        return x
    spec = _ACT_SPECS[kind](r, x.shape)
    return jax.lax.with_sharding_constraint(x, spec)


def _cache_spec(r, shape):
    # (L, B, S, Hkv, Dh): heads over tp when divisible; otherwise shard
    # the HEAD DIM (contracting-dim TP -- partial logits + all-reduce).
    # Sharding S instead would make the decode dynamic-update-slice cross
    # shards and force a full cache rematerialization (perf iteration H4).
    if r.tp and shape[3] % r.tp_size == 0:
        return P(None, r.dp, None, r.tp, None)
    if r.tp and shape[4] % r.tp_size == 0:
        return P(None, r.dp, None, None, r.tp)
    return P(None, r.dp, None, None, None)


def _cache_seqshard_spec(r, shape):
    axes = tuple(a for a in (r.fsdp, r.tp) if a)
    return P(None, None, axes, None, None)


def _state_spec(r, shape):
    # recurrent state (L, B, H/feat, ...): feature over tp when divisible
    tp = r.tp if (r.tp and shape[2] % r.tp_size == 0) else None
    return P(None, r.dp, tp, *([None] * (len(shape) - 3)))


_ACT_SPECS = {
    # (B, S, D) replicated D between blocks
    "hidden": lambda r, s: P(r.dp, *([None] * (len(s) - 1))),
    # (B, S, V) vocab-sharded logits
    "logits": lambda r, s: P(r.dp, *([None] * (len(s) - 2)), r.tp),
    # (B, S, H*, ...) head-sharded tensor
    "heads": lambda r, s: P(r.dp, None, r.tp, *([None] * (len(s) - 3))),
    # (B, S) tokens
    "tokens": lambda r, s: P(r.dp, *([None] * (len(s) - 1))),
    "cache": _cache_spec,
    "cache_seqshard": _cache_seqshard_spec,
    "state": _state_spec,
}


# ------------------------------------------------------------- params

# (pattern, spec builder) -- first match wins; `l` = stacked-layer prefix
def _pp(*names):
    return re.compile("|".join(names))


_PARAM_RULES = [
    # embeddings
    (_pp(r"embedding$"), lambda r: P(r.tp, r.fsdp)),
    (_pp(r"lm_head$"), lambda r: P(r.fsdp, r.tp)),
    # attention
    (_pp(r"\bwq$", r"\bwk$", r"\bwv$"), lambda r: P(r.fsdp, r.tp)),
    (_pp(r"\bwo$"), lambda r: P(r.tp, r.fsdp)),
    (_pp(r"\bbq$", r"\bbk$", r"\bbv$"), lambda r: P(r.tp)),
    # mlp
    (_pp(r"w_gate$", r"w_up$", r"c_wk$", r"c_wr$", r"\bwr$", r"\bwg$"),
     lambda r: P(r.fsdp, r.tp)),
    (_pp(r"w_down$", r"c_wv$"), lambda r: P(r.tp, r.fsdp)),
    (_pp(r"b_up$"), lambda r: P(r.tp)),
    # moe (expert-parallel leading dim)
    (_pp(r"router$"), lambda r: P(r.fsdp, None)),
    (_pp(r"experts?/w_gate$",), lambda r: P(r.tp, r.fsdp, None)),
    # mamba
    (_pp(r"in_proj$", r"dt_proj$"), lambda r: P(r.fsdp, r.tp)),
    (_pp(r"out_proj$"), lambda r: P(r.tp, r.fsdp)),
    (_pp(r"x_proj$", r"a_log$"), lambda r: P(r.tp, None)),
    (_pp(r"conv_w$"), lambda r: P(None, r.tp)),
    (_pp(r"conv_b$", r"dt_bias$", r"d_skip$"), lambda r: P(r.tp)),
    # rwkv decay lora
    (_pp(r"w_lora_a$"), lambda r: P(r.fsdp, None)),
    (_pp(r"w_lora_b$"), lambda r: P(None, r.tp)),
]

_MOE_EXPERT = re.compile(r"(^|/)(w_gate|w_up|w_down)$")


def _leaf_spec(path: str, ndim: int, n_stack: int, r: ShardingRules) -> P:
    # expert tensors are 3D (E, ., .): match before generic mlp rules
    if ndim - n_stack == 3 and _MOE_EXPERT.search(path):
        if path.endswith("w_down"):
            base = (r.tp, None, r.fsdp)
        else:
            base = (r.tp, r.fsdp, None)
        return P(*([None] * n_stack), *base)
    for pat, builder in _PARAM_RULES:
        if pat.search(path):
            base = builder(r)
            base_t = tuple(base)
            # pad/trim to actual rank after the stacked prefix
            rank = ndim - n_stack
            if len(base_t) > rank:
                base_t = base_t[:rank]
            base_t = base_t + (None,) * (rank - len(base_t))
            return P(*([None] * n_stack), *base_t)
    return P()  # replicate (norm scales, small vectors)


def _path_str(path) -> str:
    parts = []
    for pk in path:
        if hasattr(pk, "key"):
            parts.append(str(pk.key))
        elif hasattr(pk, "idx"):
            parts.append(str(pk.idx))
        else:
            parts.append(str(pk))
    return "/".join(parts)


def param_specs(params_shape, rules: ShardingRules, stacked_prefixes=("blocks",
                "enc_blocks", "dec_blocks", "superblocks")):
    """Pytree of PartitionSpec matching `params_shape` (shapes/arrays)."""

    def spec(path, leaf):
        ps = _path_str(path)
        n_stack = 1 if any(f"{sp}/" in ps or ps.startswith(f"{sp}/")
                           for sp in stacked_prefixes) else 0
        nd = len(leaf.shape)
        if nd == 0:
            return P()
        s = _leaf_spec(ps, nd, n_stack, rules)
        # drop specs on dims that do not divide the mesh cleanly enough to
        # matter is left to GSPMD (it pads); nothing to do here.
        return s

    return jax.tree_util.tree_map_with_path(spec, params_shape)


def param_shardings(params_shape, mesh: Mesh):
    rules = rules_for_mesh(mesh)
    specs = param_specs(params_shape, rules)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs)


# --------------------------------------------------------- tile units
# Data parallelism for the tiled compression pipeline (core/tiling.py +
# core/pipeline.py BatchFns): (tile, window) units of one batching
# signature are stacked on a leading axis and mapped with vmap,
# shard_mapped over a 1-axis "tiles" mesh so the batch splits across
# every local device.  Tiles are independent by construction (halo-exact
# eb + seam-agreed verify), so the mapping needs no collectives --
# in_specs == out_specs == P("tiles").  Every batched pipeline stage
# (eb derivation, quantize, residuals, decode cumsum, pointwise check,
# sign screen, segment extraction) routes through map_tiles.


@functools.lru_cache(maxsize=1)
def tiles_mesh() -> Mesh:
    """1-axis mesh over every local device for tile-unit parallelism.

    Cached: the batched pipeline stages re-enter map_tiles at every jit
    trace, and mesh construction is not free."""
    # Auto axis: the padded rows are sliced off the sharded output,
    # which an Explicit (sharding-in-types) axis refuses
    return jax.make_mesh((jax.device_count(),), ("tiles",),
                         axis_types=(jax.sharding.AxisType.Auto,))


def map_tiles(fn, *batched):
    """Apply ``fn`` (one tile unit -> pytree) over a leading tile axis
    as shard_map(vmap(fn)) over the "tiles" mesh.

    A batch whose size does not divide the device count is PADDED up to
    a multiple (repeating the last tile) and the padded rows are dropped
    from every output leaf, so every batch splits across every device;
    ``fn`` must be row-independent (tile units are, by construction).
    On one device nothing is padded.
    """
    import jax.numpy as jnp

    batched = [jnp.asarray(b) for b in batched]
    n = int(batched[0].shape[0])
    pad = -n % jax.device_count()
    if pad:
        batched = [jnp.concatenate([b, jnp.repeat(b[-1:], pad, axis=0)],
                                   axis=0) for b in batched]
    spec = P("tiles")
    out = jax.shard_map(jax.vmap(fn), mesh=tiles_mesh(),
                        in_specs=spec, out_specs=spec)(*batched)
    if pad:
        out = jax.tree.map(lambda leaf: leaf[:n], out)
    return out


# --------------------------------------------------------- host workers
# Shared host-side thread pools for the out-of-core paths: the async
# stream engine's stage threads hand work off through queues, but the
# served-read layer (analysis/query.py) fans CONCURRENT RANGE READS of
# unit frames over a pool -- reads are I/O-bound (page cache misses,
# network filesystems), so a handful of threads hides most of the
# latency without oversubscribing the host.

DEFAULT_HOST_WORKERS = 8


@functools.lru_cache(maxsize=8)
def host_pool(name: str, workers: int = DEFAULT_HOST_WORKERS):
    """Named, process-lifetime ThreadPoolExecutor for host-side I/O
    concurrency.  Cached by (name, workers): callers on a hot path
    (every track query) must not pay pool construction, and idle
    threads cost nothing."""
    from concurrent.futures import ThreadPoolExecutor

    return ThreadPoolExecutor(max_workers=workers,
                              thread_name_prefix=f"repro-{name}")


def host_map(pool, fn, items):
    """``pool.map`` with STRICT failure surfacing.

    ``Executor.map`` evaluates lazily and tears down mid-iteration on
    the first worker exception, silently abandoning later results.
    Here every item is submitted up front, every future is awaited, and
    the first exception (in submission order) re-raises on the caller's
    thread with its original type -- a worker can never fail without
    the caller seeing it.  Returns results in item order.
    """
    futures = [pool.submit(fn, it) for it in items]
    results, first_exc = [], None
    for f in futures:
        try:
            results.append(f.result())
        except BaseException as e:     # noqa: BLE001 -- re-raised below
            if first_exc is None:
                first_exc = e
            results.append(None)
    if first_exc is not None:
        raise first_exc
    return results
