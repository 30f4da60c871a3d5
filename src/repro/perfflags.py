"""Perf-iteration A/B switch.

REPRO_PERF_BASELINE=1 reverts the beyond-baseline optimizations
(EXPERIMENTS.md #Perf iterations H1/H2/H3/H5) so baseline and optimized
cells can be lowered from the same tree under identical cost accounting:

  H1  flat-head sharding constraint on q/k/v projections
  H2  remat of mamba/rwkv chunk-scan bodies
  H3  bf16 chunk outputs (mamba y)
  H5  accumulator-typed norm/router statistics (vs f32 materialization)

(H4b, the padded decode KV cache, is toggled per-config via
``decode_head_pad``; H6, the sequential chunk scan, was refuted and
removed.)
"""
import os

BASELINE = os.environ.get("REPRO_PERF_BASELINE", "") == "1"


def backend_override():
    """REPRO_BACKEND=pallas|xla|numpy forces the kernel-dispatch backend
    for the compression hot path (core/backend.py); empty -> auto
    (pallas on TPU, xla elsewhere).  Read at call time so tests can
    monkeypatch the environment."""
    return os.environ.get("REPRO_BACKEND", "") or None


def fused_default():
    """REPRO_FUSED=0 reverts compressor.compress to the legacy
    (seed, per-round host-transfer) pipeline for A/B timing under
    identical accounting; default is the fused device-resident path."""
    return os.environ.get("REPRO_FUSED", "1") != "0"


# the checkout root: src/repro/perfflags.py -> <root>
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
JAX_CACHE_DIR = os.path.join(_ROOT, ".jax_cache")


def configure_compile_cache():
    """Place JAX's persistent compilation cache.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache lives at one fixed path
    inside the checkout (``<checkout>/.jax_cache``, git-ignored): the
    path is part of what the cache matches on, so it must not move
    between runs.  Returns the directory in use (None outside a source
    checkout, where the cache stays off).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if not os.path.exists(os.path.join(_ROOT, "pyproject.toml")):
        return None
    import jax

    jax.config.update("jax_compilation_cache_dir", JAX_CACHE_DIR)
    return JAX_CACHE_DIR


def checkpoint_if_optimized(fn):
    if BASELINE:
        return fn
    import jax

    return jax.checkpoint(fn)
