"""Compile guards: the main path's kernels, compiled for a described
TPU v5e at Hurricane ISABEL widths (48 x 500 x 500), padded the way
their wrappers pad, with x64 on as the program runs.

Interpret mode hides what the TPU compiler refuses (memory spaces,
block shapes, 64-bit values inside a kernel, gathers).  These tests
compile without a chip; they say nothing about results or speed.  The
topology is described inside a fixture, never at import, so every
pytest worker collects the same tests and only the one running this
file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.core  # noqa: F401  (x64 on, as in the program)
from repro.core import backend, ebound
from repro.kernels.cptest import kernel as cp_kernel
from repro.kernels.entropy import kernel as ent_kernel
from repro.kernels.lorenzo import kernel as lz_kernel
from repro.kernels.semilagrange import kernel as sl_kernel

T, H, W = 48, 500, 500          # SDRBench Hurricane ISABEL


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _pad(n, m):
    return n + (-n) % m


def test_lorenzo_kernel_compiles(one_chip):
    shape = (T, _pad(H, lz_kernel.TILE_H), _pad(W, lz_kernel.TILE_W))
    c = _compile(
        lambda d, k, ll, xi: lz_kernel.dualquant_lorenzo_residual_pallas(
            d, k, ll, xi, interpret=False),
        one_chip, (shape, jnp.int32), (shape, jnp.int32),
        (shape, jnp.bool_), ((), jnp.int64))
    assert "tpu_custom_call" in c.as_text()


def test_cptest_kernel_compiles(one_chip):
    # one 500 x 500 slab's side + internal faces, padded as ops.py pads
    n = len(ebound.slab_face_table(H, W))
    R = _pad(-(-n // cp_kernel.TILE_C), cp_kernel.TILE_R)
    plane = ((3, R, cp_kernel.TILE_C), jnp.int32)
    c = _compile(
        lambda u, v, i: cp_kernel.face_crossed_pallas(u, v, i,
                                                      interpret=False),
        one_chip, plane, plane, plane)
    assert "tpu_custom_call" in c.as_text()


def test_entropy_kernel_compiles(one_chip):
    # the u and v residual rows of one full field, padded to CHUNK
    n = _pad(T * H * W, ent_kernel.CHUNK)
    c = _compile(
        lambda s: ent_kernel.symbol_histogram_pallas(s, interpret=False),
        one_chip, ((2, n), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


def test_sl_binding_is_the_xla_stepper(one_chip):
    """SL binds to the XLA stepper on pallas (core/backend.py BINDINGS);
    that stepper compiles for the chip at frame width."""
    assert backend.BINDINGS["pallas"]["semilagrange"] == "xla"
    step = backend.sl_stepper("xla", 1.0, 1.0, 2.0, 32)
    frame = ((H, W), jnp.int64)
    _compile(lambda u, v: step(u, v, 0.01), one_chip, frame, frame)


def test_sl_kernel_gathers_refused(one_chip):
    """The reason for that binding: Mosaic refuses the SL kernel's
    per-element 2D gathers.  If this starts to compile, revisit
    BINDINGS and bind SL to the kernel."""
    frame = ((H, W), jnp.float32)
    with pytest.raises(Exception) as refused:
        _compile(lambda u, v: sl_kernel.sl_predict_pallas(
            u, v, 1.0, 1.0, 2.0, 32, interpret=False),
            one_chip, frame, frame)
    # refused by Mosaic's gather lowering, not by anything else
    assert any(e.name == "_gather_lowering_rule" for e in refused.traceback)


def test_derive_eb_fits_the_chip(one_chip):
    """Per-vertex bound derivation at frame width compiles for the chip
    with its scratch far inside 16 GB HBM (slot-major face rows: with
    (F, 3) gathers it needed 8.5 GiB of scratch and minutes to
    compile)."""
    field = ((2, H, W), jnp.int64)
    c = _compile(lambda u, v: ebound.derive_vertex_eb(u, v, 1 << 20),
                 one_chip, field, field)
    assert c.memory_analysis().temp_size_in_bytes < 2 * 2**30
