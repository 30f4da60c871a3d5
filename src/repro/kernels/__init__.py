"""Pallas TPU kernels for the compression hot spots.

Each subpackage ships:
    kernel.py -- pl.pallas_call + BlockSpec VMEM tiling (TPU target)
    ops.py    -- wrapper that pads to the tiling and runs the kernel
                 compiled on TPU, in interpret mode elsewhere
    ref.py    -- pure-jnp oracle

Kernels are validated in interpret mode on CPU (exact equality for the
integer kernels) and compiled for a described TPU v5e in
tests/test_tpu_compile.py.  The semilagrange kernel does not compile
for the TPU and is bound nowhere (core/backend.py BINDINGS).
"""
