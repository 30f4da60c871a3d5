"""Operations and bytes of a Pallas kernel call, from its shapes.

A kernel call's text in the profiler trace is its HLO instruction,
``%name = <result> custom-call(<dtype>[dims]{layout} %operand, ...)``.
The bytes a call needs are each distinct operand read once and its
result written once (a kernel that reads one buffer through two block
specs, as Lorenzo reads frames t and t-1, still needs it once); the HBM
roofline of the call is those bytes over the chip's bandwidth.
Operations are counted per result element from the kernel's
arithmetic, for the figures line only: no VPU integer peak is
published, so no compute bound is claimed.
"""
from __future__ import annotations

import re

DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
               "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
               "f64": 8}
_ARRAY = r"(pred|s8|u8|s16|u16|bf16|f16|s32|u32|f32|s64|u64|f64)\[([0-9,]*)\]"
_OPERAND = re.compile(_ARRAY + r"(?:\{[^}]*\})? (%[\w.\-]+)")
_RESULT = re.compile(_ARRAY)

# integer operations per result element (a face for cptest, a vertex
# for lorenzo), read off the kernels' arithmetic
OPS_PER_ELEMENT = {
    # three exact 61-bit determinant signs from 10-bit limbs (limb split,
    # 19 limb products and sums, carry pass, sign) and the SoS cascade
    "cptest": 3 * 70 + 30,
    # dual quantization of two frames and two block-local 2D differences
    "lorenzo": 2 * 14 + 2 * 12 + 1,
}


def _size(dtype, dims):
    n = DTYPE_BYTES[dtype]
    for d in filter(None, dims.split(",")):
        n *= int(d)
    return n


def call_bytes(text: str) -> int:
    """Result bytes plus the bytes of each distinct operand."""
    head, _, rest = text.partition(" custom-call(")
    result = sum(_size(dt, dims) for dt, dims in
                 _RESULT.findall(head.split(" = ", 1)[-1]))
    depth, end = 1, len(rest)
    for i, ch in enumerate(rest):          # the operand list's own ")"
        depth += {"(": 1, ")": -1}.get(ch, 0)
        if depth == 0:
            end = i
            break
    args = rest[:end]
    seen = {}
    for dt, dims, name in _OPERAND.findall(args):
        seen[name] = _size(dt, dims)
    return result + sum(seen.values())


def call_elements(text: str) -> int:
    """Elements of the call's (first) result."""
    head = text.partition(" custom-call(")[0].split(" = ", 1)[-1]
    m = _RESULT.search(head)
    return _size(m.group(1), m.group(2)) // DTYPE_BYTES[m.group(1)] if m \
        else 0
