"""A Pallas kernel's share of its HBM roofline, in percent: the least
time its calls in the window could take (the bytes each call needs,
from its shapes, over the chip's HBM bandwidth) over the device time
they took.  Also adds the kernel's calls, bytes, operations and device
seconds to the run's figures line."""

from bench import kernels


def read(r, kernel, match):
    calls = [c for c in r.trace.ops_matching(match) if c[3]]
    if not calls:
        return None
    t_ns = sum(e - s for _, s, e, _ in calls)
    nbytes = sum(kernels.call_bytes(text) for *_, text in calls)
    ops = kernels.OPS_PER_ELEMENT[kernel] * sum(
        kernels.call_elements(text) for *_, text in calls)
    r.figures[kernel] = {"calls": len(calls), "bytes": nbytes, "ops": ops,
                         "device_s": t_ns / 1e9,
                         "ops_per_byte": ops / max(nbytes, 1)}
    if t_ns <= 0 or nbytes <= 0:
        return None
    return 100.0 * (nbytes / r.peaks["hbm_bytes_per_s"]) / (t_ns / 1e9)
