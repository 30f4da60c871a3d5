"""Taylor-hypothesis flow: frozen small-scale turbulence advected by a
uniform carrier (a copy of the program's ``advected_turbulence``, kept
here so that later edits to the program cannot move the benchmark's
inputs).

Velocities are in grid units per frame (dt = dx = dy = 1).  The pattern
is frozen in the co-moving frame and the sampling window slides
backward, so features advect in +x at ``u0`` cells per frame and
critical points exist where the fluctuation exceeds the carrier
(``amp > 1``), the paper's hurricane-track scenario.
"""
from __future__ import annotations

import numpy as np


def generate(T, H, W, seed, shift=0, u0=3.0, amp=1.5, n_modes=24):
    """(u, v) float32 arrays of shape (T, H, W): the flow of ``seed``,
    translated by ``shift`` cells in x.  The pattern is W-periodic in x
    and frozen in a frame moving at ``u0`` cells per frame, so a shift is
    the same flow met at another moment: every frame holds the same
    values, rotated along x."""
    rng = np.random.default_rng(seed)
    Wp = W + int(np.ceil(u0 * T)) + 2
    x = np.arange(Wp)[None, :] + shift
    y = np.arange(H)[:, None]
    psi = np.zeros((H, Wp))
    for _ in range(n_modes):
        kx = rng.integers(2, 12)
        ky = rng.integers(2, 12)
        ph1, ph2 = rng.uniform(0, 2 * np.pi, 2)
        a = rng.normal(0, 1.0) / np.hypot(kx, ky)
        psi += a * np.sin(2 * np.pi * kx * x / W + ph1) * np.sin(
            2 * np.pi * ky * y / H + ph2)
    uu = np.gradient(psi, axis=0)
    vv = -np.gradient(psi, axis=1)
    peak = max(np.abs(uu).max(), np.abs(vv).max(), 1e-9)
    uu *= amp * u0 / peak
    vv *= amp * u0 / peak
    u = np.empty((T, H, W), np.float32)
    v = np.empty((T, H, W), np.float32)
    for t in range(T):
        s = u0 * (T - 1 - t)
        i0 = int(np.floor(s))
        a = s - i0
        u[t] = u0 + (1 - a) * uu[:, i0:i0 + W] + a * uu[:, i0 + 1:i0 + 1 + W]
        v[t] = (1 - a) * vv[:, i0:i0 + W] + a * vv[:, i0 + 1:i0 + 1 + W]
    return u, v
