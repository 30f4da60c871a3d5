"""Dispatch wrapper for the SL predictor kernel."""
from __future__ import annotations

import jax.numpy as jnp

from . import kernel, ref


def sl_predict(u_prev, v_prev, cfl_x, cfl_y, d_max=2.0, n_max=8,
               force_ref=False):
    """f32 semi-Lagrangian prediction of frame t from frame t-1 (the
    kernel runs in interpret mode only; see kernel.py)."""
    if force_ref:
        return ref.sl_predict(u_prev, v_prev, cfl_x, cfl_y, d_max, n_max)
    return kernel.sl_predict_pallas(
        jnp.asarray(u_prev, jnp.float32), jnp.asarray(v_prev, jnp.float32),
        float(cfl_x), float(cfl_y), float(d_max), int(n_max),
        interpret=True,
    )
