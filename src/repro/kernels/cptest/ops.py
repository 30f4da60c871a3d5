"""Dispatch wrapper for the batched face predicate."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import kernel


def face_crossed_batch(u, v, idx):
    """u, v (3, N) slot-major fixed-point values (|.| < 2^30); idx
    (3, N) vertex ids (SoS order).  Returns (N,) bool."""
    N = u.shape[1]
    on_tpu = jax.default_backend() == "tpu"
    C = kernel.TILE_C
    R = max((N + C - 1) // C, 1)
    R = -(-R // kernel.TILE_R) * kernel.TILE_R
    pad = R * C - N

    def prep(x):
        x = jnp.asarray(x).astype(jnp.int32)
        x = jnp.pad(x, ((0, 0), (0, pad)), constant_values=1)
        return x.reshape(3, R, C)

    # vertex ids fit int32 (precondition: < 2^31 space-time vertices);
    # padded faces get distinct dummy ids and are discarded below.
    idx32 = jnp.asarray(idx).astype(jnp.int32)
    idx_p = jnp.concatenate(
        [idx32, jnp.broadcast_to(jnp.arange(3, dtype=jnp.int32)[:, None],
                                 (3, pad))], axis=1).reshape(3, R, C)

    out = kernel.face_crossed_pallas(
        prep(u), prep(v), idx_p, interpret=not on_tpu
    )
    return out.reshape(-1)[:N] != 0
