"""Pure-jnp oracles for the l2_distance kernels."""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["l2_distance_ref", "l2_distance_gathered_ref"]


def l2_distance_ref(q, x):
    """q [NQ, D], x [NC, D] -> d2 [NQ, NC] (clamped at 0)."""
    q = q.astype(jnp.float32)
    x = x.astype(jnp.float32)
    dot = jnp.dot(q, x.T, precision=jax.lax.Precision.HIGHEST,
                  preferred_element_type=jnp.float32)
    qn2 = jnp.sum(q * q, axis=-1, keepdims=True)
    xn2 = jnp.sum(x * x, axis=-1, keepdims=True).T
    return jnp.maximum(qn2 + xn2 - 2.0 * dot, 0.0)


def l2_distance_gathered_ref(q, coords, xn2, qn2):
    """Per-query gathered-candidate distances (the probe epilogue form).

    q [Q, D], coords [Q, S, D] (candidate coordinates, already gathered),
    xn2 [Q, S] precomputed ||x||^2, qn2 [Q] -> d2 [Q, S], UNclamped (the
    caller masks invalid slots and clamps, mirroring core.query's oracle).
    """
    dot = jnp.einsum("qsd,qd->qs", coords.astype(jnp.float32),
                     q.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
    return xn2 - 2.0 * dot + qn2[:, None]
