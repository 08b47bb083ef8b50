"""jit'd public wrappers for l2_distance: padding + tile selection + backend
dispatch (Pallas on TPU, jnp oracle elsewhere)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..dispatch import default_interpret, use_pallas_default
from .kernel import l2_distance_gathered_pallas, l2_distance_pallas
from .ref import l2_distance_gathered_ref, l2_distance_ref

__all__ = ["l2_distance", "l2_distance_gathered"]


def _pad_to(x, mult):
    return -(-x // mult) * mult


@partial(jax.jit, static_argnames=("tile_q", "tile_c", "interpret", "force_pallas"))
def l2_distance(q, x, *, tile_q: int = 128, tile_c: int = 128,
                interpret: bool = None, force_pallas: bool = False):
    """Squared L2 distances [NQ, NC] between rows of q [NQ, D] and x [NC, D].

    Padded rows return garbage distances in the padding region only; the
    public result is sliced back to [NQ, NC]. Padding the feature dim with
    zeros is exact.
    """
    if interpret is None:
        interpret = default_interpret()
    NQ, D = q.shape
    NC, _ = x.shape
    if not force_pallas and (not use_pallas_default()
                             or (NQ < tile_q and NC < tile_c)):
        return l2_distance_ref(q, x)
    Dp = _pad_to(max(D, 128), 128)
    NQp = _pad_to(max(NQ, tile_q), tile_q)
    NCp = _pad_to(max(NC, tile_c), tile_c)
    qp = jnp.zeros((NQp, Dp), jnp.float32).at[:NQ, :D].set(q.astype(jnp.float32))
    xp = jnp.zeros((NCp, Dp), jnp.float32).at[:NC, :D].set(x.astype(jnp.float32))
    out = l2_distance_pallas(qp, xp, tile_q=tile_q, tile_c=tile_c, interpret=interpret)
    return out[:NQ, :NC]


@partial(jax.jit, static_argnames=("interpret", "force_pallas"))
def l2_distance_gathered(q, coords, xn2, qn2, *, interpret: bool = None,
                         force_pallas: bool = False):
    """Gathered-candidate distances (the query engine's Step-3 epilogue).

    q [Q, D], coords [Q, S, D], xn2 [Q, S], qn2 [Q] -> d2 [Q, S], unclamped
    (callers mask invalid slots and clamp, as core.query's oracle does).
    """
    if interpret is None:
        interpret = default_interpret()
    Q, S, D = coords.shape
    if not force_pallas and not use_pallas_default():
        return l2_distance_gathered_ref(q, coords, xn2, qn2)
    Dp = _pad_to(max(D, 128), 128)
    Sp = _pad_to(max(S, 128), 128)
    f32 = lambda v: v.astype(jnp.float32)
    qp = jnp.pad(f32(q), ((0, 0), (0, Dp - D)))[:, None]
    cp = jnp.pad(f32(coords), ((0, 0), (0, Sp - S), (0, Dp - D)))
    xn2p = jnp.pad(f32(xn2), ((0, 0), (0, Sp - S)))[:, None]
    qn2p = f32(qn2).reshape(Q, 1, 1)
    out = l2_distance_gathered_pallas(qp, cp, xn2p, qn2p, interpret=interpret)
    return out[:, 0, :S]
