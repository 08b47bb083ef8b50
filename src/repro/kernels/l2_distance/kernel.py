"""Pallas TPU kernel: tiled squared-Euclidean distance (candidate checking).

The paper accelerates distance checking with AVX-512; on TPU the dominant
term -2*Q@Xt is an MXU matmul, with the norm corrections fused as a VPU
epilogue inside the same VMEM residency:

    d2[i, j] = ||q_i||^2 + ||x_j||^2 - 2 q_i . x_j   (clamped at 0)

Layout contract (ops.py): Q [NQ, D], X [NC, D], D % 128 == 0, tiles
(TQ, D) x (TC, D) -> (TQ, TC); norms are computed in-kernel (cheap relative
to the matmul, saves two HBM-resident inputs).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["l2_distance_pallas", "l2_distance_gathered_pallas"]

_HIGHEST = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))     # contract the minor dims: a @ b.T


def _kernel(q_ref, x_ref, out_ref):
    q = q_ref[...]                                   # [TQ, D]
    x = x_ref[...]                                   # [TC, D]
    dot = jax.lax.dot_general(q, x, _NT, precision=_HIGHEST,
                              preferred_element_type=jnp.float32)  # MXU [TQ, TC]
    qn2 = jnp.sum(q * q, axis=-1, keepdims=True)     # [TQ, 1]
    xn2 = jnp.sum(x * x, axis=-1, keepdims=True).T   # [1, TC]
    out_ref[...] = jnp.maximum(qn2 + xn2 - 2.0 * dot, 0.0)


def l2_distance_pallas(q, x, *, tile_q: int = 128, tile_c: int = 128,
                       interpret: bool = False):
    NQ, D = q.shape
    NC, _ = x.shape
    assert NQ % tile_q == 0 and NC % tile_c == 0, (NQ, NC, tile_q, tile_c)
    grid = (NQ // tile_q, NC // tile_c)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_q, D), lambda i, j: (i, 0)),
            pl.BlockSpec((tile_c, D), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((tile_q, tile_c), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((NQ, NC), jnp.float32),
        interpret=interpret,
        name="l2_distance",
    )(q, x)


def _gathered_kernel(q_ref, coords_ref, xn2_ref, qn2_ref, out_ref):
    """One query per grid step: candidate matvec + norm-expansion epilogue.

    The query dim is squeezed out of every block, so the step sees its query
    [1, D] against ITS candidates [Sp, D]; norms arrive precomputed (xn2 is
    the DRAM-tier ||x||^2 cache, shared across radii), matching the oracle's
    d2 = xn2 - 2<x, q> + qn2 op order. Unclamped; callers mask + clamp.
    """
    dot = jax.lax.dot_general(q_ref[...], coords_ref[...], _NT,
                              precision=_HIGHEST,
                              preferred_element_type=jnp.float32)  # [1, Sp]
    out_ref[...] = xn2_ref[...] - 2.0 * dot + qn2_ref[...]


def l2_distance_gathered_pallas(q, coords, xn2, qn2, *, interpret: bool = False):
    """q [Q, 1, D], coords [Q, Sp, D], xn2 [Q, 1, Sp], qn2 [Q, 1, 1]
    -> d2 [Q, 1, Sp].

    D % 128 == 0 and Sp % 128 == 0 (ops.py pads); the unit dims keep each
    block's last two dims equal to the array's.
    """
    Q, Sp, D = coords.shape
    return pl.pallas_call(
        _gathered_kernel,
        grid=(Q,),
        in_specs=[
            pl.BlockSpec((None, 1, D), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, Sp, D), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, 1, Sp), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, 1, 1), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, Sp), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Q, 1, Sp), jnp.float32),
        interpret=interpret,
        name="l2_distance_gathered",
    )(q, coords, xn2, qn2)
