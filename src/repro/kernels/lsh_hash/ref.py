"""Pure-jnp oracle for the lsh_hash kernel (mirrors core.hashing exactly)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["lsh_hash_ref", "lsh_hash_all_radii_ref"]


def lsh_hash_ref(x, a, b, rm, *, w_r: float, u: int, fp_bits: int):
    """x [N, D], a [L, m, D], b [L, m], rm [L, m] -> (bucket, fp) [N, L].

    Identical math to core.hashing._hash_points_impl (the production path).
    """
    # deferred: core.query imports this package, so kernels must not pull in
    # repro.core at module-import time
    from ...core.hashing import fmix32
    proj = jnp.einsum("nd,lmd->nlm", x.astype(jnp.float32), a.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)
    hj = jnp.floor((proj + b[None] * w_r) / w_r).astype(jnp.int32)
    acc = jnp.sum(hj.astype(jnp.uint32) * rm[None].astype(jnp.uint32), axis=-1,
                  dtype=jnp.uint32)
    hv = fmix32(acc)
    bucket = (hv & jnp.uint32((1 << u) - 1)).astype(jnp.int32)
    fp = ((hv >> jnp.uint32(u)) & jnp.uint32((1 << fp_bits) - 1)).astype(jnp.int32)
    return bucket, fp


def lsh_hash_all_radii_ref(x, a, b, rm, *, w: float, radii, u: int, fp_bits: int):
    """All-radius oracle: x [N, D], a [r, L, m, D], b/rm [r, L, m]
    -> (bucket, fp) [r, N, L].

    One per-radius einsum each (bit-identical to the per-radius production
    path); the Pallas version fuses the whole schedule into one matmul.
    """
    buckets, fps = [], []
    for t, radius in enumerate(radii):
        bk, fp = lsh_hash_ref(x, a[t], b[t], rm[t],
                              w_r=float(w) * float(radius), u=u, fp_bits=fp_bits)
        buckets.append(bk)
        fps.append(fp)
    return jnp.stack(buckets), jnp.stack(fps)
