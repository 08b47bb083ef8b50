"""Compound p-stable hashing: projections, floor-quantize, bucket/fingerprint.

Pipeline (paper Secs. 2.2, 2.3, 5.2), for radius R and table l:

    proj_j = a_{l,j} . x                    (MXU matmul on TPU)
    h_j    = floor((proj_j + b_{l,j} * w * R) / (w * R))   j = 1..m
    hv32   = fmix32( sum_j rm_{l,j} * h_j )                (wrapping uint32)
    bucket = hv32 & (2^u - 1)               (hash-table address, u bits)
    fp     = (hv32 >> u) & (2^fp_bits - 1)  (fingerprint, paper Sec. 5.2)

The E2LSH package combines the m integers with a universal mod-(2^31 - 5)
hash; we use a multiply-mix combine (random odd uint32 multipliers followed by
a murmur3 finalizer), which is the same construction up to the hash family and
keeps all arithmetic in 32-bit lanes — the natural choice for TPU vector
registers. Recorded as an implementation delta in DESIGN.md.

The per-radius hash functions are independent draws (paper Sec. 5.3 builds a
separate set of L compound hashes per radius). Radius R scales the effective
bucket width: floor((a.x + b*w*R) / (w*R)) == floor((a.(x/R) + b*w) / w).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["HashFamily", "make_hash_family", "hash_points", "hash_points_radius",
           "hash_points_radius_deterministic", "fmix32"]


@dataclasses.dataclass(frozen=True)
class HashFamily:
    """Random parameters for r x L compound hashes of m functions each.

    a:  [r, L, m, d] float32  p-stable (Gaussian) projection vectors
    b:  [r, L, m]    float32  shifts in [0, 1) (scaled by w*R at use site)
    rm: [r, L, m]    uint32   random odd multipliers for the combine
    """

    a: jnp.ndarray
    b: jnp.ndarray
    rm: jnp.ndarray
    w: float
    u: int
    fp_bits: int

    @property
    def r(self) -> int:
        return self.a.shape[0]

    @property
    def L(self) -> int:
        return self.a.shape[1]

    @property
    def m(self) -> int:
        return self.a.shape[2]

    @property
    def d(self) -> int:
        return self.a.shape[3]

    def tree_flatten(self):  # pragma: no cover - convenience
        return (self.a, self.b, self.rm), (self.w, self.u, self.fp_bits)


def make_hash_family(
    key: jax.Array,
    *,
    r: int,
    L: int,
    m: int,
    d: int,
    w: float,
    u: int,
    fp_bits: int,
) -> HashFamily:
    ka, kb, km = jax.random.split(key, 3)
    a = jax.random.normal(ka, (r, L, m, d), dtype=jnp.float32)
    b = jax.random.uniform(kb, (r, L, m), dtype=jnp.float32)
    rm = jax.random.randint(km, (r, L, m), minval=1, maxval=2**31 - 1, dtype=jnp.int32)
    rm = (rm.astype(jnp.uint32) << 1) | jnp.uint32(1)  # odd multipliers
    return HashFamily(a=a, b=b, rm=rm, w=float(w), u=int(u), fp_bits=int(fp_bits))


def fmix32(h: jnp.ndarray) -> jnp.ndarray:
    """murmur3 32-bit finalizer; uniformizes the multiply-combine output."""
    h = h.astype(jnp.uint32)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


def _combine(hj: jnp.ndarray, rm: jnp.ndarray) -> jnp.ndarray:
    """Wrapping multiply-add combine of m per-function hashes -> uint32.

    hj: [..., m] int32, rm: broadcastable [..., m] uint32.
    """
    acc = jnp.sum(hj.astype(jnp.uint32) * rm, axis=-1, dtype=jnp.uint32)
    return fmix32(acc)


def _split_bucket_fp(hv32: jnp.ndarray, u: int, fp_bits: int):
    bucket = (hv32 & jnp.uint32((1 << u) - 1)).astype(jnp.int32)
    fp = ((hv32 >> jnp.uint32(u)) & jnp.uint32((1 << fp_bits) - 1)).astype(jnp.uint32)
    return bucket, fp


@partial(jax.jit, static_argnames=("u", "fp_bits"))
def _hash_points_impl(x, a_t, b_t, rm_t, wr, u, fp_bits):
    # x: [N, d]; a_t: [L, m, d]; b_t/rm_t: [L, m]; wr: scalar effective width.
    L, m, d = a_t.shape
    proj = jnp.einsum("nd,lmd->nlm", x, a_t, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)
    hj = jnp.floor((proj + b_t[None] * wr) / wr).astype(jnp.int32)  # [N, L, m]
    hv32 = _combine(hj, rm_t[None].astype(jnp.uint32))  # [N, L]
    return _split_bucket_fp(hv32, u, fp_bits)


def hash_points_radius(family: HashFamily, x: jnp.ndarray, t: int, radius: float):
    """Hash points [N, d] under radius index t. Returns (bucket, fp): [N, L]."""
    wr = jnp.float32(family.w * radius)
    return _hash_points_impl(
        x.astype(jnp.float32), family.a[t], family.b[t], family.rm[t], wr,
        family.u, family.fp_bits,
    )


def hash_points(family: HashFamily, x: jnp.ndarray, radii) -> tuple:
    """Hash points under every radius. Returns (bucket, fp): [r, N, L].

    Prefer `hash_points_radius` in build/query loops to bound memory.
    """
    buckets, fps = [], []
    for t, radius in enumerate(radii):
        b, f = hash_points_radius(family, x, t, float(radius))
        buckets.append(b)
        fps.append(f)
    return jnp.stack(buckets), jnp.stack(fps)


def _fmix32_np(h: np.ndarray) -> np.ndarray:
    """NumPy twin of `fmix32` (exact integer pipeline, no float involved)."""
    h = h.astype(np.uint32)
    h = h ^ (h >> np.uint32(16))
    h = (h * np.uint32(0x85EBCA6B)).astype(np.uint32)
    h = h ^ (h >> np.uint32(13))
    h = (h * np.uint32(0xC2B2AE35)).astype(np.uint32)
    h = h ^ (h >> np.uint32(16))
    return h


def _split_bucket_fp_np(h: np.ndarray, u: int, fp_bits: int):
    bucket = (h & np.uint32((1 << u) - 1)).astype(np.int32)
    fp = ((h >> np.uint32(u)) & np.uint32((1 << fp_bits) - 1)).astype(np.uint32)
    return bucket, fp


def hash_points_radius_np(family_np: dict, x: np.ndarray, t: int, radius: float, u: int, fp_bits: int):
    """NumPy oracle of the hash pipeline (used by tests and ref kernels)."""
    a = np.asarray(family_np["a"][t], dtype=np.float32)     # [L, m, d]
    b = np.asarray(family_np["b"][t], dtype=np.float32)     # [L, m]
    rm = np.asarray(family_np["rm"][t], dtype=np.uint32)    # [L, m]
    wr = np.float32(family_np["w"] * radius)
    proj = np.einsum("nd,lmd->nlm", x.astype(np.float32), a).astype(np.float32)
    hj = np.floor((proj + b[None] * wr) / wr).astype(np.int32)
    acc = (hj.astype(np.uint32) * rm[None]).sum(axis=-1, dtype=np.uint32)
    return _split_bucket_fp_np(_fmix32_np(acc), u, fp_bits)


def hash_points_radius_deterministic(family: HashFamily, x: np.ndarray,
                                     t: int, radius: float):
    """Deterministic BUILD-path hashing: float64-accumulated projections.

    The device einsum's GEMM may split its reduction dimension across a
    thread-count-dependent number of partial sums, so float32 projections
    near a floor() boundary can quantize differently between processes —
    the known nondeterministic-index-build bug. Accumulating in float64
    shrinks order-dependent rounding noise to ~1e-14 relative, far below
    any realizable boundary gap, and everything after the floor() is exact
    integer math — so index builds are reproducible across hosts, thread
    counts, and BLAS backends.

    Query-time hashing stays on the float32 device path (the LSH guarantee
    needs hash functions that agree statistically, not bitwise; a build/query
    quantization flip at one (t, l) costs one of L independent probes).

    Returns numpy (bucket [N, L] int32, fp [N, L] uint32).
    """
    a = np.asarray(family.a)[t].astype(np.float64)     # [L, m, d]
    b = np.asarray(family.b)[t].astype(np.float64)     # [L, m]
    rm = np.asarray(family.rm)[t].astype(np.uint32)    # [L, m]
    L, m, d = a.shape
    wr = np.float64(family.w) * np.float64(radius)
    proj = np.asarray(x, np.float64) @ a.reshape(L * m, d).T   # [N, L*m]
    hj = np.floor((proj.reshape(-1, L, m) + b[None] * wr) / wr).astype(np.int32)
    acc = (hj.astype(np.uint32) * rm[None]).sum(axis=-1, dtype=np.uint32)
    return _split_bucket_fp_np(_fmix32_np(acc), family.u, family.fp_bits)
