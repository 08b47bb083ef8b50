"""Pallas TPU kernel: fused p-stable LSH hashing (projection -> floor ->
multiply-mix combine -> bucket/fingerprint split).

This is the TPU-native version of the paper's AVX-512 hash computation: the
random projection X @ A is an MXU matmul; the quantize/combine epilogue runs
on the VPU in the same VMEM residency (no HBM round-trip for the [N, H*m]
intermediate, which is the whole point of fusing).

The effective bucket width w*R is a *per-column vector* operand, which lets
one kernel launch hash the entire radius schedule at once: the caller flattens
[r, L] compound hashes into H = r*L hashes, each column carrying its own
radius' width (ops.lsh_hash_all_radii) — one kernel launch for the whole
schedule instead of one dispatch per radius.

Layout contract (enforced by ops.py) — columns are FUNCTION-major: function
j of every compound hash owns the 128-aligned slice [j*Hp, (j+1)*Hp), so
the combine over m is a sum of m lane-aligned [TN, Hp] slices (no lane
reshape, no reduction over a minor axis):
  x:    [N, D]        float32, D % 128 == 0 (zero-padded)
  a:    [D, m*Hp]     float32, Hp = pad(H, 128)
  bvec: [1, m*Hp]     float32, pre-multiplied shift b * (w*R)
  wrvec:[1, m*Hp]     float32, per-column effective width w*R (1 in padding
                      columns so the divide is safe)
  rm:   [1, m*Hp]     int32, random odd multipliers (0 in padding columns)
  out bucket: [N, Hp] int32
  out fp:     [N, Hp] int32
The quantization math is bit-identical to the ref oracle:
floor((x@a + b*wr) / wr), evaluated with the same f32 op order; the combine
runs in int32, whose wrapping multiply-add has the same bits as the
oracle's uint32 one.

Grid: (N / TN,); each step hashes a TN-row tile against the full
projection block (VMEM-resident; ops.py checks the double-buffered
footprint against the scoped VMEM limit).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["lsh_hash_pallas"]

_HIGHEST = jax.lax.Precision.HIGHEST


def _srl(h, s):
    return jax.lax.shift_right_logical(h, jnp.int32(s))


def _fmix32(h):
    """murmur3 finalizer on int32 lanes (logical shifts, wrapping
    multiplies): the same bits as core.hashing.fmix32 on uint32."""
    h = h ^ _srl(h, 16)
    h = h * jnp.int32(0x85EBCA6B - 2**32)
    h = h ^ _srl(h, 13)
    h = h * jnp.int32(0xC2B2AE35 - 2**32)
    return h ^ _srl(h, 16)


def _kernel(x_ref, a_ref, b_ref, wr_ref, rm_ref, bucket_ref, fp_ref, *,
            m, u, fp_bits):
    x = x_ref[...]                      # [TN, D]
    Hp = bucket_ref.shape[-1]
    acc = jnp.zeros(bucket_ref.shape, jnp.int32)
    for j in range(m):                  # static, lane-aligned slices
        cols = pl.ds(j * Hp, Hp)
        proj = jnp.dot(x, a_ref[:, cols], precision=_HIGHEST,
                       preferred_element_type=jnp.float32)   # MXU [TN, Hp]
        hj = jnp.floor((proj + b_ref[:, cols]) / wr_ref[:, cols])
        acc = acc + hj.astype(jnp.int32) * rm_ref[:, cols]
    hv = _fmix32(acc)
    bucket_ref[...] = hv & jnp.int32((1 << u) - 1)
    fp_ref[...] = _srl(hv, u) & jnp.int32((1 << fp_bits) - 1)


def lsh_hash_pallas(
    x: jnp.ndarray,
    a_cols: jnp.ndarray,
    bvec: jnp.ndarray,
    wrvec: jnp.ndarray,
    rm: jnp.ndarray,
    *,
    m: int,
    u: int,
    fp_bits: int,
    tile_n: int = 256,
    interpret: bool = False,
):
    """Raw pallas_call wrapper over the function-major column layout; see
    ops.lsh_hash for the padded public API. Returns (bucket, fp) [N, Hp]."""
    N, D = x.shape
    cols = a_cols.shape[1]
    assert cols % m == 0 and (cols // m) % 128 == 0, (cols, m)
    Hp = cols // m
    assert N % tile_n == 0, (N, tile_n)
    kernel = functools.partial(_kernel, m=m, u=u, fp_bits=fp_bits)
    row = pl.BlockSpec((1, cols), lambda i: (0, 0))
    out = pl.BlockSpec((tile_n, Hp), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=(N // tile_n,),
        in_specs=[
            pl.BlockSpec((tile_n, D), lambda i: (i, 0)),
            pl.BlockSpec((D, cols), lambda i: (0, 0)),
            row, row, row,
        ],
        out_specs=[out, out],
        out_shape=[jax.ShapeDtypeStruct((N, Hp), jnp.int32)] * 2,
        interpret=interpret,
        name="lsh_hash",
    )(x, a_cols, bvec, wrvec, rm)
