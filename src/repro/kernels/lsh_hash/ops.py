"""jit'd public wrappers for the lsh_hash Pallas kernel: padding, layout,
VMEM budgeting, and backend dispatch.

Two entry points:
  * ``lsh_hash``            — one (radius, family) block (build-time path);
  * ``lsh_hash_all_radii``  — the whole radius schedule in ONE kernel launch
    (the fused query engine's Step 1): [r, L, m] hash functions flatten into
    r*L compound hashes of m projection columns, each column carrying its
    own effective width w*R, so the schedule costs one launch instead of r.

Dispatch policy: Pallas lowers natively on TPU; every other backend gets the
pure-jnp oracle (same math, XLA-fused), keeping CPU tests and benchmarks
honest without interpret-mode overhead. `force_pallas=True` (with
`interpret=True` off-TPU) pins the kernel path for parity tests. On TPU a
projection block too large for scoped VMEM runs the oracle instead, and
`REFERENCE_CALLS` counts it.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ...telemetry import get_registry
from ..dispatch import default_interpret, use_pallas_default
from .kernel import lsh_hash_pallas
from .ref import lsh_hash_all_radii_ref, lsh_hash_ref

__all__ = ["lsh_hash", "lsh_hash_all_radii", "REFERENCE_CALLS"]

# v5e's default scoped-VMEM limit: the kernel's double-buffered blocks plus
# its [TN, Hp] epilogue temporaries must fit, or Mosaic refuses the kernel.
_SCOPED_VMEM_BYTES = 16 * 2**20

# A projection block too large for VMEM runs the jnp reference instead of the
# kernel. Counted per trace of a jitted caller (the choice is static), so a
# serving path that never fell back reads 0 here.
REFERENCE_CALLS = get_registry().counter(
    "e2lsh_lsh_hash_reference_calls_total",
    "lsh_hash traces that ran the jnp reference because the projection "
    "block exceeds the scoped VMEM budget", labelnames=("op",))


def _pad_to(x, mult):
    return -(-x // mult) * mult


def _vmem_bytes(D, n_hashes, m, tile_n):
    """VMEM footprint of one kernel launch: every block double-buffered
    (a (1, cols) row pads to 8 sublanes) plus ~4 [TN, Hp] temporaries."""
    Dp = _pad_to(max(D, 128), 128)
    Hp = _pad_to(max(n_hashes, 128), 128)
    cols = m * Hp
    blocks = tile_n * Dp + Dp * cols + 3 * 8 * cols + 2 * tile_n * Hp
    return 4 * (2 * blocks + 4 * tile_n * Hp)


def _fits_vmem(op, D, n_hashes, m, tile_n, force_pallas):
    """Dispatch gate: True runs the kernel. Off-TPU (without the forced
    lane) the reference is the backend policy, not a fallback."""
    if force_pallas:
        return True
    if not use_pallas_default():
        return False
    if _vmem_bytes(D, n_hashes, m, tile_n) <= _SCOPED_VMEM_BYTES:
        return True
    REFERENCE_CALLS.inc(op=op)
    return False


def _pack_and_hash(x, a2, bwr, wr, rm_flat, *, n_hashes, m, u, fp_bits,
                   tile_n, interpret):
    """Shared pack path: a2 [n_hashes*m, D] rows in (hash, function) order,
    per-column widths. Lays the columns out function-major (see kernel.py)."""
    N, D = x.shape
    Dp = _pad_to(max(D, 128), 128)
    Hp = _pad_to(max(n_hashes, 128), 128)
    Np = _pad_to(max(N, tile_n), tile_n)

    def cols(v, fill, dtype):
        """[n_hashes*m, ...] (hash, function) rows -> [m*Hp, ...]."""
        v = jnp.swapaxes(v.astype(dtype).reshape((n_hashes, m) + v.shape[1:]),
                         0, 1)
        pad = [(0, 0), (0, Hp - n_hashes)] + [(0, 0)] * (v.ndim - 2)
        return jnp.pad(v, pad, constant_values=fill).reshape(
            (m * Hp,) + v.shape[2:])

    x_p = jnp.zeros((Np, Dp), jnp.float32).at[:N, :D].set(x.astype(jnp.float32))
    a_p = jnp.pad(cols(a2, 0, jnp.float32).T, ((0, Dp - D), (0, 0)))
    bucket, fp = lsh_hash_pallas(
        x_p, a_p, cols(bwr, 0, jnp.float32)[None],
        cols(wr, 1, jnp.float32)[None], cols(rm_flat, 0, jnp.int32)[None],
        m=m, u=u, fp_bits=fp_bits, tile_n=tile_n, interpret=interpret,
    )
    return bucket[:N, :n_hashes], fp[:N, :n_hashes]


@partial(jax.jit, static_argnames=("w_r", "u", "fp_bits", "tile_n", "interpret", "force_pallas"))
def lsh_hash(x, a, b, rm, *, w_r: float, u: int, fp_bits: int,
             tile_n: int = 256, interpret: bool = None,
             force_pallas: bool = False):
    """Hash points under one (radius, family) block.

    x [N, D] float; a [L, m, D]; b [L, m] in [0,1); rm [L, m] uint32/int32.
    Returns (bucket [N, L] int32, fp [N, L] int32).
    """
    if interpret is None:
        interpret = default_interpret()
    N, D = x.shape
    L, m, _ = a.shape
    LM = L * m
    if not _fits_vmem("lsh_hash", D, L, m, tile_n, force_pallas):
        return lsh_hash_ref(x, a, b, rm, w_r=w_r, u=u, fp_bits=fp_bits)
    wr = jnp.full((LM,), jnp.float32(w_r))
    # pre-multiply the shift (oracle computes floor((x.a + b*wr)/wr))
    bwr = b.reshape(-1).astype(jnp.float32) * jnp.float32(w_r)
    return _pack_and_hash(
        x, a.reshape(LM, -1), bwr, wr, rm.reshape(-1),
        n_hashes=L, m=m, u=u, fp_bits=fp_bits, tile_n=tile_n, interpret=interpret,
    )


@partial(jax.jit, static_argnames=("w", "radii", "u", "fp_bits", "tile_n",
                                   "interpret", "force_pallas"))
def lsh_hash_all_radii(x, a, b, rm, *, w: float, radii: tuple, u: int,
                       fp_bits: int, tile_n: int = 256,
                       interpret: bool = None, force_pallas: bool = False):
    """Hash points under the FULL radius schedule in one dispatch.

    x [N, D]; a [r, L, m, D]; b/rm [r, L, m]; radii = static schedule.
    Returns (bucket, fp) [r, N, L] int32 — same layout as stacking the
    per-radius results.
    """
    if interpret is None:
        interpret = default_interpret()
    N, D = x.shape
    r, L, m, _ = a.shape
    assert len(radii) == r, (len(radii), r)
    RLM = r * L * m
    if not _fits_vmem("lsh_hash_all_radii", D, r * L, m, tile_n,
                      force_pallas):
        return lsh_hash_all_radii_ref(x, a, b, rm, w=w, radii=radii, u=u,
                                      fp_bits=fp_bits)
    # per-column effective width: radius t owns columns [t*L*m, (t+1)*L*m)
    wr_cols = jnp.repeat(
        jnp.asarray([float(w) * float(rad) for rad in radii], jnp.float32), L * m)
    bwr = b.reshape(-1).astype(jnp.float32) * wr_cols
    bucket, fp = _pack_and_hash(
        x, a.reshape(RLM, -1), bwr, wr_cols, rm.reshape(-1),
        n_hashes=r * L, m=m, u=u, fp_bits=fp_bits, tile_n=tile_n,
        interpret=interpret,
    )
    # [N, r*L] -> [r, N, L] (row-major columns are (t, l) ordered)
    return (jnp.moveaxis(bucket.reshape(N, r, L), 1, 0),
            jnp.moveaxis(fp.reshape(N, r, L), 1, 0))
