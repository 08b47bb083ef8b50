"""Pallas TPU kernel: scalar-prefetch bucket-block gather + fingerprint filter.

TPU analogue of the paper's asynchronous 512 B block read (Fig. 10 Step 2):
the list of block rows to fetch is a *scalar-prefetch* operand, so each grid
step issues one DMA per requested block row HBM -> VMEM (all of a step's
rows in flight together) and the VPU fingerprint-filters the gathered tile.

Layout contract (ops.py):
  block_rows: [G]        int32 (scalar prefetch) block row per output row,
                         G % TILE_G == 0
  qfp:        [G, 1]     int32 query fingerprint per output row
  ids_blocks: [NB, BLKp] int32 object ids, padded slots = INVALID (HBM)
  fps_blocks: [NB, BLKp] int32 fingerprints (HBM)
  out:        [G, BLKp]  int32 ids where fingerprint matches, else INVALID

Grid: (G / TILE_G,); step i gathers rows block_rows[i*TILE_G : (i+1)*TILE_G]
into an (TILE_G, BLKp) VMEM tile — one sublane-aligned tile per step, which
is the block shape the TPU accepts (a (1, BLKp) row block is refused).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["bucket_probe_pallas", "INVALID", "TILE_G"]

INVALID = np.int32(2**31 - 1)
TILE_G = 8   # f32/int32 sublane tile


def _kernel(block_rows_ref, qfp_ref, ids_hbm, fps_hbm, out_ref, ids_v, fps_v,
            sem):
    base = pl.program_id(0) * TILE_G
    copies = []
    for k in range(TILE_G):
        row = block_rows_ref[base + k]
        for src, dst, s in ((ids_hbm, ids_v, 0), (fps_hbm, fps_v, 1)):
            cp = pltpu.make_async_copy(src.at[pl.ds(row, 1), :],
                                       dst.at[pl.ds(k, 1), :], sem.at[s])
            cp.start()
            copies.append(cp)
    for cp in copies:
        cp.wait()
    ids = ids_v[...]              # [TILE_G, BLKp]
    match = (fps_v[...] == qfp_ref[...]) & (ids != INVALID)
    out_ref[...] = jnp.where(match, ids, INVALID)


def bucket_probe_pallas(block_rows, qfp, ids_blocks, fps_blocks, *,
                        interpret: bool = False):
    G = block_rows.shape[0]
    assert G % TILE_G == 0, (G, TILE_G)
    BLKp = ids_blocks.shape[1]
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(G // TILE_G,),
            in_specs=[
                pl.BlockSpec((TILE_G, 1), lambda i, rows: (i, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((TILE_G, BLKp), lambda i, rows: (i, 0)),
            scratch_shapes=[pltpu.VMEM((TILE_G, BLKp), jnp.int32),
                            pltpu.VMEM((TILE_G, BLKp), jnp.int32),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct((G, BLKp), jnp.int32),
        interpret=interpret,
        name="bucket_probe",
    )(block_rows, qfp, ids_blocks, fps_blocks)
