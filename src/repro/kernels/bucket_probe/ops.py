"""jit'd wrapper for bucket_probe + index blockification helper.

`blockify_entries` converts the contiguous CSR entry layout of core.index
into the 2D block-store layout ([NB, BLKp] rows = the paper's 512 B blocks)
that the scalar-prefetch kernel consumes. `core.index.build_index` calls it
at BUILD time (via `IndexArrays.from_csr`), so the blockified store is the
index's native representation and CSR the derived view; query setup never
repacks unless the `block_objs` timing knob asks for a different layout. It
is fully vectorized (one scatter over all entries), which is what makes
build-time blockification of whole multi-radius tables cheap.

Dispatch policy: the scalar-prefetch Pallas kernel lowers natively on TPU;
every other backend gets the jnp gather oracle (identical results). Pass
`use_pallas=True/False` to pin a path (tests), `None` to auto-select.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..dispatch import default_interpret, use_pallas_default
from .kernel import TILE_G, bucket_probe_pallas
from .ref import bucket_probe_ref, INVALID

__all__ = ["bucket_probe", "blockify_entries", "INVALID"]


def blockify_entries(entries_id: np.ndarray, entries_fp: np.ndarray,
                     table_off: np.ndarray, table_cnt: np.ndarray,
                     block_objs: int, *, lane_pad: int = 128):
    """Pack CSR entries into [NB, BLKp] block rows (BLKp = pad to `lane_pad`).

    Returns host numpy (ids_blocks, fps_blocks, head_row, n_rows) — the
    caller decides where the store lives (one device, or one shard per
    device of a mesh) — where head_row has the
    same shape as table_off and holds the first block row of each bucket
    (-1 if empty); chains occupy consecutive rows (see core.index notes).
    Row 0 is a guaranteed-empty spare so callers can use it as safe padding.

    `lane_pad` is the row-width alignment: 128 matches the TPU lane contract
    of the scalar-prefetch kernel; off-TPU callers may pass a small value so
    the jnp gather path doesn't stream dead padding columns.
    """
    entries_id = np.asarray(entries_id)
    entries_fp = np.asarray(entries_fp).astype(np.int32)
    toff = np.asarray(table_off).reshape(-1)
    tcnt = np.asarray(table_cnt).reshape(-1)
    blkp = max(lane_pad, -(-block_objs // lane_pad) * lane_pad)
    sel = tcnt > 0
    offs = toff[sel].astype(np.int64)
    cnts = tcnt[sel].astype(np.int64)
    nblocks = -(-cnts // block_objs)
    row_base = np.zeros_like(nblocks)
    if len(nblocks) > 1:
        np.cumsum(nblocks[:-1], out=row_base[1:])
    NB = int(nblocks.sum()) + 1  # +1 spare row 0 kept for padding safety
    ids_blocks = np.full((NB, blkp), INVALID, dtype=np.int32)
    fps_blocks = np.full((NB, blkp), -1, dtype=np.int32)
    head = np.full(toff.shape, -1, dtype=np.int32)
    head_rows = row_base + 1
    head[sel] = head_rows.astype(np.int32)
    total = int(cnts.sum())
    if total:
        # per-entry source index and destination (row, col), one scatter each
        cum = np.zeros_like(cnts)
        if len(cnts) > 1:
            np.cumsum(cnts[:-1], out=cum[1:])
        local = np.arange(total, dtype=np.int64) - np.repeat(cum, cnts)
        src = np.repeat(offs, cnts) + local
        rows = np.repeat(head_rows, cnts) + local // block_objs
        cols = local % block_objs
        ids_blocks[rows, cols] = entries_id[src]
        fps_blocks[rows, cols] = entries_fp[src]
    return ids_blocks, fps_blocks, head.reshape(np.asarray(table_off).shape), NB


@partial(jax.jit, static_argnames=("interpret", "use_pallas"))
def bucket_probe(block_rows, qfp, ids_blocks, fps_blocks, *,
                 interpret: Optional[bool] = None,
                 use_pallas: Optional[bool] = None):
    """Fetch + fingerprint-filter a list of bucket blocks.

    block_rows [G] int32 (row 0 = guaranteed-empty spare -> safe padding),
    qfp [G] int32. Returns [G, BLKp] int32 with INVALID in non-matching slots.
    `use_pallas=None` auto-selects: Pallas on TPU (or REPRO_FORCE_PALLAS),
    jnp gather elsewhere; `interpret=None` follows the same env policy.
    """
    if use_pallas is None:
        use_pallas = use_pallas_default()
    if interpret is None:
        interpret = default_interpret()
    if not use_pallas:
        return bucket_probe_ref(block_rows, qfp, ids_blocks, fps_blocks)
    G = block_rows.shape[0]
    pad = -G % TILE_G        # padding steps read spare row 0 (empty)
    rows = jnp.pad(block_rows.astype(jnp.int32), (0, pad))
    qfp2 = jnp.pad(qfp.astype(jnp.int32), (0, pad)).reshape(-1, 1)
    out = bucket_probe_pallas(rows, qfp2, ids_blocks, fps_blocks,
                              interpret=interpret)
    return out[:G]
