"""E2LSHoS index construction — the paper's Fig. 9/10 layout, TPU-adapted.

Storage-tier layout (per paper Sec. 5.1/5.3):
  * hash table per (radius t, table l): 2^u buckets -> head address of the
    bucket's block chain, plus the bucket size;
  * bucket blocks of `block_objs` object infos (512 B blocks: 16 B header +
    99 x 5 B infos), chained until the bucket is exhausted;
  * object info = object id + fingerprint (paper Sec. 5.2).

The index's NATIVE representation is the typed `IndexArrays` pytree, emitted
by `build_index` directly in the blockified block-store layout the fused
query engine reads ([NB, BLKp] block rows + per-bucket head rows): the
paper's 512 B blocks ARE the on-device data structure, not a view derived at
query setup. The flat CSR arrays (`table_off`/`table_cnt` over contiguous
`entries_*`) ride along as the DERIVED view consumed by the unrolled oracle
and the io-count replay paths — both layouts hold exactly the same entries
in the same chunk order, which is what makes the engines bit-identical.

TPU adaptation (recorded in DESIGN.md): chains are laid out *contiguously*
(block j of a bucket is row `head + j`), so pointer chasing becomes an offset
computation. Build-time allocators produce exactly this layout anyway
(buckets are written whole), the per-block I/O accounting is unchanged (one
read per `block_objs` chunk + one read per hash-table lookup), and contiguous
chains remove the serial read dependency of a linked list — a strictly better
analogue of the paper's "issue many reads in parallel" design on TPU, where
gathers are batched.
"""
from __future__ import annotations

import dataclasses
import pathlib
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .hashing import (HashFamily, hash_points_radius,
                      hash_points_radius_deterministic, make_hash_family)
from .probabilities import LSHParams
from ..kernels.bucket_probe.ops import blockify_entries
from ..kernels.dispatch import native_lane_pad

__all__ = ["IndexArrays", "E2LSHIndex", "build_index", "build_host_index",
           "IndexStats"]

def _static():
    return dataclasses.field(metadata=dict(static=True))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class IndexArrays:
    """The typed index pytree — ONE object that crosses every jit/shard_map
    boundary (replaces the untyped ``arrays: dict`` of the seed API).

    Array leaves flatten as pytree data; ``block_objs``/``lane_pad`` are
    static metadata (part of the treedef, hence of jit cache keys), so a
    re-blockified index is a *different* pytree type and can never silently
    hit a stale compiled program.

    Layout groups:
      hash family      a [r, L, m, d] f32, b/rm [r, L, m]
      block store      ids_blocks/fps_blocks [NB, BLKp] i32 (native layout;
                       row 0 is a guaranteed-empty spare used as safe
                       padding), blocks_head [r, L, 2^u] i32 (-1 empty)
      CSR derived view table_off/table_cnt [r, L, 2^u] i32 over
                       entries_id [E] i32 / entries_fp [E] u16
      DRAM tier        db [n, d] f32, db_norm2 [n] f32
    """

    a: jnp.ndarray
    b: jnp.ndarray
    rm: jnp.ndarray
    ids_blocks: jnp.ndarray
    fps_blocks: jnp.ndarray
    blocks_head: jnp.ndarray
    table_off: jnp.ndarray
    table_cnt: jnp.ndarray
    entries_id: jnp.ndarray
    entries_fp: jnp.ndarray
    db: jnp.ndarray
    db_norm2: jnp.ndarray
    block_objs: int = _static()
    lane_pad: int = _static()

    # field-name groups (used by distributed stacking/spec construction)
    REPLICATED = ("a", "b", "rm")

    @staticmethod
    def array_fields() -> tuple:
        return tuple(f.name for f in dataclasses.fields(IndexArrays)
                     if f.name not in ("block_objs", "lane_pad"))

    @staticmethod
    def from_csr(**csr) -> "IndexArrays":
        """Blockify a CSR layout into the native block-store representation,
        placed on the default device (`host_from_csr`'s arguments).

        This is the one conversion path in core; `build_index` calls it with
        the freshly packed numpy CSR so the device arrays are born
        blockified.
        """
        return jax.tree.map(jnp.asarray, IndexArrays.host_from_csr(**csr))

    @staticmethod
    def host_from_csr(*, a, b, rm, table_off, table_cnt, entries_id,
                      entries_fp, db, block_objs: int,
                      lane_pad: Optional[int] = None,
                      db_norm2=None) -> "IndexArrays":
        """`from_csr` with every leaf left in host numpy (the sharded build
        stacks host shards and places each on its own device)."""
        lp = native_lane_pad() if lane_pad is None else int(lane_pad)
        ids_b, fps_b, head, _ = blockify_entries(
            np.asarray(entries_id), np.asarray(entries_fp),
            np.asarray(table_off), np.asarray(table_cnt),
            int(block_objs), lane_pad=lp,
        )
        if db_norm2 is None:
            db_norm2 = np.sum(np.asarray(db, np.float32) ** 2, axis=-1,
                              dtype=np.float32)
        return IndexArrays(
            a=np.asarray(a), b=np.asarray(b), rm=np.asarray(rm),
            ids_blocks=ids_b, fps_blocks=fps_b, blocks_head=head,
            table_off=np.asarray(table_off, np.int32),
            table_cnt=np.asarray(table_cnt, np.int32),
            entries_id=np.asarray(entries_id),
            entries_fp=np.asarray(entries_fp),
            db=np.asarray(db, np.float32),
            db_norm2=np.asarray(db_norm2, np.float32),
            block_objs=int(block_objs), lane_pad=lp,
        )

    def spill(self, path, *, params=None, stats=None) -> None:
        """Write this index to ``path`` in the external-memory spill format
        (page-aligned sections, versioned header; see docs/storage.md).
        ``repro.storage.load_arrays`` round-trips every leaf bit-for-bit;
        ``repro.storage.load_external`` serves the file under
        ``plan="external"`` (pass ``params`` — or spill via
        ``E2LSHIndex.spill``, which includes them — to make the file
        servable)."""
        from ..storage.format import spill_index
        spill_index(path, self, params=params, stats=stats)

    def with_block_objs(self, block_objs: int,
                        lane_pad: Optional[int] = None) -> "IndexArrays":
        """Re-blockify under a different block size (the timing knob). The
        CSR derived view is the source of truth for the repack; same-size
        requests return self."""
        lp = self.lane_pad if lane_pad is None else int(lane_pad)
        if int(block_objs) == self.block_objs and lp == self.lane_pad:
            return self
        return IndexArrays.from_csr(
            a=self.a, b=self.b, rm=self.rm,
            table_off=self.table_off, table_cnt=self.table_cnt,
            entries_id=self.entries_id, entries_fp=self.entries_fp,
            db=self.db, db_norm2=self.db_norm2,
            block_objs=int(block_objs), lane_pad=lp,
        )


@dataclasses.dataclass
class IndexStats:
    """Build-time statistics (feed Table 6 and the io model)."""

    n: int
    entries: int
    nonempty_buckets: int
    storage_blocks: int          # paper-layout 512 B blocks: sum ceil(k / block_objs)
    index_storage_bytes: int     # paper layout: blocks * block_bytes + tables
    table_storage_bytes: int
    dram_index_bytes: int        # non-empty bitmap kept in DRAM (skip empty-bucket I/O)
    db_bytes: int
    max_bucket: int

    def total_storage_bytes(self) -> int:
        return self.index_storage_bytes


@dataclasses.dataclass
class E2LSHIndex:
    params: LSHParams
    family: HashFamily
    arrays: IndexArrays
    stats: IndexStats

    # -- legacy field access (the CSR view used by older call sites) --------
    @property
    def table_off(self) -> jnp.ndarray:
        return self.arrays.table_off

    @property
    def table_cnt(self) -> jnp.ndarray:
        return self.arrays.table_cnt

    @property
    def entries_id(self) -> jnp.ndarray:
        return self.arrays.entries_id

    @property
    def entries_fp(self) -> jnp.ndarray:
        return self.arrays.entries_fp

    @property
    def db(self) -> jnp.ndarray:
        return self.arrays.db

    # The checkpoint persists the CSR source of truth + layout metadata only:
    # the lane-padded block store is ~2.7x the CSR bytes and blockify_entries
    # reproduces it bit-for-bit from the CSR view (test_build_emits_native_
    # blockified_layout), so load() re-derives it (and db_norm2) instead.
    _SAVED_FIELDS = ("a", "b", "rm", "table_off", "table_cnt",
                     "entries_id", "entries_fp", "db")

    def save(self, path: str | pathlib.Path) -> None:
        ix = self.arrays
        p = pathlib.Path(path)
        p.parent.mkdir(parents=True, exist_ok=True)
        np.savez_compressed(
            p,
            **{name: np.asarray(getattr(ix, name))
               for name in self._SAVED_FIELDS},
            layout_meta=np.asarray([ix.block_objs, ix.lane_pad], np.int64),
            params=np.array([dataclasses.asdict(self.params)], dtype=object),
            stats=np.array([dataclasses.asdict(self.stats)], dtype=object),
        )

    def spill(self, path: str | pathlib.Path) -> None:
        """Spill to the external-memory format WITH params + build stats, so
        ``repro.storage.load_external(path)`` can serve the file directly
        (block rows on disk, hash tables resident)."""
        self.arrays.spill(path, params=self.params, stats=self.stats)

    @staticmethod
    def load(path: str | pathlib.Path) -> "E2LSHIndex":
        z = np.load(path, allow_pickle=True)
        pdict = z["params"][0]
        pdict["radii"] = tuple(pdict["radii"])
        params = LSHParams(**pdict)
        stats = IndexStats(**z["stats"][0])
        family = HashFamily(
            a=jnp.asarray(z["a"]), b=jnp.asarray(z["b"]), rm=jnp.asarray(z["rm"]),
            w=params.w, u=params.u, fp_bits=params.fp_bits,
        )
        if "layout_meta" in z.files:
            bo, lp = (int(v) for v in z["layout_meta"])
        else:  # pre-blockified checkpoint: CSR only, current-backend layout
            bo, lp = params.block_objs, None
        arrays = IndexArrays.from_csr(
            a=z["a"], b=z["b"], rm=z["rm"],
            table_off=z["table_off"], table_cnt=z["table_cnt"],
            entries_id=z["entries_id"], entries_fp=z["entries_fp"],
            db=z["db"], block_objs=bo, lane_pad=lp,
        )
        return E2LSHIndex(params=params, family=family, arrays=arrays,
                          stats=stats)


def _pack_radius_table(
    bucket: np.ndarray,   # [n, L] int32 bucket addresses for radius t
    fp: np.ndarray,       # [n, L] uint32 fingerprints
    u: int,
    block_objs: int,
):
    """Pack one radius worth of buckets. Returns per-l CSR pieces."""
    n, L = bucket.shape
    toff = np.full((L, 1 << u), -1, dtype=np.int64)
    tcnt = np.zeros((L, 1 << u), dtype=np.int32)
    ids_parts, fp_parts = [], []
    nonempty = 0
    storage_blocks = 0
    max_bucket = 0
    cursor = 0
    for l in range(L):
        order = np.argsort(bucket[:, l], kind="stable")
        sb = bucket[order, l]
        ids_parts.append(order.astype(np.int32))
        fp_parts.append(fp[order, l].astype(np.uint16))
        # group boundaries
        starts = np.flatnonzero(np.concatenate(([True], sb[1:] != sb[:-1])))
        sizes = np.diff(np.concatenate((starts, [n])))
        bvals = sb[starts]
        toff[l, bvals] = starts + cursor
        tcnt[l, bvals] = sizes
        nonempty += len(starts)
        storage_blocks += int(np.sum((sizes + block_objs - 1) // block_objs))
        max_bucket = max(max_bucket, int(sizes.max()) if len(sizes) else 0)
        cursor += n
    return toff, tcnt, ids_parts, fp_parts, nonempty, storage_blocks, max_bucket


def build_index(db: np.ndarray, params: LSHParams, **kw) -> E2LSHIndex:
    """`build_host_index`, with the arrays placed on the default device."""
    index = build_host_index(db, params, **kw)
    return dataclasses.replace(
        index, arrays=jax.tree.map(jnp.asarray, index.arrays))


def build_host_index(
    db: np.ndarray,
    params: LSHParams,
    *,
    key: Optional[jax.Array] = None,
    family: Optional[HashFamily] = None,
    hash_batch: int = 262144,
    deterministic: bool = True,
    lane_pad: Optional[int] = None,
) -> E2LSHIndex:
    """Build the full multi-radius index (paper Sec. 5.3), emitting the
    blockified `IndexArrays` natively (packing runs in NumPy; the block
    store is laid out before anything touches the device).

    `deterministic=True` (default) computes build-time hash projections with
    a float64 accumulation on the host, making index contents reproducible
    across processes and thread counts (the device GEMM's reduction order is
    thread-count-dependent). Set False to hash on the accelerator (faster
    for huge builds, not bit-reproducible across hosts). Every array leaf
    stays in host numpy.
    """
    db = np.asarray(db)
    n, d = db.shape
    assert n == params.n and d == params.d, (db.shape, params.n, params.d)
    if family is None:
        if key is None:
            key = jax.random.PRNGKey(params.seed)
        family = make_hash_family(
            key, r=params.r, L=params.L, m=params.m, d=d,
            w=params.w, u=params.u, fp_bits=params.fp_bits,
        )

    r, L, u = params.r, params.L, params.u
    toff_all = np.full((r, L, 1 << u), -1, dtype=np.int64)
    tcnt_all = np.zeros((r, L, 1 << u), dtype=np.int32)
    ids_all, fps_all = [], []
    nonempty = 0
    storage_blocks = 0
    max_bucket = 0
    db_f32 = db.astype(np.float32)
    if deterministic:
        # bound the [batch, L*m] float64 projection scratch to ~256 MB
        hash_batch = max(1024, min(hash_batch, (32 << 20) // max(1, L * params.m)))
    for t, radius in enumerate(params.radii):
        # hash all objects for radius t (batched to bound memory)
        buckets, fps = [], []
        for s in range(0, n, hash_batch):
            if deterministic:
                bkt, f = hash_points_radius_deterministic(
                    family, db_f32[s:s + hash_batch], t, float(radius))
            else:
                bkt, f = hash_points_radius(
                    family, jnp.asarray(db_f32[s:s + hash_batch]), t, float(radius))
            buckets.append(np.asarray(bkt))
            fps.append(np.asarray(f))
        bucket_np = np.concatenate(buckets, axis=0)
        fp_np = np.concatenate(fps, axis=0)
        toff, tcnt, ids_parts, fp_parts, ne, sb, mb = _pack_radius_table(
            bucket_np, fp_np, u, params.block_objs
        )
        base = sum(len(x) for x in ids_all)
        valid = toff >= 0
        toff[valid] += base
        toff_all[t] = toff
        tcnt_all[t] = tcnt
        ids_all.extend(ids_parts)
        fps_all.extend(fp_parts)
        nonempty += ne
        storage_blocks += sb
        max_bucket = max(max_bucket, mb)

    entries_id = np.concatenate(ids_all) if ids_all else np.zeros((0,), np.int32)
    entries_fp = np.concatenate(fps_all) if fps_all else np.zeros((0,), np.uint16)
    assert entries_id.shape[0] == n * L * r

    # Paper-layout storage accounting (Table 6): 512 B blocks + on-storage
    # hash tables (8 B per address entry: storage address + size), plus the
    # DRAM-resident non-empty bitmap that lets us skip I/Os for empty buckets.
    table_storage = r * L * (1 << u) * 8
    index_storage = storage_blocks * params.block_bytes + table_storage
    dram_bitmap = (r * L * (1 << u) + 7) // 8
    stats = IndexStats(
        n=n,
        entries=int(entries_id.shape[0]),
        nonempty_buckets=int(nonempty),
        storage_blocks=int(storage_blocks),
        index_storage_bytes=int(index_storage),
        table_storage_bytes=int(table_storage),
        dram_index_bytes=int(dram_bitmap),
        db_bytes=int(db.nbytes),
        max_bucket=int(max_bucket),
    )
    if entries_id.shape[0] >= 2**31:
        raise ValueError("entry space exceeds int32 addressing; shard the index")
    arrays = IndexArrays.host_from_csr(
        a=family.a, b=family.b, rm=family.rm,
        table_off=toff_all.astype(np.int32), table_cnt=tcnt_all,
        entries_id=entries_id, entries_fp=entries_fp, db=db_f32,
        block_objs=params.block_objs, lane_pad=lane_pad,
    )
    return E2LSHIndex(params=params, family=family, arrays=arrays, stats=stats)
