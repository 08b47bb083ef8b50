"""Distributed E2LSHoS: index shards across devices, queries fanned out.

The paper runs one node with 1-12 drives (Table 5, Fig. 15: query speed scales
with aggregate IOPS). The TPU-native generalization treats *each device's HBM
as one drive*: the object set is range-partitioned, every shard builds its own
bucket/table structure under a SHARED hash family, and a query probes all
shards in parallel (shard_map), merging local top-k via an all-gather over the
index axes — the collective analogue of the paper's multi-drive aggregation.

The per-shard index is the same typed `IndexArrays` pytree the single-device
engine consumes, stacked along a leading shard dim (hash family replicated),
with per-shard BLOCKIFIED stores padded to a common row count — so the
sharded plan dispatches the SAME fused one-dispatch early-exit body per
device inside shard_map (`SearchEngine(sharded, mesh=...).query(qs,
plan="sharded")`), and `plan="oracle"` runs the per-shard unrolled reference
through the identical merge for bit-exact parity.

Two parallelism axes compose (mesh axes are configurable):
  * index parallelism  — shards of the database/index (paper: more drives);
  * query parallelism  — batch sharding (paper: multi-threading, Fig. 16).

Per-shard candidate budget: the paper examines S candidates per (R, c)-NN;
with SH shards we default to ceil(S / SH) per shard so aggregate work matches
the single-node algorithm (set `s_cap_per_shard` to override; the full-S
setting trades extra work for recall).
"""
from __future__ import annotations

import dataclasses
import math
from concurrent.futures import ThreadPoolExecutor
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .hashing import make_hash_family
from .index import IndexArrays, build_host_index
from .probabilities import LSHParams, solve_params
from .query import QueryConfig, QueryResult, fused_plan_body, oracle_plan_body

__all__ = ["ShardedIndexArrays", "build_sharded_index", "sharded_query_result",
           "make_sharded_query_fn"]

_INVALID = np.int32(2**31 - 1)


@dataclasses.dataclass
class ShardedIndexArrays:
    """Typed per-shard index pytree; non-replicated leaves carry a leading
    shard dim (hash family a/b/rm stays replicated — shared across shards).

    `build_sharded_index` returns it with host numpy leaves; `to_mesh`
    places it (SearchEngine does so when given a mesh)."""

    arrays: IndexArrays       # stacked: [SH, ...] except IndexArrays.REPLICATED
    shard_offsets: jnp.ndarray  # [SH] global id base per shard
    params: LSHParams
    num_shards: int

    def specs(self, index_axes) -> IndexArrays:
        """PartitionSpec pytree matching `arrays`: shard dim over
        `index_axes`, hash family replicated."""
        return _stacked_specs(self.arrays, tuple(index_axes))

    def to_mesh(self, mesh: Mesh,
                index_axes=("shard",)) -> "ShardedIndexArrays":
        """Place every leaf on `mesh` straight from host memory: each device
        receives only its own shard (replicated leaves go to all), so an
        index larger than one chip never lands whole on device 0."""
        specs = self.specs(index_axes)

        def put(v, spec):
            return jax.device_put(np.asarray(v), NamedSharding(mesh, spec))

        arrays = dataclasses.replace(self.arrays, **{
            name: put(getattr(self.arrays, name), getattr(specs, name))
            for name in IndexArrays.array_fields()})
        return dataclasses.replace(
            self, arrays=arrays,
            shard_offsets=put(self.shard_offsets, P(tuple(index_axes))))

    def with_block_objs(self, block_objs: int,
                        lane_pad: Optional[int] = None) -> "ShardedIndexArrays":
        """Re-blockify EVERY shard's block store at a new block size (the
        timing knob, ROADMAP "sharded block_objs knob"): each shard's CSR
        slice repacks through `blockify_entries` (padded entries sit behind
        table_cnt, so they are never read), and the per-shard stores re-pad
        to the new common row count. Same-size requests return self."""
        from ..kernels.bucket_probe.ops import blockify_entries

        ix = self.arrays
        lp = ix.lane_pad if lane_pad is None else int(lane_pad)
        if int(block_objs) == ix.block_objs and lp == ix.lane_pad:
            return self
        per_shard = []
        for s in range(self.num_shards):
            ids_b, fps_b, head, _ = blockify_entries(
                np.asarray(ix.entries_id[s]), np.asarray(ix.entries_fp[s]),
                np.asarray(ix.table_off[s]), np.asarray(ix.table_cnt[s]),
                int(block_objs), lane_pad=lp)
            per_shard.append((ids_b, fps_b, head))
        NB_max = max(p[0].shape[0] for p in per_shard)
        arrays = dataclasses.replace(
            ix,
            ids_blocks=np.stack(
                [_pad_rows(p[0], NB_max, int(_INVALID)) for p in per_shard]),
            fps_blocks=np.stack(
                [_pad_rows(p[1], NB_max, -1) for p in per_shard]),
            blocks_head=np.stack([p[2] for p in per_shard]),
            block_objs=int(block_objs), lane_pad=lp,
        )
        return dataclasses.replace(self, arrays=arrays)

    def to_global(self) -> IndexArrays:
        """Reassemble the ONE global index this sharded build partitions.

        Every shard hashes with the SHARED family, and the partition is a
        range partition, so a global bucket's entries are exactly the
        concatenation of its per-shard entries in shard order (ascending
        global id — the same order a single-node ``build_index(db, params,
        family=family)`` packs). The result re-blockifies through the one
        conversion path (``IndexArrays.from_csr``), giving the global
        chain-block layout the storage tier spills — which is NOT the
        per-shard layout (``sum(ceil(cnt_s/BLK)) != ceil(cnt/BLK)``); that
        difference is why the sharded-external plan stripes THIS index
        instead of spilling the per-shard stores.
        """
        ix = self.arrays
        sh = self.num_shards
        offs = np.asarray(self.shard_offsets, np.int64)
        bounds = np.append(offs, int(self.params.n))
        cnt = np.asarray(ix.table_cnt, np.int64)        # [SH, r, L, 2^u]
        off = np.asarray(ix.table_off, np.int64)
        gcnt = cnt.sum(axis=0)
        flat = gcnt.reshape(-1)
        goff = np.zeros_like(flat)
        np.cumsum(flat[:-1], out=goff[1:])
        total = int(flat.sum())
        gid = np.zeros((total,), dtype=np.asarray(ix.entries_id).dtype)
        gfp = np.zeros((total,), dtype=np.asarray(ix.entries_fp).dtype)
        before = np.cumsum(cnt, axis=0) - cnt   # earlier shards' entries/bucket
        for s in range(sh):
            c = cnt[s].reshape(-1)
            o = off[s].reshape(-1)
            nz = np.nonzero(c > 0)[0]
            if nz.size == 0:
                continue
            reps = c[nz]
            # per-bucket ramp 0..cnt-1 without a Python loop over buckets
            ramp = (np.arange(int(reps.sum()), dtype=np.int64)
                    - np.repeat(np.cumsum(reps) - reps, reps))
            src = np.repeat(o[nz], reps) + ramp
            dst = np.repeat(goff[nz] + before[s].reshape(-1)[nz], reps) + ramp
            gid[dst] = np.asarray(ix.entries_id[s])[src] + offs[s]
            gfp[dst] = np.asarray(ix.entries_fp[s])[src]
        db = np.concatenate(
            [np.asarray(ix.db[s])[: int(bounds[s + 1] - bounds[s])]
             for s in range(sh)], axis=0)
        toff = np.where(flat > 0, goff, -1).reshape(gcnt.shape)
        return IndexArrays.from_csr(
            a=ix.a, b=ix.b, rm=ix.rm,
            table_off=toff, table_cnt=gcnt.astype(np.int32),
            entries_id=gid, entries_fp=gfp, db=db,
            block_objs=ix.block_objs, lane_pad=ix.lane_pad,
        )

    def spill(self, path, *, params=None, stats=None) -> dict:
        """Write this sharded index as a sharded external-memory spill
        directory: the GLOBAL index's block store striped round-robin over
        ``num_shards`` crc-guarded files, resident sections, and a
        versioned ``MANIFEST.json`` (``repro.storage.spill_index_sharded``;
        format in docs/storage.md). Serve it with
        ``repro.storage.load_external_sharded(path)`` under
        ``plan="sharded_external"`` — bit-exact with ``plan="fused"`` over
        ``to_global()``. Returns the manifest payload."""
        from ..storage.format import spill_index_sharded
        return spill_index_sharded(
            path, self.to_global(), self.num_shards,
            params=params if params is not None else self.params,
            stats=stats)


def _stacked_specs(arrays: IndexArrays, index_axes: tuple) -> IndexArrays:
    def spec(name: str, v) -> P:
        if name in IndexArrays.REPLICATED:
            return P(*([None] * np.ndim(v)))
        return P(index_axes, *([None] * (np.ndim(v) - 1)))

    return IndexArrays(
        **{name: spec(name, getattr(arrays, name))
           for name in IndexArrays.array_fields()},
        block_objs=arrays.block_objs, lane_pad=arrays.lane_pad,
    )


def _pad_rows(x: np.ndarray, rows: int, fill) -> np.ndarray:
    pad = rows - x.shape[0]
    if pad == 0:
        return x
    widths = ((0, pad),) + tuple((0, 0) for _ in x.shape[1:])
    return np.pad(x, widths, constant_values=fill)


def build_sharded_index(
    db: np.ndarray,
    num_shards: int,
    *,
    c: float = 2.0,
    w: float = 4.0,
    gamma: float = 1.0,
    s_scale: float = 1.0,
    seed: int = 0,
    max_L: int = 64,
    u_bits: Optional[int] = None,
) -> ShardedIndexArrays:
    """Range-partition `db` and build one sub-index per shard under a shared
    hash family. Every shard's `IndexArrays` is emitted natively blockified
    by `build_index`; entry arrays, block stores, and the db tier are padded
    to the max shard extent so the stacked pytree is rectangular (padding is
    masked: pad entries sit behind table_cnt, pad block rows behind
    blocks_head, pad db rows behind the entry ids). Every leaf stays in host
    numpy: `SearchEngine(sharded, mesh=...)` places each shard on its own
    device (`ShardedIndexArrays.to_mesh`)."""
    db = np.asarray(db)
    n, d = db.shape
    bounds = np.linspace(0, n, num_shards + 1).astype(np.int64)
    n_shard_max = int(np.max(np.diff(bounds)))
    x_max = float(np.abs(db).max())
    # Parameters follow the GLOBAL n (paper Eq. 5 — sublinearity is in the
    # total database size); the per-shard table width follows the shard size.
    params = solve_params(
        n, d, c=c, w=w, gamma=gamma, x_max=x_max, seed=seed, s_scale=s_scale,
        max_L=max_L,
        u_bits=u_bits if u_bits is not None
        else max(8, int(math.floor(math.log2(max(n_shard_max, 256)))) - 1),
    )
    key = jax.random.PRNGKey(seed)
    family = make_hash_family(
        key, r=params.r, L=params.L, m=params.m, d=d,
        w=params.w, u=params.u, fp_bits=params.fp_bits,
    )

    def build_shard(s: int) -> IndexArrays:
        lo, hi = int(bounds[s]), int(bounds[s + 1])
        sp = dataclasses.replace(params, n=hi - lo)
        return build_host_index(db[lo:hi], sp, family=family).arrays

    # the shard builds are independent host work, and numpy releases the
    # GIL in their heavy array ops, so they overlap on a multi-core host
    with ThreadPoolExecutor(max_workers=num_shards) as pool:
        per_shard = list(pool.map(build_shard, range(num_shards)))

    E_max = max(int(ix.entries_id.shape[0]) for ix in per_shard)
    NB_max = max(int(ix.ids_blocks.shape[0]) for ix in per_shard)
    fills = dict(entries_id=0, entries_fp=0, ids_blocks=int(_INVALID),
                 fps_blocks=-1, db=0, db_norm2=0)
    rows = dict(entries_id=E_max, entries_fp=E_max, ids_blocks=NB_max,
                fps_blocks=NB_max, db=n_shard_max, db_norm2=n_shard_max)

    def stack(name: str):
        parts = [np.asarray(getattr(ix, name)) for ix in per_shard]
        if name in rows:
            parts = [_pad_rows(p, rows[name], fills[name]) for p in parts]
        return np.stack(parts)

    arrays = IndexArrays(
        a=np.asarray(family.a), b=np.asarray(family.b),  # replicated
        rm=np.asarray(family.rm),
        **{name: stack(name) for name in IndexArrays.array_fields()
           if name not in IndexArrays.REPLICATED},
        block_objs=per_shard[0].block_objs,
        lane_pad=per_shard[0].lane_pad,
    )
    return ShardedIndexArrays(
        arrays=arrays,
        shard_offsets=bounds[:-1].astype(np.int32),
        params=params,
        num_shards=num_shards,
    )


def _local_view(ix: IndexArrays) -> IndexArrays:
    """Drop the (size-1) local shard dim shard_map leaves on stacked fields."""
    return IndexArrays(
        **{name: (getattr(ix, name) if name in IndexArrays.REPLICATED
                  else getattr(ix, name)[0])
           for name in IndexArrays.array_fields()},
        block_objs=ix.block_objs, lane_pad=ix.lane_pad,
    )


def _local_shard_query(local: IndexArrays, shard_off, queries, valid,
                       cfg: QueryConfig, index_axes: tuple, k: int,
                       local_plan: str):
    """Runs inside shard_map: local plan body + cross-shard top-k merge.

    `local_plan="fused"` dispatches the production single-dispatch engine on
    the shard's blockified store; `"oracle"` runs the unrolled CSR reference
    through the identical merge (the sharded parity target). `valid` masks
    padded serving rows — inert on every shard, so the merged result rows
    are INVALID/inf with zero aggregate I/O.
    """
    body = fused_plan_body if local_plan == "fused" else oracle_plan_body
    res = body(local, queries, cfg, valid)
    ids = jnp.where(res.ids == jnp.int32(_INVALID), jnp.int32(_INVALID),
                    res.ids + shard_off)
    d2 = jnp.where(jnp.isinf(res.dists), jnp.inf, res.dists ** 2)
    # merge over index axes: gather every shard's local top-k
    all_ids = ids
    all_d2 = d2
    for ax in index_axes:
        all_ids = jax.lax.all_gather(all_ids, ax, axis=0, tiled=False)
        all_d2 = jax.lax.all_gather(all_d2, ax, axis=0, tiled=False)
        all_ids = all_ids.reshape((-1,) + ids.shape[1:]) if all_ids.ndim > ids.ndim + 1 else all_ids
        all_d2 = all_d2.reshape((-1,) + d2.shape[1:]) if all_d2.ndim > d2.ndim + 1 else all_d2
        # flatten shard dim into candidate dim and keep merging
        all_ids = jnp.moveaxis(all_ids, 0, 1).reshape(ids.shape[0], -1)
        all_d2 = jnp.moveaxis(all_d2, 0, 1).reshape(d2.shape[0], -1)
        order = jnp.argsort(all_d2, axis=1)[:, :k]
        all_ids = jnp.take_along_axis(all_ids, order, axis=1)
        all_d2 = jnp.take_along_axis(all_d2, order, axis=1)
        ids, d2 = all_ids, all_d2
    # aggregate stats across shards (paper Fig. 15: total observed IOPS);
    # `found` is any-shard success, `radii_searched` the deepest schedule
    # any shard walked for the query
    nio_t, nio_b, cands = (res.nio_table, res.nio_blocks, res.cands_checked)
    found = res.found.astype(jnp.int32)
    radii = res.radii_searched
    for ax in index_axes:
        nio_t = jax.lax.psum(nio_t, ax)
        nio_b = jax.lax.psum(nio_b, ax)
        cands = jax.lax.psum(cands, ax)
        found = jax.lax.pmax(found, ax)
        radii = jax.lax.pmax(radii, ax)
    return (ids, jnp.sqrt(all_d2), found > 0, radii, nio_t, nio_b, cands)


def sharded_query_result(
    sharded: ShardedIndexArrays,
    queries: jnp.ndarray,
    mesh: Mesh,
    *,
    k: int = 1,
    index_axes: Sequence[str] = ("shard",),
    query_axes: Sequence[str] = (),
    s_cap: Optional[int] = None,
    s_cap_per_shard: Optional[int] = None,
    local_plan: str = "fused",
    valid: Optional[jnp.ndarray] = None,
) -> QueryResult:
    """shard_map query over `mesh`, returning a full merged `QueryResult`.

    Index over `index_axes`, query batch over `query_axes`. This is the
    execution body behind ``SearchEngine(sharded, mesh=...).query(qs,
    plan="sharded"|"oracle")``; `probe_sizes` is not collected under
    shard_map. `valid` [Q] bool masks padded serving rows (replicated like
    the query batch unless `query_axes` shards it).
    """
    if local_plan not in ("fused", "oracle"):
        raise ValueError(f"unknown local_plan {local_plan!r}")
    p = sharded.params
    sh = 1
    index_axes = tuple(index_axes)
    query_axes = tuple(query_axes)
    for ax in index_axes:
        sh *= mesh.shape[ax]
    assert sh == sharded.num_shards, (sh, sharded.num_shards)
    base_S = int(s_cap or p.S)
    cap = s_cap_per_shard or max(4 * k, -(-base_S // sharded.num_shards))
    # the executor's chunking follows the ARRAYS' layout (a re-blockified
    # stack carries its block size as static metadata); both local plans
    # read the same cfg, which keeps the sharded/oracle parity intact
    bo = sharded.arrays.block_objs
    cfg = QueryConfig.from_params(p, k=k).replace(
        s_cap=int(cap), block_objs=(bo if bo != p.block_objs else None))
    if valid is None:
        valid = jnp.ones((queries.shape[0],), dtype=bool)
    ids, dists, found, radii, nio_t, nio_b, cands = _sharded_program(
        sharded.arrays, sharded.shard_offsets, queries.astype(jnp.float32),
        valid.astype(bool), mesh=mesh, cfg=cfg, index_axes=index_axes,
        query_axes=query_axes, k=k, local_plan=local_plan)
    return QueryResult(
        ids=ids, dists=dists, found=found, radii_searched=radii,
        nio_table=nio_t, nio_blocks=nio_b, cands_checked=cands,
        probe_sizes=None,
    )


# One jitted program, never eager shard_map: run eagerly, shard_map
# dispatches op by op and materializes a copy of each device's whole shard
# (the `[0]` of _local_view), which runs a chip out of HBM once an index
# fills ~40% of it; it also fuses differently from the queue's jitted path.
@partial(jax.jit, static_argnames=("mesh", "cfg", "index_axes", "query_axes",
                                   "k", "local_plan"))
def _sharded_program(arrays, shard_offsets, queries, valid, *, mesh, cfg,
                     index_axes, query_axes, k, local_plan):
    qspec = P(query_axes if query_axes else None)
    in_specs = (_stacked_specs(arrays, index_axes), P(index_axes), qspec,
                qspec)

    def body(ix, shard_off, qs, ok):
        return _local_shard_query(_local_view(ix), shard_off[0], qs, ok, cfg,
                                  index_axes, k, local_plan)

    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=(qspec,) * 7, check_vma=False)(
        arrays, shard_offsets, queries, valid)


def make_sharded_query_fn(sharded: ShardedIndexArrays, mesh: Mesh, **kw):
    """jit-wrapped sharded query (for benchmarking / serving); returns a
    merged `QueryResult`."""
    @jax.jit
    def fn(arrays, shard_offsets, queries):
        tmp = dataclasses.replace(sharded, arrays=arrays, shard_offsets=shard_offsets)
        return sharded_query_result(tmp, queries, mesh, **kw)
    return fn
