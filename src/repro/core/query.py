"""Batched multi-radius (R, c)-NN query processing (paper Secs. 2.3, 5.4).

For each radius R in (1, c, c^2, ...):
  1. hash the query into L buckets (Step 1 of Fig. 10 = hash-table read);
  2. walk each non-empty bucket's block chain, `block_objs` entries per read
     (Step 2 = bucket block reads), fingerprint-filtering object infos,
     until S candidates are collected (paper stops at S per (R, c)-NN);
  3. distance-check candidates against the DRAM-resident database (Step 3),
     merge into the running top-k (dedup by id), and mark the query done when
     k results lie within c*R (top-k c-ANNS per Sec. 2.1).

The public API is ONE typed entry point over pluggable execution plans —
the paper's own framing (Secs. 4-5: one algorithm, different execution
tiers, only the cost model changes):

    engine = SearchEngine(index)            # index: E2LSHoS / E2LSHIndex /
    res = engine.query(qs, plan="fused")    #        ShardedIndexArrays

Plans over a single-device `IndexArrays`:

* ``plan="fused"``  — the production engine: the whole radius schedule's
  query hashes are precomputed in ONE kernel dispatch
  (kernels.lsh_hash_all_radii), the chain walk reads the natively blockified
  block store through kernels.bucket_probe, the distance epilogue runs
  through kernels.l2_distance_gathered, and the radius loop is a
  ``jax.lax.while_loop`` INSIDE the jitted computation — early exit costs
  zero device->host syncs. One dispatch per query batch.
* ``plan="oracle"`` — the reference: all radii unrolled at trace time with
  done-masking, per-radius einsum hashing and a dense CSR gather chain walk.
  Simple, obviously correct, and the parity target for everything else.
* ``plan="host"``   — the pre-fusion host-driven loop (one jitted call + one
  device->host sync per radius), kept for benchmarking dispatch overhead.

Plans over an `ExternalIndex` (repro.storage.load_external — block rows on
disk, hash tables resident):

* ``plan="external"`` — the split dispatch: hash + table lookup + chain
  planning on device, block fetches through the pluggable BlockStore on
  host (batched per rung, next rung prefetched under the distance
  epilogue), Step-3 epilogue back on device. Bit-exact with plan="fused"
  on a spilled copy of the same index (repro.storage.external).

Plans over a `ShardedIndexArrays` (requires `mesh=`):

* ``plan="sharded"`` — the fused engine dispatched per device inside
  shard_map over per-shard blockified stores (core.distributed);
* ``plan="oracle"``  — the same shard_map with the local oracle (the
  bit-exact parity target for the sharded plan).

All shapes are fixed (TPU requirement): the candidate buffer holds SBUF >= S
slots, chains are walked for a static `max_chain` steps with masking.

I/O accounting (paper Sec. 4.3): one I/O per *non-empty* probed bucket for the
hash-table read (empty buckets are skipped via the DRAM-resident bitmap, as
the paper prescribes) plus one I/O per block chunk actually read. Reads are
round-robin across the L buckets (chunk j of every active bucket per step)
instead of bucket-sequential; both orders examine an arbitrary S-subset of
candidates, and round-robin is the batched-gather (queue-depth-maximizing)
order on TPU. The S cap still truncates chains mid-bucket.

Serving front-ends (serving.BatchQueue) dispatch PADDED batches: every plan
accepts an optional per-query ``valid`` mask, and masked rows are **inert**
— they start in the done state, probe nothing, count zero I/O, and report
``found=False`` / INVALID ids — so a padded tick is bit-exact with
dispatching each real request alone (the queue's parity contract).

The seed's free-function surface (`query_batch*`, `ensure_fused_arrays`,
`make_query_fn`) was deprecated for exactly one PR and is now DELETED;
`make deprecation-lane` asserts the names stay gone.
"""
from __future__ import annotations

import dataclasses
import warnings
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .hashing import fmix32
from .index import IndexArrays
from .probabilities import LSHParams
from ..kernels.bucket_probe.ops import bucket_probe
from ..kernels.dispatch import on_tpu
from ..kernels.l2_distance.ops import l2_distance_gathered
from ..kernels.lsh_hash.ops import lsh_hash_all_radii
from ..telemetry import get_registry, get_tracer

__all__ = ["QueryConfig", "QueryResult", "SearchEngine"]

_QUERY_CALLS = get_registry().counter(
    "e2lsh_query_calls_total", "SearchEngine.query calls",
    labelnames=("plan",))

_INVALID = np.int32(2**31 - 1)

# f32 products on every backend: a TPU's default f32 matmul is one bf16
# pass, which would move query hashes across the float64 build's floor()
# boundaries and perturb distances.
_HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class QueryConfig:
    """Static query-plan parameters (hashable -> usable as a jit static)."""

    L: int
    m: int
    u: int
    fp_bits: int
    w: float
    c: float
    radii: tuple          # full schedule
    S: int                # candidate cap per radius
    block_objs: int       # entries per storage block read
    k: int = 1
    max_chain: int = 4    # static chain-walk steps per radius
    sbuf: int = 0         # candidate buffer width (0 -> derived)
    collect_probe_sizes: bool = False  # record probed bucket sizes (Fig. 3)

    def __post_init__(self):
        if self.sbuf == 0:
            object.__setattr__(self, "sbuf", max(128, -(-self.S // 128) * 128))

    def replace(self, *, s_cap: Optional[int] = None,
                block_objs: Optional[int] = None, **changes) -> "QueryConfig":
        """Constructor path for derived plans (frozen dataclass — never mutate).

        `s_cap` re-derives the candidate buffer width; `block_objs` re-derives
        the chain depth so the narrower chunks still cover S candidates.
        Any other field goes through **changes verbatim.
        """
        if s_cap is not None:
            changes.update(S=int(s_cap), sbuf=0)
        if block_objs is not None and block_objs != self.block_objs:
            S = int(changes.get("S", self.S))
            changes.update(block_objs=int(block_objs),
                           max_chain=max(1, -(-S // int(block_objs)) + 1))
        return dataclasses.replace(self, **changes)

    @staticmethod
    def from_params(p: LSHParams, *, k: int = 1, max_chain: int = 0,
                    collect_probe_sizes: bool = False) -> "QueryConfig":
        if max_chain <= 0:
            # enough steps to reach S candidates even through partial blocks
            max_chain = max(1, min(8, -(-p.S // p.block_objs) + 1))
        return QueryConfig(
            L=p.L, m=p.m, u=p.u, fp_bits=p.fp_bits, w=p.w, c=p.c,
            radii=tuple(p.radii), S=p.S, block_objs=p.block_objs, k=k,
            max_chain=max_chain, collect_probe_sizes=collect_probe_sizes,
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class QueryResult:
    ids: jnp.ndarray          # [Q, k] int32 (INVALID if unfound)
    dists: jnp.ndarray        # [Q, k] float32 (Euclidean, inf if unfound)
    found: jnp.ndarray        # [Q] bool: (R, c)-NN succeeded at some radius
    radii_searched: jnp.ndarray   # [Q] int32
    nio_table: jnp.ndarray    # [Q] int32 hash-table reads (non-empty buckets)
    nio_blocks: jnp.ndarray   # [Q] int32 bucket-block reads
    cands_checked: jnp.ndarray  # [Q] int32 distance computations
    probe_sizes: Optional[jnp.ndarray] = None  # [Q, r, L] int32 (-1 unprobed)

    @property
    def nio(self) -> jnp.ndarray:
        """Total I/O count per query, N_io (paper Sec. 4.3)."""
        return self.nio_table + self.nio_blocks

    # -- row algebra (the serving queue's scatter/gather) -------------------
    def slice_rows(self, lo: int, hi: int) -> "QueryResult":
        """Rows [lo, hi) as a standalone result. Mask-aware by construction:
        padded rows of a queued tick are inert (zero counters, unprobed
        trace), so slicing the real rows back out IS the per-request result
        — there is nothing to renormalize."""
        take = lambda x: None if x is None else x[lo:hi]
        return QueryResult(**{f.name: take(getattr(self, f.name))
                              for f in dataclasses.fields(QueryResult)})

    @staticmethod
    def concat_rows(parts: "list[QueryResult]") -> "QueryResult":
        """Stitch row slices back into one result (a queued request whose
        segments spilled across ticks). Host-side: leaves come back as
        numpy (the queue device_gets each tick once; per-segment device
        slicing would cost more than the dispatch itself)."""
        if len(parts) == 1:
            return parts[0]
        cat = (lambda vs: None if any(v is None for v in vs)
               else np.concatenate([np.asarray(v) for v in vs], axis=0))
        return QueryResult(**{
            f.name: cat([getattr(p, f.name) for p in parts])
            for f in dataclasses.fields(QueryResult)})


def _hash_queries(q, a_t, b_t, rm_t, wr, u, fp_bits):
    """[Q, d] -> bucket [Q, L] int32, fp [Q, L] uint32."""
    proj = jnp.einsum("qd,lmd->qlm", q, a_t, precision=_HIGHEST,
                      preferred_element_type=jnp.float32)
    hj = jnp.floor((proj + b_t[None] * wr) / wr).astype(jnp.int32)
    acc = jnp.sum(hj.astype(jnp.uint32) * rm_t[None].astype(jnp.uint32), axis=-1,
                  dtype=jnp.uint32)
    hv = fmix32(acc)
    bucket = (hv & jnp.uint32((1 << u) - 1)).astype(jnp.int32)
    fp = (hv >> jnp.uint32(u)) & jnp.uint32((1 << fp_bits) - 1)
    return bucket, fp


def _append_candidates(buf_id, count, flat_id, flat_ok, S, SBUF):
    """Compact-append fingerprint matches into the candidate buffer (trunc at S)."""
    Q = buf_id.shape[0]
    rows = jnp.arange(Q, dtype=jnp.int32)[:, None]
    pos = count[:, None] + jnp.cumsum(flat_ok, axis=1) - flat_ok
    keep = flat_ok & (pos < S)
    pos_w = jnp.where(keep, pos, SBUF)  # out-of-range -> dropped
    buf_id = buf_id.at[rows, pos_w].set(flat_id, mode="drop")
    count = jnp.minimum(count + jnp.sum(flat_ok, axis=1, dtype=jnp.int32), S)
    return buf_id, count


def _probe_radius(ix: IndexArrays, queries, qnorm2, t, radius, cfg: QueryConfig,
                  active_q):
    """One (R, c)-NN probe for every query in the batch (ORACLE path).

    Reads the CSR derived view of the block store. Returns (cand_id [Q, SBUF],
    cand_d2 [Q, SBUF], stats dict). `active_q` masks queries already done
    (their I/O is not counted and their buffers are ignored by the caller).
    """
    Q = queries.shape[0]
    L, BLK, S, SBUF = cfg.L, cfg.block_objs, cfg.S, cfg.sbuf
    wr = jnp.float32(cfg.w * radius)
    a_t = jax.lax.dynamic_index_in_dim(ix.a, t, 0, keepdims=False)
    b_t = jax.lax.dynamic_index_in_dim(ix.b, t, 0, keepdims=False)
    rm_t = jax.lax.dynamic_index_in_dim(ix.rm, t, 0, keepdims=False)
    bucket, qfp = _hash_queries(queries, a_t, b_t, rm_t, wr, cfg.u, cfg.fp_bits)

    # hash-table lookup (Step 1): flatten (l, bucket) -> one gather
    toff_t = jax.lax.dynamic_index_in_dim(ix.table_off, t, 0, keepdims=False)
    tcnt_t = jax.lax.dynamic_index_in_dim(ix.table_cnt, t, 0, keepdims=False)
    flat = jnp.arange(L, dtype=jnp.int32)[None, :] * (1 << cfg.u) + bucket
    off = jnp.take(toff_t.reshape(-1), flat, axis=0)     # [Q, L]
    cnt = jnp.take(tcnt_t.reshape(-1), flat, axis=0)     # [Q, L]
    nonempty = (cnt > 0) & active_q[:, None]

    buf_id = jnp.full((Q, SBUF), _INVALID, dtype=jnp.int32)
    count = jnp.zeros((Q,), dtype=jnp.int32)
    blocks_read = jnp.zeros((Q,), dtype=jnp.int32)
    slots = jnp.arange(BLK, dtype=jnp.int32)

    for step in range(cfg.max_chain):
        # a bucket chunk is read iff the bucket still has entries at this depth
        # and the query's S budget is not exhausted (paper: stop mid-bucket at S)
        has_chunk = cnt > step * BLK
        active = nonempty & has_chunk & (count < S)[:, None]      # [Q, L]
        blocks_read = blocks_read + jnp.sum(active, axis=1, dtype=jnp.int32)
        base = off + step * BLK
        idx = base[:, :, None] + slots[None, None, :]             # [Q, L, BLK]
        in_bucket = (step * BLK + slots)[None, None, :] < cnt[:, :, None]
        ok_read = active[:, :, None] & in_bucket
        idx_safe = jnp.where(ok_read, idx, 0)
        eid = jnp.take(ix.entries_id, idx_safe, axis=0)
        efp = jnp.take(ix.entries_fp, idx_safe, axis=0).astype(jnp.uint32)
        ok = ok_read & (efp == qfp[:, :, None])                   # fingerprint filter
        buf_id, count = _append_candidates(
            buf_id, count, eid.reshape(Q, L * BLK), ok.reshape(Q, L * BLK),
            S, SBUF)

    # distance check (Step 3) against the DRAM-tier coordinates
    valid = buf_id != _INVALID
    safe_id = jnp.where(valid, buf_id, 0)
    coords = jnp.take(ix.db, safe_id, axis=0)                     # [Q, SBUF, d]
    dot = jnp.einsum("qsd,qd->qs", coords, queries, precision=_HIGHEST,
                     preferred_element_type=jnp.float32)
    xn2 = jnp.take(ix.db_norm2, safe_id, axis=0)
    d2 = xn2 - 2.0 * dot + qnorm2[:, None]
    d2 = jnp.where(valid, jnp.maximum(d2, 0.0), jnp.inf)

    stats = dict(
        nio_table=jnp.sum(nonempty, axis=1, dtype=jnp.int32),
        nio_blocks=blocks_read,
        cands=count,
    )
    if cfg.collect_probe_sizes:
        stats["probe_sizes"] = jnp.where(nonempty, cnt, -1)
    return buf_id, d2, stats


def _probe_radius_fused(ix: IndexArrays, queries, qnorm2, cnt, head, qfp,
                        cfg: QueryConfig, active_q):
    """One (R, c)-NN probe on the blockified store (FUSED path).

    `cnt`/`head`/`qfp` [Q, L] arrive precomputed (all radii hashed AND looked
    up in one batched pass before the radius loop). Step 2 reads ALL chain
    steps' block rows
    through ONE bucket_probe dispatch (scalar-prefetch gather + fingerprint
    filter on TPU — one call per radius maximizes the DMA queue depth the
    paper's async reads rely on; jnp gather oracle elsewhere), then folds the
    oracle's sequential `count < S` read-gating back in with a scalar scan
    over chain depth. Step 3 runs the l2_distance_gathered epilogue.
    Candidate contents, order, and I/O counts are identical to `_probe_radius`
    (the blockified rows hold exactly the CSR chunk entries, flattened in the
    oracle's (step, l, slot) round-robin order).
    """
    Q = queries.shape[0]
    L, BLK, S, C = cfg.L, cfg.block_objs, cfg.S, cfg.max_chain
    SBUF = _fused_sbuf(cfg)
    BLKp = ix.ids_blocks.shape[1]
    nonempty = (cnt > 0) & active_q[:, None]

    # one gather for the whole chain walk: chunk c of bucket (q, l) is row
    # `head + c` (contiguous rows); chunks past the chain end (and masked
    # queries) read spare row 0, which holds no entries
    steps = jnp.arange(C, dtype=jnp.int32)
    readable = nonempty[:, None, :] & (cnt[:, None, :] > steps[None, :, None] * BLK)
    rows = jnp.where(readable, head[:, None, :] + steps[None, :, None], 0)
    qfp_rep = jnp.broadcast_to(qfp.astype(jnp.int32)[:, None, :], (Q, C, L))
    filt = bucket_probe(rows.reshape(-1), qfp_rep.reshape(-1),
                        ix.ids_blocks, ix.fps_blocks)     # [Q*C*L, BLKp]
    match = filt.reshape(Q, C, L * BLKp)

    # replay the oracle's per-step S-budget gate: chunks at depth c are read
    # iff the candidate count entering step c is below S (count only grows,
    # so this is a C-step scalar scan; matches in unread chunks don't count)
    m_all = jnp.sum(match != _INVALID, axis=2, dtype=jnp.int32)   # [Q, C]
    count = jnp.zeros((Q,), dtype=jnp.int32)
    gates = []
    for c in range(C):
        gate = count < S
        gates.append(gate)
        count = jnp.minimum(count + jnp.where(gate, m_all[:, c], 0), S)
    step_active = jnp.stack(gates, axis=1)                        # [Q, C]
    blocks_read = jnp.sum(readable & step_active[:, :, None], axis=(1, 2),
                          dtype=jnp.int32)

    buf_id = jnp.full((Q, SBUF), _INVALID, dtype=jnp.int32)
    flat_ok = (match != _INVALID) & step_active[:, :, None]
    buf_id, count = _append_candidates(
        buf_id, jnp.zeros((Q,), dtype=jnp.int32),
        match.reshape(Q, C * L * BLKp), flat_ok.reshape(Q, C * L * BLKp),
        S, SBUF)

    # distance check (Step 3) against the DRAM-tier coordinates
    valid = buf_id != _INVALID
    safe_id = jnp.where(valid, buf_id, 0)
    coords = jnp.take(ix.db, safe_id, axis=0)                     # [Q, SBUF, d]
    xn2 = jnp.take(ix.db_norm2, safe_id, axis=0)
    d2 = l2_distance_gathered(queries, coords, xn2, qnorm2)
    d2 = jnp.where(valid, jnp.maximum(d2, 0.0), jnp.inf)

    stats = dict(
        nio_table=jnp.sum(nonempty, axis=1, dtype=jnp.int32),
        nio_blocks=blocks_read,
        cands=count,
    )
    if cfg.collect_probe_sizes:
        stats["probe_sizes"] = jnp.where(nonempty, cnt, -1)
    return buf_id, d2, stats


def _merge_topk(best_id, best_d2, new_id, new_d2, k):
    """Merge candidate set into running top-k with id-dedup."""
    ids = jnp.concatenate([best_id, new_id], axis=1)
    d2 = jnp.concatenate([best_d2, new_d2], axis=1)
    order = jnp.argsort(ids, axis=1)          # INVALID sorts last
    ids_s = jnp.take_along_axis(ids, order, axis=1)
    d2_s = jnp.take_along_axis(d2, order, axis=1)
    dup = jnp.concatenate(
        [jnp.zeros_like(ids_s[:, :1], dtype=bool), ids_s[:, 1:] == ids_s[:, :-1]], axis=1
    ) & (ids_s != _INVALID)
    d2_s = jnp.where(dup, jnp.inf, d2_s)
    order2 = jnp.argsort(d2_s, axis=1)[:, :k]
    out_d2 = jnp.take_along_axis(d2_s, order2, axis=1)
    out_id = jnp.take_along_axis(ids_s, order2, axis=1)
    out_id = jnp.where(jnp.isinf(out_d2), _INVALID, out_id)
    return out_id, out_d2


def _update_state(state, cid, cd2, st, t, radius_thresh2, cfg: QueryConfig):
    """Fold one radius' probe results into the running state (done-masked)."""
    (best_id, best_d2, done, radii_searched, nio_t, nio_b, cands, probe_sizes) = state
    active_q = ~done
    new_id, new_d2 = _merge_topk(best_id, best_d2, cid, cd2, cfg.k)
    # freeze results of queries that were already done (paper reports at the
    # first successful radius)
    best_id = jnp.where(done[:, None], best_id, new_id)
    best_d2 = jnp.where(done[:, None], best_d2, new_d2)
    within = jnp.sum((best_d2 <= radius_thresh2), axis=1) >= cfg.k
    newly_done = within & active_q
    radii_searched = radii_searched + active_q.astype(jnp.int32)
    nio_t = nio_t + st["nio_table"]
    nio_b = nio_b + st["nio_blocks"]
    cands = cands + st["cands"]
    if cfg.collect_probe_sizes:
        probe_sizes = probe_sizes.at[:, t, :].set(
            jnp.where(active_q[:, None], st["probe_sizes"], -1)
        )
    done = done | newly_done
    return (best_id, best_d2, done, radii_searched, nio_t, nio_b, cands, probe_sizes)


def _radius_step(ix, queries, qnorm2, state, t, radius, cfg: QueryConfig):
    active_q = ~state[2]
    cid, cd2, st = _probe_radius(ix, queries, qnorm2, t, radius, cfg, active_q)
    thresh = jnp.float32((cfg.c * radius) ** 2)
    return _update_state(state, cid, cd2, st, t, thresh, cfg)


def _init_state(Q, cfg: QueryConfig, valid=None):
    """Fresh per-query search state. Masked (padded) rows start DONE, which
    makes them inert everywhere downstream: `active_q = ~done` gates the
    probes, the I/O counters, the probe trace, and the while_loop early
    exit, so a padding row never reads a bucket and never holds a tick
    open past the real queries' schedule."""
    r = len(cfg.radii)
    probe_sizes = (
        jnp.full((Q, r, cfg.L), -1, dtype=jnp.int32) if cfg.collect_probe_sizes
        else jnp.zeros((0,), dtype=jnp.int32)
    )
    done0 = (jnp.zeros((Q,), dtype=bool) if valid is None
             else ~valid.astype(bool))
    return (
        jnp.full((Q, cfg.k), _INVALID, dtype=jnp.int32),
        jnp.full((Q, cfg.k), jnp.inf, dtype=jnp.float32),
        done0,
        jnp.zeros((Q,), dtype=jnp.int32),
        jnp.zeros((Q,), dtype=jnp.int32),
        jnp.zeros((Q,), dtype=jnp.int32),
        jnp.zeros((Q,), dtype=jnp.int32),
        probe_sizes,
    )


def _result_from_state(state, cfg, valid=None) -> QueryResult:
    (best_id, best_d2, done, radii_searched, nio_t, nio_b, cands, probe_sizes) = state
    found = done if valid is None else done & valid.astype(bool)
    return QueryResult(
        ids=best_id,
        dists=jnp.sqrt(best_d2),
        found=found,
        radii_searched=radii_searched,
        nio_table=nio_t,
        nio_blocks=nio_b,
        cands_checked=cands,
        probe_sizes=probe_sizes if cfg.collect_probe_sizes else None,
    )


def _prep_queries(queries):
    queries = queries.astype(jnp.float32)
    return queries, jnp.sum(queries * queries, axis=-1)


# XLA lowers a Q=1 batch's contractions as a matvec whose accumulation order
# differs from the gemm every Q>=2 shape shares, so a lone query's hashes and
# distances would not be bit-identical with the same row inside a padded
# serving tick. Dispatching Q=1 as a masked Q=2 keeps every plan on the
# row-stable gemm path — the shape-independence the queue's parity contract
# (queued == direct, any ladder rung) is built on; tests/test_serving_queue
# pins it across the whole ladder.
_MIN_DISPATCH_Q = 2


def _pad_min_q(queries, valid):
    """Pad a sub-minimum batch with masked rows. Returns (queries, valid,
    real_Q); real_Q is static under jit, so callers slice at trace time."""
    Q = queries.shape[0]
    if Q >= _MIN_DISPATCH_Q:
        return queries, valid, Q
    pad = _MIN_DISPATCH_Q - Q
    queries = jnp.concatenate(
        [queries, jnp.zeros((pad,) + queries.shape[1:], queries.dtype)])
    v = jnp.ones((Q,), dtype=bool) if valid is None else valid.astype(bool)
    valid = jnp.concatenate([v, jnp.zeros((pad,), dtype=bool)])
    return queries, valid, Q


def _fused_sbuf(cfg: QueryConfig) -> int:
    """Internal candidate-buffer width for the fused probe.

    cfg.sbuf carries the TPU 128-lane alignment; off-TPU the padding slots
    are pure dead work (they are always INVALID), so the fused engine tightens
    the buffer to S rounded to the SIMD-friendly 8. Results are identical for
    any width >= S — padding slots never hold candidates.
    """
    return cfg.sbuf if on_tpu() else max(8, -(-cfg.S // 8) * 8)


# --------------------------------------------------------------------------
# Plan bodies: traceable over an IndexArrays pytree. These are what the
# jitted plan entry points AND the shard_map local plans (core.distributed)
# share — the whole point of the typed seam.
# --------------------------------------------------------------------------

def oracle_plan_body(ix: IndexArrays, queries: jnp.ndarray,
                     cfg: QueryConfig, valid=None) -> QueryResult:
    """Reference ORACLE plan: all radii unrolled with done-masking, CSR
    gathers. jit-able and shard_map-able; every other plan must match it.
    `valid` [Q] bool masks padded serving rows (inert: see _init_state)."""
    queries, valid, realQ = _pad_min_q(queries, valid)
    queries, qnorm2 = _prep_queries(queries)
    state = _init_state(queries.shape[0], cfg, valid)
    for t, radius in enumerate(cfg.radii):
        state = _radius_step(ix, queries, qnorm2, state, t, float(radius), cfg)
    return _result_from_state(state, cfg, valid).slice_rows(0, realQ)


def fused_plan_body(ix: IndexArrays, queries: jnp.ndarray,
                    cfg: QueryConfig, valid=None) -> QueryResult:
    """FUSED plan: precomputed all-radius hashes + table lookups, blockified
    kernel-backed probes, device-side while_loop early exit. Consumes the
    block store the build emitted natively."""
    if ix.block_objs != cfg.block_objs:  # a raise survives python -O
        raise ValueError(
            f"IndexArrays blockified at block_objs={ix.block_objs} but the "
            f"query plan wants {cfg.block_objs}; re-blockify with "
            "IndexArrays.with_block_objs (SearchEngine does this "
            "automatically)")
    queries, valid, realQ = _pad_min_q(queries, valid)
    queries, qnorm2 = _prep_queries(queries)
    Q = queries.shape[0]
    r = len(cfg.radii)
    # Step 1 for the WHOLE schedule: one kernel dispatch hashes every radius
    # (the per-radius a/b/rm tensors are stacked [r, ...] already)
    bucket_all, qfp_all = lsh_hash_all_radii(
        queries, ix.a, ix.b, ix.rm,
        w=cfg.w, radii=cfg.radii, u=cfg.u, fp_bits=cfg.fp_bits,
    )
    # ... and the hash-table lookups for the whole schedule too: bucket sizes
    # and chain-head rows for every (t, q, l) in two batched gathers, so the
    # radius loop only slices [Q, L] views
    tl = (jnp.arange(r, dtype=jnp.int32)[:, None, None] * cfg.L
          + jnp.arange(cfg.L, dtype=jnp.int32)[None, None, :])
    flat_all = tl * (1 << cfg.u) + bucket_all                  # [r, Q, L]
    cnt_all = jnp.take(ix.table_cnt.reshape(-1), flat_all, axis=0)
    head_all = jnp.take(ix.blocks_head.reshape(-1), flat_all, axis=0)
    thresh2 = jnp.asarray([(cfg.c * float(rad)) ** 2 for rad in cfg.radii],
                          jnp.float32)
    state0 = _init_state(Q, cfg, valid)

    def cond(carry):
        t, state = carry
        return (t < r) & ~jnp.all(state[2])

    def body(carry):
        t, state = carry
        cnt = jax.lax.dynamic_index_in_dim(cnt_all, t, 0, keepdims=False)
        head = jax.lax.dynamic_index_in_dim(head_all, t, 0, keepdims=False)
        qfp = jax.lax.dynamic_index_in_dim(qfp_all, t, 0, keepdims=False)
        active_q = ~state[2]
        cid, cd2, st = _probe_radius_fused(
            ix, queries, qnorm2, cnt, head, qfp, cfg, active_q)
        state = _update_state(state, cid, cd2, st, t, thresh2[t], cfg)
        return t + 1, state

    _, state = jax.lax.while_loop(cond, body, (jnp.int32(0), state0))
    return _result_from_state(state, cfg, valid).slice_rows(0, realQ)


@partial(jax.jit, static_argnames=("cfg",))
def _oracle_jit(ix: IndexArrays, queries, cfg: QueryConfig) -> QueryResult:
    return oracle_plan_body(ix, queries, cfg)


@partial(jax.jit, static_argnames=("cfg",))
def _fused_jit(ix: IndexArrays, queries, cfg: QueryConfig) -> QueryResult:
    return fused_plan_body(ix, queries, cfg)


# masked variants: the serving queue's dispatch targets. Separate jit
# wrappers (not a None default on the plain ones) so the unmasked entry
# points keep their argument treedefs — and their compile caches — unchanged.
@partial(jax.jit, static_argnames=("cfg",))
def _oracle_masked_jit(ix: IndexArrays, queries, valid,
                       cfg: QueryConfig) -> QueryResult:
    return oracle_plan_body(ix, queries, cfg, valid)


@partial(jax.jit, static_argnames=("cfg",))
def _fused_masked_jit(ix: IndexArrays, queries, valid,
                      cfg: QueryConfig) -> QueryResult:
    return fused_plan_body(ix, queries, cfg, valid)


@partial(jax.jit, static_argnames=("cfg", "t_static"))
def _one_radius_jit(ix, queries, qnorm2, state, t_static, cfg):
    return _radius_step(ix, queries, qnorm2, state, t_static,
                        float(cfg.radii[t_static]), cfg)


def _host_plan(ix: IndexArrays, queries: jnp.ndarray,
               cfg: QueryConfig, valid=None) -> QueryResult:
    """PRE-FUSION adaptive path, kept as the benchmark baseline: one jitted
    dispatch plus one device->host sync per radius. Identical results."""
    queries, valid, realQ = _pad_min_q(jnp.asarray(queries), valid)
    queries, qnorm2 = _prep_queries(queries)
    state = _init_state(queries.shape[0], cfg, valid)
    for t in range(len(cfg.radii)):
        state = _one_radius_jit(ix, queries, qnorm2, state, t, cfg)
        if bool(jax.device_get(jnp.all(state[2]))):
            break
    return _result_from_state(state, cfg, valid).slice_rows(0, realQ)


# --------------------------------------------------------------------------
# The facade
# --------------------------------------------------------------------------

class SearchEngine:
    """One query entry point over pluggable execution plans.

    ``index`` may be an ``E2LSHoS`` facade, a bare ``E2LSHIndex``, or a
    ``core.distributed.ShardedIndexArrays`` (then pass ``mesh=`` and the
    sharded plans apply). The engine memoizes re-blockified layouts per
    ``block_objs`` so the timing knob repacks once, and every plan receives
    the same typed `IndexArrays` pytree.
    """

    SINGLE_PLANS = ("fused", "host", "oracle")
    SHARDED_PLANS = ("sharded", "oracle")
    EXTERNAL_PLANS = ("external",)
    SHARDED_EXTERNAL_PLANS = ("sharded_external",)

    def __init__(self, index, *, mesh=None, index_axes=("shard",),
                 query_axes=()):
        if hasattr(index, "index") and hasattr(index, "tier"):  # E2LSHoS
            index = index.index
        self.params: LSHParams = index.params
        self.mesh = mesh
        self.index_axes = tuple(index_axes)
        self.query_axes = tuple(query_axes)
        self._single = self._sharded = self._external = None
        if hasattr(index, "store") and hasattr(index, "blocks_head"):
            # ExternalIndex (repro.storage): block rows live on disk behind
            # the BlockStore; there is no in-memory IndexArrays to serve.
            # A ShardedExternalIndex (striped per-shard stores) carries a
            # num_shards attr and serves under plan="sharded_external".
            self._external = index
            self._external_striped = hasattr(index, "num_shards")
            self._base_block_objs = index.block_objs
            self._by_block_objs = {}
            return
        if hasattr(index, "num_shards"):      # ShardedIndexArrays
            if mesh is not None:   # each shard straight to its own device
                index = index.to_mesh(mesh, self.index_axes)
            self._sharded = index
            self._sharded_by_bo = {index.arrays.block_objs: index}
        else:                                  # E2LSHIndex
            self._single = index
        base: IndexArrays = index.arrays
        self._by_block_objs = {base.block_objs: base}
        self._base_block_objs = base.block_objs

    # -- introspection ------------------------------------------------------
    @property
    def plans(self) -> tuple:
        if self._external is not None:
            return (self.SHARDED_EXTERNAL_PLANS if self._external_striped
                    else self.EXTERNAL_PLANS)
        return self.SHARDED_PLANS if self._sharded is not None else self.SINGLE_PLANS

    @property
    def default_plan(self) -> str:
        if self._external is not None:
            return "sharded_external" if self._external_striped else "external"
        return "sharded" if self._sharded is not None else "fused"

    @property
    def external(self):
        """The engine's ``ExternalIndex`` (None for in-memory engines) —
        the typed handle to the storage tier's observability surfaces:
        ``.last_plan_stats`` (the most recent plan call's instrumentation),
        ``.plan_totals`` (the accumulating roll-up the queued serving path
        needs — last_plan_stats is overwritten per tick), and ``.store``
        (the live I/O ledger)."""
        return self._external

    @property
    def last_external_stats(self):
        """Deprecated (one-PR window, telemetry PR): use
        ``engine.external.last_plan_stats`` — or ``.plan_totals`` /
        ``telemetry.snapshot()`` when accumulating across queued ticks,
        which this overwritten-per-call surface silently cannot do."""
        warnings.warn(
            "SearchEngine.last_external_stats is deprecated: use "
            "engine.external.last_plan_stats (per-call), "
            "engine.external.plan_totals (accumulating), or "
            "repro.telemetry.snapshot() (unified metrics)",
            DeprecationWarning, stacklevel=2)
        return (self._external.last_plan_stats
                if self._external is not None else None)

    # -- typed array access -------------------------------------------------
    def arrays(self, block_objs: Optional[int] = None) -> IndexArrays:
        """The typed index pytree, re-blockified (and memoized) on demand.
        On a sharded engine this repacks EVERY shard's store and re-pads the
        stacked rows to the new common extent (once per block size)."""
        if self._external is not None:
            raise ValueError(
                "an external index keeps its block rows on disk — there is "
                "no in-memory IndexArrays to serve. Use plan=\"external\" "
                "(the BlockStore streams the rows), or materialize the full "
                f"pytree with repro.storage.load_arrays({self._external.path!r})")
        bo = int(block_objs or self._base_block_objs)
        if self._sharded is not None:
            return self._sharded_for(bo).arrays
        if bo not in self._by_block_objs:
            self._by_block_objs[bo] = (
                self._by_block_objs[self._base_block_objs].with_block_objs(bo))
        return self._by_block_objs[bo]

    def _sharded_for(self, block_objs: Optional[int]):
        """The (memoized) ShardedIndexArrays re-blockified per shard at the
        requested block size (ROADMAP "sharded block_objs knob")."""
        bo = int(block_objs or self._base_block_objs)
        if bo not in self._sharded_by_bo:
            sh = self._sharded_by_bo[self._base_block_objs].with_block_objs(bo)
            if self.mesh is not None:
                sh = sh.to_mesh(self.mesh, self.index_axes)
            self._sharded_by_bo[bo] = sh
        return self._sharded_by_bo[bo]

    def config(self, *, k: int = 1, collect_probe_sizes: bool = False,
               s_cap: Optional[int] = None, max_chain: int = 0,
               block_objs: Optional[int] = None) -> QueryConfig:
        cfg = QueryConfig.from_params(
            self.params, k=k, max_chain=max_chain,
            collect_probe_sizes=collect_probe_sizes,
        )
        # narrower gather chunks (timing knob): identical candidates and
        # results; storage-block I/O accounting is replayed separately at
        # the paper's 512 B granularity (io_count)
        return cfg.replace(s_cap=s_cap, block_objs=block_objs)

    # -- the entry point ----------------------------------------------------
    def query(self, queries, *, plan: Optional[str] = None, k: int = 1,
              s_cap: Optional[int] = None, block_objs: Optional[int] = None,
              collect_probe_sizes: bool = False,
              s_cap_per_shard: Optional[int] = None,
              valid=None) -> QueryResult:
        """Run a query batch under the selected execution plan.

        plan: "fused" (production single-dispatch while_loop), "oracle"
        (unrolled reference; on a sharded engine, the per-shard reference),
        "host" (pre-fusion per-radius host loop, benchmarking only), or
        "sharded" (fused engine per device inside shard_map). None selects
        the production plan for the index type.

        valid: optional [Q] bool mask for padded serving batches — masked
        rows are inert (no probes, zero I/O counters, unprobed trace,
        found=False) and the unmasked rows are bit-exact with an unpadded
        dispatch.
        """
        plan = plan or self.default_plan
        _QUERY_CALLS.inc(plan=plan)
        tr = get_tracer()
        if not tr.enabled:
            return self._query_impl(
                queries, plan=plan, k=k, s_cap=s_cap, block_objs=block_objs,
                collect_probe_sizes=collect_probe_sizes,
                s_cap_per_shard=s_cap_per_shard, valid=valid)
        with tr.span("query", plan=plan, k=k):
            return self._query_impl(
                queries, plan=plan, k=k, s_cap=s_cap, block_objs=block_objs,
                collect_probe_sizes=collect_probe_sizes,
                s_cap_per_shard=s_cap_per_shard, valid=valid)

    def _query_impl(self, queries, *, plan: str, k: int,
                    s_cap: Optional[int], block_objs: Optional[int],
                    collect_probe_sizes: bool,
                    s_cap_per_shard: Optional[int],
                    valid) -> QueryResult:
        queries = jnp.asarray(queries)
        if valid is not None:
            valid = jnp.asarray(valid, dtype=bool)
        if self._external is not None:
            allowed = self.plans
            if plan not in allowed:
                raise ValueError(
                    f"unknown plan {plan!r} for an external index; expected "
                    f"one of {allowed} (load the index in memory "
                    "for the fused/oracle plans)")
            if s_cap_per_shard is not None:
                raise ValueError("s_cap_per_shard only applies to the "
                                 "in-memory sharded plans (the striped "
                                 "external plan keeps the global S budget — "
                                 "that is what makes it bit-exact with "
                                 "fused)")
            # the on-disk layout is fixed at spill time: the store's block
            # size is the ONLY valid cfg.block_objs (external_plan enforces)
            bo = (block_objs if block_objs is not None
                  else self._external.block_objs)
            cfg = self.config(k=k, collect_probe_sizes=collect_probe_sizes,
                              s_cap=s_cap, block_objs=bo)
            if self._external_striped:
                from ..storage.sharded import sharded_external_plan
                return sharded_external_plan(self._external, queries, cfg,
                                             valid)
            from ..storage.external import external_plan
            return external_plan(self._external, queries, cfg, valid)
        if self._sharded is not None:
            if plan not in self.SHARDED_PLANS:
                raise ValueError(
                    f"unknown plan {plan!r} for a sharded index; expected one "
                    f"of {self.SHARDED_PLANS}")
            if collect_probe_sizes:
                raise ValueError("collect_probe_sizes is not supported under "
                                 "the sharded plans")
            if self.mesh is None:
                raise ValueError("sharded plans need SearchEngine(..., mesh=)")
            from .distributed import sharded_query_result
            return sharded_query_result(
                self._sharded_for(block_objs), queries, self.mesh, k=k,
                index_axes=self.index_axes, query_axes=self.query_axes,
                s_cap=s_cap, s_cap_per_shard=s_cap_per_shard,
                local_plan="fused" if plan == "sharded" else "oracle",
                valid=valid,
            )
        if plan not in self.SINGLE_PLANS:
            raise ValueError(f"unknown plan {plan!r}; expected one of "
                             f"{self.SINGLE_PLANS + ('sharded',)} "
                             "(sharded needs a ShardedIndexArrays index)")
        if s_cap_per_shard is not None:
            raise ValueError("s_cap_per_shard only applies to sharded plans; "
                             "use s_cap for a single-device index")
        cfg = self.config(k=k, collect_probe_sizes=collect_probe_sizes,
                          s_cap=s_cap, block_objs=block_objs)
        if plan == "host":
            return _host_plan(self.arrays(), queries, cfg, valid)
        ix = self.arrays(cfg.block_objs if plan == "fused" else None)
        if valid is None:
            run = _fused_jit if plan == "fused" else _oracle_jit
            return run(ix, queries, cfg)
        run = _fused_masked_jit if plan == "fused" else _oracle_masked_jit
        return run(ix, queries, valid, cfg)

    def make_plan_fn(self, *, plan: Optional[str] = None, k: int = 1,
                     masked: bool = False, **kw):
        """(cfg, fn): a QueryConfig plus a closure pinned to one plan — what
        serving loops close over. For single-index plans the config and
        (re-blockified) arrays are resolved ONCE here, so the closure adds
        zero per-call host work to the dispatch path.

        masked=False: ``fn(queries) -> QueryResult``.
        masked=True:  ``fn(queries, valid) -> QueryResult`` — the padded-tick
        dispatch target of serving.BatchQueue (valid [Q] bool; masked rows
        inert)."""
        plan = plan or self.default_plan
        if self._external is not None:
            allowed = self.plans
            if plan not in allowed:
                raise ValueError(
                    f"unknown plan {plan!r} for an external index; expected "
                    f"one of {allowed}")
            bo = kw.pop("block_objs", None)
            cfg = self.config(k=k, block_objs=(
                bo if bo is not None else self._external.block_objs), **kw)
            if self._external_striped:
                from ..storage.sharded import sharded_external_plan as run_ext
            else:
                from ..storage.external import external_plan as run_ext
            ext = self._external
            if masked:
                def fn(queries, valid):
                    return run_ext(ext, queries, cfg, valid)
            else:
                def fn(queries):
                    return run_ext(ext, queries, cfg)
            return cfg, fn
        if self._sharded is not None:
            # the sharded executor rebuilds its per-shard config from params
            # (sharded_query_result applies the S budget internally), so any
            # knob it cannot honor must be REJECTED here — silently accepting
            # collect_probe_sizes/max_chain would return a cfg that lies
            # about the executed plan. block_objs IS honored now: the stack
            # is re-blockified per shard (memoized) and the executor derives
            # its chunking from the arrays' layout.
            s_cap_per_shard = kw.pop("s_cap_per_shard", None)
            if kw.get("collect_probe_sizes"):
                raise ValueError("collect_probe_sizes is not supported under "
                                 "the sharded plans")
            if kw.get("max_chain"):
                raise ValueError("max_chain override is not supported under "
                                 "the sharded plans (the per-shard schedule "
                                 "is derived from the index params)")
            unknown = set(kw) - {"s_cap", "collect_probe_sizes", "block_objs",
                                 "max_chain"}
            if unknown:
                raise TypeError(f"unexpected plan kwargs {sorted(unknown)}")
            sh = self._sharded_for(kw.get("block_objs"))
            # the returned cfg reflects the pre-shard schedule
            cfg = self.config(k=k, s_cap=kw.get("s_cap"),
                              block_objs=kw.get("block_objs"))

            if masked:
                # the serving queue's dispatch target: ONE jitted program per
                # batch shape (the eager shard_map path would re-trace every
                # tick, breaking the queue's warmed-ladder no-retrace
                # contract)
                if plan not in self.SHARDED_PLANS:
                    raise ValueError(
                        f"unknown plan {plan!r} for a sharded index; expected "
                        f"one of {self.SHARDED_PLANS}")
                if self.mesh is None:
                    raise ValueError("sharded plans need SearchEngine(..., "
                                     "mesh=)")
                from .distributed import sharded_query_result
                mesh = self.mesh
                index_axes, query_axes = self.index_axes, self.query_axes
                s_cap = kw.get("s_cap")
                local_plan = "fused" if plan == "sharded" else "oracle"

                @jax.jit
                def run(arrays, offs, queries, valid):
                    tmp = dataclasses.replace(sh, arrays=arrays,
                                              shard_offsets=offs)
                    return sharded_query_result(
                        tmp, queries, mesh, k=k, index_axes=index_axes,
                        query_axes=query_axes, s_cap=s_cap,
                        s_cap_per_shard=s_cap_per_shard,
                        local_plan=local_plan, valid=valid)

                def fn(queries, valid):
                    return run(sh.arrays, sh.shard_offsets,
                               jnp.asarray(queries),
                               jnp.asarray(valid, dtype=bool))
            else:
                s_cap = kw.get("s_cap")
                block_objs = kw.get("block_objs")

                def fn(queries):
                    return self.query(queries, plan=plan, k=k, s_cap=s_cap,
                                      block_objs=block_objs,
                                      s_cap_per_shard=s_cap_per_shard)

            return cfg, fn
        if plan not in self.SINGLE_PLANS:
            raise ValueError(f"unknown plan {plan!r}; expected one of "
                             f"{self.SINGLE_PLANS}")
        cfg = self.config(k=k, **kw)
        ix = self.arrays(cfg.block_objs if plan == "fused" else None)
        if masked:
            run = {"fused": _fused_masked_jit, "oracle": _oracle_masked_jit,
                   "host": (lambda ix_, q, v, c: _host_plan(ix_, q, c, v))}[plan]

            def fn(queries, valid):
                return run(ix, jnp.asarray(queries),
                           jnp.asarray(valid, dtype=bool), cfg)
        else:
            run = {"fused": _fused_jit, "oracle": _oracle_jit,
                   "host": _host_plan}[plan]

            def fn(queries):
                return run(ix, jnp.asarray(queries), cfg)

        return cfg, fn


