"""The ``plan="external"`` execution plan: E2LSHoS actually run from storage.

This is the paper's headline configuration (Secs. 5-6) made real: hash
tables and family params stay resident, bucket block rows live on disk, and
a query alternates device compute with host block fetches at the natural
seam of the fused plan:

  1. **Setup (device, one dispatch).** The whole radius schedule's query
     hashes run through ``kernels.lsh_hash_all_radii`` and the hash-table
     lookups (bucket sizes + chain head rows for every ``(t, q, l)``) batch
     into two gathers — identical programs to the fused plan's pre-loop, so
     buckets/fingerprints are bit-identical.
  2. **Chain walk (host, per radius rung).** Block rows are fetched through
     the pluggable :class:`~repro.storage.blockstore.BlockStore` — batched
     per chain step so the ``aio`` backend sees deep queues — and
     fingerprint-filtered with exactly the oracle's round-robin append
     semantics (S-cap gating per step, ``(l, slot)`` flat order). Every
     fetch is counted: the store's logical ``reads`` ledger is the measured
     N_io that must equal the Eq. 6/7 replay.
  3. **Distance epilogue (device, one dispatch per rung).** Candidates go
     through the same ``l2_distance_gathered`` kernel + top-k merge the
     fused plan uses, while the host **prefetches the next rung's chain
     heads** into the block-store cache — the fetch/compute overlap of the
     paper's async design (Eq. 7's ``max(T_compute, T_storage)``).

Parity contract: on a spilled copy of an index, ``plan="external"`` (any
backend) is bit-exact with ``plan="fused"`` on every ``QueryResult`` field —
the chain walk replicates the oracle's integer candidate selection on the
host, and the float epilogues reuse the same kernel ops with the same
operand shapes (tests/test_storage_external.py pins this, and the
measured-vs-replay N_io tie-out lives in tests/test_io_count.py).
"""
from __future__ import annotations

import dataclasses
import threading
import time
import weakref
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.index import IndexStats
from ..core.probabilities import LSHParams
from ..core.query import (QueryConfig, QueryResult, _fused_sbuf, _init_state,
                          _pad_min_q, _result_from_state, _update_state)
from ..kernels.l2_distance.ops import l2_distance_gathered
from ..kernels.lsh_hash.ops import lsh_hash_all_radii
from ..telemetry import get_registry, get_tracer
from .blockstore import BlockStore, StoreStats

__all__ = ["ExternalIndex", "ExternalPlanStats", "ExternalPlanTotals",
           "RungStats", "external_plan"]

_INVALID = np.int32(2**31 - 1)


@dataclasses.dataclass
class ExternalPlanTotals:
    """ACCUMULATING roll-up of every external-plan call on one index — the
    concurrency-safe counterpart of ``last_plan_stats`` (which is per-call
    and overwritten by design): under a BatchQueue every tick's stats fold
    in here instead of clobbering each other. ``snapshot()``/``since()``
    bracket a window the way ``StoreStats`` does; the telemetry registry
    reads these totals live."""

    calls: int = 0
    queries: int = 0
    nio_blocks: int = 0             # logical block reads (== io.reads sums)
    prefetch_rows: int = 0
    setup_ms: float = 0.0
    total_ms: float = 0.0
    fetch_ms: float = 0.0
    compute_wait_ms: float = 0.0
    overlap_ms: float = 0.0
    # per rung POSITION t (bounded by the radius schedule length)
    rung_blocks: dict = dataclasses.field(default_factory=dict)
    rung_entered: dict = dataclasses.field(default_factory=dict)

    _SCALARS = ("calls", "queries", "nio_blocks", "prefetch_rows", "setup_ms",
                "total_ms", "fetch_ms", "compute_wait_ms", "overlap_ms")

    def add(self, ps: "ExternalPlanStats") -> None:
        self.calls += 1
        self.queries += ps.queries
        self.nio_blocks += ps.io.reads
        self.setup_ms += ps.setup_ms
        self.total_ms += ps.total_ms
        for r in ps.rungs:
            self.prefetch_rows += r.prefetch_rows
            self.fetch_ms += r.fetch_ms
            self.compute_wait_ms += r.compute_wait_ms
            self.overlap_ms += r.overlap_ms
            self.rung_blocks[r.t] = (self.rung_blocks.get(r.t, 0)
                                     + r.blocks_fetched)
            self.rung_entered[r.t] = self.rung_entered.get(r.t, 0) + 1

    def snapshot(self) -> "ExternalPlanTotals":
        return dataclasses.replace(self, rung_blocks=dict(self.rung_blocks),
                                   rung_entered=dict(self.rung_entered))

    def since(self, base: "ExternalPlanTotals") -> "ExternalPlanTotals":
        out = ExternalPlanTotals(**{
            f: getattr(self, f) - getattr(base, f) for f in self._SCALARS})
        for name in ("rung_blocks", "rung_entered"):
            mine, theirs = getattr(self, name), getattr(base, name)
            d = {t: v - theirs.get(t, 0) for t, v in mine.items()}
            setattr(out, name, {t: v for t, v in d.items() if v})
        return out

    def as_dict(self) -> dict:
        d = {f: getattr(self, f) for f in self._SCALARS}
        d["rung_blocks"] = dict(self.rung_blocks)
        d["rung_entered"] = dict(self.rung_entered)
        return d


# totals accumulate under one module lock (ticks already serialize at the
# queue; this guards direct multi-threaded engine use)
_TOTALS_LOCK = threading.Lock()


@dataclasses.dataclass(eq=False)      # identity semantics: telemetry weak-set
class ExternalIndex:
    """A spilled index opened for external-memory querying: resident hash
    tables + DRAM tier, block rows behind a :class:`BlockStore`. Built by
    ``repro.storage.load_external``; served by ``SearchEngine(ext)`` under
    ``plan="external"``."""

    params: LSHParams
    a: jnp.ndarray            # hash family [r, L, m, d]
    b: jnp.ndarray
    rm: jnp.ndarray
    blocks_head: jnp.ndarray  # [r, L, 2^u] first block row per bucket
    table_cnt: jnp.ndarray    # [r, L, 2^u] bucket sizes
    db: jnp.ndarray           # DRAM tier [n, d]
    db_norm2: jnp.ndarray
    block_objs: int
    lane_pad: int
    blkp: int                 # padded block-row width of the spilled store
    store: BlockStore
    path: str
    stats: Optional[IndexStats] = None
    last_plan_stats: Optional["ExternalPlanStats"] = None
    # the accumulating ledger every external_plan call folds into (never
    # overwritten — the BatchQueue-safe stat surface; see ExternalPlanTotals)
    plan_totals: ExternalPlanTotals = dataclasses.field(
        default_factory=ExternalPlanTotals)
    # chain steps of the NEXT rung pushed into the store's queue while the
    # device fold runs (Eq. 7 overlap). 1 = chain heads only (PR 4
    # behavior); deeper values keep an async backend's queue full across
    # the rung boundary — worth raising with `uring` on a real device.
    prefetch_depth: int = 1
    # probe-trace row histogram (block row -> times walked), accumulated by
    # external_plan when enabled — the serving queue's cache-warming signal
    collect_row_hist: bool = False
    row_hist: Optional[dict] = None

    def __post_init__(self):
        self._retired = False
        _LIVE_EXTERNAL.add(self)

    @property
    def backend(self) -> str:
        return self.store.name

    def record_probe_rows(self, rows) -> None:
        """Fold one chain step's block rows into the probe-trace histogram."""
        if self.row_hist is None:
            self.row_hist = {}
        h = self.row_hist
        uniq, counts = np.unique(np.asarray(rows, np.int64).ravel(),
                                 return_counts=True)
        for g, c in zip(uniq.tolist(), counts.tolist()):
            h[g] = h.get(g, 0) + c

    def hot_rows(self, top: Optional[int] = None) -> np.ndarray:
        """The most-walked block rows, hottest first (empty until a plan ran
        with ``collect_row_hist``)."""
        if not self.row_hist:
            return np.zeros((0,), dtype=np.int64)
        rows = sorted(self.row_hist, key=self.row_hist.get, reverse=True)
        if top is not None:
            rows = rows[:int(top)]
        return np.asarray(rows, dtype=np.int64)

    def warm_cache(self, top: Optional[int] = 1024) -> int:
        """Prefetch the hottest probe-trace rows into the store's cache
        arena (each shard's own arena when the store is striped). Advisory:
        prefetch never touches the logical ``reads`` ledger. Returns the
        number of rows pushed."""
        rows = self.hot_rows(top)
        if rows.size:
            self.store.prefetch(rows)
        return int(rows.size)

    def close(self) -> None:
        self.store.close()
        _retire_external(self)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# -- registry glue: live indices + retired totals ---------------------------
_LIVE_EXTERNAL: "weakref.WeakSet" = weakref.WeakSet()
_RETIRED_TOTALS: dict = {}          # backend -> ExternalPlanTotals
_RETIRED_EXT_LOCK = threading.Lock()


def _retire_external(ext: "ExternalIndex") -> None:
    if getattr(ext, "_retired", True):
        return
    ext._retired = True
    _LIVE_EXTERNAL.discard(ext)
    with _RETIRED_EXT_LOCK:
        agg = _RETIRED_TOTALS.setdefault(ext.backend, ExternalPlanTotals())
        snap = ext.plan_totals.snapshot()
        for f in ExternalPlanTotals._SCALARS:
            setattr(agg, f, getattr(agg, f) + getattr(snap, f))
        for name in ("rung_blocks", "rung_entered"):
            mine = getattr(agg, name)
            for t, v in getattr(snap, name).items():
                mine[t] = mine.get(t, 0) + v


def _collect_external_metrics() -> dict:
    """Registry collector: the per-backend ExternalPlanTotals roll-up —
    call/query counts, the fetch/compute/overlap time split of the Eq. 6/7
    decomposition, and per-rung-position block counts."""
    with _RETIRED_EXT_LOCK:
        groups = {b: t.snapshot() for b, t in _RETIRED_TOTALS.items()}
    for ext in list(_LIVE_EXTERNAL):
        agg = groups.setdefault(ext.backend, ExternalPlanTotals())
        with _TOTALS_LOCK:
            snap = ext.plan_totals.snapshot()
        for f in ExternalPlanTotals._SCALARS:
            setattr(agg, f, getattr(agg, f) + getattr(snap, f))
        for name in ("rung_blocks", "rung_entered"):
            mine = getattr(agg, name)
            for t, v in getattr(snap, name).items():
                mine[t] = mine.get(t, 0) + v
    helps = dict(
        calls="external-plan calls",
        queries="real query rows served by the external plan",
        nio_blocks="logical block reads (measured N_io)",
        prefetch_rows="next-rung rows pushed to the cache",
        setup_ms="device setup + schedule transfer time",
        total_ms="end-to-end external-plan time",
        fetch_ms="host chain-walk time (block fetch + filter)",
        compute_wait_ms="host wait on the device fold after prefetch",
        overlap_ms="host prefetch time hidden under device compute",
    )
    out = {}
    for f in ExternalPlanTotals._SCALARS:
        out[f"e2lsh_external_{f}_total"] = dict(
            type="counter", help=helps[f],
            samples=[dict(labels={"backend": b}, value=getattr(t, f))
                     for b, t in sorted(groups.items())])
    for name, help_ in (("rung_blocks", "block reads per rung position"),
                        ("rung_entered", "times each rung position ran")):
        samples = []
        for b, tot in sorted(groups.items()):
            for t, v in sorted(getattr(tot, name).items()):
                samples.append(dict(labels={"backend": b, "t": str(t)},
                                    value=v))
        out[f"e2lsh_external_{name}_total"] = dict(
            type="counter", help=help_, samples=samples)
    return out


get_registry().register_collector(_collect_external_metrics,
                                  name="storage.external")


@dataclasses.dataclass
class RungStats:
    """One radius rung's fetch/compute overlap record."""

    t: int                  # radius index
    active_queries: int
    blocks_fetched: int     # logical block reads this rung
    fetch_ms: float         # host chain walk (block fetches + filtering)
    prefetch_rows: int      # next-rung rows pushed to the cache
    compute_wait_ms: float  # host wait on the device fold AFTER prefetching
    overlap_ms: float       # host prefetch time hidden under device compute


@dataclasses.dataclass
class ExternalPlanStats:
    """Per-call instrumentation of the external plan (the measured side of
    the Eq. 6/7 validation)."""

    backend: str
    queries: int
    rungs: list                     # [RungStats]
    io: StoreStats                  # store ledger DELTA for this call
    nio_blocks_counted: int         # sum of QueryResult.nio_blocks
    setup_ms: float = 0.0
    total_ms: float = 0.0

    @property
    def measured_nio_blocks(self) -> int:
        """Logical block reads the store served for this call — must equal
        ``nio_blocks_counted`` (and the io_count replay) exactly."""
        return self.io.reads

    @property
    def cache_hit_rate(self) -> float:
        return self.io.hit_rate

    @property
    def fetch_ms_total(self) -> float:
        return sum(r.fetch_ms for r in self.rungs)

    @property
    def compute_wait_ms_total(self) -> float:
        return sum(r.compute_wait_ms for r in self.rungs)

    @property
    def overlap_ms_total(self) -> float:
        return sum(r.overlap_ms for r in self.rungs)

    def as_dict(self) -> dict:
        return dict(
            backend=self.backend, queries=self.queries,
            measured_nio_blocks=self.measured_nio_blocks,
            nio_blocks_counted=self.nio_blocks_counted,
            cache_hit_rate=self.cache_hit_rate,
            device_reads=self.io.device_reads,
            prefetch_reads=self.io.prefetch_reads,
            setup_ms=self.setup_ms, total_ms=self.total_ms,
            fetch_ms_total=self.fetch_ms_total,
            compute_wait_ms_total=self.compute_wait_ms_total,
            overlap_ms_total=self.overlap_ms_total,
            rungs=[dataclasses.asdict(r) for r in self.rungs],
        )


# --------------------------------------------------------------------------
# Device programs: the two seams of the split dispatch. Same kernel ops and
# operand shapes as the fused plan, so float outputs are bit-identical.
# --------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg",))
def _external_setup_jit(a, b, rm, table_cnt, blocks_head, queries,
                        cfg: QueryConfig):
    """Step 1 for the WHOLE schedule + the batched hash-table lookups —
    the fused plan's pre-loop, verbatim."""
    queries = queries.astype(jnp.float32)
    qnorm2 = jnp.sum(queries * queries, axis=-1)
    bucket_all, qfp_all = lsh_hash_all_radii(
        queries, a, b, rm,
        w=cfg.w, radii=cfg.radii, u=cfg.u, fp_bits=cfg.fp_bits,
    )
    r = len(cfg.radii)
    tl = (jnp.arange(r, dtype=jnp.int32)[:, None, None] * cfg.L
          + jnp.arange(cfg.L, dtype=jnp.int32)[None, None, :])
    flat_all = tl * (1 << cfg.u) + bucket_all                  # [r, Q, L]
    cnt_all = jnp.take(table_cnt.reshape(-1), flat_all, axis=0)
    head_all = jnp.take(blocks_head.reshape(-1), flat_all, axis=0)
    return queries, qnorm2, cnt_all, head_all, qfp_all


@partial(jax.jit, static_argnames=("cfg",))
def _external_fold_jit(db, db_norm2, queries, qnorm2, state, buf_id,
                       nio_table, nio_blocks, cands, probe_sizes_t, t,
                       thresh2_t, cfg: QueryConfig):
    """Step 3 for one rung: the fused plan's distance epilogue + state fold
    over host-fetched candidates. ``t`` is traced, so ONE compiled program
    serves every rung of the schedule."""
    valid_slots = buf_id != jnp.int32(_INVALID)
    safe_id = jnp.where(valid_slots, buf_id, 0)
    coords = jnp.take(db, safe_id, axis=0)                    # [Q, SBUF, d]
    xn2 = jnp.take(db_norm2, safe_id, axis=0)
    d2 = l2_distance_gathered(queries, coords, xn2, qnorm2)
    d2 = jnp.where(valid_slots, jnp.maximum(d2, 0.0), jnp.inf)
    st = dict(nio_table=nio_table, nio_blocks=nio_blocks, cands=cands)
    if cfg.collect_probe_sizes:
        st["probe_sizes"] = probe_sizes_t
    return _update_state(state, buf_id, d2, st, t, thresh2_t, cfg)


# --------------------------------------------------------------------------
# Host chain walk: the oracle's integer candidate selection, block rows
# served by the BlockStore instead of a device gather.
# --------------------------------------------------------------------------

def _append_candidates_np(buf_id, count, flat_id, flat_ok, S):
    """NumPy mirror of core.query._append_candidates (exact integer math)."""
    ok = flat_ok.astype(np.int32)
    pos = count[:, None] + np.cumsum(ok, axis=1) - ok
    keep = flat_ok & (pos < S)
    qi, ci = np.nonzero(keep)
    buf_id[qi, pos[qi, ci]] = flat_id[qi, ci]
    count = np.minimum(count + ok.sum(axis=1, dtype=np.int32), S)
    return buf_id, count.astype(np.int32)


def _walk_rung_host(store: BlockStore, cnt, head, qfp, active_q,
                    cfg: QueryConfig, blkp: int, sbuf: int, record=None):
    """One rung's chain walk. Fetches are batched per chain step (every
    still-active bucket's step-j row in ONE read_rows call — the deep queue
    the aio backend fans out), gated by the S budget exactly like the
    oracle: a chunk is read iff the bucket still has entries at this depth
    AND the query's candidate count entering the step is below S. Returns
    (buf_id, count, blocks_read, nonempty)."""
    Q, L = cnt.shape
    BLK, S = cfg.block_objs, cfg.S
    nonempty = (cnt > 0) & active_q[:, None]
    buf_id = np.full((Q, sbuf), _INVALID, dtype=np.int32)
    count = np.zeros((Q,), dtype=np.int32)
    blocks_read = np.zeros((Q,), dtype=np.int32)
    slots = np.arange(blkp)
    for step in range(cfg.max_chain):
        active = nonempty & (cnt > step * BLK) & (count < S)[:, None]
        if not active.any():
            break
        qi, li = np.nonzero(active)
        step_rows = head[qi, li] + step
        if record is not None:
            record(step_rows)
        ids_rows, fps_rows = store.read_rows(step_rows)
        blocks_read += active.sum(axis=1, dtype=np.int32)
        # fingerprint filter (padding slots hold fp=-1 / id=INVALID, so the
        # match test alone reproduces bucket_probe's semantics), scattered
        # back to the oracle's (l, slot) flat order before the append
        ok = (fps_rows == qfp[qi, li][:, None]) & (ids_rows != _INVALID)
        flat_id = np.full((Q, L * blkp), _INVALID, dtype=np.int32)
        flat_ok = np.zeros((Q, L * blkp), dtype=bool)
        cols = li[:, None] * blkp + slots[None, :]
        flat_id[qi[:, None], cols] = ids_rows
        flat_ok[qi[:, None], cols] = ok
        buf_id, count = _append_candidates_np(buf_id, count, flat_id,
                                              flat_ok, S)
    return buf_id, count, blocks_read, nonempty


# --------------------------------------------------------------------------
# The plan
# --------------------------------------------------------------------------

def external_plan(ext: ExternalIndex, queries, cfg: QueryConfig,
                  valid=None) -> QueryResult:
    """Run a query batch from storage. Semantics identical to
    ``plan="fused"``; the block store is the only data source for bucket
    rows. Records per-call instrumentation on ``ext.last_plan_stats``."""
    if cfg.block_objs != ext.block_objs:
        raise ValueError(
            f"spilled store is laid out at block_objs={ext.block_objs} but "
            f"the query plan wants {cfg.block_objs}; re-spill the index at "
            "the desired block size (the on-disk layout cannot be repacked "
            "in place)")
    t_start = time.perf_counter()
    io_base = ext.store.stats.snapshot()
    tracer = get_tracer()
    root = tracer.begin("plan.external", backend=ext.backend)
    try:
        queries = jnp.asarray(queries)
        if valid is not None:
            valid = jnp.asarray(valid, dtype=bool)
        queries, valid, realQ = _pad_min_q(queries, valid)
        with tracer.span("external.setup"):
            qdev, qnorm2, cnt_all, head_all, qfp_all = _external_setup_jit(
                ext.a, ext.b, ext.rm, ext.table_cnt, ext.blocks_head,
                queries, cfg)
            # chain-walk plan comes to the host ONCE for the whole schedule
            cnt_np = np.asarray(cnt_all)
            head_np = np.asarray(head_all)
            qfp_np = np.asarray(qfp_all).astype(np.int64)
        setup_ms = (time.perf_counter() - t_start) * 1e3

        Q = qdev.shape[0]
        r = len(cfg.radii)
        sbuf = _fused_sbuf(cfg)
        state = _init_state(Q, cfg, valid)
        done_np = np.asarray(state[2])
        zeros_ps = jnp.zeros((Q, cfg.L), dtype=jnp.int32)
        rungs = []
        for t in range(r):
            if done_np.all():
                break
            active_q = ~done_np
            rsp = tracer.begin("external.rung", t=t,
                               radius=float(cfg.radii[t]),
                               active=int(active_q.sum()))
            try:
                t0 = time.perf_counter()
                buf_id, count, blocks_read, nonempty = _walk_rung_host(
                    ext.store, cnt_np[t], head_np[t], qfp_np[t], active_q,
                    cfg, ext.blkp, sbuf,
                    record=(ext.record_probe_rows if ext.collect_row_hist
                            else None))
                t1 = time.perf_counter()
                probe_sizes_t = (jnp.asarray(np.where(nonempty, cnt_np[t], -1)
                                             .astype(np.int32))
                                 if cfg.collect_probe_sizes else zeros_ps)
                # dispatch the fold (async on device) ...
                with tracer.span("external.fold_dispatch", t=t):
                    state = _external_fold_jit(
                        ext.db, ext.db_norm2, qdev, qnorm2, state,
                        jnp.asarray(buf_id),
                        jnp.asarray(nonempty.sum(axis=1, dtype=np.int32)),
                        jnp.asarray(blocks_read), jnp.asarray(count),
                        probe_sizes_t, jnp.int32(t),
                        jnp.float32((cfg.c * float(cfg.radii[t])) ** 2),
                        cfg)
                # ... and hide the next rung's chain reads under it (Eq. 7's
                # overlap): still-active queries' first `prefetch_depth`
                # chain-step rows go into the store's queue while the
                # distance epilogue computes. Depth 1 = heads only; deeper
                # keeps an async backend's device queue full across the rung
                # boundary.
                n_prefetch = 0
                if t + 1 < r:
                    nxt = (cnt_np[t + 1] > 0) & active_q[:, None]
                    depth = max(1, int(ext.prefetch_depth))
                    nxt_cnt = cnt_np[t + 1][nxt]
                    nxt_head = head_np[t + 1][nxt]
                    rows = [nxt_head]
                    for j in range(1, min(depth, cfg.max_chain)):
                        deeper = nxt_cnt > j * cfg.block_objs
                        if not deeper.any():
                            break
                        rows.append(nxt_head[deeper] + j)
                    rows = np.concatenate(rows) if len(rows) > 1 else rows[0]
                    n_prefetch = int(rows.size)
                    if n_prefetch:
                        ext.store.prefetch(rows)
                t2 = time.perf_counter()
                with tracer.span("external.fold_wait", t=t):
                    done_np = np.asarray(state[2])  # blocks on the fold
                t3 = time.perf_counter()
                rungs.append(RungStats(
                    t=t, active_queries=int(active_q.sum()),
                    blocks_fetched=int(blocks_read.sum()),
                    fetch_ms=(t1 - t0) * 1e3,
                    prefetch_rows=n_prefetch,
                    overlap_ms=(t2 - t1) * 1e3,
                    compute_wait_ms=(t3 - t2) * 1e3,
                ))
            finally:
                rsp.set(blocks_fetched=int(blocks_read.sum()),
                        prefetch_rows=n_prefetch)
                rsp.end()
        res = _result_from_state(state, cfg, valid).slice_rows(0, realQ)
        ps = ExternalPlanStats(
            backend=ext.backend, queries=realQ, rungs=rungs,
            io=ext.store.stats.since(io_base),
            nio_blocks_counted=int(np.asarray(res.nio_blocks).sum()),
            setup_ms=setup_ms,
            total_ms=(time.perf_counter() - t_start) * 1e3,
        )
        ext.last_plan_stats = ps
        with _TOTALS_LOCK:       # accumulate, never overwrite (queue-safe)
            ext.plan_totals.add(ps)
        root.set(queries=realQ, rungs=len(rungs), nio_blocks=ps.io.reads)
        # complete on return: a caller (BatchQueue) reads readiness to tell
        # a plan that did its work inside the call from one still computing
        return jax.block_until_ready(res)
    finally:
        root.end()
