"""Serving front-ends over the E2LSHoS query engine.

Two layers live here:

* ``BatchQueue`` — the dynamic micro-batching request queue for the ANN
  workload itself (the paper's serving story at "millions of users" scale):
  callers submit arbitrary-size query batches, the queue assembles them
  into fixed compiled-shape *ticks* (pad + mask to a small ladder of batch
  shapes warmed up at startup), dispatches ONE fused-plan call per tick,
  and scatters per-request ``QueryResult``s back with the padding rows
  dropped. Queued results are bit-exact with calling ``plan="fused"``
  directly on each request — the parity contract of
  tests/test_serving_queue.py.

* ``ServeEngine`` — batched LM prefill + greedy decode with an optional
  retrieval hook (kNN-LM-style: the decode state queries the index and
  neighbor ids/distances ride alongside logits).
"""
from __future__ import annotations

import dataclasses
import threading
import time
import weakref
from collections import deque
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.query import QueryResult, SearchEngine
from ..models.model import Model
from ..telemetry import get_registry, get_tracer

__all__ = ["BatchQueue", "DeadlineExceeded", "QueryTicket", "TickStats",
           "ServeEngine", "GenerationResult"]


# --------------------------------------------------------------------------
# Dynamic micro-batching over the fused plan
# --------------------------------------------------------------------------

class DeadlineExceeded(RuntimeError):
    """A queued request's deadline expired before its tick could serve it;
    the QoS router shed it (fail-fast at pack time) instead of spending
    tick rows on a result nobody is waiting for."""


@dataclasses.dataclass
class TickStats:
    """One tick's dispatch record (the serving observability surface)."""

    tick: int            # ordinal
    shape: int           # compiled batch shape dispatched (ladder rung)
    rows: int            # real query rows served
    segments: int        # request segments packed into the tick
    pad_rows: int        # masked padding rows (shape - rows)
    occupancy: float     # rows / shape
    dispatch_ms: float   # launch_ms + wait_ms: the single fused dispatch
    shed: int = 0        # tickets shed (DeadlineExceeded) at this tick's pack
    # host phases, ms on perf_counter; each the tick's own, so they sum to
    # <= wall_ms (back to back unless the tick was left in flight)
    pack_ms: float = 0.0     # lock, pack, shed, pad to the rung
    launch_ms: float = 0.0   # inputs to the device + plan call (enqueue)
    wait_ms: float = 0.0     # block_until_ready: the host waits on the device
    fetch_ms: float = 0.0    # device_get of the tick's result
    deliver_ms: float = 0.0  # per-segment slices, ticket delivery, QoS records
    wall_ms: float = 0.0     # the whole tick, from its start to its commit
    queued_ms: float = 0.0   # mean over segments of pack time - submit time
    overlapped: bool = False  # launched while an earlier tick was in flight


# the host phases of one tick, in order; each is a TickStats ``<phase>_ms``
TICK_PHASES = ("pack", "launch", "wait", "fetch", "deliver")


@dataclasses.dataclass
class _Launched:
    """One tick between its plan call and its delivery."""

    batch: list          # its _Pending segments, in row order
    rows: int
    shape: int
    shed: int
    now: float           # time.monotonic() at pack
    t_start: float       # perf_counter() at the tick's start
    pack_ms: float
    launch_ms: float
    res: Any             # the plan's QueryResult, possibly still computing
    ready: bool          # res.ids was complete when the launch returned
    overlapped: bool     # launched while an earlier tick was in flight
    root: Any            # its serve.tick span
    dsp: Any             # its tick.dispatch span


@dataclasses.dataclass
class _Pending:
    """One enqueued request segment awaiting a tick."""

    ticket: "QueryTicket"
    seg_idx: int
    seg: np.ndarray            # [b, d]
    priority: int              # 0 = highest; strict across classes
    deadline: Optional[float]  # absolute time.monotonic(), None = none
    seq: int                   # submission order (the FIFO tiebreaker)


class QueryTicket:
    """Per-request handle. A request larger than ``max_batch`` is split into
    segments that spill across consecutive ticks; the ticket reassembles the
    full ``QueryResult`` (row order preserved) once every segment landed."""

    def __init__(self, n_segments: int, *, priority: int = 0,
                 deadline: Optional[float] = None,
                 submit_t: Optional[float] = None):
        self._parts: list = [None] * n_segments
        self._remaining = n_segments
        self._lock = threading.Lock()   # segments may land from racing ticks
        self._event = threading.Event()
        self._result: Optional[QueryResult] = None
        self._error: Optional[BaseException] = None
        self.priority = int(priority)
        self.deadline = deadline            # absolute monotonic, or None
        self.submit_t = (time.monotonic() if submit_t is None
                         else float(submit_t))
        self._qos_logged = False            # one QoS record per ticket

    def _deliver(self, seg_idx: int, part: QueryResult) -> None:
        with self._lock:
            if self._error is not None:   # a sibling segment's tick failed
                return
            self._parts[seg_idx] = part
            self._remaining -= 1
            if self._remaining > 0:
                return
            self._result = QueryResult.concat_rows(self._parts)
            self._parts = []
        self._event.set()

    def _fail(self, exc: BaseException) -> None:
        """A tick's dispatch died: resolve the ticket with the error so
        waiters raise instead of hanging forever. A ticket already served
        or failed keeps its outcome."""
        with self._lock:
            if self._result is not None or self._error is not None:
                return
            self._error = exc
            self._parts = []
        self._event.set()

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None) -> QueryResult:
        """Block until served (drive ticks via BatchQueue.tick()/drain() or a
        running background loop). Raises ``DeadlineExceeded`` if the QoS
        router shed the request, RuntimeError if the serving tick's dispatch
        failed."""
        if not self._event.wait(timeout):
            raise TimeoutError(
                "queued request not served yet — call BatchQueue.tick()/"
                "drain(), or start() the background tick loop")
        if self._error is not None:
            if isinstance(self._error, DeadlineExceeded):
                raise self._error
            raise RuntimeError(
                f"queued request failed in its serving tick: {self._error!r}"
            ) from self._error
        return self._result


class BatchQueue:
    """Dynamic micro-batching request queue in front of ``SearchEngine``,
    with a QoS-aware tick packer.

    Requests (arbitrary per-caller batch sizes) are packed into ticks of at
    most ``max_batch`` rows, padded + masked up to the smallest rung of the
    compiled batch-shape ``ladder``, and served by ONE masked plan dispatch
    per tick (`SearchEngine.make_plan_fn(masked=True)`, the typed seam
    built for this layer). Padding rows are provably inert (core.query mask
    contract), so the scattered-back per-request results are bit-exact with
    direct per-request dispatch.

    **Pack order (QoS).** ``submit(..., priority=, deadline_ms=)`` attaches
    a priority class (0 = highest, strict across classes) and an optional
    deadline; within a class, segments pack earliest-deadline-first (EDF;
    deadline-less segments last, FIFO by submission order — so all-default
    traffic reduces exactly to the original FIFO packer). Packing stops at
    the first segment that does not fit (head-of-line: nothing behind the
    head jumps the line; oversize requests spill to later ticks unchanged).

    **Load shedding.** A segment whose deadline has already expired at pack
    time is shed: its ticket fails fast with :class:`DeadlineExceeded`
    (sibling segments of the ticket drop with it) instead of occupying tick
    rows. Shed counts ride on ``TickStats.shed`` / ``stats_summary()``.

    **Adaptive ladder.** With ``adaptive_ladder=True`` the packer keeps a
    windowed occupancy histogram from the tick log and stops packing at the
    preferred rung (the smallest ladder shape covering the window's p90
    rows) instead of always filling toward ``max_batch`` — unless a waiting
    segment's deadline slack is inside ~2 tick periods, in which case the
    packer fills for it (latency beats shape reuse).

    **Cache warming.** With ``warm_cache_rows=N`` over an external engine,
    the plan's probe-trace row histogram is collected and the background
    loop prefetches the N hottest block rows into the store cache (each
    shard's own clock arena under ``plan="sharded_external"``) whenever the
    queue goes idle — advisory, never counted in the logical read ledger.

    The ladder is warmed up at construction: every rung's program is
    compiled once, and steady-state ticks can never retrace (asserted by
    the jit-cache probe in tests). ``dispatch_count`` counts real plan
    dispatches — the test probe for "one dispatch per tick".

    Drive it synchronously (``tick()`` / ``drain()`` / ``query()``) or run
    the background loop (``start()``/``stop()``), which fires a tick every
    ``tick_us`` microseconds while requests are pending and services
    back-to-back full ticks immediately under queue pressure.

    **One tick in flight.** The background loop launches tick N+1 before it
    waits on, fetches and delivers tick N, so the device runs N+1 while the
    host finishes N — but only where N's result was not yet complete when
    its launch returned (``res.ids.is_ready()``). A plan that does its work
    inside the call (the external plan) is completed before the next pack,
    as the synchronous entry points always are; they first complete a tick
    the loop left in flight. ``TickStats.overlapped`` marks each tick
    launched while an earlier one was in flight.
    """

    @staticmethod
    def resolve_ladder(ladder: Sequence[int],
                       max_batch: Optional[int] = None) -> tuple:
        """Normalize a batch-shape ladder: positive rungs, sorted, deduped,
        trimmed to max_batch — which is always itself a rung (it is the
        largest shape a replica compiles). Shared with the dryrun warmup
        cell so the recorded compile bill matches what serving pays."""
        rungs = sorted({int(s) for s in ladder if int(s) > 0})
        if not rungs and max_batch is None:
            raise ValueError(f"empty batch-shape ladder {ladder!r}")
        if max_batch is not None:
            if int(max_batch) <= 0:
                raise ValueError(f"max_batch must be positive, got {max_batch}")
            rungs = [s for s in rungs if s <= int(max_batch)]
            if not rungs or rungs[-1] != int(max_batch):
                rungs.append(int(max_batch))
        return tuple(rungs)

    def __init__(self, index, *, plan: Optional[str] = None, k: int = 1,
                 ladder: Sequence[int] = (8, 32, 128),
                 max_batch: Optional[int] = None, tick_us: float = 200.0,
                 warmup: bool = True, adaptive_ladder: bool = False,
                 window: int = 64, warm_cache_rows: int = 0, **plan_kw):
        self.engine: SearchEngine = (
            index if isinstance(index, SearchEngine) else SearchEngine(index))
        self.ladder: tuple = self.resolve_ladder(ladder, max_batch)
        self.max_batch: int = self.ladder[-1]
        self.tick_us = float(tick_us)
        self.adaptive_ladder = bool(adaptive_ladder)
        self.window = int(window)
        self.warm_cache_rows = int(warm_cache_rows)
        self.plan = plan or self.engine.default_plan
        self.cfg, self._fn = self.engine.make_plan_fn(
            plan=self.plan, k=k, masked=True, **plan_kw)
        self._d = int(self.engine.params.d)
        self._pending: deque = deque()   # _Pending segments awaiting a tick
        self._lock = threading.Lock()        # guards _pending / _seq
        self._serve_lock = threading.Lock()  # serializes whole ticks
        self._thread: Optional[threading.Thread] = None
        self._inflight: Optional[_Launched] = None  # the loop's launched tick
        self._stop = threading.Event()
        self._seq = 0                    # submission counter (FIFO tiebreak)
        self._qos_pending = 0            # pending segments with QoS attrs
        # one lock owns every stats surface below: tick commits
        # (dispatch_count + tick_log row land atomically), QoS records,
        # stats_summary() reads, and reset_stats() — the window-vs-reset
        # race fix (a summary can never see a cleared log with a stale
        # dispatch count, or iterate tick_log mid-clear)
        self._stats_lock = threading.Lock()
        self.dispatch_count = 0          # the one-dispatch-per-tick probe
        self.tick_log: list = []         # TickStats per tick
        self.qos_log: list = []          # one dict per deadline/priority ticket
        self.shed_count = 0              # tickets shed with DeadlineExceeded
        self._warmed_at = -1             # dispatch_count at last cache warm
        # registry cells bound once: one observe per phase per tick
        self._phase_hist = {p: _TICK_PHASE_MS.labels(plan=self.plan, phase=p)
                            for p in TICK_PHASES}
        self._queued_hist = _QUEUED_MS.labels(plan=self.plan)
        _LIVE_QUEUES.add(self)           # telemetry collector (module foot)
        ext = self.engine.external
        if self.warm_cache_rows > 0 and ext is not None:
            ext.collect_row_hist = True  # feed warm_cache() the probe trace
        if warmup:
            self.warmup()

    # -- compile cache ------------------------------------------------------
    def warmup(self) -> None:
        """Compile every ladder rung up front (not counted by the dispatch
        probe). The dummy rows are live (valid) far-away points that match
        nothing, so data-adaptive plans compile their WHOLE radius schedule
        here — the host plan's per-radius programs would otherwise early-exit
        at radius 0 and leak compiles into the first real tick."""
        for shape in self.ladder:
            res = self._fn(jnp.full((shape, self._d), 1e6, jnp.float32),
                           jnp.ones((shape,), dtype=bool))
            jax.block_until_ready(res.ids)

    def shape_for(self, rows: int) -> int:
        """Smallest ladder rung holding `rows` (rows <= max_batch)."""
        for s in self.ladder:
            if s >= rows:
                return s
        raise ValueError(f"{rows} rows exceed max_batch={self.max_batch}")

    # -- request side -------------------------------------------------------
    def submit(self, queries, *, priority: int = 0,
               deadline_ms: Optional[float] = None) -> QueryTicket:
        """Enqueue one request ([b, d] or [d]); returns its ticket. Requests
        wider than max_batch are segmented; the tail spills to later ticks.

        ``priority`` (0 = highest) ranks strictly across classes in the tick
        packer; ``deadline_ms`` is a relative budget from now — segments
        still unserved when it expires are shed with ``DeadlineExceeded``
        instead of dispatched. A ticket's segments share one deadline."""
        q = np.asarray(queries, dtype=np.float32)
        if q.ndim == 1:
            q = q[None, :]
        if q.ndim != 2 or q.shape[1] != self._d:
            raise ValueError(f"expected [b, {self._d}] queries, got {q.shape}")
        if q.shape[0] == 0:
            raise ValueError("empty request")
        if priority < 0:
            raise ValueError(f"priority must be >= 0, got {priority}")
        if deadline_ms is not None and deadline_ms <= 0:
            raise ValueError(f"deadline_ms must be positive, got {deadline_ms}")
        now = time.monotonic()
        deadline = None if deadline_ms is None else now + deadline_ms * 1e-3
        segs = [q[i:i + self.max_batch]
                for i in range(0, q.shape[0], self.max_batch)]
        ticket = QueryTicket(len(segs), priority=priority, deadline=deadline,
                             submit_t=now)
        with self._lock:
            for i, s in enumerate(segs):
                self._pending.append(_Pending(
                    ticket=ticket, seg_idx=i, seg=s, priority=int(priority),
                    deadline=deadline, seq=self._seq))
                self._seq += 1
            if priority != 0 or deadline is not None:
                self._qos_pending += len(segs)
        return ticket

    def query(self, queries, *, timeout: float = 600.0) -> QueryResult:
        """Synchronous convenience: submit + (if no loop is running) drain."""
        ticket = self.submit(queries)
        if self._thread is None:
            self.drain()
        return ticket.result(timeout=timeout)

    # -- tick side ----------------------------------------------------------
    def _record_qos(self, ticket: QueryTicket, *, now: float,
                    shed: bool) -> None:
        """One QoS record per ticket, at resolution (served or shed)."""
        if ticket._qos_logged:
            return
        ticket._qos_logged = True
        deadline_ms = (None if ticket.deadline is None
                       else (ticket.deadline - ticket.submit_t) * 1e3)
        hit = (not shed) and (ticket.deadline is None
                              or now <= ticket.deadline)
        with self._stats_lock:
            self.qos_log.append(dict(
                priority=ticket.priority,
                latency_ms=(now - ticket.submit_t) * 1e3,
                deadline_ms=deadline_ms, hit=bool(hit), shed=bool(shed)))

    def _target_rows(self) -> int:
        """Adaptive ladder: smallest rung covering the window's p90 rows —
        the packer's soft fill target (max_batch stays the hard cap)."""
        if not self.adaptive_ladder:
            return self.max_batch
        with self._stats_lock:           # _lock -> _stats_lock (fixed order)
            recent = [t.rows for t in self.tick_log[-self.window:]]
        if not recent:
            return self.max_batch
        p90 = float(np.percentile(recent, 90))
        for s in self.ladder:
            if s >= p90:
                return s
        return self.max_batch

    def tick(self) -> Optional[TickStats]:
        """Serve one tick: shed expired segments, pack the live ones in QoS
        order (strict priority, EDF within class, FIFO tiebreak) up to
        max_batch rows, pad + mask to the smallest ladder rung, dispatch
        ONCE, scatter back. Returns None (no dispatch) when nothing packed.
        A tick the background loop left in flight is completed first, so
        the call still serves its own batch before it returns.
        Thread-safe: whole ticks are serialized (concurrent callers — e.g.
        several synchronous query() drains — each serve complete ticks,
        never interleave one)."""
        with self._serve_lock:
            tr = get_tracer()
            self._settle(tr)
            fl = self._launch(tr, overlapped=False)
            return None if fl is None else self._complete(tr, fl)

    def _settle(self, tr) -> None:
        """Complete the tick left in flight, if any (under ``_serve_lock``)."""
        fl, self._inflight = self._inflight, None
        if fl is not None:
            self._complete(tr, fl)

    def _step(self) -> Optional[int]:
        """One pass of the background loop: launch the next tick, then
        complete the one in flight, so the device runs the next tick while
        the host waits on, fetches and delivers this one. A tick whose
        result is already complete when its launch returns (a plan that did
        its work inside the call) is completed in the same pass, before
        anything else is packed; one that is not stays in flight until the
        next pass. Returns the rows launched (None: nothing packed). A
        failed tick fails only its own tickets; the first error is raised
        once the other tick has been dealt with."""
        with self._serve_lock:
            tr = get_tracer()
            prev, self._inflight = self._inflight, None
            errors, fl = [], None
            try:
                fl = self._launch(tr, overlapped=prev is not None)
            except Exception as e:
                errors.append(e)
            done = [prev] if prev is not None else []
            if fl is not None and fl.ready:
                done.append(fl)
            elif fl is not None:
                self._inflight = fl
            for t in done:
                try:
                    self._complete(tr, t)
                except Exception as e:
                    errors.append(e)
            if errors:
                raise errors[0]
            return None if fl is None else fl.rows

    def _pack(self, now: float) -> tuple:
        """Pop the next tick's segments in QoS order and shed the expired
        ones: ``(batch, rows, shed)``."""
        urgent_s = 2.0 * self.tick_us * 1e-6   # slack beating shape reuse
        with self._lock:
            shed_tickets: dict = {}
            target = self._target_rows()
            batch, rows = [], 0
            if self._qos_pending == 0:
                # fast path — no priorities, no deadlines pending: the
                # deque IS the pack order (seq), so the original O(batch)
                # FIFO popleft packer applies; the backlog is never
                # scanned or sorted (this is the high-arrival serving
                # regime the queued-vs-direct bench measures)
                while self._pending:
                    e = self._pending[0]
                    if e.ticket.done():   # an earlier tick failed it
                        self._pending.popleft()
                        continue
                    nrows = e.seg.shape[0]
                    if rows + nrows > self.max_batch:
                        break   # head-of-line: the head spills
                    if batch and rows + nrows > target:
                        break   # adaptive soft stop (nothing is urgent)
                    batch.append(self._pending.popleft())
                    rows += nrows
            else:
                live = []
                for e in self._pending:
                    if e.ticket.done():   # sibling shed / tick failure
                        continue
                    if e.deadline is not None and e.deadline <= now:
                        shed_tickets[id(e.ticket)] = e.ticket
                        continue
                    live.append(e)
                live.sort(key=lambda e: (
                    e.priority,
                    e.deadline if e.deadline is not None else float("inf"),
                    e.seq))
                spilled = []
                for i, e in enumerate(live):
                    nrows = e.seg.shape[0]
                    if rows + nrows > self.max_batch:
                        # strict head-of-line: nothing behind the first
                        # non-fitting segment jumps the line
                        spilled = live[i:]
                        break
                    if (batch and rows + nrows > target
                            and not (e.deadline is not None
                                     and e.deadline - now < urgent_s)):
                        spilled = live[i:]
                        break   # adaptive soft stop at the preferred rung
                    batch.append(e)
                    rows += nrows
                # unpacked segments return in submission order so the
                # next tick's sort sees the same FIFO tiebreak
                self._pending = deque(sorted(spilled, key=lambda e: e.seq))
                self._qos_pending = sum(
                    1 for e in self._pending
                    if e.priority != 0 or e.deadline is not None)
        for t in shed_tickets.values():
            with self._stats_lock:
                self.shed_count += 1
            budget_ms = (t.deadline - t.submit_t) * 1e3
            t._fail(DeadlineExceeded(
                f"request shed: {budget_ms:.1f}ms deadline expired "
                f"{(now - t.deadline) * 1e3:.1f}ms before its tick"))
            self._record_qos(t, now=now, shed=True)
        return batch, rows, len(shed_tickets)

    def _launch(self, tr, *, overlapped: bool) -> Optional[_Launched]:
        """Pack a tick and launch its plan call; None when nothing packed.
        The tick's ``serve.tick`` and ``tick.dispatch`` spans stay open
        until it completes, possibly after the next tick's launch, so they
        are detached and every phase span names its parent. A failed
        launch fails the tick's tickets and raises."""
        t_start = time.perf_counter()
        now = time.monotonic()
        root = tr.begin("serve.tick", detached=True, plan=self.plan)
        psp = tr.begin("tick.pack", parent=root)
        batch, rows, n_shed = self._pack(now)
        if not batch:
            if n_shed:
                psp.set(segments=0, rows=0, shed=n_shed)
                psp.end()
                root.end()
            else:
                psp.cancel()      # idle poll: keep the span ring quiet
                root.cancel()     # nothing happened; drop the empty tick
            return None
        shape = self.shape_for(rows)
        qs = np.zeros((shape, self._d), dtype=np.float32)
        qs[:rows] = np.concatenate([e.seg for e in batch], axis=0)
        valid = np.zeros((shape,), dtype=bool)
        valid[:rows] = True
        psp.set(segments=len(batch), rows=rows, shed=n_shed)
        psp.end()
        t_pack = time.perf_counter()
        root.set(shape=shape, rows=rows, segments=len(batch))
        dsp = tr.begin("tick.dispatch", detached=True, parent=root,
                       shape=shape, rows=rows)
        try:
            with tr.span("tick.launch", parent=dsp):
                res = self._fn(jnp.asarray(qs), jnp.asarray(valid))
        except Exception as e:
            # the popped segments can never be re-served at this point:
            # fail their tickets (waiters raise instead of hanging) and
            # surface the error to whoever drove the tick
            for p in batch:
                p.ticket._fail(e)
            dsp.end()
            root.end()
            raise
        t_launch = time.perf_counter()
        return _Launched(
            batch=batch, rows=rows, shape=shape, shed=n_shed, now=now,
            t_start=t_start, pack_ms=(t_pack - t_start) * 1e3,
            launch_ms=(t_launch - t_pack) * 1e3, res=res,
            ready=res.ids.is_ready(), overlapped=overlapped, root=root,
            dsp=dsp)

    def _complete(self, tr, fl: _Launched) -> TickStats:
        """Wait for a launched tick, fetch its result, deliver each segment
        and commit its stats. A failed wait, fetch or delivery fails the
        tick's unserved tickets and raises."""
        batch = fl.batch
        t_wait0 = time.perf_counter()
        try:
            try:
                with tr.span("tick.wait", parent=fl.dsp):
                    jax.block_until_ready(fl.res.ids)
            finally:
                fl.dsp.end()
            t_wait = time.perf_counter()
            # ONE device->host transfer for the whole tick; the per-segment
            # scatter is then numpy views (per-segment device slicing costs
            # more than the dispatch itself at high request counts)
            with tr.span("tick.scatter", parent=fl.root,
                         segments=len(batch)):
                with tr.span("tick.fetch"):
                    host = jax.device_get(fl.res)
                t_fetch = time.perf_counter()
                with tr.span("tick.deliver"):
                    done_t = time.monotonic()
                    lo = 0
                    for p in batch:
                        hi = lo + p.seg.shape[0]
                        p.ticket._deliver(p.seg_idx, host.slice_rows(lo, hi))
                        lo = hi
                        if p.ticket.done():
                            self._record_qos(p.ticket, now=done_t,
                                             shed=False)
        except Exception as e:
            for p in batch:
                p.ticket._fail(e)
            fl.root.end()
            raise
        t_end = time.perf_counter()
        queued_ms = [(fl.now - p.ticket.submit_t) * 1e3 for p in batch]
        phase_ms = dict(pack=fl.pack_ms, launch=fl.launch_ms,
                        wait=(t_wait - t_wait0) * 1e3,
                        fetch=(t_fetch - t_wait) * 1e3,
                        deliver=(t_end - t_fetch) * 1e3)
        for phase, ms in phase_ms.items():
            self._phase_hist[phase].observe(ms)
        for ms in queued_ms:
            self._queued_hist.observe(ms)
        # atomic stats commit: a concurrent stats_summary() can never
        # see the new dispatch count without its tick row (or vice versa)
        with self._stats_lock:
            self.dispatch_count += 1
            stats = TickStats(
                tick=len(self.tick_log), shape=fl.shape, rows=fl.rows,
                segments=len(batch), pad_rows=fl.shape - fl.rows,
                occupancy=fl.rows / fl.shape,
                dispatch_ms=phase_ms["launch"] + phase_ms["wait"],
                shed=fl.shed, **{f"{k}_ms": v for k, v in phase_ms.items()},
                wall_ms=(time.perf_counter() - fl.t_start) * 1e3,
                queued_ms=sum(queued_ms) / len(queued_ms),
                overlapped=fl.overlapped,
            )
            self.tick_log.append(stats)
        fl.root.set(tick=stats.tick)
        fl.root.end()
        return stats

    def drain(self) -> int:
        """Tick until the queue is empty; returns ticks run."""
        n = 0
        while self.tick() is not None:
            n += 1
        return n

    @property
    def depth(self) -> int:
        """Pending rows not yet served."""
        with self._lock:
            return sum(e.seg.shape[0] for e in self._pending)

    # -- cache warming ------------------------------------------------------
    def warm_cache(self, top: Optional[int] = None) -> int:
        """Prefetch the hottest probe-trace rows into the external store's
        cache (per-shard arenas under a striped store). Advisory: prefetches
        ride the ledger's ``prefetch_reads`` lane, never logical ``reads``.
        Returns rows warmed (0 when not an external engine / no trace)."""
        ext = self.engine.external
        if ext is None:
            return 0
        n = top if top is not None else self.warm_cache_rows
        if n <= 0:
            return 0
        return ext.warm_cache(top=n)

    # -- background loop ----------------------------------------------------
    def start(self) -> "BatchQueue":
        """Run the tick loop on a daemon thread (tick every tick_us while
        idle-ish; full ticks are followed immediately under pressure, with
        one tick in flight while the last one completes: ``_step``)."""
        if self._thread is not None:
            return self
        self._stop.clear()

        def loop():
            while not self._stop.is_set():
                try:
                    rows = self._step()
                except Exception:
                    # the affected tickets were failed inside the step; keep
                    # the loop alive for the next batch instead of dying
                    # silently with requests still flowing in
                    rows = None
                if rows is None or rows < self.max_batch:
                    if (rows is None and self.warm_cache_rows > 0
                            and self.dispatch_count != self._warmed_at):
                        # idle: re-warm the store cache from the probe trace
                        # (once per dispatch generation — the histogram only
                        # changes when ticks actually ran)
                        self._warmed_at = self.dispatch_count
                        self.warm_cache()
                    self._stop.wait(self.tick_us * 1e-6)
            with self._serve_lock:
                try:
                    self._settle(get_tracer())
                except Exception:
                    pass          # its tickets were failed in _complete

        self._thread = threading.Thread(
            target=loop, name="batch-queue-tick", daemon=True)
        self._thread.start()
        return self

    def stop(self, *, drain: bool = True) -> None:
        if self._thread is None:
            return
        self._stop.set()
        self._thread.join()
        self._thread = None
        if drain:
            self.drain()

    def __enter__(self) -> "BatchQueue":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- observability ------------------------------------------------------
    def stats_summary(self, window: Optional[int] = None) -> dict:
        """Aggregate tick stats: occupancy, pad waste, dispatch p50/p99,
        the ladder-rung histogram, the mean of each host phase
        (``pack_ms`` ... ``deliver_ms``) and of the whole tick
        (``tick_wall_ms``), queue wait from submit to pack p50/p99 (over
        the ticks' means), the share of ticks launched while an earlier
        tick was in flight (``overlap_share``), and the QoS block (shed
        counts + deadline hit rates, overall and per priority class).

        ``window=N`` restricts the tick aggregates to the last N ticks (the
        sliding view the adaptive packer sees); the default is cumulative.
        The QoS block and dispatch/shed counters are always cumulative —
        they describe tickets, which have no tick alignment.

        When the engine serves an external index, the block store's
        cumulative I/O ledger rides along as ``external_store``, tagged
        with the resolved backend (and the fallback that produced it — the
        serve-startup provenance line), plus per-shard ledgers when the
        store is striped."""
        if window is not None and window <= 0:
            raise ValueError(f"window must be positive, got {window}")
        # one consistent cut of every stats surface: tick rows, the dispatch
        # counter, and the QoS log are copied under the same lock tick()
        # commits under, so a concurrent reset_stats() (or a tick landing
        # mid-summary) can never tear the view
        with self._stats_lock:
            log = list(self.tick_log)
            dispatches = self.dispatch_count
            qlog = list(self.qos_log)
            shed = self.shed_count
        if window is not None:
            log = log[-window:]
        if not log:
            out = dict(ticks=0, dispatches=dispatches, rows_served=0)
        else:
            dms = np.asarray([t.dispatch_ms for t in log])
            qms = np.asarray([t.queued_ms for t in log])
            slots = sum(t.shape for t in log)
            rows = sum(t.rows for t in log)
            rung_hist = {int(s): 0 for s in self.ladder}
            for t in log:
                rung_hist[int(t.shape)] = rung_hist.get(int(t.shape), 0) + 1
            out = dict(
                ticks=len(log),
                dispatches=dispatches,
                rows_served=rows,
                segments=sum(t.segments for t in log),
                occupancy_mean=float(np.mean([t.occupancy for t in log])),
                pad_waste=float((slots - rows) / slots),
                p50_dispatch_ms=float(np.percentile(dms, 50)),
                p99_dispatch_ms=float(np.percentile(dms, 99)),
                rung_hist=rung_hist,
                tick_wall_ms=float(np.mean([t.wall_ms for t in log])),
                p50_queued_ms=float(np.percentile(qms, 50)),
                p99_queued_ms=float(np.percentile(qms, 99)),
                overlap_share=float(np.mean([t.overlapped for t in log])),
            )
            for p in TICK_PHASES:
                out[f"{p}_ms"] = float(np.mean(
                    [getattr(t, f"{p}_ms") for t in log]))
        out["qos"] = self._qos_summary(qlog, shed)
        ext = self.engine.external
        if ext is not None:
            store = ext.store
            es = store.stats.as_dict()
            es["backend"] = store.name
            es["submit"] = getattr(store, "submit", None)
            es["fallback_from"] = getattr(store, "fallback_from", None)
            es["fallback_reason"] = getattr(store, "fallback_reason", None)
            shards = getattr(store, "num_shards", None)
            if shards is not None:
                es["num_shards"] = int(shards)
                es["per_shard"] = [s.as_dict()
                                   for s in store.per_shard_stats()]
            out["external_store"] = es
        return out

    def _qos_summary(self, qlog: Optional[list] = None,
                     shed: Optional[int] = None) -> dict:
        """Cumulative QoS roll-up. Hit rates are computed over
        deadline-bearing tickets only (a deadline-less ticket can't miss).
        Callers that already hold a consistent cut pass it in; bare calls
        take one under the stats lock."""
        if qlog is None:
            with self._stats_lock:
                qlog, shed = list(self.qos_log), self.shed_count
        tracked = [r for r in qlog if r["deadline_ms"] is not None]
        out = dict(shed=shed, tickets=len(qlog), tracked=len(tracked))
        if tracked:
            out["deadline_hit_rate"] = float(
                np.mean([r["hit"] for r in tracked]))
        by_class: dict = {}
        for pri in sorted({r["priority"] for r in qlog}):
            rows = [r for r in qlog if r["priority"] == pri]
            trk = [r for r in rows if r["deadline_ms"] is not None]
            cls = dict(tickets=len(rows), tracked=len(trk),
                       shed=sum(1 for r in rows if r["shed"]),
                       p99_latency_ms=float(np.percentile(
                           [r["latency_ms"] for r in rows], 99)))
            if trk:
                cls["hit_rate"] = float(np.mean([r["hit"] for r in trk]))
            by_class[int(pri)] = cls
        out["by_class"] = by_class
        return out

    def reset_stats(self) -> None:
        """Clear the tick log, QoS log, and counters in one atomic step
        w.r.t. concurrent ``tick()`` commits and ``stats_summary()`` readers
        (the window-vs-reset race regression test drives all three at
        once). The registry's process-lifetime counters are NOT touched —
        use ``telemetry.reset()`` to re-baseline those."""
        with self._stats_lock:
            self.tick_log.clear()
            self.qos_log.clear()
            self.dispatch_count = 0
            self.shed_count = 0
            self._warmed_at = -1


# -- registry collector over the live queues' ledgers -----------------------
# TickStats / the QoS log stay the source of truth; the collector is a
# window onto them (grouped by plan — replicas of one plan sum into one
# series, Prometheus-style). Queues are weakly held: a gc'd queue's series
# disappear, which the registry's baseline clamp tolerates.
_LIVE_QUEUES: "weakref.WeakSet[BatchQueue]" = weakref.WeakSet()
_TICK_PHASE_MS = get_registry().histogram(
    "e2lsh_serve_tick_phase_ms",
    "host time of one tick phase (ms): pack, launch, wait, fetch, deliver",
    labelnames=("plan", "phase"))
_QUEUED_MS = get_registry().histogram(
    "e2lsh_serve_queued_ms",
    "wait of one request segment from submit to its tick's pack (ms)",
    labelnames=("plan",))


def _collect_queue_metrics() -> dict:
    cuts = []
    for q in list(_LIVE_QUEUES):
        depth = q.depth                       # takes q._lock; NEVER nest it
        with q._stats_lock:                   # inside the stats lock
            cuts.append(dict(
                plan=q.plan, depth=depth, log=list(q.tick_log),
                dispatches=q.dispatch_count, shed=q.shed_count,
                qlog=list(q.qos_log)))
    by_plan: dict = {}
    for c in cuts:
        by_plan.setdefault(c["plan"], []).append(c)

    counters = dict(ticks=[], dispatches=[], rows=[], pad_rows=[],
                    segments=[], shed=[], overlapped_ticks=[])
    gauges = dict(queue_depth=[], occupancy_mean=[], deadline_hit_rate=[])
    rungs, cls_tickets, cls_shed, cls_hit = [], [], [], []
    for plan, group in sorted(by_plan.items()):
        lab = dict(plan=plan)
        log = [t for c in group for t in c["log"]]
        qlog = [r for c in group for r in c["qlog"]]
        counters["ticks"].append(dict(labels=lab, value=len(log)))
        counters["dispatches"].append(dict(
            labels=lab, value=sum(c["dispatches"] for c in group)))
        counters["rows"].append(dict(
            labels=lab, value=sum(t.rows for t in log)))
        counters["pad_rows"].append(dict(
            labels=lab, value=sum(t.pad_rows for t in log)))
        counters["segments"].append(dict(
            labels=lab, value=sum(t.segments for t in log)))
        counters["shed"].append(dict(
            labels=lab, value=sum(c["shed"] for c in group)))
        counters["overlapped_ticks"].append(dict(
            labels=lab, value=sum(1 for t in log if t.overlapped)))
        gauges["queue_depth"].append(dict(
            labels=lab, value=sum(c["depth"] for c in group)))
        if log:
            gauges["occupancy_mean"].append(dict(
                labels=lab,
                value=float(np.mean([t.occupancy for t in log]))))
        tracked = [r for r in qlog if r["deadline_ms"] is not None]
        if tracked:
            gauges["deadline_hit_rate"].append(dict(
                labels=lab,
                value=float(np.mean([r["hit"] for r in tracked]))))
        shape_hist: dict = {}
        for t in log:
            shape_hist[int(t.shape)] = shape_hist.get(int(t.shape), 0) + 1
        rungs.extend(dict(labels=dict(plan=plan, shape=str(s)), value=n)
                     for s, n in sorted(shape_hist.items()))
        for pri in sorted({r["priority"] for r in qlog}):
            rows = [r for r in qlog if r["priority"] == pri]
            trk = [r for r in rows if r["deadline_ms"] is not None]
            plab = dict(plan=plan, priority=str(int(pri)))
            cls_tickets.append(dict(labels=plab, value=len(rows)))
            cls_shed.append(dict(
                labels=plab, value=sum(1 for r in rows if r["shed"])))
            if trk:
                cls_hit.append(dict(
                    labels=plab,
                    value=float(np.mean([r["hit"] for r in trk]))))

    helps = dict(
        ticks="serving ticks dispatched",
        dispatches="fused plan dispatches (one per tick)",
        rows="real query rows served",
        pad_rows="masked padding rows dispatched",
        segments="request segments packed",
        shed="tickets shed with DeadlineExceeded",
        overlapped_ticks="ticks launched while an earlier tick was in flight",
    )
    out = {f"e2lsh_serve_{k}_total": dict(type="counter", help=helps[k],
                                          samples=v)
           for k, v in counters.items()}
    out["e2lsh_serve_queue_depth"] = dict(
        type="gauge", help="pending rows not yet served",
        samples=gauges["queue_depth"])
    out["e2lsh_serve_occupancy_mean"] = dict(
        type="gauge", help="mean tick occupancy (rows / shape)",
        samples=gauges["occupancy_mean"])
    out["e2lsh_serve_deadline_hit_rate"] = dict(
        type="gauge",
        help="deadline hit rate over deadline-bearing tickets",
        samples=gauges["deadline_hit_rate"])
    out["e2lsh_serve_rung_ticks_total"] = dict(
        type="counter", help="ticks dispatched at each compiled batch shape",
        samples=rungs)
    out["e2lsh_serve_class_tickets_total"] = dict(
        type="counter", help="resolved tickets per priority class",
        samples=cls_tickets)
    out["e2lsh_serve_class_shed_total"] = dict(
        type="counter", help="shed tickets per priority class",
        samples=cls_shed)
    out["e2lsh_serve_class_hit_rate"] = dict(
        type="gauge", help="deadline hit rate per priority class",
        samples=cls_hit)
    return out


get_registry().register_collector(_collect_queue_metrics,
                                  name="serving.batch_queue")


# --------------------------------------------------------------------------
# LM serving with the retrieval hook
# --------------------------------------------------------------------------


@dataclasses.dataclass
class GenerationResult:
    tokens: jnp.ndarray              # [B, steps]
    logits_last: jnp.ndarray         # [B, vocab]
    neighbors: Optional[jnp.ndarray] = None  # [B, steps, k] retrieval ids


class ServeEngine:
    def __init__(self, model: Model, params, *, max_seq: int = 4096,
                 cache_dtype=jnp.bfloat16,
                 retrieval_fn: Optional[Callable] = None):
        """retrieval_fn(hidden [B, d]) -> (ids [B, k], dists [B, k])."""
        self.model = model
        self.params = params
        self.max_seq = max_seq
        self.cache_dtype = cache_dtype
        self.retrieval_fn = retrieval_fn
        self._prefill = jax.jit(self.model.prefill)
        self._decode = jax.jit(self.model.decode_step)

    @staticmethod
    def make_retrieval_fn(index, *, k: int = 8, normalize: bool = True) -> Callable:
        """Retrieval hook closing over the FUSED single-dispatch query plan.

        `index` is a core.E2LSHoS (or anything SearchEngine accepts); the
        returned fn keeps the whole probe on device (one dispatch per decode
        step, no host round-trip), so decode streams are never stalled by
        per-radius syncs.
        """
        _, query_fn = SearchEngine(index).make_plan_fn(plan="fused", k=k)

        def retrieval_fn(hidden):
            h = hidden.astype(jnp.float32)
            if normalize:
                h = h / jnp.maximum(
                    jnp.linalg.norm(h, axis=1, keepdims=True), 1e-9)
            res = query_fn(h)
            return res.ids, res.dists

        return retrieval_fn

    def generate(self, batch: dict, *, steps: int = 16) -> GenerationResult:
        B = batch["tokens"].shape[0]
        cache = self.model.init_cache(B, self.max_seq, self.cache_dtype)
        logits, cache = self._prefill(self.params, batch, cache)
        toks = []
        neigh = [] if self.retrieval_fn is not None else None
        cur = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        for _ in range(steps):
            toks.append(cur)
            logits, cache = self._decode(self.params, cur, cache)
            if self.retrieval_fn is not None:
                # kNN-LM hook: probe the index with the pre-softmax hidden
                # proxy (logits' argmax embedding would need the hidden; we
                # expose logits-space retrieval at the engine level and the
                # sharded hidden-space probe in launch/serve.py)
                ids, _ = self.retrieval_fn(logits[:, 0])
                neigh.append(ids)
            cur = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)[:, None]
        return GenerationResult(
            tokens=jnp.concatenate(toks, axis=1),
            logits_last=logits[:, 0],
            neighbors=jnp.stack(neigh, axis=1) if neigh else None,
        )
