"""Dynamic micro-batching queue: parity, tick semantics, dispatch probe.

The contract (docs/serving.md): requests of arbitrary batch size, packed
FIFO into padded fixed-shape ticks, must come back BIT-EXACT with calling
``plan="fused"`` directly on each request — padding rows are inert by the
core.query mask contract, and every Q>=1 dispatch rides the same row-stable
gemm path (core.query._pad_min_q). Steady state is ONE fused dispatch per
tick: the ladder is warmed at construction, so ticks never retrace.
"""
import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # property-based sweep when the dev dep is present, fixed grid otherwise
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core import E2LSHoS, SearchEngine
from repro.serving import BatchQueue, TickStats
from repro.storage import load_external
from repro.serving.engine import TICK_PHASES

_EXACT_FIELDS = ("ids", "dists", "found", "radii_searched", "nio_table",
                 "nio_blocks", "cands_checked")

LADDER = (4, 8, 16)
MAX_BATCH = 16


@pytest.fixture(scope="module")
def queue_env():
    """Small index + engine + a direct fused baseline (module-scoped: the
    queue tests dispatch many small ticks)."""
    rng = np.random.default_rng(11)
    n, d = 2500, 12
    centers = rng.normal(size=(24, d)).astype(np.float32)
    db = (centers[rng.integers(0, 24, n)]
          + 0.18 * rng.normal(size=(n, d))).astype(np.float32) / 1.5
    idx = E2LSHoS.build(db, gamma=0.7, s_scale=2.0, max_L=8, seed=3)
    engine = SearchEngine(idx)
    _, direct = engine.make_plan_fn(plan="fused", k=2)
    rng_q = np.random.default_rng(5)

    def make_request(b):
        base = db[rng_q.choice(n, b, replace=False)]
        return (base + 0.05 * rng_q.normal(size=base.shape)).astype(np.float32)

    return dict(engine=engine, direct=direct, make_request=make_request, d=d,
                idx=idx)


def _fresh_queue(env, **kw):
    kw.setdefault("ladder", LADDER)
    kw.setdefault("max_batch", MAX_BATCH)
    kw.setdefault("k", 2)
    return BatchQueue(env["engine"], plan="fused", **kw)


def _assert_queued_matches_direct(env, queue, sizes):
    requests = [env["make_request"](b) for b in sizes]
    tickets = [queue.submit(r) for r in requests]
    queue.drain()
    for b, req, ticket in zip(sizes, requests, tickets):
        got = ticket.result(timeout=0)
        want = env["direct"](req)
        assert np.asarray(got.ids).shape == (b, 2)
        for name in _EXACT_FIELDS:
            np.testing.assert_array_equal(
                np.asarray(getattr(got, name)),
                np.asarray(getattr(want, name)),
                err_msg=f"queued request of size {b} diverged from a direct "
                        f"plan='fused' dispatch on {name}")


def test_queued_bit_exact_across_ragged_sizes(queue_env):
    """The headline parity contract: size 1, ladder-boundary sizes, an exact
    max-batch request, and a > max-batch request that spills across ticks —
    all bit-exact vs direct dispatch (dists included)."""
    queue = _fresh_queue(queue_env)
    _assert_queued_matches_direct(
        queue_env, queue,
        sizes=(1, LADDER[0], LADDER[1], MAX_BATCH, MAX_BATCH + 9, 3))


def test_one_dispatch_per_tick_steady_state(queue_env):
    """The dispatch-count probe: after warmup, every tick is exactly one
    fused dispatch and never a recompile (the jit cache stays frozen across
    ragged tick shapes)."""
    from repro.core.query import _fused_masked_jit

    queue = _fresh_queue(queue_env)           # warmup compiles the ladder
    cache_after_warmup = _fused_masked_jit._cache_size()
    assert queue.dispatch_count == 0          # warmup is not counted
    for sizes in ((1, 2), (7,), (5, 5, 5), (2,)):
        for b in sizes:
            queue.submit(queue_env["make_request"](b))
        queue.tick()
    assert queue.dispatch_count == 4
    assert len(queue.tick_log) == 4
    assert _fused_masked_jit._cache_size() == cache_after_warmup, \
        "a steady-state tick recompiled despite the warmed shape ladder"


def test_tick_packs_fifo_and_pads_to_smallest_rung(queue_env):
    queue = _fresh_queue(queue_env)
    for b in (3, 2, 9):                        # 14 rows -> rung 16
        queue.submit(queue_env["make_request"](b))
    st = queue.tick()
    assert isinstance(st, TickStats)
    assert (st.rows, st.shape, st.segments) == (14, 16, 3)
    assert st.pad_rows == 2 and st.occupancy == pytest.approx(14 / 16)
    assert queue.tick() is None                # queue drained

    # 5 rows -> rung 8 (smallest holding rung, not max_batch)
    queue.submit(queue_env["make_request"](5))
    st = queue.tick()
    assert (st.rows, st.shape) == (5, 8)


def test_head_of_line_request_spills_not_reorders(queue_env):
    """A request that does not fit the remaining tick budget waits for the
    next tick (FIFO preserved) rather than being overtaken."""
    queue = _fresh_queue(queue_env)
    t1 = queue.submit(queue_env["make_request"](10))
    t2 = queue.submit(queue_env["make_request"](9))   # 19 > max_batch
    st1 = queue.tick()
    assert st1.rows == 10 and t1.done() and not t2.done()
    st2 = queue.tick()
    assert st2.rows == 9 and t2.done()


def test_oversize_request_segments_reassemble_in_order(queue_env):
    b = 2 * MAX_BATCH + 5                      # 3 segments across 3 ticks
    req = queue_env["make_request"](b)
    queue = _fresh_queue(queue_env)
    ticket = queue.submit(req)
    ticks = queue.drain()
    assert ticks == 3 and queue.dispatch_count == 3
    got = ticket.result(timeout=0)
    want = queue_env["direct"](req)
    for name in _EXACT_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                      np.asarray(getattr(want, name)),
                                      err_msg=f"spilled request: {name}")


def test_background_loop_serves_tickets(queue_env):
    queue = _fresh_queue(queue_env, tick_us=100.0)
    with queue:
        tickets = [queue.submit(queue_env["make_request"](b))
                   for b in (1, 4, 7, 2)]
        results = [t.result(timeout=60.0) for t in tickets]
    assert [np.asarray(r.ids).shape[0] for r in results] == [1, 4, 7, 2]
    assert queue.dispatch_count == len(queue.tick_log) > 0


def test_concurrent_synchronous_callers(queue_env):
    """Multiple caller threads driving query() (submit + drain) at once,
    including a spilling request: ticks are serialized, every ticket
    resolves, every result is bit-exact, and the probe still counts one
    dispatch per tick."""
    queue = _fresh_queue(queue_env)
    sizes = (1, MAX_BATCH + 3, 5, 2, 9, MAX_BATCH, 4, 7)
    requests = [queue_env["make_request"](b) for b in sizes]
    results = [None] * len(sizes)
    errors = []

    def caller(j):
        try:
            results[j] = queue.query(requests[j], timeout=120.0)
        except Exception as e:   # surfaced below; don't hang the join
            errors.append((j, repr(e)))

    threads = [threading.Thread(target=caller, args=(j,))
               for j in range(len(sizes))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=240.0)
    assert not errors, errors
    for b, req, got in zip(sizes, requests, results):
        want = queue_env["direct"](req)
        assert np.asarray(got.ids).shape == (b, 2)
        for name in _EXACT_FIELDS:
            np.testing.assert_array_equal(
                np.asarray(getattr(got, name)),
                np.asarray(getattr(want, name)),
                err_msg=f"concurrent caller (size {b}) diverged on {name}")
    assert queue.dispatch_count == len(queue.tick_log)
    assert queue.depth == 0


def test_stats_summary_accounting(queue_env):
    queue = _fresh_queue(queue_env)
    for b in (3, 2, 9, 5):
        queue.submit(queue_env["make_request"](b))
    queue.drain()
    s = queue.stats_summary()
    assert s["rows_served"] == 19 and s["segments"] == 4
    assert s["dispatches"] == s["ticks"] == len(queue.tick_log)
    assert 0.0 < s["occupancy_mean"] <= 1.0
    assert 0.0 <= s["pad_waste"] < 1.0
    assert s["p99_dispatch_ms"] >= s["p50_dispatch_ms"] > 0.0


def test_tick_phase_counters_cover_the_tick(queue_env):
    """Each tick times its host phases back to back: every phase is
    positive, dispatch_ms is launch + wait, the phases fit inside the
    tick's wall time, and stats_summary() carries their window means and
    the queue wait from submit to pack."""
    queue = _fresh_queue(queue_env)
    for b in (3, 2, 9, 5):
        queue.submit(queue_env["make_request"](b))
    queue.drain()
    assert len(queue.tick_log) >= 2
    for t in queue.tick_log:
        phases = [getattr(t, f"{p}_ms") for p in TICK_PHASES]
        assert all(v > 0.0 for v in phases), phases
        assert t.dispatch_ms == pytest.approx(t.launch_ms + t.wait_ms)
        assert sum(phases) <= t.wall_ms
        assert t.queued_ms > 0.0
    s = queue.stats_summary()
    for p in TICK_PHASES:
        want = np.mean([getattr(t, f"{p}_ms") for t in queue.tick_log])
        assert s[f"{p}_ms"] == pytest.approx(want)
    assert s["tick_wall_ms"] == pytest.approx(
        np.mean([t.wall_ms for t in queue.tick_log]))
    assert s["tick_wall_ms"] >= sum(s[f"{p}_ms"] for p in TICK_PHASES)
    assert s["p99_queued_ms"] >= s["p50_queued_ms"] > 0.0


def test_queued_ms_counts_from_submit_to_pack(queue_env):
    """A segment that waits for a later tick reads a longer queue wait."""
    import time
    queue = _fresh_queue(queue_env)
    queue.submit(queue_env["make_request"](MAX_BATCH))
    queue.submit(queue_env["make_request"](MAX_BATCH))   # spills a tick
    time.sleep(0.02)
    first, second = queue.tick(), queue.tick()
    assert first.queued_ms >= 20.0
    assert second.queued_ms >= first.queued_ms + first.wall_ms - 1.0


def test_failed_dispatch_fails_tickets_not_hangs(queue_env):
    """If a tick's dispatch dies, its tickets resolve with the error (no
    eternal hang), the exception surfaces to the tick driver, and the queue
    keeps serving subsequent batches."""
    queue = _fresh_queue(queue_env)
    real_fn = queue._fn
    calls = {"n": 0}

    def flaky(qs, valid):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("injected dispatch failure")
        return real_fn(qs, valid)

    queue._fn = flaky
    doomed = queue.submit(queue_env["make_request"](3))
    with pytest.raises(RuntimeError, match="injected"):
        queue.tick()
    assert doomed.done()
    with pytest.raises(RuntimeError, match="injected"):
        doomed.result(timeout=0)
    # the queue is still alive: the next request is served normally
    ok = queue.query(queue_env["make_request"](2))
    assert np.asarray(ok.ids).shape == (2, 2)


def test_ladder_normalization_shared_helper():
    assert BatchQueue.resolve_ladder((32, 8, 8, 128)) == (8, 32, 128)
    assert BatchQueue.resolve_ladder((8, 32, 128), 64) == (8, 32, 64)
    assert BatchQueue.resolve_ladder((0, -4, 8), 16) == (8, 16)
    assert BatchQueue.resolve_ladder((), 16) == (16,)
    with pytest.raises(ValueError, match="ladder"):
        BatchQueue.resolve_ladder(())
    with pytest.raises(ValueError, match="max_batch"):
        BatchQueue.resolve_ladder((8,), 0)


def test_bad_requests_rejected(queue_env):
    queue = _fresh_queue(queue_env, warmup=False)
    with pytest.raises(ValueError, match="empty request"):
        queue.submit(np.zeros((0, queue_env["d"]), np.float32))
    with pytest.raises(ValueError, match="expected"):
        queue.submit(np.zeros((3, queue_env["d"] + 1), np.float32))
    with pytest.raises(ValueError, match="ladder"):
        BatchQueue(queue_env["engine"], ladder=(), warmup=False)


def _check_random_sequence(env, sizes):
    queue = _fresh_queue(env)
    _assert_queued_matches_direct(env, queue, sizes)
    # the probe: however ragged the arrivals, dispatches == ticks
    assert queue.dispatch_count == len(queue.tick_log)


if HAVE_HYPOTHESIS:
    @settings(max_examples=8, deadline=None)
    @given(sizes=st.lists(st.integers(1, MAX_BATCH + 6), min_size=1,
                          max_size=8))
    def test_random_request_sequences_bit_exact(queue_env, sizes):
        _check_random_sequence(queue_env, sizes)
else:
    @pytest.mark.parametrize("sizes", [
        (1,), (2, 2, 2), (16, 1, 5), (22, 3), (4, 8, 16, 1, 1, 1),
    ])
    def test_random_request_sequences_bit_exact(queue_env, sizes):
        _check_random_sequence(queue_env, sizes)


def test_queue_over_oracle_and_host_plans(queue_env):
    """The queue is plan-agnostic: oracle and host plans serve padded ticks
    with the same parity (the masked seam is in the engine, not the queue)."""
    for plan in ("oracle", "host"):
        queue = BatchQueue(queue_env["engine"], plan=plan, k=2,
                           ladder=(8,), max_batch=8)
        req = queue_env["make_request"](5)
        got = queue.query(req)
        want = queue_env["engine"].query(req, plan=plan, k=2)
        np.testing.assert_array_equal(np.asarray(got.ids),
                                      np.asarray(want.ids), err_msg=plan)


# --------------------------------------------------------------------------
# One tick in flight: the background loop launches tick N+1 before it waits
# on, fetches and delivers tick N, wherever N's result is not yet complete
# when its launch returns.

class _GatedIds:
    """The ``ids`` of a stub plan's result: not ready, and held at the
    wait, until the test opens ``gate``; ``waiting`` is set when the queue
    starts to wait; ``fail_fetch`` makes the transfer to the host raise."""

    def __init__(self, ids, gate, waiting=None, fail_fetch=False):
        self._ids, self._gate = ids, gate
        self._waiting, self._fail_fetch = waiting, fail_fetch

    def is_ready(self):
        return self._gate.is_set()

    def block_until_ready(self):
        if self._waiting is not None:
            self._waiting.set()
        assert self._gate.wait(60.0), "the test never opened the gate"
        return self

    def __array__(self, dtype=None, copy=None):
        if self._fail_fetch:
            raise RuntimeError("injected fetch failure")
        return np.asarray(self._ids, dtype=dtype)


def _gate_plan(queue, gates, **kw):
    """Make launch i of ``queue`` return the real result with its ids
    behind ``gates[i]`` (launches past the list reuse the last gate)."""
    real_fn, calls = queue._fn, []

    def gated(qs, valid):
        res = real_fn(qs, valid)
        gate = gates[min(len(calls), len(gates) - 1)]
        calls.append(gate)
        return dataclasses.replace(res, ids=_GatedIds(res.ids, gate, **kw))

    queue._fn = gated
    return calls


def _assert_exact(got, want, what):
    for name in _EXACT_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(got, name)), np.asarray(getattr(want, name)),
            err_msg=f"{what}: {name}")


def test_loop_launches_next_tick_before_fetching_the_last(queue_env):
    """With results that are not ready at launch, the loop launches tick 1
    while tick 0 is still waited on (tick 0 unserved at tick 1's launch),
    flags the overlapped ticks, counts them in overlap_share, and every
    request is still bit-exact with a direct dispatch."""
    queue = _fresh_queue(queue_env)
    gate, waiting = threading.Event(), threading.Event()
    real_fn = queue._fn
    requests = [queue_env["make_request"](MAX_BATCH) for _ in range(3)]
    tickets = [queue.submit(r) for r in requests]     # before the loop runs
    seen = []                  # at each launch: which tickets were served

    def recording(qs, valid):
        seen.append([t.done() for t in tickets])
        return real_fn(qs, valid)

    queue._fn = recording
    _gate_plan(queue, [gate], waiting=waiting)
    queue.start()
    try:
        # the loop waits on tick 0 only after tick 1's launch has returned
        assert waiting.wait(60.0)
        assert len(seen) == 2 and seen[1] == [False, False, False]
        gate.set()
        results = [t.result(timeout=60.0) for t in tickets]
    finally:
        gate.set()
        queue.stop()
    for req, got in zip(requests, results):
        _assert_exact(got, queue_env["direct"](req), "overlapped tick")
    # tick 2 launched behind tick 1, after the gate opened: ready at launch
    assert [t.overlapped for t in queue.tick_log] == [False, True, True]
    assert queue.stats_summary()["overlap_share"] == pytest.approx(2 / 3)
    assert queue.dispatch_count == len(queue.tick_log) == 3


@pytest.mark.parametrize("plan", ["stub", "external"])
def test_ready_results_never_overlap(queue_env, tmp_path, plan):
    """A plan whose result is complete when its call returns (a stub that
    blocks before it returns; the external plan over the mem store) never
    overlaps: each tick is delivered before the next is packed, in FIFO
    order, bit-exact with a direct call."""
    sizes = (MAX_BATCH, 5, MAX_BATCH, 9)     # one request per tick
    requests = [queue_env["make_request"](b) for b in sizes]
    with load_external(_spill(queue_env, tmp_path), backend="mem") as ext:
        if plan == "external":
            engine = SearchEngine(ext)
            queue = BatchQueue(engine, plan="external", k=2, ladder=LADDER,
                               max_batch=MAX_BATCH)
            direct = engine.make_plan_fn(plan="external", k=2)[1]
            inner = queue._fn
        else:
            queue, direct = _fresh_queue(queue_env), queue_env["direct"]
            real_fn = queue._fn

            def inner(qs, valid):
                return jax.block_until_ready(real_fn(qs, valid))
        tickets, seen = [], []

        def recording(qs, valid):
            seen.append([t.done() for t in tickets])
            return inner(qs, valid)

        queue._fn = recording
        tickets.extend(queue.submit(r) for r in requests)
        with queue:
            results = [t.result(timeout=60.0) for t in tickets]
        assert seen == [[True] * i + [False] * (len(sizes) - i)
                        for i in range(len(sizes))]
        for req, got in zip(requests, results):
            _assert_exact(got, direct(req), f"{plan} queued")
    assert [t.rows for t in queue.tick_log] == list(sizes)
    assert not any(t.overlapped for t in queue.tick_log)
    assert queue.stats_summary()["overlap_share"] == 0.0


def _spill(env, tmp_path):
    path = tmp_path / "index.e2l"
    env["idx"].index.spill(path)
    return path


def test_launch_failure_fails_only_its_tick(queue_env):
    """Tick 1's launch dies while tick 0 is in flight: tick 1's tickets
    fail, tick 0 is still delivered, and the error surfaces."""
    queue = _fresh_queue(queue_env)
    gate = threading.Event()
    _gate_plan(queue, [gate])
    gated_fn, calls = queue._fn, []

    def flaky(qs, valid):
        calls.append(1)
        if len(calls) == 2:
            raise RuntimeError("injected launch failure")
        return gated_fn(qs, valid)

    queue._fn = flaky
    requests = [queue_env["make_request"](MAX_BATCH) for _ in range(2)]
    first, second = (queue.submit(r) for r in requests)
    assert queue._step() == MAX_BATCH         # tick 0 left in flight
    assert not first.done() and queue.dispatch_count == 0
    gate.set()
    with pytest.raises(RuntimeError, match="injected launch"):
        queue._step()
    _assert_exact(first.result(timeout=0), queue_env["direct"](requests[0]),
                  "tick in flight behind a failed launch")
    with pytest.raises(RuntimeError, match="injected launch"):
        second.result(timeout=0)
    assert queue.dispatch_count == len(queue.tick_log) == 1


def test_fetch_failure_fails_only_its_tick(queue_env):
    """Tick 0's fetch dies after tick 1 was launched behind it: tick 0's
    tickets fail (the request whose tail rides in tick 1 too), tick 1
    stays in flight and drain() delivers its other request."""
    queue = _fresh_queue(queue_env)
    gates = [threading.Event(), threading.Event()]
    real_fn = queue._fn
    calls = _gate_plan(queue, gates)
    gated_fn = queue._fn

    def failing_fetch(qs, valid):
        res = gated_fn(qs, valid)
        if len(calls) == 1:
            res = dataclasses.replace(res, ids=_GatedIds(
                real_fn(qs, valid).ids, gates[0], fail_fetch=True))
        return res

    queue._fn = failing_fetch
    requests = [queue_env["make_request"](b) for b in (MAX_BATCH + 3, 5)]
    first, second = (queue.submit(r) for r in requests)
    queue._step()                             # tick 0 in flight
    gates[0].set()
    with pytest.raises(RuntimeError, match="injected fetch"):
        queue._step()                         # tick 1 launched, tick 0 dies
    with pytest.raises(RuntimeError, match="injected fetch"):
        first.result(timeout=0)
    assert not second.done() and queue.dispatch_count == 0
    gates[1].set()
    assert queue.drain() == 0                 # completes tick 1, packs none
    _assert_exact(second.result(timeout=0), queue_env["direct"](requests[1]),
                  "tick in flight behind a failed fetch")
    assert [(t.rows, t.overlapped) for t in queue.tick_log] == [(8, True)]
    assert queue.dispatch_count == len(queue.tick_log) == 1


@pytest.mark.parametrize("entry", ["tick", "drain", "stop"])
def test_sync_entry_points_complete_a_tick_in_flight(queue_env, entry):
    """tick(), drain() and stop(drain=True) first complete a tick the loop
    left in flight, then serve what is pending as before."""
    queue = _fresh_queue(queue_env)
    gate = threading.Event()
    _gate_plan(queue, [gate])
    requests = [queue_env["make_request"](b) for b in (MAX_BATCH, 3)]
    tickets = [queue.submit(r) for r in requests]
    queue._step()                             # tick 0 left in flight
    assert not tickets[0].done() and queue.dispatch_count == 0
    gate.set()
    if entry == "tick":
        stats = queue.tick()                    # serves its own batch too
        assert (stats.rows, stats.overlapped) == (3, False)
    elif entry == "drain":
        assert queue.drain() == 1
    else:
        queue.start()
        queue.stop(drain=True)
    for req, t in zip(requests, tickets):
        _assert_exact(t.result(timeout=0), queue_env["direct"](req), entry)
    assert [t.rows for t in queue.tick_log] == [MAX_BATCH, 3]
    assert queue.dispatch_count == len(queue.tick_log) == 2
    assert queue._inflight is None
