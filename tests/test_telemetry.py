"""The unified telemetry layer (docs/telemetry.md): registry exactness,
tracer semantics, exporters, the live /metrics server, and the
trace-vs-ledger consistency contract.

The load-bearing contract (ISSUE acceptance): a traced external-plan query
at sampling=1.0 must be SELF-VERIFYING — the sum of its ``store.read``
spans' ``rows`` attributes equals the StoreStats logical-read ledger delta,
equals the plan's ``measured_nio_blocks``, equals the Eq. 6/7 io_count
replay of the recorded probe trace, on every backend. Telemetry reports the
pinned ledger semantics; it never gets its own parallel truth.
"""
import dataclasses
import gc
import json
import threading
import time
import urllib.request

import jax
import numpy as np
import pytest

from repro import storage as st
from repro import telemetry
from repro.core import E2LSHoS, SearchEngine
from repro.core.io_count import nio_for_block_size
from repro.serving import BatchQueue
from repro.serving.engine import TICK_PHASES
from repro.telemetry import (MetricsServer, NOOP_SPAN, Registry, Tracer,
                             render_prometheus, spans_to_chrome)

_BACKENDS = ("mem", "mmap", "aio", "uring")


@pytest.fixture(autouse=True)
def _telemetry_clean():
    """Tracing is process-global state: every test leaves it off + empty."""
    yield
    telemetry.disable()
    telemetry.get_tracer().clear()


def _require_uring(path) -> None:
    caps = st.capabilities(path)
    if not caps["uring_store"]:
        pytest.skip(f"io_uring unavailable: {caps['io_uring_reason']}")


# Same sizing as test_storage_external's storage_index: this file runs
# under the forced interpret kernel lane (`make telemetry-lane`), where
# every distinct batch shape recompiles — small index, shared shapes.
@pytest.fixture(scope="module")
def storage_index():
    rng = np.random.default_rng(7)
    n, d = 1500, 12
    centers = rng.normal(size=(24, d)).astype(np.float32)
    db = (centers[rng.integers(0, 24, n)]
          + 0.18 * rng.normal(size=(n, d))).astype(np.float32)
    qs = (db[rng.choice(n, 24, replace=False)]
          + 0.05 * rng.normal(size=(24, d))).astype(np.float32)
    s = float(np.median(np.linalg.norm(db - db.mean(0), axis=1))) / 3
    return E2LSHoS.build(db / s, gamma=0.7, s_scale=2.0, max_L=8,
                         seed=3), qs / s


@pytest.fixture(scope="module")
def spilled(storage_index, tmp_path_factory):
    idx, _ = storage_index
    path = tmp_path_factory.mktemp("tel_spill") / "index.e2l"
    idx.index.spill(path)
    return path


# --------------------------------------------------------------------------
# Registry
# --------------------------------------------------------------------------

def test_counter_exact_under_threads():
    """The lock-free hot path loses no increments: 8 threads x 5000 incs
    per labeled series sum exactly."""
    reg = Registry()
    c = reg.counter("t_reads_total", "reads", labelnames=("backend",))

    def work(backend):
        for _ in range(5000):
            c.inc(backend=backend)

    threads = [threading.Thread(target=work, args=(b,))
               for b in ("mem", "aio") for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = reg.snapshot()["t_reads_total"]
    assert snap["type"] == "counter"
    by_label = {s["labels"]["backend"]: s["value"] for s in snap["samples"]}
    assert by_label == {"mem": 20000, "aio": 20000}


def test_gauge_is_instantaneous_and_unbaselined():
    reg = Registry()
    g = reg.gauge("t_depth", "queue depth", labelnames=("plan",))
    g.set(7, plan="fused")
    reg.reset()                      # baselines must not touch gauges
    g.set(3, plan="fused")
    (s,) = reg.snapshot()["t_depth"]["samples"]
    assert s["value"] == 3.0


def test_histogram_buckets_and_quantile():
    reg = Registry()
    h = reg.histogram("t_ms", "latency", buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 2.0, 3.0, 4.0, 50.0, 200.0):
        h.observe(v)
    (s,) = reg.snapshot()["t_ms"]["samples"]
    assert s["counts"] == [1, 3, 1, 1] and s["count"] == 6
    assert s["sum"] == pytest.approx(259.5)
    # p50 lands in the (1, 10] bucket; interpolation stays inside it
    q50 = h.quantile(0.5)
    assert 1.0 < q50 <= 10.0


def test_registry_type_conflict_and_label_mismatch():
    reg = Registry()
    reg.counter("t_x", labelnames=("a",))
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("t_x")
    with pytest.raises(ValueError, match="takes labels"):
        reg.counter("t_x", labelnames=("a",)).inc(b=1)
    # get-or-create: same name + kind returns the same metric object
    assert reg.counter("t_x", labelnames=("a",)) is reg.counter(
        "t_x", labelnames=("a",))


def test_reset_is_baseline_subtraction_clamped():
    """reset() re-baselines counters; a collector whose source shrank
    afterwards (object died, ledger cleared) clamps at 0, never negative."""
    reg = Registry()
    c = reg.counter("t_total")
    src = {"v": 10}
    reg.register_collector(
        lambda: {"t_coll_total": dict(
            type="counter", help="",
            samples=[dict(labels={}, value=src["v"])])},
        name="t")
    c.inc(5)
    reg.reset()
    assert reg.snapshot()["t_total"]["samples"][0]["value"] == 0
    c.inc(3)
    assert reg.snapshot()["t_total"]["samples"][0]["value"] == 3
    src["v"] = 4                    # collector source went BACKWARDS
    assert reg.snapshot()["t_coll_total"]["samples"][0]["value"] == 0
    src["v"] = 15
    assert reg.snapshot()["t_coll_total"]["samples"][0]["value"] == 5


def test_collector_merges_into_existing_series():
    """A collector emitting an already-registered metric name extends its
    sample list (live + retired stores summing into one series)."""
    reg = Registry()
    reg.counter("t_m_total", labelnames=("src",)).inc(2, src="native")
    reg.register_collector(
        lambda: {"t_m_total": dict(
            type="counter", help="",
            samples=[dict(labels=dict(src="ledger"), value=9)])},
        name="t")
    labels = {s["labels"]["src"]: s["value"]
              for s in reg.snapshot()["t_m_total"]["samples"]}
    assert labels == {"native": 2, "ledger": 9}


# --------------------------------------------------------------------------
# Tracer
# --------------------------------------------------------------------------

def test_span_tree_parent_links():
    tr = Tracer(enabled=True)
    with tr.span("root", a=1) as root:
        with tr.span("child") as child:
            with tr.span("grandchild") as gc:
                pass
    spans = tr.spans()
    assert [s.name for s in spans] == ["grandchild", "child", "root"]
    assert root.parent is None
    assert child.parent == root.sid and gc.parent == child.sid
    assert all(s.dur_ns is not None and s.dur_ns >= 0 for s in spans)
    assert root.attrs == dict(a=1)


def test_sampling_decided_at_root_inherited_by_children():
    """sampling=0.0 drops the whole tree — a child can never outlive its
    root's coin flip (a rung span never loses its read spans)."""
    tr = Tracer(enabled=True, sampling=0.0)
    with tr.span("root"):
        with tr.span("child"):
            pass
    assert len(tr) == 0
    tr.configure(sampling=1.0)
    with tr.span("root"):
        with tr.span("child"):
            pass
    assert len(tr) == 2
    with pytest.raises(ValueError, match="sampling"):
        tr.configure(sampling=1.5)


def test_disabled_tracer_is_noop():
    tr = Tracer(enabled=False)
    sp = tr.begin("anything", x=1)
    assert sp is NOOP_SPAN
    sp.set(y=2)
    sp.end()
    assert len(tr) == 0


def test_cancel_drops_span():
    tr = Tracer(enabled=True)
    sp = tr.begin("maybe")
    sp.cancel()                     # idle tick: begun, then never happened
    with tr.span("real"):
        pass
    assert [s.name for s in tr.spans()] == ["real"]


def test_ring_capacity_bounds_memory():
    tr = Tracer(enabled=True, capacity=8)
    for i in range(20):
        with tr.span("s", i=i):
            pass
    spans = tr.spans()
    assert len(spans) == 8
    assert [s.attrs["i"] for s in spans] == list(range(12, 20))
    assert tr.spans(last=3)[-1].attrs["i"] == 19


def test_detached_span_does_not_parent():
    """detached=True skips the thread-local stack: async waves ended out of
    lexical order never adopt unrelated children."""
    tr = Tracer(enabled=True)
    wave = tr.begin("wave", detached=True)
    with tr.span("other") as other:
        pass
    wave.end()
    assert other.parent is None


def test_explicit_parent_beats_the_stack():
    """``parent=`` hangs a span under a span that is not the stack's top
    (an earlier tick's detached root) and inherits its sampling; a no-op
    parent gives a no-op child."""
    tr = Tracer(enabled=True)
    first = tr.begin("first", detached=True)
    with tr.span("second") as second:
        with tr.span("child", parent=first) as child:
            with tr.span("grandchild") as grand:
                pass
    first.end()
    assert child.parent == first.sid and grand.parent == child.sid
    assert second.parent is None
    assert tr.begin("orphan", parent=NOOP_SPAN) is NOOP_SPAN
    tr.configure(sampling=0.0)
    unsampled = tr.begin("root", detached=True)
    tr.configure(sampling=1.0)
    tr.begin("child", parent=unsampled).end()
    unsampled.end()
    assert [s.name for s in tr.spans()] == ["grandchild", "child", "second",
                                             "first"]


def test_env_kill_switch_beats_enable(monkeypatch):
    monkeypatch.setenv(telemetry.TELEMETRY_ENV, "off")
    assert telemetry.telemetry_forced_off()
    tr = telemetry.enable(sampling=1.0)
    assert not tr.enabled
    assert tr.begin("x") is NOOP_SPAN
    monkeypatch.delenv(telemetry.TELEMETRY_ENV)
    assert telemetry.enable().enabled     # programmatic control returns


# --------------------------------------------------------------------------
# Exporters
# --------------------------------------------------------------------------

def _sample_spans():
    tr = Tracer(enabled=True)
    with tr.span("plan.external", backend="mem"):
        for t in range(2):
            with tr.span("external.rung", t=t):
                with tr.span("store.read", rows=4):
                    pass
    return tr.spans()


def test_chrome_trace_export_is_loadable(tmp_path):
    """The acceptance bar: the export is the traceEvents format Perfetto /
    chrome://tracing load directly — complete X events, us timestamps
    normalized to 0, span ids riding in args."""
    spans = _sample_spans()
    path = tmp_path / "trace.json"
    n = telemetry.export_chrome_trace(path, spans)
    assert n == len(spans) == 5
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    evs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert len(evs) == 5 and metas, "missing X events or thread_name meta"
    for e in evs:
        assert e["ts"] >= 0.0 and e["dur"] >= 0.0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert "sid" in e["args"]
    by_sid = {e["args"]["sid"]: e for e in evs}
    reads = [e for e in evs if e["name"] == "store.read"]
    assert len(reads) == 2
    for e in reads:                 # the tree survives via args.parent
        assert by_sid[e["args"]["parent"]]["name"] == "external.rung"
    assert {e["cat"] for e in reads} == {"store"}


def test_jsonl_export(tmp_path):
    spans = _sample_spans()
    path = tmp_path / "trace.jsonl"
    assert telemetry.export_jsonl(path, spans) == len(spans)
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert len(lines) == len(spans)
    for rec in lines:
        assert {"name", "sid", "parent", "tid", "ts_us", "dur_us",
                "attrs"} <= set(rec)


def test_render_prometheus_text_format():
    reg = Registry()
    reg.counter("t_reads_total", "logical reads",
                labelnames=("backend",)).inc(42, backend="aio")
    h = reg.histogram("t_ms", "latency", buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(5.0)
    body = render_prometheus(reg.snapshot())
    assert "# HELP t_reads_total logical reads" in body
    assert "# TYPE t_reads_total counter" in body
    assert 't_reads_total{backend="aio"} 42' in body
    # classic histogram triple with CUMULATIVE le buckets
    assert 't_ms_bucket{le="1.0"} 1' in body
    assert 't_ms_bucket{le="10.0"} 2' in body
    assert 't_ms_bucket{le="+Inf"} 2' in body
    assert "t_ms_count 2" in body and "t_ms_sum 5.5" in body


# --------------------------------------------------------------------------
# Trace-vs-ledger consistency: the self-verifying query
# --------------------------------------------------------------------------

@pytest.mark.parametrize("backend", _BACKENDS)
def test_trace_matches_ledger_and_replay(storage_index, spilled, backend):
    """On every backend: sum of store.read span rows == StoreStats.reads
    delta == measured_nio_blocks == the io_count replay's block share ==
    the runtime nio_blocks counters. One query, four independent witnesses,
    one number."""
    if backend == "uring":
        _require_uring(spilled)
    idx, qs = storage_index
    p = idx.params
    kw = dict(qd=4) if backend in ("aio", "uring") else {}
    tr = telemetry.enable(sampling=1.0)
    tr.clear()
    with st.load_external(spilled, backend=backend, **kw) as ext:
        engine = SearchEngine(ext)
        io0 = ext.store.stats.snapshot()
        res = engine.query(qs, k=2, collect_probe_sizes=True)
        ps = ext.last_plan_stats
        ledger_delta = ext.store.stats.reads - io0.reads
    spans = tr.spans()
    reads = [s for s in spans if s.name == "store.read"]
    assert reads, "enabled tracing recorded no store.read spans"
    span_rows = sum(s.attrs["rows"] for s in reads)

    replay = nio_for_block_size(np.asarray(res.probe_sizes), s_cap=p.S,
                                block_bytes=p.block_bytes)
    blocks_replayed = (int(replay.sum())
                       - int(np.asarray(res.nio_table).sum()))
    assert span_rows == ledger_delta
    assert span_rows == ps.measured_nio_blocks
    assert span_rows == blocks_replayed
    assert span_rows == int(np.asarray(res.nio_blocks).sum())
    # the pinned ledger identity holds inside the spans too
    assert all(s.attrs["rows"] == s.attrs["cache_hits"]
               + s.attrs["device_reads"] for s in reads)
    # prefetch rides its own lane: never a store.read span, never reads
    assert sum(s.attrs["rows"] for s in spans
               if s.name == "store.prefetch") == ps.io.prefetch_reads


def test_per_rung_spans_reconstruct_rung_stats(storage_index, spilled):
    """The span TREE carries the per-rung breakdown: grouping store.read
    children under their external.rung parent reproduces each rung's
    blocks-fetched count — the trace alone reconstructs RungStats."""
    idx, qs = storage_index
    tr = telemetry.enable(sampling=1.0)
    tr.clear()
    with st.load_external(spilled, backend="mem") as ext:
        engine = SearchEngine(ext)
        engine.query(qs, k=2)
        ps = ext.last_plan_stats
    spans = tr.spans()
    rungs = {s.sid: s for s in spans if s.name == "external.rung"}
    assert len(rungs) == len(ps.rungs)
    fetched_by_parent: dict = {}
    for s in spans:
        if s.name == "store.read":
            assert s.parent in rungs, "read span outside any rung"
            fetched_by_parent[s.parent] = (
                fetched_by_parent.get(s.parent, 0) + s.attrs["rows"])
    for sid, rsp in rungs.items():
        assert fetched_by_parent.get(sid, 0) == rsp.attrs["blocks_fetched"]
    # and the roots chain up: rung -> plan.external -> query
    (plan_sp,) = [s for s in spans if s.name == "plan.external"]
    (query_sp,) = [s for s in spans if s.name == "query"]
    assert all(r.parent == plan_sp.sid for r in rungs.values())
    assert plan_sp.parent == query_sp.sid and query_sp.parent is None
    assert plan_sp.attrs["nio_blocks"] == ps.io.reads


def test_disabled_telemetry_changes_nothing(storage_index, spilled):
    """The off-switch is total: zero spans recorded, and the ledgers and
    results are identical to an enabled run — instrumentation must never
    perturb what it observes."""
    idx, qs = storage_index
    telemetry.enable(sampling=1.0)
    with st.load_external(spilled, backend="mem") as ext:
        res_on = SearchEngine(ext).query(qs, k=2)
        reads_on = ext.store.stats.reads
    tr = telemetry.disable()
    tr.clear()
    with st.load_external(spilled, backend="mem") as ext:
        res_off = SearchEngine(ext).query(qs, k=2)
        reads_off = ext.store.stats.reads
    assert len(tr) == 0, "disabled tracer recorded spans"
    assert reads_on == reads_off
    for name in ("ids", "dists", "found", "nio_blocks", "radii_searched"):
        np.testing.assert_array_equal(np.asarray(getattr(res_on, name)),
                                      np.asarray(getattr(res_off, name)))


def test_store_collector_in_unified_snapshot(storage_index, spilled):
    """telemetry.snapshot() windows the live StoreStats ledgers (and
    retired totals survive close()) without owning them."""
    idx, qs = storage_index
    telemetry.reset()
    with st.load_external(spilled, backend="mem") as ext:
        SearchEngine(ext).query(qs, k=2)
        reads = ext.store.stats.reads
        snap_live = telemetry.snapshot()
    snap_closed = telemetry.snapshot()      # store retired by close()
    for snap, where in ((snap_live, "live"), (snap_closed, "retired")):
        sm = snap["e2lsh_store_reads_total"]
        assert sm["type"] == "counter"
        got = sum(s["value"] for s in sm["samples"]
                  if s["labels"].get("backend") == "mem")
        assert got >= reads, f"{where}: collector lost ledger reads"
    assert "e2lsh_query_calls_total" in snap_closed
    # ledger identity, seen through the registry window
    for snap in (snap_live, snap_closed):
        r = sum(s["value"]
                for s in snap["e2lsh_store_reads_total"]["samples"])
        d = sum(s["value"]
                for s in snap["e2lsh_store_device_reads_total"]["samples"])
        h = sum(s["value"]
                for s in snap["e2lsh_store_cache_hits_total"]["samples"])
        assert r == d + h


# --------------------------------------------------------------------------
# Serving-tier integration: tick phase spans, stats races, live /metrics
# --------------------------------------------------------------------------

def test_plan_totals_accumulate_across_calls(storage_index, spilled):
    """Satellite (b): the accumulating roll-up the queued path needs —
    last_plan_stats is per-call, plan_totals sums."""
    idx, qs = storage_index
    with st.load_external(spilled, backend="mem") as ext:
        engine = SearchEngine(ext)
        base = ext.plan_totals.snapshot()
        engine.query(qs, k=2)
        first = ext.last_plan_stats.io.reads
        engine.query(qs, k=2)
        delta = ext.plan_totals.since(base)
    assert delta.calls == 2
    assert delta.queries == 2 * len(qs)
    assert delta.nio_blocks == first + ext.last_plan_stats.io.reads


_TICK_TREE = {"tick.pack": "serve.tick", "tick.dispatch": "serve.tick",
              "tick.scatter": "serve.tick", "tick.launch": "tick.dispatch",
              "tick.wait": "tick.dispatch", "tick.fetch": "tick.scatter",
              "tick.deliver": "tick.scatter"}


def test_served_tick_records_its_phase_tree(storage_index):
    """With tracing on, one served tick records serve.tick (carrying its
    TickStats id) with pack / dispatch (launch, wait) / scatter (fetch,
    deliver) nested in order; with tracing off it records nothing, and
    the answers are bit-exact with the traced tick's."""
    idx, qs = storage_index
    q = BatchQueue(SearchEngine(idx), plan="fused", ladder=(8,), k=2)
    q.submit(qs[:5])
    q.tick()                                 # a first tick, not recorded
    telemetry.enable(sampling=1.0)
    tr = telemetry.get_tracer()
    tr.clear()
    on = q.submit(qs[5:10])
    stats = q.tick()
    spans = tr.drain()
    by_name = {s.name: s for s in spans}
    assert sorted(by_name) == sorted(["serve.tick", *_TICK_TREE])
    assert len(spans) == len(by_name)        # one of each
    root = by_name["serve.tick"]
    assert root.parent is None and root.attrs["tick"] == stats.tick == 1
    for child, parent in _TICK_TREE.items():
        sp = by_name[child]
        assert sp.parent == by_name[parent].sid, child
        assert sp.ts_ns >= by_name[parent].ts_ns
        assert sp.ts_ns + sp.dur_ns <= by_name[parent].ts_ns + \
            by_name[parent].dur_ns
    order = ["tick.pack", "tick.launch", "tick.wait", "tick.fetch",
             "tick.deliver"]
    ends = [by_name[n].ts_ns + by_name[n].dur_ns for n in order]
    starts = [by_name[n].ts_ns for n in order]
    assert all(e <= s for e, s in zip(ends, starts[1:]))

    telemetry.disable()
    off = q.submit(qs[5:10])
    q.tick()
    assert len(tr) == 0
    a, b = on.result(timeout=0), off.result(timeout=0)
    for name in ("ids", "dists", "found", "nio_blocks", "radii_searched",
                 "nio_table", "cands_checked"):
        np.testing.assert_array_equal(np.asarray(getattr(a, name)),
                                      np.asarray(getattr(b, name)))


class _Unready:
    """The ``ids`` of a stub plan's result that reports not ready when its
    launch returns (its wait and fetch then go through at once)."""

    def __init__(self, ids):
        self._ids = ids

    def is_ready(self):
        return False

    def block_until_ready(self):
        return self

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self._ids, dtype=dtype)


def _alternate_readiness(q):
    """Launches 1, 3, 5, ... of ``q`` report their result not ready; the
    others return it complete. The loop then keeps ticks 0, 2, 4 in flight
    and launches ticks 1, 3 behind them."""
    real_fn, calls = q._fn, []

    def fn(qs, valid):
        calls.append(1)
        res = jax.block_until_ready(real_fn(qs, valid))
        if len(calls) % 2:
            return dataclasses.replace(res, ids=_Unready(res.ids))
        return res

    q._fn = fn


def _overlapped_ticks(plan: str) -> float:
    entry = telemetry.get_registry().collect()[
        "e2lsh_serve_overlapped_ticks_total"]
    return sum(s["value"] for s in entry["samples"]
               if s["labels"] == {"plan": plan})


def test_overlapped_ticks_counter_matches_tick_stats(storage_index):
    """e2lsh_serve_overlapped_ticks_total{plan} counts the TickStats rows
    with ``overlapped`` set."""
    idx, qs = storage_index
    gc.collect()          # the collector sums every live queue of a plan
    before = _overlapped_ticks("fused")
    q = BatchQueue(SearchEngine(idx), plan="fused", ladder=(8,), k=2)
    _alternate_readiness(q)
    for lo in (0, 8, 16, 0, 8):               # five ticks of 8 rows
        q.submit(qs[lo:lo + 8])
    while q._step() is not None:
        pass
    assert [t.overlapped for t in q.tick_log] == [False, True, False, True,
                                                  False]
    assert _overlapped_ticks("fused") - before == 2
    assert q.stats_summary()["overlap_share"] == pytest.approx(2 / 5)


def test_interleaved_ticks_keep_their_own_span_trees(storage_index):
    """Tick 1 is packed and launched while tick 0 is in flight, so its
    spans begin inside tick 0's; each phase span still hangs under its own
    tick's serve.tick, whose ``tick`` names the TickStats row."""
    idx, qs = storage_index
    q = BatchQueue(SearchEngine(idx), plan="fused", ladder=(8,), k=2)
    _alternate_readiness(q)
    q.submit(qs[:8])
    q.submit(qs[8:16])
    tr = telemetry.enable(sampling=1.0)
    tr.clear()
    q._step()                                 # tick 0 left in flight
    q._step()                                 # tick 1 launched behind it
    spans = tr.drain()
    by_sid = {s.sid: s for s in spans}

    def root_of(sp):
        while sp.parent is not None:
            sp = by_sid[sp.parent]
        return sp

    roots = sorted((s for s in spans if s.name == "serve.tick"),
                   key=lambda s: s.attrs["tick"])
    assert [r.attrs["tick"] for r in roots] == [0, 1]
    assert all(r.parent is None for r in roots)
    assert roots[0].ts_ns < roots[1].ts_ns < roots[0].ts_ns + roots[0].dur_ns
    for r in roots:
        mine = [s for s in spans if s.name in _TICK_TREE and root_of(s) is r]
        assert sorted(s.name for s in mine) == sorted(_TICK_TREE)
        for sp in mine:
            parent = by_sid[sp.parent]
            assert parent.name == _TICK_TREE[sp.name], sp.name
            assert parent.ts_ns <= sp.ts_ns
            assert sp.ts_ns + sp.dur_ns <= parent.ts_ns + parent.dur_ns


def test_stats_summary_window_vs_reset_race(storage_index):
    """Satellite (a) regression: tick() commits, stats_summary(window=N)
    reads, and reset_stats() clears — concurrently, for a while. Every
    summary must be a consistent cut: a dispatch is never visible without
    its tick row (cumulative view: dispatches == ticks, always)."""
    idx, qs = storage_index
    engine = SearchEngine(idx)
    q = BatchQueue(engine, plan="fused", ladder=(4,), max_batch=4, k=2)
    q.submit(qs[:3])
    q.tick()                         # compile the one rung up front
    q.reset_stats()
    stop = threading.Event()
    errors: list = []

    def reader():
        try:
            while not stop.is_set():
                full = q.stats_summary()
                assert full["dispatches"] == full["ticks"], \
                    f"torn cut: {full['dispatches']} != {full['ticks']}"
                windowed = q.stats_summary(window=3)
                assert windowed["ticks"] <= 3
                assert windowed["dispatches"] >= windowed["ticks"]
        except Exception as e:       # surface thread failures to pytest
            errors.append(e)

    def resetter():
        try:
            while not stop.is_set():
                q.reset_stats()
                time.sleep(0.002)
        except Exception as e:
            errors.append(e)

    threads = [threading.Thread(target=reader) for _ in range(2)]
    threads.append(threading.Thread(target=resetter))
    for t in threads:
        t.start()
    try:
        for _ in range(60):
            q.submit(qs[:3])
            q.tick()
    finally:
        stop.set()
        for t in threads:
            t.join()
    assert not errors, errors[0]


def test_live_metrics_server_under_load(storage_index, spilled):
    """The acceptance bar: serve with --metrics-port semantics (queue over
    an external engine, MetricsServer on an ephemeral port) and scrape LIVE
    Prometheus counters for reads, cache hits, and deadline hit rate."""
    idx, qs = storage_index
    telemetry.reset()                # this test's deltas only
    telemetry.enable(sampling=1.0)
    with st.load_external(spilled, backend="mem") as ext:
        engine = SearchEngine(ext)
        q = BatchQueue(engine, plan="external", ladder=(4, 8),
                       max_batch=8, k=2)
        with MetricsServer(0) as server:
            tickets = [q.submit(qs[i:i + 4], deadline_ms=60_000)
                       for i in range(0, 16, 4)]
            while q.depth:
                q.tick()
            for t in tickets:
                assert t.result(timeout=5).ids.shape[0] == 4

            def get(path):
                with urllib.request.urlopen(server.url + path,
                                            timeout=5) as r:
                    return r.read().decode()

            body = get("/metrics")
            metrics = {}
            for line in body.splitlines():
                if line and not line.startswith("#"):
                    key, val = line.rsplit(" ", 1)
                    metrics[key] = float(val)

            def series(prefix):
                return {k: v for k, v in metrics.items()
                        if k.startswith(prefix)}

            assert sum(series("e2lsh_store_reads_total").values()) > 0
            assert series("e2lsh_store_cache_hits_total"), \
                "cache-hit series missing from exposition"
            # 4 requests x 4 rows pack 2-per-tick under max_batch=8
            ticks = series("e2lsh_serve_ticks_total{")
            assert sum(ticks.values()) >= 2
            hit = series("e2lsh_serve_deadline_hit_rate{")
            assert hit and all(v == 1.0 for v in hit.values()), \
                f"60s deadlines should all hit: {hit}"
            for p in TICK_PHASES:
                assert metrics[f'e2lsh_serve_tick_phase_ms_count{{phase="{p}"'
                               f',plan="external"}}'] >= 2, p
            assert metrics['e2lsh_serve_queued_ms_count{plan="external"}'] == 4

            # /trace serves the same chrome-trace doc the exporter writes
            doc = json.loads(get("/trace?last=64"))
            names = {e["name"] for e in doc["traceEvents"]
                     if e["ph"] == "X"}
            assert {"serve.tick", "tick.dispatch",
                    "plan.external"} <= names
            assert json.loads(get("/snapshot"))["e2lsh_store_reads_total"]
            assert get("/healthz").strip() == "ok"
    # queue summary and registry agree on the ledger
    s = q.stats_summary()
    assert s["qos"]["deadline_hit_rate"] == 1.0
    assert s["external_store"]["reads"] > 0
