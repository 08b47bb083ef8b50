"""The external-memory storage subsystem (repro.storage): on-disk format
round-trips, corruption rejection, and the plan="external" parity contract.

Parity contract (docs/storage.md): on a spilled copy of an index,
``plan="external"`` — any backend — must match ``plan="fused"`` on every
``QueryResult`` field, and the block reads the store actually served
(measured N_io) must equal the runtime counters exactly. Under the forced
interpret kernel lane (`make storage-lane`) float distances are held to the
kernel lane's allclose contract (interpreter matmul reassociation); on the
default backend they are bit-exact.
"""
import numpy as np
import pytest

from repro.core import E2LSHoS, SearchEngine
from repro.core.index import E2LSHIndex, IndexArrays
from repro.kernels.dispatch import force_pallas_env
from repro import storage as st

_EXACT_FIELDS = ("ids", "found", "radii_searched", "nio_table", "nio_blocks",
                 "cands_checked")
_BACKENDS = ("mem", "mmap", "aio", "uring")


def _require_uring(path, *, ring: bool = False) -> None:
    """Skip (with the probe's reason) where the real uring store can't run —
    the capability probe is the SAME gate make_store uses, so this skip
    fires exactly when production would fall back to aio. ``ring=True``
    asks for io_uring itself, not the O_DIRECT pread path."""
    caps = st.capabilities(path)
    if not caps["io_uring" if ring else "uring_store"]:
        pytest.skip(f"io_uring unavailable: {caps['io_uring_reason']}")


@pytest.fixture
def no_io_uring(spilled, monkeypatch):
    """io_uring switched off, as on a kernel without it; skips (with the
    probe's reason) where the filesystem refuses O_DIRECT, since the uring
    store then has no path left."""
    import repro.storage.uring as uring_mod

    align, reason = st.probe_o_direct(str(spilled))
    if not align:
        pytest.skip(f"O_DIRECT unavailable: {reason}")
    monkeypatch.setattr(uring_mod, "probe_io_uring",
                        lambda: (False, "forced off by test"))


def _assert_matches(ref, out, *, probe_sizes=False):
    for name in _EXACT_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(ref, name)), np.asarray(getattr(out, name)),
            err_msg=f"external plan diverged from fused on {name}")
    if force_pallas_env():   # interpret-lane float contract (kernel lane)
        np.testing.assert_allclose(np.asarray(ref.dists),
                                   np.asarray(out.dists),
                                   rtol=1e-4, atol=1e-5)
    else:
        np.testing.assert_array_equal(np.asarray(ref.dists),
                                      np.asarray(out.dists))
    if probe_sizes:
        np.testing.assert_array_equal(np.asarray(ref.probe_sizes),
                                      np.asarray(out.probe_sizes))


# A dedicated SMALL index (not the big session fixture): this file also
# runs under the forced interpret kernel path (`make storage-lane`), where
# every distinct batch shape recompiles the interpret kernels — the lane
# stays fast only if the index and query set stay small (same sizing as
# test_force_pallas_lane's lane_index).
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
    path = tmp_path_factory.mktemp("spill") / "index.e2l"
    idx.index.spill(path)
    return path


@pytest.fixture(scope="module")
def hard_queries(storage_index):
    """Mostly-easy queries plus one far outlier that walks several radii —
    exercises the multi-rung loop, prefetch, and early exit."""
    _, qs = storage_index
    q = qs[:15]
    far = np.full((1, q.shape[1]), 40.0, dtype=np.float32)
    return np.concatenate([q, far])


# --------------------------------------------------------------------------
# On-disk format
# --------------------------------------------------------------------------

def test_spill_load_roundtrip_bit_exact(storage_index, spilled):
    """Every IndexArrays leaf and the layout metadata survive
    spill -> load_arrays bit-for-bit (crc-verified on the way in)."""
    idx, _ = storage_index
    loaded = st.load_arrays(spilled)
    for name in IndexArrays.array_fields():
        np.testing.assert_array_equal(
            np.asarray(getattr(loaded, name)),
            np.asarray(getattr(idx.index.arrays, name)),
            err_msg=f"leaf {name} changed across spill/load")
    assert loaded.block_objs == idx.index.arrays.block_objs
    assert loaded.lane_pad == idx.index.arrays.lane_pad
    hdr = st.verify_file(spilled)      # full-crc pass, blocks included
    assert hdr.version == st.FORMAT_VERSION
    assert hdr.params is not None and hdr.stats is not None
    # sections are page-aligned (the mmap/aio backends rely on it)
    for sec in hdr.sections.values():
        assert sec["offset"] % hdr.page_size == 0


def test_header_carries_params_for_serving(storage_index, spilled):
    idx, _ = storage_index
    hdr = st.read_header(spilled)
    assert hdr.block_objs == idx.params.block_objs
    assert tuple(hdr.params["radii"]) == idx.params.radii
    assert hdr.nb == int(idx.index.arrays.ids_blocks.shape[0])


def test_rejects_wrong_magic(tmp_path):
    bad = tmp_path / "not_an_index.bin"
    bad.write_bytes(b"NOTANIDX" + b"\x00" * 64)
    with pytest.raises(st.StorageFormatError, match="magic"):
        st.read_header(bad)


def test_rejects_future_version(spilled, tmp_path):
    data = bytearray(spilled.read_bytes())
    data[8] = 0xFE                       # bump the version field
    bad = tmp_path / "future.e2l"
    bad.write_bytes(bytes(data))
    with pytest.raises(st.StorageFormatError, match="version"):
        st.read_header(bad)


def test_rejects_corrupted_header(spilled, tmp_path):
    data = bytearray(spilled.read_bytes())
    data[40] ^= 0xFF                     # flip a byte inside the header JSON
    bad = tmp_path / "corrupt.e2l"
    bad.write_bytes(bytes(data))
    with pytest.raises(st.StorageFormatError, match="corrupted header"):
        st.read_header(bad)


def test_rejects_corrupted_section(spilled, tmp_path):
    hdr = st.read_header(spilled)
    data = bytearray(spilled.read_bytes())
    data[hdr.sections["db"]["offset"]] ^= 0xFF
    bad = tmp_path / "corrupt_section.e2l"
    bad.write_bytes(bytes(data))
    with pytest.raises(st.StorageFormatError, match="crc32"):
        st.load_arrays(bad)


def test_spill_without_params_is_not_servable(storage_index, tmp_path):
    path = tmp_path / "bare.e2l"
    storage_index[0].index.arrays.spill(path)      # no params attached
    st.load_arrays(path)                           # round-trip still fine
    with pytest.raises(st.StorageFormatError, match="LSHParams"):
        st.load_external(path)


# --------------------------------------------------------------------------
# plan="external" parity
# --------------------------------------------------------------------------

@pytest.mark.parametrize("backend", _BACKENDS)
def test_external_plan_matches_fused(storage_index, spilled, hard_queries,
                                     backend):
    """The acceptance contract: external == fused on every field, any
    backend, measured N_io == runtime counters."""
    if backend == "uring":
        _require_uring(str(spilled))
    ref = SearchEngine(storage_index[0]).query(hard_queries, plan="fused",
                                               k=3, collect_probe_sizes=True)
    with st.load_external(spilled, backend=backend, qd=8) as ext:
        engine = SearchEngine(ext)
        assert engine.plans == ("external",)
        assert engine.default_plan == "external"
        out = engine.query(hard_queries, k=3, collect_probe_sizes=True)
        _assert_matches(ref, out, probe_sizes=True)
        ps = engine.external.last_plan_stats
        # under a forced lane (REPRO_STORE_BACKEND) the env wins; a forced
        # uring lane without io_uring resolves to the documented fallback
        expected = st.store_backend_env() or backend
        if expected == "uring" and not st.capabilities(str(spilled))["uring_store"]:
            expected = "aio"
        assert ps.backend == expected
        assert ps.measured_nio_blocks == ps.nio_blocks_counted
        assert ps.measured_nio_blocks == int(np.asarray(out.nio_blocks).sum())
        assert len(ps.rungs) >= 1
        # the per-rung overlap records partition the total block fetches
        assert sum(r.blocks_fetched for r in ps.rungs) == ps.nio_blocks_counted


@pytest.mark.parametrize("backend", ("mem", "aio"))
def test_external_plan_s_cap_knob(storage_index, spilled, hard_queries,
                                  backend):
    ref = SearchEngine(storage_index[0]).query(hard_queries, plan="fused",
                                               k=1, s_cap=8)
    with st.load_external(spilled, backend=backend, qd=4) as ext:
        out = SearchEngine(ext).query(hard_queries, k=1, s_cap=8)
        _assert_matches(ref, out)


def test_external_single_query_padding(storage_index, spilled, hard_queries):
    """Q=1 routes through the same masked Q=2 gemm path as every plan."""
    ref = SearchEngine(storage_index[0]).query(hard_queries[:1],
                                               plan="fused", k=2)
    with st.load_external(spilled, backend="mem") as ext:
        out = SearchEngine(ext).query(hard_queries[:1], k=2)
        _assert_matches(ref, out)


def test_external_masked_rows_inert(storage_index, spilled):
    """The serving mask contract holds from storage: masked rows fetch no
    blocks, count zero I/O, and leave the real rows bit-identical."""
    idx, qs = storage_index
    q = qs[:9]
    pad = np.concatenate([q, np.full((7, q.shape[1]), 1e6, np.float32)])
    valid = np.arange(16) < 9
    ref = SearchEngine(idx).query(q, plan="fused", k=2)
    with st.load_external(spilled, backend="aio", qd=4) as ext:
        engine = SearchEngine(ext)
        out = engine.query(pad, k=2, valid=valid)
        _assert_matches(ref, out.slice_rows(0, 9))
        tail = out.slice_rows(9, 16)
        assert (np.asarray(tail.ids) == np.int32(2**31 - 1)).all()
        assert not np.asarray(tail.found).any()
        assert (np.asarray(tail.nio) == 0).all()


def test_external_rejects_foreign_plans_and_knobs(spilled):
    with st.load_external(spilled, backend="mem") as ext:
        engine = SearchEngine(ext)
        with pytest.raises(ValueError, match="unknown plan"):
            engine.query(np.zeros((2, ext.db.shape[1]), np.float32),
                         plan="fused")
        with pytest.raises(ValueError, match="re-spill"):
            engine.query(np.zeros((2, ext.db.shape[1]), np.float32),
                         block_objs=16)
        with pytest.raises(ValueError, match="on disk"):
            engine.arrays()


def test_unknown_backend_rejected(spilled):
    with pytest.raises(ValueError, match="unknown block-store backend"):
        st.load_external(spilled, backend="warp")


# --------------------------------------------------------------------------
# The aio page cache
# --------------------------------------------------------------------------

def test_aio_cache_hits_on_repeat_queries(storage_index, spilled):
    """Re-running the same batch serves the second pass mostly from the
    clock cache; the logical read ledger (measured N_io) is unchanged."""
    q = storage_index[1][:16]
    with st.load_external(spilled, backend="aio", qd=8) as ext:
        engine = SearchEngine(ext)
        first = engine.query(q, k=1)
        nio1 = engine.external.last_plan_stats.measured_nio_blocks
        hits1 = engine.external.last_plan_stats.io.cache_hits
        second = engine.query(q, k=1)
        ps2 = engine.external.last_plan_stats
        assert ps2.measured_nio_blocks == nio1   # logical N_io is identical
        assert ps2.io.cache_hits > hits1         # but served from the cache
        assert ps2.cache_hit_rate > 0.9
        np.testing.assert_array_equal(np.asarray(first.ids),
                                      np.asarray(second.ids))


def test_aio_tiny_cache_still_correct(storage_index, spilled, hard_queries):
    """A cache too small to hold the working set must only cost device
    reads, never correctness."""
    ref = SearchEngine(storage_index[0]).query(hard_queries, plan="fused",
                                               k=1)
    with st.load_external(spilled, backend="aio", qd=2, cache_rows=4) as ext:
        out = SearchEngine(ext).query(hard_queries, k=1)
        _assert_matches(ref, out)


def test_store_counters_ledger(spilled):
    """reads = device_reads + cache_hits, always (the measured-N_io ledger
    the Eq. 6/7 validation is built on)."""
    with st.load_external(spilled, backend="aio", qd=4) as ext:
        engine = SearchEngine(ext)
        q = np.asarray(ext.db[:8]) * 1.01
        engine.query(q, k=1)
        engine.query(np.asarray(ext.db[4:12]) * 1.01, k=1)
        s = ext.store.stats
        assert s.reads == s.device_reads + s.cache_hits
        assert s.read_batches >= 2


# --------------------------------------------------------------------------
# Serving: BatchQueue over plan="external"
# --------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ("aio", "uring"))
def test_batch_queue_over_external_plan(storage_index, spilled, backend):
    """Queued ragged requests through the external plan are bit-exact with
    direct external dispatch per request — the queue's parity contract
    holds when the block rows come from disk (through the thread-pool
    emulation and through the real io_uring engine alike)."""
    from repro.serving import BatchQueue

    if backend == "uring":
        _require_uring(str(spilled))
    q = storage_index[1]
    with st.load_external(spilled, backend=backend, qd=8) as ext:
        engine = SearchEngine(ext)
        queue = BatchQueue(engine, k=2, ladder=(4, 8), tick_us=50.0)
        assert queue.plan == "external"
        _, direct = engine.make_plan_fn(plan="external", k=2)
        reqs = [q[:1], q[1:6], q[6:17], q[3:7]]   # incl. a >max_batch spill
        tickets = [queue.submit(r) for r in reqs]
        queue.drain()
        for t, r in zip(tickets, reqs):
            got, want = t.result(0), direct(r)
            for name in _EXACT_FIELDS + ("dists",):
                np.testing.assert_array_equal(
                    np.asarray(getattr(got, name)),
                    np.asarray(getattr(want, name)),
                    err_msg=f"queued external {name} diverged")
        s = queue.stats_summary()
        assert s["dispatches"] == s["ticks"]
        assert "external_store" in s
        assert s["external_store"]["reads"] > 0


# --------------------------------------------------------------------------
# The uring async engine: capability gate, fallback, env override, O_DIRECT
# --------------------------------------------------------------------------

def test_capabilities_report_shape(spilled):
    """The runtime probe reports every key the lanes and docs rely on, and
    `uring_store` holds where io_uring or O_DIRECT works (either gives the
    store a queue of reads that bypass the page cache)."""
    caps = st.capabilities(str(spilled))
    for key in ("io_uring", "io_uring_reason", "o_direct_align",
                "o_direct_reason", "uring_store", "kernel"):
        assert key in caps, f"capabilities() lost key {key!r}"
    assert caps["uring_store"] == (caps["io_uring"]
                                   or caps["o_direct_align"] > 0)
    assert caps["o_direct_align"] in (0, 512, 4096)
    ok, reason = st.probe_io_uring()
    assert ok == caps["io_uring"] and isinstance(reason, str)


def test_aligned_extent_covers_and_aligns():
    """aligned_extent returns an aligned covering extent for any offset,
    and the payload slice lands inside it."""
    for align in (512, 4096):
        for offset in (0, 1, 511, 512, 4096 + 64, 123457):
            for nbytes in (1, 64, 512, 833):
                astart, alen, inner = st.aligned_extent(offset, nbytes, align)
                assert astart % align == 0 and alen % align == 0
                assert astart <= offset
                assert astart + alen >= offset + nbytes
                assert inner == offset - astart and 0 <= inner < align


def test_uring_fallback_to_aio_and_strict(spilled, monkeypatch):
    """Where neither io_uring nor O_DIRECT can run, make_store('uring')
    degrades to the aio backend (tagged with the reason) unless strict —
    the graceful-fallback contract that keeps every uring-requesting caller
    working anywhere."""
    import repro.storage.uring as uring_mod

    monkeypatch.setattr(uring_mod, "probe_io_uring",
                        lambda: (False, "forced off by test"))
    monkeypatch.setattr(uring_mod, "probe_o_direct",
                        lambda path: (0, "O_DIRECT refused by test"))
    with pytest.warns(RuntimeWarning, match="falling back"):
        ext = st.load_external(spilled, backend="uring", qd=4)
    with ext:
        assert ext.store.name == "aio"
        assert ext.store.fallback_from == "uring"
        assert "forced off" in ext.store.fallback_reason
        out = SearchEngine(ext).query(np.asarray(ext.db[:4]) * 1.01, k=1)
        assert np.asarray(out.found).any()
    with pytest.raises(st.UringUnavailable):
        st.load_external(spilled, backend="uring", strict=True)


def test_store_backend_env_override(spilled, monkeypatch):
    """REPRO_STORE_BACKEND pins every make_store call to one backend (the
    REPRO_FORCE_PALLAS lane idiom); unknown values fail loudly."""
    monkeypatch.setenv(st.STORE_BACKEND_ENV, "mem")
    assert st.store_backend_env() == "mem"
    with st.load_external(spilled, backend="aio") as ext:
        assert ext.store.name == "mem"       # the override won
    monkeypatch.setenv(st.STORE_BACKEND_ENV, "warp9")
    with pytest.raises(ValueError, match="REPRO_STORE_BACKEND"):
        st.load_external(spilled, backend="aio")
    monkeypatch.delenv(st.STORE_BACKEND_ENV)
    assert st.store_backend_env() is None


def test_uring_buffered_mode_parity(storage_index, spilled, hard_queries):
    """direct=False keeps the ring but reads through the page cache — the
    submission discipline alone must not change any result."""
    _require_uring(str(spilled), ring=True)
    ref = SearchEngine(storage_index[0]).query(hard_queries, plan="fused",
                                               k=2)
    with st.load_external(spilled, backend="uring", qd=8,
                          direct=False) as ext:
        assert ext.store.name == "uring" and not ext.store.o_direct
        out = SearchEngine(ext).query(hard_queries, k=2)
        _assert_matches(ref, out)


def test_uring_wave_larger_than_ring(storage_index, spilled):
    """A miss batch bigger than the ring splits into waves internally —
    reads stay correct and the ledger still balances."""
    _require_uring(str(spilled))
    idx, _ = storage_index
    nb = int(idx.index.arrays.ids_blocks.shape[0])
    rows = np.arange(1, min(nb, 130), dtype=np.int64)   # >> qd=4 ring
    with st.load_external(spilled, backend="uring", qd=4) as ext:
        ids, fps = ext.store.read_rows(rows)
        want_ids = np.asarray(idx.index.arrays.ids_blocks)[rows]
        want_fps = np.asarray(idx.index.arrays.fps_blocks)[rows]
        np.testing.assert_array_equal(ids, want_ids)
        np.testing.assert_array_equal(fps, want_fps)
        s = ext.store.stats
        assert s.reads == s.device_reads + s.cache_hits == rows.size


def test_uring_without_io_uring_reads_o_direct_from_a_pread_pool(
        storage_index, spilled, hard_queries, no_io_uring):
    """No io_uring, O_DIRECT works: the strict uring store keeps its
    contract on the pread path — O_DIRECT reads, qd workers — with rows
    bit-identical to mem, the same ledger, and plan="external" over it
    bit-exact with plan="fused"."""
    hdr = st.read_header(spilled)
    nb = hdr.nb
    rows = np.random.default_rng(5).permutation(np.arange(1, nb))[:97]
    with st.make_store("uring", spilled, hdr, qd=8, strict=True) as store, \
            st.make_store("mem", spilled, hdr) as mem:
        assert store.name == "uring" and store.submit == "pread"
        assert store.o_direct and store.qd == 8
        assert getattr(store, "fallback_from", None) is None
        got, want = store.read_rows(rows), mem.read_rows(rows)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(a, b)
        ledger = {f: getattr(store.stats, f) for f in
                  ("reads", "device_reads", "cache_hits", "prefetch_reads",
                   "read_batches")}
        assert ledger == {f: getattr(mem.stats, f) for f in ledger}
    ref = SearchEngine(storage_index[0]).query(hard_queries, plan="fused",
                                               k=3, collect_probe_sizes=True)
    with st.load_external(spilled, backend="uring", qd=8,
                          strict=True) as ext:
        assert ext.store.submit == "pread"
        out = SearchEngine(ext).query(hard_queries, k=3,
                                      collect_probe_sizes=True)
        _assert_matches(ref, out, probe_sizes=True)
        ps = ext.last_plan_stats
        assert ps.measured_nio_blocks == int(np.asarray(out.nio_blocks).sum())


@pytest.mark.parametrize("submit", ("io_uring", "pread"))
def test_read_us_counts_device_reads_not_cache_hits(spilled, request,
                                                    submit):
    """read_us grows with every read issued to the file, shows on the
    store.read span and in the registry, and stays put over a batch the
    cache serves whole."""
    from repro import telemetry

    if submit == "pread":
        request.getfixturevalue("no_io_uring")
    else:
        _require_uring(str(spilled), ring=True)
    hdr = st.read_header(spilled)
    rows = np.arange(1, min(hdr.nb, 65), dtype=np.int64)
    tr = telemetry.enable(sampling=1.0)
    tr.clear()
    try:
        with st.make_store("uring", spilled, hdr, qd=4, strict=True) as s:
            assert s.submit == submit
            s.read_rows(rows)
            cold = s.stats.snapshot()
            assert cold.device_reads == rows.size and cold.read_us > 0
            s.read_rows(rows)
            warm = s.stats.since(cold)
            assert warm.cache_hits == rows.size and warm.device_reads == 0
            assert warm.read_us == 0
            snap = telemetry.snapshot()["e2lsh_store_read_us_total"]
            assert snap["type"] == "counter"
            assert sum(x["value"] for x in snap["samples"]
                       if x["labels"]["backend"] == "uring") >= cold.read_us
        spans = [sp for sp in tr.spans() if sp.name == "store.read"]
        assert [sp.attrs["read_us"] for sp in spans] == [cold.read_us, 0]
    finally:
        telemetry.disable()
        tr.clear()


# --------------------------------------------------------------------------
# StoreStats invariants under concurrency
# --------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ("aio", "uring", "pread"))
def test_store_stats_ledger_under_concurrency(storage_index, spilled,
                                              backend, request):
    """prefetch() racing read_rows() across threads: the ledger must keep
    `reads == device_reads + cache_hits` exactly, `reads` must equal the
    demand rows requested (prefetch_reads NEVER leak into logical N_io),
    and every returned row must be correct. ``pread`` is the uring store
    without io_uring: its qd workers share the landing slots."""
    import threading

    if backend == "uring":
        _require_uring(str(spilled))
    if backend == "pread":
        request.getfixturevalue("no_io_uring")
        backend = "uring"
    idx, _ = storage_index
    blocks = np.stack([np.asarray(idx.index.arrays.ids_blocks),
                       np.asarray(idx.index.arrays.fps_blocks)], axis=1)
    nb = blocks.shape[0]
    rng = np.random.default_rng(11)
    n_workers, n_rounds, batch = 4, 12, 48
    demand = rng.integers(1, nb, size=(n_workers, n_rounds, batch))
    spec_rows = rng.integers(1, nb, size=(n_rounds, batch))

    with st.load_external(spilled, backend=backend, qd=8,
                          cache_rows=max(8, nb // 4)) as ext:
        store = ext.store
        errors = []

        def reader(w):
            try:
                for r in range(n_rounds):
                    rows = demand[w, r]
                    ids, fps = store.read_rows(rows)
                    if not (np.array_equal(ids, blocks[rows, 0])
                            and np.array_equal(fps, blocks[rows, 1])):
                        errors.append(f"worker {w} round {r}: wrong data")
                        return
            except Exception as e:          # pragma: no cover - diagnostic
                errors.append(f"worker {w}: {e!r}")

        def prefetcher():
            try:
                for r in range(n_rounds):
                    store.prefetch(spec_rows[r])
            except Exception as e:          # pragma: no cover - diagnostic
                errors.append(f"prefetcher: {e!r}")

        threads = ([threading.Thread(target=reader, args=(w,))
                    for w in range(n_workers)]
                   + [threading.Thread(target=prefetcher)])
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # drain in-flight prefetch landings before reading the ledger
        store._pool.shutdown(wait=True)
        assert not errors, errors

        s = store.stats
        assert s.reads == s.device_reads + s.cache_hits, (
            f"ledger broke under the race: reads={s.reads} != "
            f"device={s.device_reads} + hits={s.cache_hits}")
        assert s.reads == demand.size, (
            "logical N_io drifted from the demand rows requested "
            f"({s.reads} != {demand.size}) — prefetch leaked into reads")
        assert s.prefetch_reads <= spec_rows.size
        assert s.read_us > 0
