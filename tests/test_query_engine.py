"""Plan parity: every SearchEngine execution plan vs the unrolled oracle.

The contract (docs/query_engine.md): on the same backend, `plan="fused"`
(precomputed all-radius hashes + blockified kernel-dispatch probes +
while_loop early exit, reading the block store `build_index` emitted
natively) must match `plan="oracle"` (the unrolled reference) BIT-FOR-BIT on
ids, dists, found, radii_searched and both I/O counters — including under
the `s_cap` and `block_objs` override knobs. `plan="host"` (the pre-fusion
per-radius host loop) must match as well: early exit only skips radii no
query would use.

The seed's free functions were deprecated wrappers for exactly one PR and
are now deleted; test_legacy_wrapper_surface_is_gone pins the removal
(`make deprecation-lane` asserts the same at import time).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import IndexArrays, SearchEngine
from repro.core.query import QueryConfig

_EXACT_FIELDS = ("ids", "found", "radii_searched", "nio_table", "nio_blocks",
                 "cands_checked")


def _assert_identical(ref, fus, *, probe_sizes=False):
    for name in _EXACT_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(ref, name)), np.asarray(getattr(fus, name)),
            err_msg=f"field {name} diverged from the oracle")
    # bit-identical floats too (same backend, same op order by contract)
    np.testing.assert_array_equal(np.asarray(ref.dists), np.asarray(fus.dists))
    np.testing.assert_array_equal(np.asarray(ref.nio), np.asarray(fus.nio))
    if probe_sizes:
        np.testing.assert_array_equal(np.asarray(ref.probe_sizes),
                                      np.asarray(fus.probe_sizes))


@pytest.fixture(scope="module")
def engine(built_index):
    return SearchEngine(built_index)


@pytest.mark.parametrize("k", [1, 8])
def test_fused_plan_matches_oracle(engine, clustered_data, k):
    q = clustered_data["queries"]
    ref = engine.query(q, plan="oracle", k=k)
    fus = engine.query(q, plan="fused", k=k)
    _assert_identical(ref, fus)


def test_default_plan_is_fused(engine, clustered_data):
    """SearchEngine.query with no plan routes to the production fused plan."""
    q = clustered_data["queries"][:16]
    a = engine.query(q, k=3)
    b = engine.query(q, plan="fused", k=3)
    _assert_identical(a, b)
    assert engine.default_plan == "fused"
    assert engine.plans == ("fused", "host", "oracle")


def test_host_plan_matches_fused(engine, clustered_data):
    """The pre-fusion per-radius host loop agrees with the engine. Its
    per-radius jit programs fuse float ops differently than the one-dispatch
    graph, so distances carry ulp-level noise (same contract the seed's
    test_adaptive_matches_full documented) — ids can swap only on near-ties;
    the algorithmic outputs (found/radii/I/O) stay exact."""
    q = clustered_data["queries"][:24]
    host = engine.query(q, plan="host", k=3)
    fus = engine.query(q, plan="fused", k=3)
    assert np.mean(np.asarray(host.ids) == np.asarray(fus.ids)) > 0.95
    np.testing.assert_allclose(np.asarray(host.dists), np.asarray(fus.dists),
                               rtol=1e-3, atol=1e-4)
    for name in ("found", "radii_searched", "nio_table", "nio_blocks",
                 "cands_checked"):
        np.testing.assert_array_equal(
            np.asarray(getattr(host, name)), np.asarray(getattr(fus, name)),
            err_msg=f"field {name} diverged")


@pytest.mark.parametrize("s_cap", [8, None])
def test_fused_matches_oracle_with_s_cap(engine, built_index, clustered_data, s_cap):
    q = clustered_data["queries"][:24]
    s = s_cap if s_cap is not None else built_index.params.S
    ref = engine.query(q, plan="oracle", k=1, s_cap=s)
    fus = engine.query(q, plan="fused", k=1, s_cap=s)
    _assert_identical(ref, fus)


def test_fused_matches_oracle_with_block_objs(engine, clustered_data):
    """The narrower-gather-chunk timing knob re-blockifies and stays exact."""
    q = clustered_data["queries"][:24]
    ref = engine.query(q, plan="oracle", k=1, block_objs=16)
    fus = engine.query(q, plan="fused", k=1, block_objs=16)
    _assert_identical(ref, fus)


def test_fused_probe_sizes_match_oracle(engine, clustered_data):
    q = clustered_data["queries"][:16]
    ref = engine.query(q, plan="oracle", k=1, collect_probe_sizes=True)
    fus = engine.query(q, plan="fused", k=1, collect_probe_sizes=True)
    _assert_identical(ref, fus, probe_sizes=True)


def test_unknown_plan_rejected(engine, clustered_data):
    with pytest.raises(ValueError, match="unknown plan"):
        engine.query(clustered_data["queries"][:2], plan="warp")
    with pytest.raises(ValueError, match="unknown plan"):
        engine.make_plan_fn(plan="warp")


def test_fused_plan_is_one_jitted_dispatch(engine, clustered_data):
    """The fused plan lowers to ONE jitted computation over the typed
    IndexArrays pytree: tracing its jit wrapper once covers the whole radius
    schedule (no per-radius retrace), and it jits from inside an outer jit
    (serving composes it)."""
    cfg = engine.config(k=1)
    ix = engine.arrays(cfg.block_objs)
    from repro.core.query import _fused_jit
    q = jnp.asarray(clustered_data["queries"][:8])
    lowered = _fused_jit.lower(ix, q, cfg)
    text = lowered.as_text()
    assert "while" in text  # radius loop is a device-side while_loop
    out = _fused_jit(ix, q, cfg)
    assert out.ids.shape == (8, 1)


def test_make_plan_fn_closures(engine, clustered_data):
    q = jnp.asarray(clustered_data["queries"][:8])
    cfg_f, fn_f = engine.make_plan_fn(plan="fused", k=2)
    cfg_o, fn_o = engine.make_plan_fn(plan="oracle", k=2)
    assert cfg_f == cfg_o
    _assert_identical(fn_o(q), fn_f(q))


def test_native_blockified_arrays_memoized(engine, built_index):
    """`build_index` emits the blockified layout natively — the engine's base
    arrays ARE the index arrays (no repack), and the `block_objs` knob
    re-blockifies once per size."""
    base = engine.arrays()
    assert base is built_index.index.arrays
    assert base.block_objs == built_index.params.block_objs
    narrow = engine.arrays(16)
    assert narrow.block_objs == 16
    assert engine.arrays(16) is narrow                  # memoized
    assert engine.arrays(base.block_objs) is base
    # the repack reads the CSR derived view: same entries, new rows
    assert narrow.ids_blocks.shape[1] != base.ids_blocks.shape[1] or \
        narrow.ids_blocks.shape[0] != base.ids_blocks.shape[0]


def test_index_arrays_is_a_pytree(engine):
    """IndexArrays crosses jit boundaries as a pytree: array leaves flatten,
    layout metadata rides the treedef (static -> part of jit cache keys)."""
    ix = engine.arrays()
    leaves, treedef = jax.tree_util.tree_flatten(ix)
    assert len(leaves) == len(IndexArrays.array_fields())
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert rebuilt.block_objs == ix.block_objs
    assert rebuilt.lane_pad == ix.lane_pad
    # a re-blockified index is a DIFFERENT treedef: no stale jit-cache hits
    _, treedef16 = jax.tree_util.tree_flatten(ix.with_block_objs(16))
    assert treedef16 != treedef


def test_queryconfig_replace_constructor_path():
    cfg = QueryConfig(L=8, m=4, u=10, fp_bits=8, w=4.0, c=2.0,
                      radii=(1.0, 2.0), S=96, block_objs=99)
    assert cfg.sbuf == 128
    capped = cfg.replace(s_cap=300)
    assert capped.S == 300 and capped.sbuf == 384  # re-derived, not stale
    narrow = cfg.replace(block_objs=16)
    assert narrow.block_objs == 16
    assert narrow.max_chain == -(-cfg.S // 16) + 1
    both = cfg.replace(s_cap=32, block_objs=16)
    assert both.S == 32 and both.max_chain == 3 and both.sbuf == 128
    # frozen dataclass: the original is untouched
    assert cfg.S == 96 and cfg.block_objs == 99


# --------------------------------------------------------------------------
# Legacy wrapper surface: deprecated for exactly one PR (PR 2), deleted now.
# --------------------------------------------------------------------------

def test_legacy_wrapper_surface_is_gone(built_index):
    """ROADMAP schedule: the one-PR migration shims must be deleted, not
    warning. New code has exactly one entry point: SearchEngine."""
    import repro.core as core
    import repro.core.distributed as dist
    import repro.core.query as query
    from repro.core import E2LSHoS, SearchEngine
    from repro.core.index import E2LSHIndex, IndexArrays

    for name in ("query_batch", "query_batch_fused", "query_batch_adaptive",
                 "query_batch_adaptive_host", "ensure_fused_arrays",
                 "make_query_fn"):
        assert not hasattr(core, name), f"repro.core.{name} resurfaced"
        assert not hasattr(query, name), f"core.query.{name} resurfaced"
        assert name not in core.__all__ and name not in query.__all__
    assert not hasattr(dist, "sharded_query")
    assert "sharded_query" not in dist.__all__
    for cls, name in ((IndexArrays, "from_dict"), (IndexArrays, "as_dict"),
                      (E2LSHIndex, "as_arrays"), (E2LSHoS, "arrays"),
                      (E2LSHoS, "fused_arrays"),
                      (SearchEngine, "last_external_stats")):
        assert not hasattr(cls, name), f"{cls.__name__}.{name} resurfaced"
    # the typed field (NOT the deleted dict accessor) is still the index API
    assert isinstance(built_index.index.arrays, IndexArrays)
    with pytest.raises(TypeError):
        built_index.query(np.zeros((2, built_index.params.d)), engine="oracle")


def test_masked_query_rows_are_inert(engine, clustered_data):
    """The serving-queue seam: a padded batch with a valid mask returns
    bit-identical rows for the real queries and INVALID/inf/zero-I/O rows
    for the masked padding (every plan)."""
    q = clustered_data["queries"][:9]
    pad = np.concatenate([q, np.full((7, q.shape[1]), 50.0, np.float32)])
    valid = np.arange(16) < 9
    for plan in ("fused", "oracle"):
        ref = engine.query(q, plan=plan, k=2)
        out = engine.query(pad, plan=plan, k=2, valid=valid)
        _assert_identical(ref, out.slice_rows(0, 9))
        tail = out.slice_rows(9, 16)
        assert (np.asarray(tail.ids) == np.int32(2**31 - 1)).all()
        assert np.isinf(np.asarray(tail.dists)).all()
        assert not np.asarray(tail.found).any()
        assert (np.asarray(tail.nio) == 0).all()


# (Q, G block rows, W slots per row, S, SBUF): the HBM cell's fused probe
# (128 queries, 2 chain steps x 32 tables, 128 lanes, S=64 in a 128-lane
# buffer) and an off-TPU shape whose buffer is exactly S wide (_fused_sbuf)
_COMPACT_SHAPES = {"cell": (128, 64, 128, 64, 128), "sbuf_eq_s": (16, 12, 8, 16, 16)}


@pytest.mark.parametrize("density", ["none", "sparse", "about_s", "dense"])
@pytest.mark.parametrize("shape", sorted(_COMPACT_SHAPES))
def test_compact_candidates_matches_scatter_append(shape, density):
    """The fused plan's gather compaction fills the candidate buffer and
    count element for element as the oracle's scatter append does from an
    empty buffer: the first S matches per row in flat (row, slot) order."""
    from repro.core.query import _INVALID, _append_candidates, _compact_candidates

    Q, G, W, S, SBUF = _COMPACT_SHAPES[shape]
    N = G * W
    rng = np.random.default_rng([Q, N, len(density)])
    p = {"none": 0.0, "sparse": 0.001, "about_s": S / N, "dense": 0.5}[density]
    ok = rng.random((Q, N)) < p
    if density != "none":
        ok[:4] = False                                  # row 0 keeps none
        ok[1, rng.choice(N, S, replace=False)] = True   # exactly S
        ok[2, rng.choice(N, 2 * S, replace=False)] = True   # more than S
        ok[3, -1] = True                                # only the last slot
    ids = rng.integers(0, 2**30, (Q, N), dtype=np.int32)
    want_id, want_n = jax.jit(_append_candidates, static_argnums=(4, 5))(
        jnp.full((Q, SBUF), _INVALID, jnp.int32), jnp.zeros((Q,), jnp.int32),
        ids, ok, S, SBUF)
    got_id, got_n = jax.jit(_compact_candidates, static_argnums=(2, 3))(
        ids.reshape(Q, G, W), ok.reshape(Q, G, W), S, SBUF)
    assert got_id.shape == (Q, SBUF)
    np.testing.assert_array_equal(np.asarray(got_id), np.asarray(want_id))
    np.testing.assert_array_equal(np.asarray(got_n), np.asarray(want_n))
