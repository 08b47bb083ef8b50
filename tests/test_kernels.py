"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (blockify_entries, bucket_probe, bucket_probe_ref,
                           l2_distance, l2_distance_gathered,
                           l2_distance_gathered_ref, l2_distance_ref, lsh_hash,
                           lsh_hash_all_radii, lsh_hash_all_radii_ref,
                           lsh_hash_ref)

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("n,d,L,m", [
    (1, 4, 1, 1), (100, 32, 8, 6), (257, 100, 16, 20), (64, 420, 25, 14),
    (128, 128, 32, 8),
])
def test_lsh_hash_matches_ref(n, d, L, m):
    x = RNG.normal(size=(n, d)).astype(np.float32)
    a = RNG.normal(size=(L, m, d)).astype(np.float32)
    b = RNG.uniform(size=(L, m)).astype(np.float32)
    rm = ((RNG.integers(1, 2**31, size=(L, m)).astype(np.uint32) << 1) | 1).astype(np.int32)
    kw = dict(w_r=4.0, u=14, fp_bits=12)
    bk, fp = lsh_hash(jnp.asarray(x), jnp.asarray(a), jnp.asarray(b),
                      jnp.asarray(rm), interpret=True, force_pallas=True, **kw)
    bk_r, fp_r = lsh_hash_ref(jnp.asarray(x), jnp.asarray(a), jnp.asarray(b),
                              jnp.asarray(rm), **kw)
    np.testing.assert_array_equal(np.asarray(bk), np.asarray(bk_r))
    np.testing.assert_array_equal(np.asarray(fp), np.asarray(fp_r))


@pytest.mark.parametrize("w_r", [0.5, 1.0, 8.0])
def test_lsh_hash_radius_sweep(w_r):
    x = RNG.normal(size=(96, 64)).astype(np.float32)
    a = RNG.normal(size=(4, 5, 64)).astype(np.float32)
    b = RNG.uniform(size=(4, 5)).astype(np.float32)
    rm = ((RNG.integers(1, 2**31, size=(4, 5)).astype(np.uint32) << 1) | 1).astype(np.int32)
    bk, fp = lsh_hash(jnp.asarray(x), jnp.asarray(a), jnp.asarray(b),
                      jnp.asarray(rm), w_r=w_r, u=10, fp_bits=16,
                      interpret=True, force_pallas=True)
    bk_r, fp_r = lsh_hash_ref(jnp.asarray(x), jnp.asarray(a), jnp.asarray(b),
                              jnp.asarray(rm), w_r=w_r, u=10, fp_bits=16)
    np.testing.assert_array_equal(np.asarray(bk), np.asarray(bk_r))


@pytest.mark.parametrize("r,L,m,d,radii", [
    (1, 4, 3, 16, (1.0,)),
    (3, 8, 6, 32, (1.0, 2.0, 4.0)),
    (5, 5, 4, 100, (1.0, 2.0, 4.0, 8.0, 16.0)),
])
def test_lsh_hash_all_radii_matches_ref(r, L, m, d, radii):
    """One fused kernel launch over the whole radius schedule == stacked
    per-radius oracle (the fused query engine's Step 1 contract)."""
    x = RNG.normal(size=(70, d)).astype(np.float32)
    a = RNG.normal(size=(r, L, m, d)).astype(np.float32)
    b = RNG.uniform(size=(r, L, m)).astype(np.float32)
    rm = ((RNG.integers(1, 2**31, size=(r, L, m)).astype(np.uint32) << 1) | 1).astype(np.int32)
    kw = dict(w=4.0, radii=radii, u=12, fp_bits=10)
    bk, fp = lsh_hash_all_radii(jnp.asarray(x), jnp.asarray(a), jnp.asarray(b),
                                jnp.asarray(rm), interpret=True,
                                force_pallas=True, **kw)
    bk_r, fp_r = lsh_hash_all_radii_ref(jnp.asarray(x), jnp.asarray(a),
                                        jnp.asarray(b), jnp.asarray(rm), **kw)
    assert bk.shape == (r, 70, L)
    np.testing.assert_array_equal(np.asarray(bk), np.asarray(bk_r))
    np.testing.assert_array_equal(np.asarray(fp), np.asarray(fp_r))


@pytest.mark.parametrize("nq,nc,d,dtype", [
    (1, 1, 8, np.float32), (10, 50, 32, np.float32),
    (130, 200, 100, np.float32), (64, 64, 960, np.float32),
    (33, 190, 128, np.float16),
])
def test_l2_distance_matches_ref(nq, nc, d, dtype):
    q = RNG.normal(size=(nq, d)).astype(dtype)
    x = RNG.normal(size=(nc, d)).astype(dtype)
    got = np.asarray(l2_distance(jnp.asarray(q), jnp.asarray(x),
                                 interpret=True, force_pallas=True))
    want = np.asarray(l2_distance_ref(jnp.asarray(q), jnp.asarray(x)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    # and vs an independent numpy computation
    ref2 = ((q.astype(np.float64)[:, None] - x.astype(np.float64)[None]) ** 2).sum(-1)
    np.testing.assert_allclose(got, ref2, rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("q,s,d", [(1, 1, 8), (5, 17, 24), (48, 128, 100)])
def test_l2_distance_gathered_matches_ref(q, s, d):
    """Per-query candidate epilogue (the fused engine's Step 3 form)."""
    qs = RNG.normal(size=(q, d)).astype(np.float32)
    coords = RNG.normal(size=(q, s, d)).astype(np.float32)
    xn2 = (coords.astype(np.float64) ** 2).sum(-1).astype(np.float32)
    qn2 = (qs.astype(np.float64) ** 2).sum(-1).astype(np.float32)
    got = np.asarray(l2_distance_gathered(
        jnp.asarray(qs), jnp.asarray(coords), jnp.asarray(xn2),
        jnp.asarray(qn2), interpret=True, force_pallas=True))
    want = np.asarray(l2_distance_gathered_ref(
        jnp.asarray(qs), jnp.asarray(coords), jnp.asarray(xn2), jnp.asarray(qn2)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    ref2 = ((qs.astype(np.float64)[:, None] - coords.astype(np.float64)) ** 2).sum(-1)
    np.testing.assert_allclose(got, ref2, rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("block_objs", [8, 16, 99])
def test_bucket_probe_matches_ref(block_objs):
    n_entries = 600
    eid = RNG.integers(0, 5000, size=n_entries).astype(np.int32)
    efp = RNG.integers(0, 64, size=n_entries).astype(np.uint16)
    toff = np.array([0, 37, 200, -1, 595])
    tcnt = np.array([37, 163, 395, 0, 5])
    ids_b, fps_b, head, NB = blockify_entries(eid, efp, toff, tcnt, block_objs)
    G = 16
    rows = RNG.integers(0, NB, size=G).astype(np.int32)
    qfp = RNG.integers(0, 64, size=G).astype(np.int32)
    got = np.asarray(bucket_probe(jnp.asarray(rows), jnp.asarray(qfp),
                                  ids_b, fps_b, interpret=True, use_pallas=True))
    want = np.asarray(bucket_probe_ref(jnp.asarray(rows), jnp.asarray(qfp),
                                       ids_b, fps_b))
    np.testing.assert_array_equal(got, want)


def test_blockify_preserves_entries():
    eid = np.arange(300, dtype=np.int32)
    efp = (np.arange(300) % 7).astype(np.uint16)
    toff = np.array([0, 100])
    tcnt = np.array([100, 200])
    ids_b, fps_b, head, NB = blockify_entries(eid, efp, toff, tcnt, 16)
    # walk bucket 1 (200 entries from offset 100 -> 13 rows)
    hr = int(np.asarray(head)[1])
    rows = np.asarray(ids_b)[hr:hr + 13].reshape(-1)
    got = rows[rows != np.int32(2**31 - 1)]
    np.testing.assert_array_equal(np.sort(got), np.arange(100, 300))


def test_kernels_used_by_core_match_production_hash(built_index):
    """lsh_hash kernel output == the production hashing path on real index
    params (same family arrays)."""
    fam = built_index.index.family
    db = np.asarray(built_index.index.db)[:64]
    t = 0
    from repro.core.hashing import hash_points_radius
    bk_prod, fp_prod = hash_points_radius(fam, jnp.asarray(db), t, 1.0)
    bk_k, fp_k = lsh_hash(jnp.asarray(db), fam.a[t], fam.b[t],
                          fam.rm[t].astype(jnp.int32),
                          w_r=fam.w * 1.0, u=fam.u, fp_bits=fam.fp_bits,
                          interpret=True, force_pallas=True)
    np.testing.assert_array_equal(np.asarray(bk_prod), np.asarray(bk_k))
    np.testing.assert_array_equal(np.asarray(fp_prod),
                                  np.asarray(fp_k).astype(np.uint32))


def test_lsh_hash_vmem_overflow_runs_reference_and_counts(monkeypatch):
    """A projection block past the scoped-VMEM budget runs the jnp reference
    (same answers) and shows on the reference-path counter, per op."""
    from repro.kernels.lsh_hash import ops

    monkeypatch.setattr(ops, "use_pallas_default", lambda: True)
    r, L, m, d = 2, 64, 16, 1024       # a [1024, 16 * 128] block: 8 MiB
    assert ops._vmem_bytes(d, r * L, m, 256) > ops._SCOPED_VMEM_BYTES
    x = RNG.normal(size=(8, d)).astype(np.float32)
    a = RNG.normal(size=(r, L, m, d)).astype(np.float32)
    b = RNG.uniform(size=(r, L, m)).astype(np.float32)
    rm = ((RNG.integers(1, 2**31, size=(r, L, m)).astype(np.uint32) << 1)
          | 1).astype(np.int32)
    kw = dict(w=4.0, radii=(1.0, 2.0), u=12, fp_bits=10)
    before = ops.REFERENCE_CALLS.labels(op="lsh_hash_all_radii").value()
    got = lsh_hash_all_radii(*map(jnp.asarray, (x, a, b, rm)), **kw)
    want = lsh_hash_all_radii_ref(*map(jnp.asarray, (x, a, b, rm)), **kw)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    assert ops.REFERENCE_CALLS.labels(op="lsh_hash_all_radii").value() \
        == before + 1
