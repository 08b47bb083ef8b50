"""Seeded inputs of a cell: the vector set, the query pool, the hash family.

The generator is a frozen copy of the SIFT-shaped mixture of the program's
``data/synthetic.py`` (``_gen_points`` and ``nn_scale``), so a change to the
program cannot move the data the benchmark measures on:

* base and held-out points come from one call, so they share the mixture's
  cluster centres; byte-valued data is clipped to the 1st..99th percentile,
  stretched to 0..255 and rounded;
* the query pool is ``perturbed_share`` jittered base points (the standard
  ANN benchmark set-up) and the rest held-out points;
* every coordinate is divided by the scale that puts the median exact 1-NN
  distance of the first ``scale_queries`` pool queries at ``nn_target``
  (the radius schedule starts at R = 1).

The hash family is the index's random parameters, the counterpart of a
model's weights: drawn here from the seed and handed to the program's build,
so the plain reference can use the same draw without taking anything the
program made.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class CellData:
    db: np.ndarray        # [n, d] float32, scaled
    pool: np.ndarray      # [P, d] float32, scaled
    scale: float


@dataclasses.dataclass
class Family:
    a: np.ndarray         # [r, L, m, d] float32
    b: np.ndarray         # [r, L, m] float32
    rm: np.ndarray        # [r, L, m] uint32, odd


def _rng(seed: int, stream: int) -> np.random.Generator:
    # SeedSequence takes any non-negative integer, so seeds past 2**32 work
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def _gen_points(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    d = spec["d"]
    centers = rng.normal(0.0, 1.0, size=(spec["clusters"], d))
    assign = rng.integers(0, spec["clusters"], size=n)
    x = centers[assign] + spec["spread"] * rng.normal(size=(n, d))
    if spec["dtype"] == "byte":
        lo, hi = np.percentile(x, [1, 99])
        x = np.clip((x - lo) / max(hi - lo, 1e-9) * 255.0, 0, 255)
        x = np.round(x)
    return x.astype(np.float32)


def nn1_dists(db: np.ndarray, queries: np.ndarray, block: int = 65536
              ) -> np.ndarray:
    """Exact float64 1-NN distance of each query over ``db``."""
    q = queries.astype(np.float64)
    q2 = np.sum(q * q, axis=1)
    best = np.full(q.shape[0], np.inf)
    for s in range(0, db.shape[0], block):
        x = db[s:s + block].astype(np.float64)
        d2 = np.sum(x * x, axis=1)[None, :] - 2.0 * q @ x.T + q2[:, None]
        best = np.minimum(best, d2.min(axis=1))
    return np.sqrt(np.maximum(best, 0.0))


def make_data(spec: dict, seed: int) -> CellData:
    """The configuration's vector set and query pool for ``seed``."""
    rng = _rng(seed, 0)
    n, pool = spec["n"], spec["pool"]
    n_pert = int(round(spec["perturbed_share"] * pool))
    n_held = pool - n_pert
    pts = _gen_points(spec, n + n_held, rng)
    db = pts[:n]
    idx = rng.choice(n, n_pert, replace=False)
    jitter = 0.35 * np.std(db, axis=0, keepdims=True)
    q_pert = db[idx] + (rng.normal(size=(n_pert, spec["d"])).astype(np.float32)
                        * jitter * 0.3)
    queries = np.concatenate([q_pert, pts[n:]], axis=0).astype(np.float32)
    order = rng.permutation(pool)          # held-out queries spread over the pool
    queries = queries[order]
    nn = nn1_dists(db, queries[:spec["scale_queries"]])
    s = max(float(np.median(nn)) / spec["nn_target"], 1e-12)
    return CellData(db=db / np.float32(s), pool=queries / np.float32(s),
                    scale=s)


def make_family(shape: dict, d: int, seed: int) -> Family:
    """p-stable Gaussian projections, uniform shifts in [0, 1) and odd
    32-bit multipliers for ``r`` radii x ``L`` tables x ``m`` functions."""
    rng = _rng(seed, 1)
    r, L, m = shape["r"], shape["L"], shape["m"]
    a = rng.standard_normal((r, L, m, d), dtype=np.float32)
    b = rng.random((r, L, m), dtype=np.float32)
    rm = (rng.integers(1, 2**31 - 1, size=(r, L, m), dtype=np.uint32)
          << np.uint32(1)) | np.uint32(1)
    return Family(a=a, b=b, rm=rm)
