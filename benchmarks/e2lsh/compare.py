"""The comparison that decides ``correct``: served answers against the plain
reference's, number by number, each beside its limit.

Numbers compared (limits in the configuration's ``compare`` group):

* ``missing`` -- answers due in the window that never came or failed;
* ``mismatch_share`` -- share of the sampled answers that differ from the
  reference: ``found``, ``radii_searched``, ``nio_table``, ``nio_blocks`` or
  ``cands_checked`` unequal, or an id unequal at a rank where the exact
  distances of the two ids differ by more than the float32 rounding bound
  (byte-valued data has many exact ties, which float32 orders freely);
* ``dist_gap`` -- the widest gap between a served squared distance and the
  exact float64 squared distance of the id served with it, in units of that
  float32 rounding bound, over every rank of every sampled answer.
"""
from __future__ import annotations

import numpy as np

from reference import INVALID

NUMBERS = ("missing", "mismatch_share", "dist_gap")
_FIELDS = ("found", "radii_searched", "nio_table", "nio_blocks",
           "cands_checked")


def f32_bound(db: np.ndarray, q: np.ndarray) -> np.ndarray:
    """[Q] worst-case gap between two float32 evaluations of
    ||x||^2 - 2<x, q> + ||q||^2 summed in different orders:
    2 (d + 2) eps (max ||x||^2 + ||q||^2)."""
    d = db.shape[1]
    x2 = float(np.max(np.sum(db.astype(np.float64) ** 2, axis=1)))
    q2 = np.sum(q.astype(np.float64) ** 2, axis=1)
    return 2 * (d + 2) * float(np.finfo(np.float32).eps) * (x2 + q2)


def exact_d2(db: np.ndarray, q: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """[Q, k] float64 squared distances of ids to their queries (inf where
    the id is INVALID)."""
    ok = ids != INVALID
    x = db[np.where(ok, ids, 0)].astype(np.float64)
    d2 = np.sum((x - q[:, None].astype(np.float64)) ** 2, axis=-1)
    return np.where(ok, d2, np.inf)


def compare(db: np.ndarray, queries: np.ndarray, got: dict, want,
            missing: int, limits: dict) -> dict:
    """``got``: served fields of the sampled answers, row-aligned with
    ``queries`` [Q, d] and with ``want`` (reference ``Answers`` for the same
    rows). Returns {number: {"value", "limit"}} and ``correct``."""
    bound = f32_bound(db, queries)[:, None]
    gid = np.asarray(got["ids"]).astype(np.int64)
    gd = np.asarray(got["dists"], np.float64)
    ex_got = exact_d2(db, queries, gid)
    ex_want = exact_d2(db, queries, want.ids.astype(np.int64))
    # served distance against the exact distance of the id served with it
    both_inf = np.isinf(gd) & np.isinf(ex_got)
    gap = np.where(both_inf, 0.0, np.abs(gd * gd - ex_got) / bound)
    gap = np.where(np.isnan(gap), np.inf, gap)
    # ids: equal, or tied within the bound
    tie = np.abs(ex_got - ex_want) / bound
    tie = np.where(np.isinf(ex_got) & np.isinf(ex_want), 0.0, tie)
    tie = np.where(np.isnan(tie), np.inf, tie)
    id_bad = np.any((gid != want.ids) & ~(tie <= 1.0), axis=1)
    field_bad = np.zeros(len(gid), bool)
    for f in _FIELDS:
        field_bad |= (np.asarray(got[f]).astype(np.int64)
                      != np.asarray(getattr(want, f)).astype(np.int64))
    bad = id_bad | field_bad
    values = dict(missing=float(missing),
                  mismatch_share=float(bad.mean()) if bad.size else 0.0,
                  dist_gap=float(gap.max()) if gap.size else 0.0)
    out = {k: dict(value=values[k], limit=float(limits[k])) for k in NUMBERS}
    out["correct"] = all(v["value"] <= v["limit"] for k, v in out.items()
                         if k in NUMBERS)
    out["rows_compared"] = int(len(gid))
    out["rows_differing"] = int(bad.sum())
    out["ids_tied"] = int(np.sum(np.any(gid != want.ids, axis=1) & ~id_bad))
    return out


def as_fields(ans) -> dict:
    """A reference ``Answers`` in the served fields' shape (the control puts
    the reference, computed one precision lower, in the program's place)."""
    return dict(ids=ans.ids, dists=np.sqrt(ans.d2), found=ans.found,
                radii_searched=ans.radii_searched, nio_table=ans.nio_table,
                nio_blocks=ans.nio_blocks, cands_checked=ans.cands_checked)
