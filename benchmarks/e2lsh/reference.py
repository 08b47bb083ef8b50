"""Plain reference of the E2LSH search the configuration states, written from
the paper (Datar et al.'s p-stable hashing; E2LSHoS Secs. 2.3, 5.1-5.4) and
the configuration's own numbers, independent of the program under test.

Index semantics (stated in the configuration's ``index`` group):

* radius ``R_t = c**t``; function ``j`` of table ``l`` hashes a point to
  ``h = floor((a.x + b*w*R_t) / (w*R_t))``, computed in float64 at build;
* the ``m`` values combine as ``fmix32(sum_j h_j * rm_j mod 2**32)``
  (murmur3's finalizer); the low ``u`` bits address the bucket, the next
  ``fp_bits`` bits are the fingerprint;
* a bucket holds its points in ascending id, in chunks (blocks) of
  ``block_objs``.

Query semantics, for each radius until the query is done:

* hash the query (float32 on the chip: the reference uses float64);
* walk the ``L`` buckets round-robin, chunk ``s`` of every bucket at step
  ``s < max_chain``, reading a chunk only while the query holds fewer than
  ``S`` candidates entering the step; a read chunk counts one block read;
  its entries whose fingerprint equals the query's append, in (table, slot)
  order, up to ``S`` in all;
* a non-empty probed bucket counts one table read; the candidates count
  ``min(S, matches)``;
* candidates merge into the running top ``k`` by exact distance (ids
  deduplicated, ties by the lower id); the query is done once ``k`` results
  lie within ``c*R_t``.

The database side is hashed on the device in float32 at the highest matmul
precision, and every hash whose float32 value lies near a floor() boundary
(within a bound on float32 rounding) is recomputed on the host in float64,
so the result is the float64 build's. ``precision="control"`` computes the
same search one precision lower (float32 database hashes with no repair;
bfloat16 operands for the query hashes and the distances), the step a
later change would be tempted to take.
"""
from __future__ import annotations

import dataclasses

import numpy as np

INVALID = np.int32(2**31 - 1)
_M32 = np.uint64(0xFFFFFFFF)
_EPS32 = 2.0 ** -24
_TOL = 128.0                 # float32 rounding bound, in units of _EPS32


@dataclasses.dataclass
class Answers:
    ids: np.ndarray           # [Q, k] int32, INVALID where unfound
    d2: np.ndarray            # [Q, k] float64 squared distances (inf unfound)
    found: np.ndarray         # [Q] bool
    radii_searched: np.ndarray
    nio_table: np.ndarray
    nio_blocks: np.ndarray
    cands_checked: np.ndarray


def fmix32(h: np.ndarray) -> np.ndarray:
    h = h.astype(np.uint64) & _M32
    h ^= h >> np.uint64(16)
    h = (h * np.uint64(0x85EBCA6B)) & _M32
    h ^= h >> np.uint64(13)
    h = (h * np.uint64(0xC2B2AE35)) & _M32
    h ^= h >> np.uint64(16)
    return h


def combine(hj: np.ndarray, rm: np.ndarray) -> np.ndarray:
    """[..., m] integer hashes and uint32 multipliers -> uint64 holding the
    32-bit compound hash."""
    hu = hj.astype(np.int64).astype(np.uint64) & _M32
    acc = np.sum((hu * rm.astype(np.uint64)) & _M32, axis=-1,
                 dtype=np.uint64) & _M32
    return fmix32(acc)


def split(h: np.ndarray, u: int, fp_bits: int):
    bucket = (h & np.uint64((1 << u) - 1)).astype(np.int64)
    fp = ((h >> np.uint64(u)) & np.uint64((1 << fp_bits) - 1)).astype(np.int64)
    return bucket, fp


def _bf16(x: np.ndarray) -> np.ndarray:
    import jax.numpy as jnp
    return np.asarray(jnp.asarray(x, jnp.float32).astype(jnp.bfloat16)
                      .astype(jnp.float32))


def hash_queries(q: np.ndarray, a_t: np.ndarray, b_t: np.ndarray,
                 rm_t: np.ndarray, wr: float, ix: dict, control: bool):
    """[Q, d] -> bucket, fp [Q, L]."""
    L, m, d = a_t.shape
    if control:
        proj = (_bf16(q) @ _bf16(a_t.reshape(L * m, d)).T).astype(np.float32)
        wr32 = np.float32(wr)
        hj = np.floor((proj.reshape(-1, L, m) + b_t[None] * wr32) / wr32)
    else:
        proj = q.astype(np.float64) @ a_t.reshape(L * m, d).astype(np.float64).T
        hj = np.floor((proj.reshape(-1, L, m)
                       + b_t[None].astype(np.float64) * wr) / wr)
    return split(combine(hj, rm_t[None]), ix["u"], ix["fp_bits"])


def _device_hasher():
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST

    @jax.jit
    def run(x, a, absa, b, rm, wr):
        proj = jnp.dot(x, a.T, precision=hi)                 # [n, L*m]
        mag = jnp.dot(jnp.abs(x), absa.T, precision=hi)
        qv = (proj + b * wr) / wr
        hj = jnp.floor(qv)
        near = jnp.abs(qv - jnp.round(qv))
        tol = _TOL * _EPS32 * (mag / wr + jnp.abs(b) + jnp.abs(qv) + 1.0)
        n = x.shape[0]
        L, m = rm.shape
        hu = hj.astype(jnp.int32).astype(jnp.uint32).reshape(n, L, m)
        acc = jnp.sum(hu * rm[None], axis=-1, dtype=jnp.uint32)
        acc = acc ^ (acc >> 16)
        acc = acc * jnp.uint32(0x85EBCA6B)
        acc = acc ^ (acc >> 13)
        acc = acc * jnp.uint32(0xC2B2AE35)
        acc = acc ^ (acc >> 16)
        flag = jnp.any((near < tol).reshape(n, L, m), axis=-1)
        return acc, flag
    return run


class DatabaseHashes:
    """The database's compound hashes for one radius at a time, with the
    bucket members of any query bucket looked up in ascending id."""

    def __init__(self, db: np.ndarray, family, ix: dict, control: bool,
                 rows: int = 65536):
        import jax.numpy as jnp
        self.db = db
        self.family = family
        self.ix = ix
        self.control = control
        self._run = _device_hasher()
        n = db.shape[0]
        rows = min(rows, n)
        pad = np.zeros((-(-n // rows) * rows - n, db.shape[1]), db.dtype)
        x = np.concatenate([db, pad])             # equal chunks: one program
        self._x = [jnp.asarray(x[s:s + rows]) for s in range(0, n, rows)]
        self._cache: dict = {}
        self.repaired = 0

    def radius(self, t: int):
        """(bucket [n, L], fp [n, L]) of every database point at radius t."""
        if t in self._cache:
            return self._cache[t]
        import jax.numpy as jnp
        a = self.family.a[t]
        L, m, d = a.shape
        wr = float(self.ix["w"]) * float(self.ix["c"]) ** t
        a2 = jnp.asarray(a.reshape(L * m, d))
        absa = jnp.abs(a2)
        b = jnp.asarray(self.family.b[t].reshape(L * m))
        rm = jnp.asarray(self.family.rm[t])
        hs, fl = [], []
        for x in self._x:
            h, f = self._run(x, a2, absa, b, rm, jnp.float32(wr))
            hs.append(np.asarray(h))
            fl.append(np.asarray(f))
        n = self.db.shape[0]
        h = np.concatenate(hs)[:n].astype(np.uint64)
        flag = np.concatenate(fl)[:n]
        if not self.control:
            self._repair(h, flag, t, wr)
        bucket, fp = split(h, self.ix["u"], self.ix["fp_bits"])
        self._cache = {t: (bucket, fp)}
        return bucket, fp

    def _repair(self, h: np.ndarray, flag: np.ndarray, t: int, wr: float):
        """Recompute in float64 every compound hash with a function value
        near a floor() boundary, table by table."""
        self.repaired += int(flag.sum())
        a64 = self.family.a[t].astype(np.float64)           # [L, m, d]
        b64 = self.family.b[t].astype(np.float64)
        rm = self.family.rm[t]
        for l in range(a64.shape[0]):
            i = np.flatnonzero(flag[:, l])
            if i.size:
                proj = self.db[i].astype(np.float64) @ a64[l].T   # [g, m]
                hj = np.floor((proj + b64[l] * wr) / wr)
                h[i, l] = combine(hj, rm[l][None])


def members(keys: np.ndarray, fps: np.ndarray, want: np.ndarray):
    """For one table: for each wanted bucket, its member ids in ascending
    order and their fingerprints."""
    uniq = np.unique(want)
    idx = np.flatnonzero(np.isin(keys, uniq))
    k = keys[idx]
    order = np.argsort(k, kind="stable")
    idx, k = idx[order], k[order]
    lo = np.searchsorted(k, want, "left")
    hi = np.searchsorted(k, want, "right")
    return [(idx[a:b], fps[idx[a:b]]) for a, b in zip(lo, hi)]


def distances(db: np.ndarray, q: np.ndarray, ids: np.ndarray,
              control: bool) -> np.ndarray:
    """Squared distances of db[ids] to q: exact (float64), or the control's
    ||x||^2 - 2<x, q> + ||q||^2 with bfloat16 operands in the dot."""
    x = db[ids]
    if not control:
        diff = x.astype(np.float64) - q.astype(np.float64)[None]
        return np.sum(diff * diff, axis=1)
    xn = np.sum(x * x, axis=1, dtype=np.float32)
    qn = np.float32(np.sum(q * q, dtype=np.float32))
    dot = (_bf16(x) @ _bf16(q[None]).T)[:, 0].astype(np.float32)
    return np.maximum(xn - np.float32(2.0) * dot + qn, 0.0).astype(np.float64)


def search(db: np.ndarray, queries: np.ndarray, family, ix: dict, *,
           precision: str = "reference", hashes: DatabaseHashes = None,
           timings: dict = None) -> Answers:
    """Run the stated search for ``queries`` [Q, d]; ``timings``, if given,
    collects seconds by phase."""
    import time
    tm = {} if timings is None else timings
    for k in ("hash_s", "lookup_s", "walk_s"):
        tm.setdefault(k, 0.0)
    control = precision == "control"
    if precision not in ("reference", "control"):
        raise ValueError(f"unknown precision {precision!r}")
    if hashes is None:
        hashes = DatabaseHashes(db, family, ix, control)
    Q = queries.shape[0]
    k, S, BLK, C = ix["k"], ix["S"], ix["block_objs"], ix["max_chain"]
    r, L = family.a.shape[0], family.a.shape[1]
    best_id = np.full((Q, k), INVALID, np.int64)
    best_d2 = np.full((Q, k), np.inf)
    done = np.zeros(Q, bool)
    radii = np.zeros(Q, np.int64)
    nio_t = np.zeros(Q, np.int64)
    nio_b = np.zeros(Q, np.int64)
    cands = np.zeros(Q, np.int64)
    for t in range(r):
        act = np.flatnonzero(~done)
        if act.size == 0:
            break
        wr = float(ix["w"]) * float(ix["c"]) ** t
        qb, qf = hash_queries(queries[act], family.a[t], family.b[t],
                              family.rm[t], wr, ix, control)
        t0 = time.perf_counter()
        bucket, fp = hashes.radius(t)
        t1 = time.perf_counter()
        mem = [members(bucket[:, l], fp[:, l], qb[:, l]) for l in range(L)]
        t2 = time.perf_counter()
        tm["hash_s"] += t1 - t0
        tm["lookup_s"] += t2 - t1
        thresh = (float(ix["c"]) * float(ix["c"]) ** t) ** 2
        for j, q in enumerate(act):
            chains = [mem[l][j] for l in range(L)]
            cnt = np.asarray([c[0].size for c in chains])
            nonempty = cnt > 0
            count, found_ids, blocks = 0, [], 0
            for step in range(C):
                if count >= S:
                    break
                step_ids = []
                for l in np.flatnonzero(nonempty & (cnt > step * BLK)):
                    blocks += 1
                    ids_c, fps_c = chains[l]
                    sl = slice(step * BLK, (step + 1) * BLK)
                    step_ids.append(ids_c[sl][fps_c[sl] == qf[j, l]])
                got = (np.concatenate(step_ids) if step_ids
                       else np.zeros(0, np.int64))
                found_ids.append(got[:max(0, S - count)])
                count = min(count + got.size, S)
            cand = (np.concatenate(found_ids) if found_ids
                    else np.zeros(0, np.int64))
            nio_t[q] += int(nonempty.sum())
            nio_b[q] += blocks
            cands[q] += count
            radii[q] += 1
            ids = np.unique(np.concatenate(
                [best_id[q][best_id[q] != INVALID], cand]))
            d2 = distances(db, queries[q], ids, control)
            order = np.lexsort((ids, d2))[:k]
            best_id[q] = INVALID
            best_d2[q] = np.inf
            best_id[q, :order.size] = ids[order]
            best_d2[q, :order.size] = d2[order]
            if np.sum(best_d2[q] <= thresh) >= k:
                done[q] = True
        tm["walk_s"] += time.perf_counter() - t2
    tm["repaired"] = hashes.repaired
    return Answers(ids=best_id.astype(np.int32), d2=best_d2, found=done,
                   radii_searched=radii, nio_table=nio_t, nio_blocks=nio_b,
                   cands_checked=cands)
