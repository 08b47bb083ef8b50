#!/usr/bin/env python3
"""Chip smoke test: the E2LSH query path on a TPU, through its entry points.

    python chip_smoke.py              # one chip (default; one device even on
                                      # a host with four)
    python chip_smoke.py --chips 4    # the sharded plan over four chips

Default phase: SIFT-shaped data (the paper's Table 1: d=128, byte-valued) at
n=300,000 with 256 held-out queries, indexed with the serve CLI's gamma=0.8,
max_L=32 — about 9 GB of index arrays in HBM — and served at k=10 through
``SearchEngine.query(plan="fused")``, ``plan="oracle"``, a ``BatchQueue`` of
ragged requests, and ``plan="external"`` over a spill on local disk behind
the best block store ``repro.storage.capabilities()`` allows (opened
strict: no silent fallback). Checked on the chip:

* the compiled fused step holds all three Pallas kernels as
  ``tpu_custom_call``; the external plan's setup program holds ``lsh_hash``
  and its fold program ``l2_distance_gathered``; no kernel fell back to its
  jnp reference;
* fused == oracle == queued == external on ``found``, ``radii_searched``,
  ``nio_*`` and ``cands_checked``; ``dists`` within the f32 rounding bound;
  ``ids`` equal, except where a plan with other f32 distance arithmetic
  orders candidates whose exact distances tie within that bound (printed
  per comparison);
* recall@10 against the exact ground truth, no more than 0.01 below the
  oracle plan run on the host CPU over the same index;
* query-side hashes of 4,096 database points agree with the float64 build
  at least as often as the CPU's;
* each kernel against its jnp reference on the chip.

``--chips 4``: SIFT-shaped data at n=1,000,000 (the paper's SIFT1M), about
25 GB of index sharded over four chips with ``build_sharded_index`` and
``SearchEngine(sharded, mesh=...)``; ``plan="sharded"`` is checked against
the sharded ``plan="oracle"`` on the same mesh, for its kernels, and for
recall@10 no more than 0.01 below the sharded oracle run on four virtual
devices of the host CPU over the same shards. Nothing else runs in that
phase.

Seconds are printed for information only. The script exits non-zero, and
prints no result line, when JAX finds no TPU, when the repository's sources
are missing, or when any phase fails. On success the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import pathlib
import re
import shutil
import sys
import tempfile
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
# the grading references (recall floor, hash agreement) run on the host CPU
# next to the chip; a platform list without "cpu" would hide that backend
_platforms = os.environ.get("JAX_PLATFORMS")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import cache_events, enable_compile_cache  # noqa: E402

K = 10
D = 128                         # SIFT's dimension
N_QUERIES = 256
GAMMA, MAX_L = 0.8, 32          # the serve CLI's defaults
HASH_POINTS = 4096
RECALL_SLACK = 0.01
INT_FIELDS = ("ids", "found", "radii_searched", "nio_table", "nio_blocks",
              "cands_checked")
KERNELS = ("lsh_hash", "bucket_probe", "l2_distance_gathered")


class SmokeFailure(AssertionError):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    log(f"[ok] {what}")


@contextlib.contextmanager
def timed(what: str):
    t0 = time.perf_counter()
    yield
    log(f"[time] {what}: {time.perf_counter() - t0:.1f} s (information only)")


@contextlib.contextmanager
def capture_calls(module, name: str, seen: dict):
    """Record the first call's arguments of the jitted ``module.name`` (the
    program a plan actually dispatched), so it can be lowered again."""
    fn = getattr(module, name)

    def spy(*args, **kwargs):
        seen.setdefault(name, (fn, args, kwargs))
        return fn(*args, **kwargs)

    setattr(module, name, spy)
    try:
        yield
    finally:
        setattr(module, name, fn)


def kernels_of(seen: dict, name: str) -> set:
    """Names of the Pallas kernels the captured program compiles to as
    tpu_custom_call (each pallas_call carries its kernel's name)."""
    fn, args, kwargs = seen[name]
    names = set()
    for line in fn.lower(*args, **kwargs).compile().as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = re.search(r"%([\w.\-]+) = ", line)
            if m:
                names.add(re.sub(r"(\.\d+)+$", "", m.group(1)))
    return names


def fallback_calls() -> int:
    from repro.kernels.lsh_hash.ops import REFERENCE_CALLS
    return int(sum(s["value"] for s in REFERENCE_CALLS.samples()))


def f32_bound(ds) -> np.ndarray:
    """[Q, 1] worst-case gap between two f32 evaluations of
    d^2 = ||x||^2 - 2<x, q> + ||q||^2 that sum in different orders:
    2(D+2) eps (||x||^2 + ||q||^2), with ||x||^2 the database maximum."""
    x2 = float(np.max(np.sum(ds.db.astype(np.float64) ** 2, axis=1)))
    q2 = np.sum(ds.queries.astype(np.float64) ** 2, axis=1)
    return (2 * (D + 2) * np.finfo(np.float32).eps * (x2 + q2))[:, None]


def exact_d2(ds, ids) -> np.ndarray:
    """float64 squared distances of result ids to their queries (inf for
    unfound slots)."""
    ids = np.asarray(ids)
    ok = ids != np.int32(2**31 - 1)
    x = ds.db[np.where(ok, ids, 0)].astype(np.float64)
    d2 = np.sum((x - ds.queries[:, None].astype(np.float64)) ** 2, axis=-1)
    return np.where(ok, d2, np.inf)


def compare(got, want, what: str, ds) -> None:
    """The parity contract: integer fields equal, dists allclose.

    Two plans that evaluate distances with different f32 arithmetic (the
    l2_distance_gathered kernel vs the oracle's einsum) may order
    candidates whose exact distances tie within the f32 bound differently;
    byte-valued data has many such ties. So `ids` may differ only at ranks
    where the exact float64 distances of both plans' ids agree within that
    bound; every other integer field must be equal, and the dists agree
    within the bound."""
    n = len(np.asarray(want.found))
    rows_of = lambda f: np.nonzero(np.any(
        np.asarray(getattr(got, f)).reshape(n, -1)
        != np.asarray(getattr(want, f)).reshape(n, -1), axis=1))[0]
    diff = {f: rows_of(f) for f in INT_FIELDS}
    for f, rows in diff.items():
        if rows.size:
            log(f"[diff] {what}: {f} differs on {rows.size} queries "
                f"(first rows {rows[:8].tolist()})")
    others = [f for f in INT_FIELDS if f != "ids" and diff[f].size]
    check(not others, f"{what}: {', '.join(INT_FIELDS[1:])} equal")
    bound = f32_bound(ds)
    gd = np.asarray(got.dists, np.float64)
    wd = np.asarray(want.dists, np.float64)
    fin = np.isfinite(wd)
    err = np.where(fin, np.abs(gd**2 - wd**2), 0.0)
    worst = float(np.max(err / bound))
    check(np.array_equal(np.isfinite(gd), fin) and worst <= 1.0,
          f"{what}: dists allclose (max |d^2 diff| {float(err.max()):.3g}, "
          f"{worst:.3f} of the f32 bound; bit-equal "
          f"{np.array_equal(gd, wd)})")
    rows = diff["ids"]
    if not rows.size:
        check(True, f"{what}: ids equal")
        return
    gap = np.abs(exact_d2(ds, got.ids) - exact_d2(ds, want.ids))[rows]
    gap = np.where(np.isnan(gap), 0.0, gap)      # inf - inf: both unfound
    tied = float(np.max(gap / bound[rows]))
    check(tied <= 1.0,
          f"{what}: ids equal on {n - rows.size}/{n} queries; the other "
          f"{rows.size} differ only among candidates whose exact distances "
          f"tie within the f32 bound ({tied:.3f} of it)")


def recall_at_k(ids, gt_ids, k: int = K) -> float:
    ids = np.asarray(ids)[:, :k]
    return float(np.mean([len(set(a.tolist()) & set(b[:k].tolist())) / k
                          for a, b in zip(ids, gt_ids)]))


def report_quality(res, ds, what: str) -> float:
    from repro.core import overall_ratio
    rec = recall_at_k(res.ids, ds.gt_ids)
    ratio = overall_ratio(np.asarray(res.dists), ds.gt_dists[:, :K])
    log(f"[quality] {what}: recall@{K}={rec:.4f} overall_ratio={ratio:.4f} "
        f"found={float(np.mean(np.asarray(res.found))):.4f} "
        f"nio/query={float(np.mean(np.asarray(res.nio))):.1f}")
    return rec


def hash_agreement(family, params, pts, hash_fn, device) -> tuple:
    """Share of (radius, point, table) compound hashes whose bucket AND
    fingerprint equal the float64 build's, for `hash_fn` run on `device`."""
    from repro.core.hashing import hash_points_radius_deterministic
    want_b, want_f = zip(*(hash_points_radius_deterministic(family, pts, t, R)
                           for t, R in enumerate(params.radii)))
    put = lambda v: jax.device_put(np.asarray(v), device)
    bk, fp = hash_fn(put(pts), put(family.a), put(family.b), put(family.rm),
                     w=params.w, radii=tuple(params.radii), u=params.u,
                     fp_bits=params.fp_bits)
    same = ((np.asarray(bk) == np.stack(want_b))
            & (np.asarray(fp).astype(np.uint32) == np.stack(want_f)))
    return float(same.mean()), int(same.size - same.sum())


def hbm_line(devices) -> str:
    parts = []
    for d in devices:
        st = d.memory_stats() or {}
        parts.append(f"{d.id}:{st.get('bytes_in_use', 0) / 1e9:.3f}"
                     f"/{st.get('bytes_limit', 0) / 1e9:.3f}")
    return "device:in_use/limit GB " + " ".join(parts)


def array_bytes(arrays) -> int:
    from repro.core.index import IndexArrays
    total = 0
    for name in IndexArrays.array_fields():
        v = getattr(arrays, name)
        nb = int(np.prod(v.shape)) * np.dtype(v.dtype).itemsize
        total += nb
        log(f"[index] {name:12s} {str(tuple(v.shape)):24s} {v.dtype}: "
            f"{nb / 1e9:.3f} GB")
    log(f"[index] total {total / 1e9:.3f} GB")
    return total


def run_one_chip(n: int, phases: list) -> None:
    """The default phase on the default device (see module docstring)."""
    import repro.core.query as query_mod
    import repro.storage.external as ext_mod
    from repro.core import E2LSHoS, SearchEngine
    from repro.data import make_dataset
    from repro.kernels.lsh_hash import lsh_hash_all_radii, lsh_hash_all_radii_ref
    from repro.launch.serve import _ragged_requests
    from repro.serving import BatchQueue
    from repro.storage import capabilities, load_external

    dev = jax.devices()[0]
    with timed(f"dataset sift n={n}"):
        ds = make_dataset("sift", n=n, n_queries=N_QUERIES, seed=0)
    with timed("index build (host) + placement"):
        idx = E2LSHoS.build(ds.db, gamma=GAMMA, max_L=MAX_L, seed=0)
    p = idx.params
    log(f"[index] n={p.n} d={p.d} r={p.r} L={p.L} m={p.m} S={p.S} u={p.u} "
        f"block_objs={p.block_objs}")
    index_bytes = array_bytes(idx.index.arrays)
    log(f"[hbm] after build: {hbm_line([dev])}")
    engine = SearchEngine(idx)
    qs = ds.queries

    seen: dict = {}
    res = {}

    def fused():
        with capture_calls(query_mod, "_fused_jit", seen):
            with timed("fused first call (compile + run)"):
                res["fused"] = engine.query(qs, plan="fused", k=K)
                jax.block_until_ready(res["fused"].ids)
        with timed("fused second call"):
            jax.block_until_ready(engine.query(qs, plan="fused", k=K).ids)
        found = kernels_of(seen, "_fused_jit")
        log(f"[kernels] fused step tpu_custom_call: {sorted(found)}")
        check(set(KERNELS) <= found,
              "fused step compiles all three kernels as tpu_custom_call")
        res["recall"] = report_quality(res["fused"], ds, "fused")
    phases.append(("fused", fused))

    def oracle():
        with timed("oracle (compile + run)"):
            res["oracle"] = engine.query(qs, plan="oracle", k=K)
        compare(res["fused"], res["oracle"], "fused vs oracle", ds)
    phases.append(("oracle", oracle))

    def queued():
        requests = _ragged_requests(qs, max_batch=128, seed=0)
        queue = BatchQueue(engine, plan="fused", k=K, ladder=(8, 32, 128),
                           tick_us=200.0)
        with timed(f"queue: {len(requests)} ragged requests"):
            with queue:
                tickets = [queue.submit(r) for r in requests]
                parts = [t.result(timeout=600) for t in tickets]
        s = queue.stats_summary()
        log(f"[queue] {s['ticks']} ticks, {s['dispatches']} dispatches, "
            f"occupancy {s['occupancy_mean']:.2f}")
        from repro.core.query import QueryResult
        compare(QueryResult.concat_rows(parts), res["fused"],
                "queued vs direct fused", ds)
    phases.append(("queue", queued))

    def external():
        caps = capabilities(str(ROOT))
        backend = "uring" if caps["uring_store"] else "aio"
        if caps["io_uring"]:
            why = "io_uring works here"
        elif caps["uring_store"]:
            why = f"no io_uring; O_DIRECT reads at {caps['o_direct_align']} B"
        else:
            why = f"io_uring unavailable: {caps['io_uring_reason']}"
        log(f"[external] capabilities {json.dumps(caps)}")
        log(f"[external] backend={backend} ({why}); opened strict")
        with tempfile.TemporaryDirectory(dir=ROOT,
                                         prefix=".smoke_spill_") as tmp:
            free = shutil.disk_usage(tmp).free
            need = index_bytes
            log(f"[external] spill dir on local disk, {free / 1e9:.1f} GB "
                f"free, index {need / 1e9:.1f} GB")
            check(free > 1.2 * need, "local disk holds the spill")
            path = pathlib.Path(tmp) / "index.e2l"
            with timed("spill"):
                idx.index.spill(path)
            with load_external(path, backend=backend, strict=True) as ext:
                check(ext.store.name == backend and
                      getattr(ext.store, "fallback_from", None) is None,
                      f"store backend is {backend} (no fallback)")
                eng = SearchEngine(ext)
                with capture_calls(ext_mod, "_external_setup_jit", seen), \
                        capture_calls(ext_mod, "_external_fold_jit", seen):
                    with timed("external plan (compile + run)"):
                        res["external"] = eng.query(qs, plan="external", k=K)
                ps = ext.last_plan_stats
                log(f"[external] measured N_io={ps.measured_nio_blocks} "
                    f"counted={ps.nio_blocks_counted} rungs={len(ps.rungs)}")
        setup = kernels_of(seen, "_external_setup_jit")
        fold = kernels_of(seen, "_external_fold_jit")
        log(f"[kernels] external setup {sorted(setup)}, fold {sorted(fold)}")
        check("lsh_hash" in setup and "l2_distance_gathered" in fold,
              "external setup/fold compile lsh_hash / l2_distance_gathered")
        compare(res["external"], res["fused"], "external vs fused", ds)
    phases.append(("external", external))

    def kernel_refs():
        # each kernel against its jnp reference, both compiled for the chip
        from repro.kernels import (bucket_probe, bucket_probe_ref,
                                   l2_distance_gathered,
                                   l2_distance_gathered_ref)
        ix, fam = idx.index.arrays, idx.index.family
        pts = jax.device_put(ds.db[:HASH_POINTS], dev)
        kw = dict(w=p.w, radii=tuple(p.radii), u=p.u, fp_bits=p.fp_bits)
        kb, kf = lsh_hash_all_radii(pts, fam.a, fam.b, fam.rm, **kw)
        rb, rf = lsh_hash_all_radii_ref(pts, fam.a, fam.b, fam.rm, **kw)
        n_hash = int(np.sum((np.asarray(kb) != np.asarray(rb))
                            | (np.asarray(kf) != np.asarray(rf))))
        log(f"[kernel] lsh_hash vs reference on chip: {n_hash} of "
            f"{np.asarray(kb).size} compound hashes differ")
        rows = np.arange(1, min(int(ix.ids_blocks.shape[0]), 1 << 16),
                         dtype=np.int32)
        qfp = np.asarray(ix.fps_blocks[1:1 + rows.size, 0])
        got = bucket_probe(rows, qfp, ix.ids_blocks, ix.fps_blocks)
        want = bucket_probe_ref(rows, qfp, ix.ids_blocks, ix.fps_blocks)
        check(np.array_equal(np.asarray(got), np.asarray(want)),
              f"bucket_probe == reference on chip ({rows.size} rows)")
        cand = ds.gt_ids[:, :128]
        coords = jax.device_put(ds.db[cand], dev)
        xn2 = jax.device_put(np.asarray(ix.db_norm2)[cand], dev)
        q = jax.device_put(qs, dev)
        qn2 = jax.numpy.sum(q * q, axis=-1)
        kd = np.asarray(l2_distance_gathered(q, coords, xn2, qn2), np.float64)
        rd = np.asarray(l2_distance_gathered_ref(q, coords, xn2, qn2),
                        np.float64)
        rel = float(np.max(np.abs(kd - rd) / f32_bound(ds)))
        log(f"[kernel] l2_distance_gathered vs reference on chip: "
            f"max |diff| {float(np.max(np.abs(kd - rd))):.3g} "
            f"({rel:.3f} of the f32 bound), bit-equal "
            f"{np.array_equal(kd, rd)}")
        check(rel <= 1.0, "l2_distance_gathered within the f32 bound")
    phases.append(("kernels vs references", kernel_refs))

    def hashes():
        pts = ds.db[:HASH_POINTS]
        fam = idx.index.family
        tpu, tpu_bad = hash_agreement(fam, p, pts, lsh_hash_all_radii, dev)
        cpu_dev = jax.devices("cpu")[0]
        cpu, cpu_bad = hash_agreement(fam, p, pts, lsh_hash_all_radii_ref,
                                      cpu_dev)
        log(f"[hash] agreement with the float64 build over {HASH_POINTS} "
            f"points: tpu kernel {tpu:.7f} ({tpu_bad} differ), "
            f"host cpu {cpu:.7f} ({cpu_bad} differ)")
        check(tpu_bad <= cpu_bad,
              f"tpu hash agreement no lower than on cpu ({tpu_bad} <= "
              f"{cpu_bad} differ)")
    phases.append(("hash agreement", hashes))

    def cpu_reference():
        # grading reference only: the oracle plan over the same index on the
        # host CPU (what the CPU test lane runs), for the recall floor
        cpu_dev = jax.devices("cpu")[0]
        with timed("index copy to host cpu + oracle on cpu"):
            host = dataclasses.replace(
                idx.index, arrays=jax.device_put(idx.index.arrays, cpu_dev))
            ref = SearchEngine(host).query(jax.device_put(qs, cpu_dev),
                                           plan="oracle", k=K)
            ref = jax.tree.map(np.asarray, ref)
        cpu_recall = report_quality(ref, ds, "oracle on host cpu")
        check(res["recall"] >= cpu_recall - RECALL_SLACK,
              f"tpu recall {res['recall']:.4f} >= cpu recall "
              f"{cpu_recall:.4f} - {RECALL_SLACK}")
    phases.append(("cpu reference", cpu_reference))

    def fallbacks():
        n_ref = fallback_calls()
        log(f"[kernels] lsh_hash reference-path calls: {n_ref}")
        check(n_ref == 0, "no kernel fell back to its jnp reference")
        log(f"[hbm] end: {hbm_line([dev])}, peak "
            f"{(dev.memory_stats() or {}).get('peak_bytes_in_use', 0) / 1e9:.3f} GB")
    phases.append(("fallbacks", fallbacks))


def run_sharded(n: int, chips: int, phases: list) -> None:
    """The --chips 4 phase: plan="sharded" vs the sharded oracle."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import SearchEngine
    from repro.core.distributed import build_sharded_index
    from repro.data import make_dataset

    devs = jax.devices()[:chips]
    res = {}

    def build():
        with timed(f"dataset sift n={n}"):
            res["ds"] = make_dataset("sift", n=n, n_queries=N_QUERIES, seed=0)
        with timed(f"sharded build over {chips} shards (host)"):
            res["sharded"] = sh = build_sharded_index(
                res["ds"].db, chips, gamma=GAMMA, max_L=MAX_L, seed=0)
        p = sh.params
        log(f"[index] n={p.n} d={p.d} r={p.r} L={p.L} m={p.m} S={p.S} "
            f"shards={sh.num_shards}")
        total = array_bytes(sh.arrays)
        log(f"[index] {total / chips / 1e9:.3f} GB per shard")
        mesh = Mesh(np.array(devs), ("shard",))
        with timed("placement, each shard straight to its device"):
            res["engine"] = SearchEngine(sh, mesh=mesh)
            jax.block_until_ready(res["engine"].arrays().ids_blocks)
        blocks = res["engine"].arrays().ids_blocks
        placed = sorted(s.device.id for s in blocks.addressable_shards)
        check(placed == sorted(d.id for d in devs)
              and all(s.data.shape[0] == 1 for s in blocks.addressable_shards),
              f"one shard of the block store per device {placed}")
        log(f"[hbm] after placement: {hbm_line(devs)}")
    phases.append(("sharded build", build))

    def query():
        import repro.core.distributed as dist_mod
        ds, engine = res["ds"], res["engine"]
        seen: dict = {}
        with capture_calls(dist_mod, "_sharded_program", seen):
            with timed("sharded (compile + run)"):
                a = engine.query(ds.queries, plan="sharded", k=K)
                jax.block_until_ready(a.ids)
        found = kernels_of(seen, "_sharded_program")
        log(f"[kernels] sharded program tpu_custom_call: {sorted(found)}")
        check(set(KERNELS) <= found,
              "sharded program compiles all three kernels as tpu_custom_call")
        with timed("sharded oracle (compile + run)"):
            b = engine.query(ds.queries, plan="oracle", k=K)
            jax.block_until_ready(b.ids)
        compare(a, b, "sharded vs sharded oracle", ds)
        rec = report_quality(a, ds, "sharded")
        # grading reference only: the sharded oracle over the same host
        # shards on virtual devices of the host CPU
        cpu = jax.devices("cpu")[:chips]
        check(len(cpu) == chips, f"{chips} host cpu devices for the reference")
        with timed("sharded oracle on host cpu"):
            cpu_mesh = Mesh(np.array(cpu), ("shard",))
            qs = jax.device_put(ds.queries, NamedSharding(cpu_mesh, P()))
            ref = SearchEngine(res["sharded"], mesh=cpu_mesh).query(
                qs, plan="oracle", k=K)
            ref = jax.tree.map(np.asarray, ref)
        cpu_recall = report_quality(ref, ds, "sharded oracle on host cpu")
        check(rec >= cpu_recall - RECALL_SLACK,
              f"sharded recall {rec:.4f} >= cpu recall {cpu_recall:.4f} - "
              f"{RECALL_SLACK}")
        n_ref = fallback_calls()
        log(f"[kernels] lsh_hash reference-path calls: {n_ref}")
        check(n_ref == 0, "no kernel fell back to its jnp reference")
        log(f"[hbm] end: {hbm_line(devs)}")
    phases.append(("sharded query", query))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the default one-chip phase; 4: the sharded "
                         "plan over four chips, and nothing else")
    args = ap.parse_args(argv)
    if args.chips > 1:     # host cpu devices for the sharded grading reference
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={args.chips}").strip()

    cache_dir = enable_compile_cache()
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (jax platform {devs[0].platform!r})",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devs)} devices",
              file=sys.stderr)
        return 2
    kind = devs[0].device_kind
    log(f"[env] jax {jax.__version__}, {len(devs)} x {kind}; "
        f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')}; "
        f"compile cache {cache_dir}")

    phases: list = []
    if args.chips == 1:
        run_one_chip(300_000, phases)
    else:
        run_sharded(1_000_000, args.chips, phases)
    failed = []
    for name, fn in phases:
        log(f"== phase: {name}")
        try:
            with timed(f"phase {name}"):
                fn()
        except Exception:
            traceback.print_exc(file=sys.stdout)
            log(f"[FAIL] phase {name}")
            failed.append(name)
            if name in ("fused", "sharded build"):
                break          # every later phase depends on this one
    log(f"[cache] persistent compile cache {cache_dir}: {cache_events()}")
    if failed:
        log(f"chip_smoke: FAILED phases: {failed}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
