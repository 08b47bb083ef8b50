#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip the process finds.

    python3 benchmarks/e2lsh/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

A run makes the cell's data and hash family from ``--seed``, builds the
index through the program's own build, places it as the configuration says
(HBM, or a spill on local disk behind the block store), compiles the queue's
ladder rungs the traffic can use, and drives the traffic mix into
``BatchQueue.submit``: a warm-up, then ``--seconds`` of measured window.
Once the window has closed and the program's state is freed, a sample of the
window's answers, drawn from the seed, is compared with the plain reference
(``reference.py``, ``compare.py``).

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` runs the
same window with the profiler on over a steady part of it and reports the
per-layer metrics, each read by ``metrics/<name>.py``.

The last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` also ``breakdown``,
and last ``compared``: each number compared beside its limit); the compared
numbers are also the last lines of stderr. Without a TPU, with fewer chips
than the cell asks for, outside a checkout of the repository, or where the
host keeps a spill in its page cache that the configuration says is cold,
the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
WORKDIR = HERE / ".work"            # spill files and traces (git-ignored)
CACHE_DIR = ROOT / ".jax_cache"     # fixed: the path is part of the key
sys.path.insert(0, str(HERE))


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -- compile counting --------------------------------------------------------
_COMPILES = {"n": 0}
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/backend_compile_duration")


def _on_duration(event: str, duration: float, **_kw) -> None:
    if event in _COMPILE_EVENTS:
        _COMPILES["n"] += 1


def setup_jax(require_chip: bool, chips: int, cache: bool = True):
    """Import JAX with the checkout's compile cache; check the chip."""
    if not (ROOT / "src" / "repro").is_dir():
        raise NoChip(f"no program sources under {ROOT / 'src'}")
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    if cache:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
        CACHE_DIR.mkdir(parents=True, exist_ok=True)
    # the TPU runtime's logs stay in the checkout unless the host says else
    if "TPU_LOG_DIR" not in os.environ:
        (WORKDIR / "tpu_logs").mkdir(parents=True, exist_ok=True)
        os.environ["TPU_LOG_DIR"] = str(WORKDIR / "tpu_logs")
    import jax
    if cache:
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if not _COMPILES.get("listening"):
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _COMPILES["listening"] = True
    devs = jax.devices()
    if require_chip:
        if devs[0].platform != "tpu":
            raise NoChip(f"no TPU: JAX found platform {devs[0].platform!r}")
        if len(devs) < chips:
            raise NoChip(f"the cell asks for {chips} chips, JAX found "
                         f"{len(devs)}")
    return jax


def rungs_for(traffic: dict, ladder) -> list:
    """The ladder rungs this traffic's ticks can use: from the smallest rung
    that holds one request segment, upward."""
    seg = min(int(traffic["rows"]), max(ladder))
    return [s for s in ladder if s >= min(r for r in ladder if r >= seg)]


def warm_rungs(q, pool, traffic: dict) -> list:
    """Compile each rung by serving one tick of exactly that many rows."""
    rows = int(traffic["rows"])
    used = rungs_for(traffic, q.ladder)
    at = 0
    for rung in used:
        for _ in range(max(1, rung // rows)):
            q.submit(pool[at % len(pool):at % len(pool) + rows])
            at += rows
        q.drain()
    return used


# -- one run -----------------------------------------------------------------
def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, hook=None,
             cache: bool = True) -> dict:
    """Run ``cell`` once and return the result line's object. ``hook``, if
    given, is called with the built system before the window (tests use it
    to break the timed path); ``cache=False`` leaves JAX's compile cache
    settings alone."""
    jax = setup_jax(require_chip, cell.chips, cache)
    import numpy as np

    import compare
    import datagen
    import reference
    import system as system_mod
    from cells import load_reader
    from peaks import peaks_for
    from traffic import LoadGen, percentile

    cfg, mix = cell.config, cell.traffic
    dev = jax.devices()[0]
    setup = {}
    t0 = time.perf_counter()
    spec = dict(cfg["dataset"], n=cfg["n"])
    data = datagen.make_data(spec, seed)
    ix = cfg["index"]
    family = datagen.make_family(ix, spec["d"], seed)
    setup["data_s"] = time.perf_counter() - t0
    sysm = system_mod.build(cfg, data, family, str(WORKDIR), log)
    setup.update(sysm.setup)
    if hook is not None:
        hook(sysm)
    q = sysm.queue
    t0 = time.perf_counter()
    used = warm_rungs(q, data.pool, mix)
    setup["warmup_s"] = time.perf_counter() - t0

    tracer_dir = str(WORKDIR / f"trace-{cell.name}")
    marks, layer_in = {}, {}
    q.start()
    drv = LoadGen(q, data.pool, mix, seed, float(mix["warmup_s"]), seconds)
    drv.start()
    ext = sysm.external

    def sleep_until(t):
        while True:
            left = t - time.perf_counter()
            if left <= 0:
                return
            time.sleep(min(left, 0.05))

    sleep_until(drv.w0)
    setup["traffic_warmup_s"] = float(mix["warmup_s"])
    setup_s = time.perf_counter() - T_START
    q.reset_stats()
    c0 = _COMPILES["n"]
    plan0 = ext.plan_totals.snapshot() if ext is not None else None
    io0 = ext.store.stats.snapshot() if ext is not None else None
    if trace:
        import devtrace
        from repro import telemetry
        devtrace.clear(tracer_dir)
        span = min(float(mix.get("trace_s", 3.0)), seconds / 2.0)
        sleep_until(drv.w0 + (seconds - span) / 2.0)
        telemetry.enable(jax_annotations=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0          # program spans, not every frame
        jax.profiler.start_trace(tracer_dir, profiler_options=opts)
        ann = jax.profiler.TraceAnnotation(devtrace.WINDOW)
        ann.__enter__()
        marks["t0"] = time.perf_counter()
        sleep_until(marks["t0"] + span)
        marks["t1"] = time.perf_counter()
        ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        telemetry.disable()
    sleep_until(drv.w1)
    compiles = _COMPILES["n"] - c0
    layer_in["queue"] = q.stats_summary()
    if ext is not None:
        layer_in["plan"] = ext.plan_totals.snapshot().since(plan0)
        layer_in["store"] = ext.store.stats.snapshot().since(io0)
    drv.join()
    q.stop()
    peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))

    # -- end-to-end --------------------------------------------------------------
    win = drv.window_requests()
    failed = sum(1 for r in win if r.answer is None)
    lat_ms = [(r.done - r.due) * 1e3 if r.answer is not None else float("inf")
              for r in win]
    rows_done = drv.rows_done_in(drv.w0, drv.w1)
    values = dict(setup_s=setup_s, qps=rows_done / seconds)
    if lat_ms:
        values.update(p50_ms=percentile(lat_ms, 50),
                      p95_ms=percentile(lat_ms, 95),
                      p99_ms=percentile(lat_ms, 99))
    log(f"[setup] total {setup_s:.3f} s: " + " ".join(
        f"{k}={v:.3f}" for k, v in setup.items()))
    log(f"[window] {seconds} s, compiles inside the window: {compiles}; "
        f"rungs warmed {used}; requests {len(win)}, failed {failed}, "
        f"rows answered {rows_done}")
    if lat_ms:
        log(f"[latency] ms from due to answer over {len(lat_ms)} requests: "
            f"p50 {values['p50_ms']:.3f} p95 {values['p95_ms']:.3f} "
            f"p99 {values['p99_ms']:.3f}")
    if drv.lateness:
        late = np.asarray(drv.lateness) * 1e3
        log(f"[generator] lateness ms p50 {np.percentile(late, 50):.3f} "
            f"p99 {np.percentile(late, 99):.3f} max {late.max():.3f}")
    qs = layer_in["queue"]
    log(f"[queue] ticks {qs.get('ticks')} rows {qs.get('rows_served')} "
        f"pad_waste {qs.get('pad_waste')} occupancy "
        f"{qs.get('occupancy_mean')} rungs {qs.get('rung_hist')}")
    if ext is not None:
        log(f"[storage] plan {layer_in['plan'].as_dict()} "
            f"store {layer_in['store'].as_dict()}")

    # -- per-layer (traced run) --------------------------------------------------
    breakdown, busy = None, None
    layer_values = {}
    if trace:
        import devtrace
        events = devtrace.load_events(tracer_dir)
        red = devtrace.reduce_trace(events)
        devtrace.clear(tracer_dir)
        traced = drv.answered_in(marks["t0"], marks["t1"])
        ctx = dict(
            trace=red, peaks=peaks_for(dev.device_kind), config=cfg,
            traced=dict(
                rows=sum(len(r.pool_ids) for r in traced),
                nio_blocks=int(sum(int(r.answer["nio_blocks"].sum())
                                   for r in traced)),
                cands_checked=int(sum(int(r.answer["cands_checked"].sum())
                                      for r in traced))),
            queue=layer_in["queue"], plan=layer_in.get("plan"),
            store=layer_in.get("store"))
        for m in cell.per_layer:
            v = load_reader(m["name"])(ctx)
            if v is not None:
                layer_values[m["name"]] = v
        breakdown = dict(device_ops=red["device_ops"],
                         idle_gaps=red["idle_gaps"])
        busy = red
        log(f"[trace] window {red['window_s']:.6f} s busy {red['busy_s']:.6f}"
            f" s; traced rows {ctx['traced']}; top ops {red['device_ops']}; "
            f"idle by host activity {red['idle_gaps']}")

    # -- correctness: a sample of the window's answers against the reference --
    # open loop: every request due in the window; closed loop: every request
    # answered in it
    pop = ([r for r in win if r.answer is not None] if mix["loop"] == "open"
           else drv.answered_in(drv.w0, drv.w1))
    pool_rows = [(ri, j) for ri, r in enumerate(pop)
                 for j in range(len(r.pool_ids))]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 4]))
    n_s = min(int(cfg["compare"]["sample"]), len(pool_rows))
    pick = [pool_rows[i] for i in rng.choice(len(pool_rows), n_s,
                                             replace=False)]
    got = {f: np.stack([pop[ri].answer[f][j] for ri, j in pick])
           for f in pop[0].answer} if pick else {}
    pool_ids = np.asarray([pop[ri].pool_ids[j] for ri, j in pick], np.int64)
    sysm.close()
    del sysm, q, ext, drv
    gc.collect()
    t0 = time.perf_counter()
    uniq, inv = np.unique(pool_ids, return_inverse=True)
    ref_t: dict = {}
    want_u = reference.search(data.db, data.pool[uniq], family, ix,
                              timings=ref_t)
    want = reference.Answers(**{
        f: getattr(want_u, f)[inv] for f in reference.Answers.__dataclass_fields__})
    limits = cfg["compare"]["limits"]
    if pick:
        cmp = compare.compare(data.db, data.pool[pool_ids], got, want,
                              failed, limits)
    else:
        cmp = {k: dict(value=float("inf"), limit=float(limits[k]))
               for k in compare.NUMBERS}
        cmp.update(correct=False, rows_compared=0)
    ref_s = time.perf_counter() - t0
    log(f"[reference] {len(uniq)} distinct queries in {ref_s:.3f} s "
        f"({ref_t}); rows "
        f"compared {cmp['rows_compared']}, differing "
        f"{cmp.get('rows_differing')}, ids tied {cmp.get('ids_tied')}")
    if pick:
        info_quality(jax, data, pool_ids, got, ix["k"])

    metrics = {}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if trace:
        for name, v in layer_values.items():
            metrics[name] = dict(value=v, unit=units[name])
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = dict(value=values[m["name"]], unit=m["unit"])
    device = dict(platform=dev.platform, kind=dev.device_kind,
                  count=len(jax.devices()), memory_peak_bytes=peak)
    if trace:
        device.update(busy_s=busy["busy_s"], window_s=busy["window_s"])
    compared = {k: cmp[k] for k in compare.NUMBERS}
    out = dict(correct=bool(cmp["correct"]), attempted=len(win),
               failed=failed, metrics=metrics, device=device)
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = compared
    return out


def info_quality(jax, data, pool_ids, got, k: int) -> None:
    """Recall@k and overall ratio of the sampled answers against an exact
    top-k computed on the device (information only)."""
    import jax.numpy as jnp
    import numpy as np
    hi = jax.lax.Precision.HIGHEST
    uniq, first = np.unique(pool_ids, return_index=True)
    q = jnp.asarray(data.pool[uniq])
    qn = jnp.sum(q * q, axis=1)
    best_d, best_i = None, None
    n = data.db.shape[0]
    step = min(65536, n)
    db = np.concatenate([data.db, np.full((-(-n // step) * step - n,
                                           data.db.shape[1]), 1e6,
                                          np.float32)])
    for s in range(0, n, step):
        x = jnp.asarray(db[s:s + step])
        d2 = (jnp.sum(x * x, axis=1)[None] - 2 * jnp.dot(q, x.T, precision=hi)
              + qn[:, None])
        v, i = jax.lax.top_k(-d2, k)
        i = i + s
        if best_d is None:
            best_d, best_i = v, i
        else:
            v2 = jnp.concatenate([best_d, v], 1)
            i2 = jnp.concatenate([best_i, i], 1)
            top, pos = jax.lax.top_k(v2, k)
            best_d, best_i = top, jnp.take_along_axis(i2, pos, 1)
    ex_i = np.asarray(best_i)
    ex_d = np.sqrt(np.maximum(-np.asarray(best_d, np.float64), 0))
    ids = got["ids"][first]
    dists = np.asarray(got["dists"], np.float64)[first]
    recall = np.mean([len(set(a[:k].tolist()) & set(b.tolist())) / k
                      for a, b in zip(ids, ex_i)])
    ok = np.isfinite(dists) & (ex_d > 0)
    ratio = float(np.mean(dists[ok] / ex_d[ok])) if ok.any() else float("nan")
    log(f"[quality] information only: recall@{k} {recall:.4f}, overall "
        f"ratio {ratio:.4f} over {len(uniq)} distinct sampled queries "
        f"(exact top-{k} on the device)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from cells import load_cell
    from system import NotCold
    try:
        cell = load_cell(ROOT, args.workload)
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except (NoChip, NotCold) as e:
        log(f"run.py: {e}")
        return 2
    for k, v in out["compared"].items():
        log(f"compared {k}: {v['value']!r} (limit {v['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
