"""Per-tick reduction of a traced window: the plan program each serving tick
runs on the device, the clock offset that puts device time on the
profiler's host clock, and each device-idle gap split among the program's
spans on that one clock.

It reads the event list ``devtrace.load_events`` returns. Run as a script,
it runs a cell as ``run.py`` does, with the same arguments and result line,
and logs the reduction of the traced window on stderr (``--trace 1``):

    python3 benchmarks/e2lsh/ticktrace.py --workload <cell> --seed <n> \
        --seconds <s> --trace 1

* **Plan programs.** The device's ``XLA Modules`` line holds one event per
  program run. A tick's program is the module that overlaps its
  ``tick.dispatch`` span most; the plan's module is the name most ticks
  ran, and every event of that name on the device plane is a plan program.
* **Clock offset.** ``delta`` maps device time onto the host clock
  (host = device + delta). Causality bounds it for every tick whose
  program overlaps the window: the program starts after its launch begins
  (``tick.launch``, or ``tick.dispatch`` in a trace of a program that has
  no launch span) and after the runtime's enqueue events where the trace
  holds them; it ends before ``tick.wait`` (or ``tick.dispatch``) returns
  and before the runtime's completion events. ``delta`` is the middle of
  the interval the bounds leave.
* **Idle by phase.** After the shift, each stretch of the window in which
  no device op runs is split among the innermost program spans open in it
  (the latest begun), by the length of their overlap; a parent span's
  self time is its own share. Time with no span open is ``between ticks``.
"""
from __future__ import annotations

from bisect import bisect_left, bisect_right
from collections import Counter

import devtrace

MODULES_LINE = "XLA Modules"
ENQUEUE = ("TpuLoadedExecutable::ExecuteLaunch", "DoEnqueueProgram")
COMPLETE = ("ReadSyncFlag", "tpu::System::Execute=>Done")
BETWEEN = "between ticks"


def _end(e) -> float:
    return e["start_ns"] + e["dur_ns"]


def _overlap(a0, a1, b0, b1) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def _inside(spans: list, lo: float, hi: float) -> list:
    return [s for s in spans if lo <= s["start_ns"] <= hi]


def reduce_ticks(events: list):
    """The per-tick reduction of the window (see the module docstring), or
    None where the trace holds no device program paired with a tick."""
    lo, hi = devtrace.window_of(events)
    host = [e for e in events if not devtrace._DEVICE.match(e["plane"])]
    spans = [e for e in host if devtrace._SPAN.match(e["name"])
             and e["name"] != devtrace.WINDOW and e["dur_ns"] > 0]
    named = {n: [e for e in spans if e["name"] == n]
             for n in ("tick.dispatch", "tick.launch", "tick.wait")}
    if not named["tick.dispatch"]:
        return None
    planes = sorted({e["plane"] for e in events
                     if devtrace._DEVICE.match(e["plane"])
                     and e["line"] == MODULES_LINE})
    for plane in planes:
        modules = [e for e in events if e["plane"] == plane
                   and e["line"] == MODULES_LINE]
        pairs = _pair(named["tick.dispatch"], modules)
        if pairs:
            return _reduce_plane(events, plane, modules, pairs, named,
                                 host, spans, lo, hi)
    return None


def _pair(dispatches: list, modules: list) -> list:
    """[(tick.dispatch span, its plan program)]: each dispatch takes the
    module it overlaps most, and only modules of the plan's name count."""
    best = []
    for d in dispatches:
        ov, m = max(((_overlap(d["start_ns"], _end(d), m["start_ns"],
                               _end(m)), i) for i, m in enumerate(modules)),
                    default=(0.0, None))
        if ov > 0:
            best.append((d, modules[m]))
    if not best:
        return []
    name = Counter(m["name"] for _, m in best).most_common(1)[0][0]
    return [(d, m) for d, m in best if m["name"] == name]


def _bounds(d, m, named, runtime) -> tuple:
    """(lower, upper) bound on delta from one tick's causality."""
    d0, d1 = d["start_ns"], _end(d)
    launch = min(_inside(named["tick.launch"], d0, d1) or [d],
                 key=lambda e: e["start_ns"])
    wait = max(_inside(named["tick.wait"], d0, d1) or [d], key=_end)
    t0, t1 = launch["start_ns"], _end(wait)
    before, after = [t0], [t1]
    for kind in ENQUEUE:            # the first of each kind after the launch
        starts = runtime[kind]
        i = bisect_left(starts, t0)
        if i < len(starts) and starts[i] <= t1:
            before.append(starts[i])
    for kind in COMPLETE:           # the last of each kind before the return
        starts = runtime[kind]
        i = bisect_right(starts, t1)
        if i > 0 and starts[i - 1] >= t0:
            after.append(starts[i - 1])
    return max(before) - m["start_ns"], min(after) - _end(m)


def _reduce_plane(events, plane, modules, pairs, named, host, spans,
                  lo, hi) -> dict:
    name = pairs[0][1]["name"]
    in_window = [(d, m) for d, m in pairs
                 if _overlap(lo, hi, m["start_ns"], _end(m)) > 0] or pairs
    runtime = {k: sorted(e["start_ns"] for e in host if e["name"] == k)
               for k in ENQUEUE + COMPLETE}
    bounds = [_bounds(d, m, named, runtime) for d, m in in_window]
    d_lo = max(b[0] for b in bounds)
    d_hi = min(b[1] for b in bounds)
    delta = 0.5 * (d_lo + d_hi)

    progs = sorted((m["start_ns"] + delta, _end(m) + delta)
                   for m in modules if m["name"] == name)
    whole = [t - s for s, t in progs if lo <= s and t <= hi]
    gaps = [b[0] - a[1] for a, b in zip(progs, progs[1:])
            if lo <= a[1] and b[0] <= hi]
    ops = [c for c in ((max(e["start_ns"] + delta, lo),
                        min(_end(e) + delta, hi))
                       for e in events if e["plane"] == plane
                       and e["line"] == devtrace.OPS_LINE) if c[1] > c[0]]
    edges = [lo] + [x for iv in devtrace.union_intervals(ops)
                    for x in iv] + [hi]
    idle = [(s, t) for s, t in zip(edges[::2], edges[1::2]) if t > s]
    split = split_gaps(spans, idle)
    return dict(
        module=name, plane=plane, ticks=len(in_window),
        delta_ms=delta * 1e-6, delta_lo_ms=d_lo * 1e-6,
        delta_hi_ms=d_hi * 1e-6, consistent=d_lo <= d_hi,
        device_ms=sum(whole) / len(whole) * 1e-6 if whole else None,
        programs=len(whole),
        gap_ms=sum(gaps) / len(gaps) * 1e-6 if gaps else None,
        gaps=len(gaps),
        idle_s=sum(t - s for s, t in idle) * 1e-9,
        idle_by_phase=[[k, v * 1e-9] for k, v in
                       sorted(split.items(), key=lambda kv: -kv[1])])


def timeline(spans: list) -> list:
    """[(start, end, name)]: the innermost span open (latest begun, then
    shortest) in each stretch between span boundaries where one is open."""
    bounds = sorted({x for e in spans for x in (e["start_ns"], _end(e))})
    by_start = sorted(spans, key=lambda e: e["start_ns"])
    out, active, j = [], [], 0
    for a, b in zip(bounds, bounds[1:]):
        while j < len(by_start) and by_start[j]["start_ns"] <= a:
            active.append(by_start[j])
            j += 1
        active = [e for e in active if _end(e) > a]
        if active:
            e = max(active, key=lambda e: (e["start_ns"], -e["dur_ns"]))
            out.append((a, b, e["name"]))
    return out


def split_gaps(spans: list, gaps: list) -> dict:
    """{span name: ns} of the time of the sorted, disjoint ``gaps``, each
    stretch given to the innermost span open then, or to ``between ticks``
    where none is."""
    segs = timeline(spans)
    out: dict = {}
    i = 0
    for s, t in gaps:
        while i < len(segs) and segs[i][1] <= s:
            i += 1
        covered, k = 0.0, i
        while k < len(segs) and segs[k][0] < t:
            a, b, name = segs[k]
            ov = min(b, t) - max(a, s)
            if ov > 0:
                out[name] = out.get(name, 0.0) + ov
                covered += ov
            k += 1
        if t - s > covered:
            out[BETWEEN] = out.get(BETWEEN, 0.0) + (t - s - covered)
    return out


def log_ticks(t, log) -> None:
    """The per-tick reduction ``t`` as two lines through ``log``."""
    if not t:
        log("[ticks] no plan program paired with a tick.dispatch span")
        return
    log(f"[ticks] plan program {t['module']} on {t['plane']}; device clock "
        f"+ delta = host clock, delta {t['delta_ms']:.6f} ms in "
        f"[{t['delta_lo_ms']:.6f}, {t['delta_hi_ms']:.6f}] ms over "
        f"{t['ticks']} ticks{'' if t['consistent'] else ' (bounds cross)'}")
    log(f"[ticks] device ms per tick {t['device_ms']} ({t['programs']} "
        f"programs wholly inside), gap ms {t['gap_ms']} ({t['gaps']} gaps); "
        f"idle {t['idle_s']:.6f} s by phase {t['idle_by_phase']}")


def main(argv=None) -> int:
    """``run.main`` with the traced window's events reduced per tick too."""
    import run
    reduce_trace = devtrace.reduce_trace

    def reduce_and_log_ticks(events):
        log_ticks(reduce_ticks(events), run.log)
        return reduce_trace(events)
    devtrace.reduce_trace = reduce_and_log_ticks
    return run.main(argv)


if __name__ == "__main__":
    raise SystemExit(main())
