"""Profiler trace of a steady part of the window, and its reduction to
device busy time, idle share, per-kernel device time and idle gaps.

The reduction works on a plain list of events, so the committed test can
check it on a small recorded fixture without a chip:

    {"plane": str, "line": str, "name": str, "start_ns": float,
     "dur_ns": float}

Device planes are those named ``/device:<PLATFORM>:<n>``; their ``XLA Ops``
line holds one event per operation the device ran, named by the HLO
instruction's text (``%bucket_probe.3 = s32[...] custom-call(...)``). A
kernel is found by its stable name: the instruction's name without XLA's
``.<n>`` instance suffix, which for a Pallas kernel is the kernel's ``name``.
A control-flow op (``while``, ``conditional``, ``call``) spans the ops of its
body, which the line lists too: it counts towards busy time, not towards the
ranking of ops. The window is the host annotation ``WINDOW`` the harness
opens around the traced part. Idle gaps are put down to the innermost of the
program's spans (``serve.tick``, ``tick.dispatch``, ...) open at the gap's
midpoint, or else to the innermost host event open then.
"""
from __future__ import annotations

import glob
import os
import re
import shutil

WINDOW = "e2lsh_bench.traced_window"
OPS_LINE = "XLA Ops"
_DEVICE = re.compile(r"^/device:([A-Za-z]+):(\d+)$")
_INSTR = re.compile(r"^%?([\w.\-]+)")
_SUFFIX = re.compile(r"(\.\d+)+$")
_SPAN = re.compile(r"^[a-z_]+(\.[a-z_]+)+$")
CONTROL_FLOW = ("while", "conditional", "call")


def stable_name(name: str) -> str:
    m = _INSTR.match(name)
    return _SUFFIX.sub("", m.group(1) if m else name)


def short_name(name: str, width: int = 96) -> str:
    """The op's instruction text, cut to ``width`` characters, with the
    layout annotations dropped: enough to tell two fusions apart."""
    return re.sub(r"\{[^{}]*\}", "", name)[:width]


def load_events(log_dir: str) -> list:
    """Events of the newest ``.xplane.pb`` under ``log_dir``."""
    import jax
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                out.append(dict(plane=plane.name, line=line.name,
                                name=e.name, start_ns=float(e.start_ns),
                                dur_ns=float(e.duration_ns)))
    return out


def clear(log_dir: str) -> None:
    shutil.rmtree(log_dir, ignore_errors=True)


def window_of(events: list) -> tuple:
    """(start_ns, end_ns) of the harness's window annotation."""
    spans = [e for e in events if e["name"] == WINDOW]
    if not spans:
        raise ValueError(f"trace holds no {WINDOW!r} annotation")
    e = max(spans, key=lambda e: e["dur_ns"])
    return e["start_ns"], e["start_ns"] + e["dur_ns"]


def device_ops(events: list) -> dict:
    """{device plane: [op events]} for the ``XLA Ops`` line of each device."""
    out: dict = {}
    for e in events:
        if _DEVICE.match(e["plane"]) and e["line"] == OPS_LINE:
            out.setdefault(e["plane"], []).append(e)
    return out


def _clip(e, lo, hi):
    s = max(e["start_ns"], lo)
    t = min(e["start_ns"] + e["dur_ns"], hi)
    return (s, t) if t > s else None


def union_intervals(iv: list) -> list:
    """Merge [start, end) intervals into disjoint sorted ones."""
    out: list = []
    for s, t in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return out


def reduce_trace(events: list) -> dict:
    """busy_s and window_s (busy averaged over the device planes), each
    op's (so each kernel's) summed device seconds in the window, the device ops that took
    most time, and the idle time by what the host was doing meanwhile."""
    lo, hi = window_of(events)
    planes = device_ops(events)
    if not planes:
        raise ValueError("trace holds no device op events")
    busy, op_time, kernel, gaps = [], {}, {}, []
    for plane, ops in sorted(planes.items()):
        iv = [c for c in (_clip(e, lo, hi) for e in ops) if c]
        merged = union_intervals(iv)
        busy.append(sum(t - s for s, t in merged))
        for e in ops:
            c = _clip(e, lo, hi)
            if c:
                k = stable_name(e["name"])
                kernel[k] = kernel.get(k, 0.0) + (c[1] - c[0])
                if k not in CONTROL_FLOW:
                    d = short_name(e["name"])
                    op_time[d] = op_time.get(d, 0.0) + (c[1] - c[0])
        edges = [lo] + [x for st in merged for x in st] + [hi]
        gaps += [(s, t) for s, t in zip(edges[::2], edges[1::2]) if t > s]
    host = [e for e in events if not _DEVICE.match(e["plane"])
            and e["dur_ns"] > 0 and e["name"] != WINDOW]
    gaps_by: dict = {}
    for (s, t), what in zip(gaps, host_activity(host, gaps)):
        gaps_by[what] = gaps_by.get(what, 0.0) + (t - s)
    n = len(planes)
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gaps_by.items(), key=lambda kv: -kv[1])[:10]
    return dict(
        window_s=(hi - lo) * 1e-9,
        busy_s=sum(busy) / n * 1e-9,
        devices=n,
        kernel_s={k: v / n * 1e-9 for k, v in kernel.items()},
        device_ops=[[k, v / n * 1e-9] for k, v in top_ops],
        idle_gaps=[[k, v / n * 1e-9] for k, v in top_gaps],
    )


def idle_share_pct(red: dict):
    """100 * (1 - busy / window) of a reduced trace, or None without one."""
    if not red or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])


def host_activity(host: list, gaps: list) -> list:
    """For each gap, the name of the innermost program span open at its
    midpoint, else of the innermost host event open then, else
    ``host idle``."""
    order = sorted(range(len(gaps)), key=lambda i: gaps[i][0] + gaps[i][1])
    evs = sorted(host, key=lambda e: e["start_ns"])
    out = ["host idle"] * len(gaps)
    active: list = []            # (end, not a span, dur, seq, name)
    j = 0
    for i in order:
        mid = 0.5 * (gaps[i][0] + gaps[i][1])
        while j < len(evs) and evs[j]["start_ns"] <= mid:
            e = evs[j]
            active.append((e["start_ns"] + e["dur_ns"],
                           not _SPAN.match(e["name"]), e["dur_ns"], j,
                           e["name"]))
            j += 1
        active = [a for a in active if a[0] > mid]
        if active:
            out[i] = min(active, key=lambda a: (a[1], a[2]))[4]
    return out
