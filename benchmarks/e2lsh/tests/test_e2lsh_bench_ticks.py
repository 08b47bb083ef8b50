"""The per-tick reduction (ticktrace) on the two recorded 30 ms TPU v5e
traces of sift300k-hbm.batch128: one from a program whose tick has only its
pack, dispatch and scatter spans, one with the phase spans inside them
(launch, wait, fetch, deliver); and the runner that logs it beside run.py's
result line."""
import json
import pathlib
import sys

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import devtrace  # noqa: E402
import ticktrace  # noqa: E402

EVENTS = json.loads((HERE / "trace_fixture.json").read_text())
PHASED = json.loads((HERE / "trace_fixture_phases.json").read_text())
# the program's spans of one serving tick (repro.serving.engine)
PHASES = ("serve.tick", "tick.pack", "tick.dispatch", "tick.launch",
          "tick.wait", "tick.scatter", "tick.fetch", "tick.deliver")


def _window(events):
    (w,) = [e for e in events if e["name"] == devtrace.WINDOW]
    return w["start_ns"], w["start_ns"] + w["dur_ns"]


def test_ticks_on_the_fixture_without_phase_spans():
    """The plan program, the clock offset and the gaps, on the fixture
    whose ticks predate tick.launch: delta is bound by the runtime's
    enqueue (68.50 ms) of the program that starts at 67.06 ms on the
    device, and by its completion (82.35 ms) of the one that ends at
    80.61 ms."""
    t = ticktrace.reduce_ticks(EVENTS)
    assert t["module"].startswith("jit__fused_masked_jit")
    assert t["consistent"] and t["ticks"] == 3
    assert t["delta_lo_ms"] <= t["delta_ms"] <= t["delta_hi_ms"]
    assert 1.44 <= t["delta_ms"] <= 1.74
    assert t["delta_lo_ms"] == pytest.approx(1.440, abs=1e-3)
    assert t["delta_hi_ms"] == pytest.approx(1.739, abs=1e-3)
    assert t["programs"] == 1 and t["device_ms"] == pytest.approx(13.549,
                                                                  abs=1e-3)
    assert t["gaps"] == 2 and t["gap_ms"] == pytest.approx(4.339, abs=1e-3)
    red = devtrace.reduce_trace(EVENTS)
    idle = red["window_s"] - red["busy_s"]
    assert sum(v for _, v in t["idle_by_phase"]) == pytest.approx(idle,
                                                                  rel=0.01)


def test_idle_split_gives_each_stretch_to_the_innermost_span():
    """A gap is shared by the spans open in it, by overlap; a parent's self
    time is its own; no span takes time it does not overlap."""
    def ev(name, s, t):
        return dict(plane="/host:CPU", line="python3", name=name,
                    start_ns=float(s), dur_ns=float(t - s))
    spans = [ev("serve.tick", 0, 100), ev("tick.dispatch", 10, 60),
             ev("tick.wait", 30, 60), ev("tick.scatter", 60, 90),
             ev("serve.tick", 105, 150), ev("tick.pack", 105, 110)]
    got = ticktrace.split_gaps(spans, [(50, 70), (95, 108), (140, 160)])
    assert got == {"tick.wait": 10.0, "tick.scatter": 10.0,
                   "serve.tick": 5.0 + 10.0, "between ticks": 5.0 + 10.0,
                   "tick.pack": 3.0}


def test_a_trace_with_no_tick_spans_reads_nothing():
    events = [e for e in EVENTS if not e["name"].startswith("tick.")]
    assert ticktrace.reduce_ticks(events) is None


def test_idle_split_by_phase_on_the_fixture_with_phase_spans():
    """On one clock, every tick's program lies inside its launch-to-wait
    span, and the idle time splits among the tick's phases as a 1 us grid
    of the innermost span open (latest begun) says: the leaves (launch,
    fetch, wait) take most of it, their parents only the instants between
    children."""
    t = ticktrace.reduce_ticks(PHASED)
    assert t["consistent"] and t["ticks"] == 3
    assert t["programs"] == 1 and t["gaps"] == 2
    delta = t["delta_ms"] * 1e6
    progs = [e for e in PHASED if e["line"] == "XLA Modules"]
    spans = [e for e in PHASED if e["plane"] == "/host:CPU"
             and e["name"] in PHASES]

    def within(name, s, t):
        return [e for e in spans if e["name"] == name
                and s <= e["start_ns"] <= t]
    for m in progs:
        (d,) = [e for e in spans if e["name"] == "tick.dispatch"
                and e["start_ns"] < m["start_ns"] + m["dur_ns"]
                and e["start_ns"] + e["dur_ns"] > m["start_ns"]]
        d1 = d["start_ns"] + d["dur_ns"]
        launch = within("tick.launch", d["start_ns"], d1) or [d]
        (wait,) = within("tick.wait", d["start_ns"], d1)
        assert launch[0]["start_ns"] <= m["start_ns"] + delta
        assert m["start_ns"] + m["dur_ns"] + delta <= \
            wait["start_ns"] + wait["dur_ns"]

    lo, hi = _window(PHASED)
    busy = np.zeros(int((hi - lo) // 1000), bool)
    for e in PHASED:
        if e["line"] == "XLA Ops":
            a = int(max(e["start_ns"] + delta - lo, 0) // 1000)
            b = int(min(e["start_ns"] + e["dur_ns"] + delta - lo, hi - lo)
                    // 1000)
            busy[max(a, 0):max(b, 0)] = True
    want: dict = {}
    for i in np.flatnonzero(~busy):
        x = lo + i * 1000 + 500
        here = [e for e in spans
                if e["start_ns"] <= x < e["start_ns"] + e["dur_ns"]]
        k = (max(here, key=lambda e: e["start_ns"])["name"] if here
             else ticktrace.BETWEEN)
        want[k] = want.get(k, 0.0) + 1e-6
    got = dict(t["idle_by_phase"])
    assert set(got) == set(want)
    for k in want:
        assert got[k] == pytest.approx(want[k], abs=2e-5), k
    red = devtrace.reduce_trace(PHASED)
    assert sum(got.values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=0.01)
    leaves = got["tick.launch"] + got["tick.fetch"] + got["tick.wait"]
    assert leaves > 0.75 * sum(got.values())
    for parent in ("tick.dispatch", "tick.scatter"):
        assert got[parent] < 0.1 * got["tick.launch"]


def test_the_runner_logs_the_ticks_of_run_pys_traced_window(monkeypatch,
                                                            capsys):
    """The script runs run.main unchanged, and the events run.py reduces
    are reduced per tick too: delta and its interval reach stderr."""
    import run
    reduce_trace = devtrace.reduce_trace
    monkeypatch.setattr(devtrace, "reduce_trace", reduce_trace)
    seen = {}

    def fake_main(argv):
        seen["argv"] = argv
        seen["red"] = devtrace.reduce_trace(EVENTS)
        return 0
    monkeypatch.setattr(run, "main", fake_main)
    assert ticktrace.main(["--workload", "w"]) == 0
    assert seen["argv"] == ["--workload", "w"]
    assert seen["red"] == reduce_trace(EVENTS)
    err = capsys.readouterr().err
    assert "[ticks] plan program jit__fused_masked_jit" in err
    assert "delta 1.589484 ms in [1.439839, 1.739130] ms" in err
    assert "over 3 ticks" in err
    assert "idle_by_phase" not in err and "by phase [[" in err


def test_a_window_with_no_tick_logs_that_it_read_nothing():
    lines = []
    ticktrace.log_ticks(None, lines.append)
    assert lines == ["[ticks] no plan program paired with a tick.dispatch "
                     "span"]


def _ev(name, s, t, plane="/host:CPU", line="python3"):
    return dict(plane=plane, line=line, name=name, start_ns=float(s),
                dur_ns=float(t - s))


def test_each_dispatch_pairs_with_the_plan_program_it_overlaps_most():
    """A dispatch takes the module it overlaps most; a module of another
    name than most ticks ran (a stray copy program) is no plan program."""
    dev = dict(plane="/device:TPU:0", line="XLA Modules")
    dispatches = [_ev("tick.dispatch", s, t) for s, t in
                  ((0, 100), (200, 300), (400, 500), (600, 650))]
    modules = [_ev("plan", 10, 90, **dev), _ev("copy", 95, 110, **dev),
               _ev("plan", 210, 290, **dev), _ev("copy", 380, 405, **dev),
               _ev("plan", 402, 480, **dev), _ev("copy", 610, 640, **dev)]
    pairs = ticktrace._pair(dispatches, modules)
    assert [(d["start_ns"], m["start_ns"]) for d, m in pairs] == [
        (0.0, 10.0), (200.0, 210.0), (400.0, 402.0)]
    assert ticktrace._pair(dispatches, []) == []


def test_runtime_enqueue_and_completion_tighten_the_offset_bounds():
    """delta is at least launch start - program start, raised by the first
    enqueue after the launch, and at most wait end - program end, lowered
    by the last completion event before the return."""
    d = _ev("tick.dispatch", 100, 1000)
    named = {"tick.launch": [_ev("tick.launch", 120, 300)],
             "tick.wait": [_ev("tick.wait", 300, 990)]}
    m = _ev("plan", 50, 800, plane="/device:TPU:0", line="XLA Modules")
    none = {k: [] for k in ticktrace.ENQUEUE + ticktrace.COMPLETE}
    assert ticktrace._bounds(d, m, named, none) == (70.0, 190.0)
    runtime = dict(none, DoEnqueueProgram=[90.0, 150.0, 1200.0],
                   ReadSyncFlag=[110.0, 950.0, 995.0])
    assert ticktrace._bounds(d, m, named, runtime) == (100.0, 150.0)
    # without a launch or wait span, tick.dispatch bounds both sides
    bare = {"tick.launch": [], "tick.wait": []}
    assert ticktrace._bounds(d, m, bare, none) == (50.0, 200.0)


def test_the_timeline_names_the_latest_begun_span_then_the_shortest():
    spans = [_ev("serve.tick", 0, 100), _ev("tick.dispatch", 10, 60),
             _ev("tick.launch", 10, 30), _ev("tick.wait", 30, 60)]
    assert ticktrace.timeline(spans) == [
        (0.0, 10.0, "serve.tick"), (10.0, 30.0, "tick.launch"),
        (30.0, 60.0, "tick.wait"), (60.0, 100.0, "serve.tick")]
    assert ticktrace.timeline([]) == []
