"""The trace reduction on a small recorded trace: 30 ms of a TPU v5e run of
sift300k-hbm.batch128 (device ops and host events, python frames dropped,
the window annotation cut to those 30 ms), and on a trace the CPU records
here."""
import json
import pathlib
import sys

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import devtrace  # noqa: E402

EVENTS = json.loads((HERE / "trace_fixture.json").read_text())


def _window():
    (w,) = [e for e in EVENTS if e["name"] == devtrace.WINDOW]
    return w["start_ns"], w["start_ns"] + w["dur_ns"]


def test_idle_share_is_one_minus_the_union_of_device_op_intervals():
    lo, hi = _window()
    red = devtrace.reduce_trace(EVENTS)
    # independent: mark every 10 ns step an XLA op covers
    grid = np.zeros(int((hi - lo) // 10) + 1, bool)
    for e in EVENTS:
        if e["plane"] == "/device:TPU:0" and e["line"] == "XLA Ops":
            a = int(max(e["start_ns"] - lo, 0) // 10)
            b = int(min(e["start_ns"] + e["dur_ns"] - lo, hi - lo) // 10)
            grid[a:b] = True
    assert red["window_s"] == pytest.approx(30e-3)
    assert red["busy_s"] == pytest.approx(grid.mean() * 30e-3, rel=2e-3)
    assert 0 < red["busy_s"] < red["window_s"]
    # nested ops (a while loop's body) are not counted twice
    total = sum(min(e["start_ns"] + e["dur_ns"], hi) - max(e["start_ns"], lo)
                for e in EVENTS if e["line"] == "XLA Ops"
                and e["start_ns"] < hi and e["start_ns"] + e["dur_ns"] > lo)
    assert red["busy_s"] < total * 1e-9


def test_kernel_time_is_summed_by_its_stable_name():
    lo, hi = _window()
    red = devtrace.reduce_trace(EVENTS)
    for kernel in ("bucket_probe", "l2_distance_gathered"):
        want = sum(min(e["start_ns"] + e["dur_ns"], hi)
                   - max(e["start_ns"], lo) for e in EVENTS
                   if e["line"] == "XLA Ops"
                   and e["name"].startswith(f"%{kernel}.")
                   and e["start_ns"] < hi and e["start_ns"] + e["dur_ns"] > lo)
        assert want > 0
        assert red["kernel_s"][kernel] == pytest.approx(want * 1e-9)
    names = [k for k, _ in red["device_ops"]]
    assert len(names) <= 10 and not any(n.startswith("%while") for n in names)
    gaps = sum(v for _, v in red["idle_gaps"])
    assert gaps <= red["window_s"] - red["busy_s"] + 1e-12


def test_stable_names():
    assert devtrace.stable_name(
        "%bucket_probe.3 = s32[8192,128]{1,0} custom-call(...)") == \
        "bucket_probe"
    assert devtrace.stable_name("%fusion.40 = s32[16384]") == "fusion"
    assert devtrace.stable_name("l2_distance_gathered.1.2") == \
        "l2_distance_gathered"


def test_union_of_intervals():
    assert devtrace.union_intervals([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [[0, 3], [5, 8]]


def test_a_cpu_trace_loads_with_its_window(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(devtrace.WINDOW):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    events = devtrace.load_events(str(tmp_path))
    lo, hi = devtrace.window_of(events)
    assert hi > lo
    with pytest.raises(ValueError):      # the CPU has no device op line
        devtrace.reduce_trace(events)
