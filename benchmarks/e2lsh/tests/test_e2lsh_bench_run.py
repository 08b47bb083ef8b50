"""Whole runs of a test-size cell on the CPU (the look for a chip skipped):
the result line, the comparison against the plain reference, and runs with
the timed path broken underneath, which have to come out not correct."""
import dataclasses
import json
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
import system  # noqa: E402
from cells import Cell  # noqa: E402

KEYS = ["correct", "attempted", "failed", "metrics", "device", "compared"]


def tiny_cell(tier: str, traffic: str) -> Cell:
    config = json.loads((HERE / f"tiny-{tier}.json").read_text())
    if traffic == "batch128":
        mix = json.loads((BENCH / "traffic" / "batch128.json").read_text())
        mix.update(rows=16)
    else:
        mix = dict(loop="open", rate_qps=60.0, rows=1)
    mix.update(warmup_s=0.5)
    return Cell(name=f"tiny-{tier}.{traffic}", chips=1, config=config,
                traffic=mix, end_to_end=[], per_layer=[])


def run_tiny(tier, traffic, seed, hook=None, end_to_end=("setup_s", "qps")):
    cell = tiny_cell(tier, traffic)
    units = dict(setup_s="s", qps="queries/s")
    cell.end_to_end = [dict(name=n, unit=units.get(n, "ms"))
                       for n in end_to_end]
    return run.run_cell(cell, seed, 1.0, False, require_chip=False,
                        hook=hook, cache=False)


def test_result_line_keys_and_a_correct_run():
    out = run_tiny("hbm", "batch128", 2**33 + 11)
    assert list(out) == KEYS                      # `compared` comes last
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert out["metrics"]["qps"]["value"] > 0
    assert out["metrics"]["setup_s"]["unit"] == "s"
    for v in out["compared"].values():
        assert set(v) == {"value", "limit"}
    assert out["compared"]["mismatch_share"]["value"] == 0.0


def test_external_plan_open_loop_is_correct():
    out = run_tiny("ssd", "open", 5,
                   end_to_end=("setup_s", "p50_ms", "p95_ms", "p99_ms"))
    assert out["correct"] is True
    assert out["attempted"] == 60 and out["failed"] == 0
    lat = [out["metrics"][m]["value"] for m in ("p50_ms", "p95_ms", "p99_ms")]
    assert 0 < lat[0] <= lat[1] <= lat[2]


@pytest.mark.parametrize("resident, refused", [(1.0, True),
                                                (float("nan"), True),
                                                (0.0, False)])
def test_a_spill_left_in_the_page_cache_is_refused(monkeypatch, tmp_path,
                                                   resident, refused):
    """A store the configuration says is cold has to be cold: where the
    host keeps the spill's pages after the drop, the build refuses and
    removes the spill."""
    from datagen import make_data, make_family
    config = json.loads((HERE / "tiny-ssd.json").read_text())
    config["store"]["cold_max_resident"] = 0.05
    monkeypatch.setattr(system, "residency", lambda path: resident)
    spec = dict(config["dataset"], n=config["n"])
    data = make_data(spec, 6)
    family = make_family(config["index"], spec["d"], 6)
    if refused:
        with pytest.raises(system.NotCold):
            system.build(config, data, family, str(tmp_path), lambda m: None)
        assert list(tmp_path.iterdir()) == []
    else:
        sysm = system.build(config, data, family, str(tmp_path),
                            lambda m: None)
        assert sysm.info["page_cache_resident"] == 0.0
        sysm.close()


def _break(sysm, alter):
    fn = sysm.queue._fn

    def broken(queries, valid):
        return alter(fn(queries, valid), valid)

    sysm.queue._fn = broken


def test_an_answer_altered_where_produced_is_not_correct():
    def alter(res, valid):
        ids = res.ids.at[:, 0].set((res.ids[:, 0] + 1) % 3000)
        return dataclasses.replace(res, ids=ids)

    out = run_tiny("hbm", "batch128", 3, hook=lambda s: _break(s, alter))
    assert out["correct"] is False
    assert out["compared"]["dist_gap"]["value"] > 1.0


def test_half_of_each_tick_left_out_is_not_correct():
    def alter(res, valid):
        keep = np.arange(res.found.shape[0]) < res.found.shape[0] // 2
        return dataclasses.replace(
            res, found=res.found & keep,
            nio_blocks=np.where(keep, res.nio_blocks, 0))

    out = run_tiny("hbm", "batch128", 4, hook=lambda s: _break(s, alter))
    assert out["correct"] is False
    assert out["compared"]["mismatch_share"]["value"] > 0.25


def test_control_one_precision_lower_is_not_correct():
    from control import control_readings
    config = json.loads((HERE / "tiny-hbm.json").read_text())
    for seed in (1, 2, 3):
        out = control_readings(config, seed)
        assert out["correct"] is False
        assert out["dist_gap"]["value"] > out["dist_gap"]["limit"]


def _cli(cwd, *extra, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/e2lsh/run.py", "--workload",
         "sift300k-hbm.batch128", "--seed", str(2**31 + 9), "--seconds", "1",
         "--trace", "0", *extra], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def _no_result(proc):
    lines = proc.stdout.strip().splitlines()
    return not lines or not lines[-1].lstrip().startswith("{")


def test_no_tpu_exits_nonzero_without_a_result():
    proc = _cli(ROOT)
    assert proc.returncode != 0
    assert _no_result(proc)
    assert "no TPU" in proc.stderr


def test_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "e2lsh",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    proc = _cli(tmp_path)
    assert proc.returncode != 0
    assert _no_result(proc)
