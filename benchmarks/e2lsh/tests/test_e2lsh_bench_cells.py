"""Cells, mixes and per-layer metrics are found by name: adding one takes new
files and new BENCHMARK.json entries, and no edit of a file that exists."""
import json
import pathlib
import shutil
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))

import cells  # noqa: E402


def test_every_cell_resolves_its_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert bench["paths"] == ["benchmarks/e2lsh"]
    for w in bench["workloads"]:
        cell = cells.load_cell(ROOT, w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["loop"] in ("open", "closed")
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert callable(cells.load_reader(m["name"]))
            assert m["moves"] in {e["name"] for e in cell.end_to_end}


class _Counts:
    def __init__(self, **kw):
        self.__dict__.update(kw)


_TRACE = dict(window_s=2.0, busy_s=1.5,
              kernel_s=dict(bucket_probe=4e-3, l2_distance_gathered=2e-3))
# 1,024 bytes a microsecond: a block row of 99 objects (1,024 B) takes 1 us
_KERNEL_RUN = dict(
    trace=_TRACE, peaks=dict(flops_bf16=1e12, hbm_bytes_per_s=1024e6),
    config=dict(index=dict(block_objs=99), dataset=dict(d=128)),
    traced=dict(nio_blocks=1000, cands_checked=1000))
_READS = [
    # (metric, what a run holds, what the reader gives; None: nothing read)
    ("kernel.bucket_probe.roofline", _KERNEL_RUN, 25.0),
    ("kernel.bucket_probe.roofline",
     dict(_KERNEL_RUN, traced=dict(nio_blocks=0, cands_checked=0)), None),
    ("kernel.l2_distance_gathered.roofline", _KERNEL_RUN, 25.0),
    ("kernel.l2_distance_gathered.roofline",
     dict(_KERNEL_RUN, trace=dict(_TRACE, kernel_s={})), None),
    ("device.idle_share.throughput", dict(trace=_TRACE), 25.0),
    ("device.idle_share.latency", dict(trace=_TRACE), 25.0),
    ("device.idle_share.latency", dict(trace=None), None),
    ("queue.pad_waste", dict(queue=dict(ticks=4, pad_waste=0.5)), 50.0),
    ("queue.pad_waste", dict(queue=dict(ticks=0)), None),
    ("storage.cache_hit_rate",
     dict(store=_Counts(reads=200, cache_hits=50)), 25.0),
    ("storage.cache_hit_rate", dict(store=None), None),
    ("storage.fetch_ms",
     dict(plan=_Counts(fetch_ms=30.0), queue=dict(rows_served=12)), 2.5),
    ("storage.fetch_ms", dict(plan=None, queue=dict(rows_served=12)), None),
]


@pytest.mark.parametrize("metric, run, want", _READS)
def test_every_reader_reads_its_layer_or_nothing(metric, run, want):
    got = cells.load_reader(metric)(run)
    assert got is None if want is None else got == pytest.approx(want)


def test_every_reader_file_is_tested():
    names = {p.stem for p in (BENCH / "metrics").glob("*.py")}
    assert names == {m for m, _, _ in _READS}


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        cells.load_cell(ROOT, "no-such-cell")


def test_a_cell_mix_and_metric_added_by_new_files_alone(tmp_path,
                                                        monkeypatch):
    root = tmp_path / "checkout"
    bench_dir = root / "benchmarks" / "e2lsh"
    shutil.copytree(BENCH, bench_dir,
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}
    (bench_dir / "traffic" / "burst64.json").write_text(json.dumps(
        dict(loop="open", rate_qps=50.0, rows=64, warmup_s=1.0)))
    (bench_dir / "metrics" / "queue.occupancy.py").write_text(
        "def read(run):\n    return 100.0 * run['queue']['occupancy_mean']\n")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append(dict(
        name="sift300k-hbm.burst64", config="sift300k-hbm",
        traffic="burst64", chips=1, why="test"))
    bench["per_layer"].append(dict(
        name="queue.occupancy", unit="%", better="higher",
        source="program_counter", layer="queue", moves="qps",
        workloads=["sift300k-hbm.burst64"]))
    for m in bench["end_to_end"]:
        if m["name"] == "qps":
            m["workloads"].append("sift300k-hbm.burst64")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(cells, "HERE", bench_dir)
    cell = cells.load_cell(root, "sift300k-hbm.burst64")
    assert cell.traffic["rows"] == 64
    assert [m["name"] for m in cell.per_layer] == ["queue.occupancy"]
    read = cells.load_reader("queue.occupancy")
    assert read({"queue": {"occupancy_mean": 0.5}}) == 50.0
    after = {p: p.read_bytes() for p in before}
    assert after == before                  # no existing file was edited
