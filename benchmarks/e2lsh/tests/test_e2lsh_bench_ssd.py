"""The SSD configuration: the same data and index as the HBM one, and a
whole test-size run on the `uring` store's pread path (no io_uring), which
reads the spill with O_DIRECT and has to come out correct."""
import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))

import cells  # noqa: E402
import run  # noqa: E402
from cells import Cell  # noqa: E402

CONFIGS = BENCH / "configs"


def _config(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


@pytest.mark.parametrize("key", ["n", "dataset", "index", "precision",
                                 "queue", "compare", "reference"])
def test_ssd_config_shares_the_hbm_index(key):
    """Same seed, same data, index and comparison: only placement differs."""
    assert _config("sift300k-ssd")[key] == _config("sift300k-hbm")[key]


def test_ssd_cell_is_the_external_plan_on_the_uring_store():
    cell = cells.load_cell(ROOT, "sift300k-ssd.batch128")
    assert cell.chips == 1
    assert (cell.config["tier"], cell.config["plan"]) == ("ssd", "external")
    assert cell.config["store"]["backend"] == "uring"
    assert cell.config["store"]["qd"] == 16
    # demand reads are O_DIRECT, so page-cache residency is not checked
    assert "cold_max_resident" not in cell.config["store"]
    assert {m["name"] for m in cell.per_layer} >= {
        "storage.fetch_ms", "storage.cache_hit_rate"}


def test_uring_store_without_io_uring_is_correct(monkeypatch):
    """The SSD configuration's store (uring, strict) on a host without
    io_uring: O_DIRECT reads from the pread pool, and the run is correct."""
    import repro.storage.uring as uring_mod

    align, reason = uring_mod.probe_o_direct(str(BENCH))
    if not align:
        pytest.skip(f"O_DIRECT unavailable: {reason}")
    monkeypatch.setattr(uring_mod, "probe_io_uring",
                        lambda: (False, "forced off by test"))
    config = json.loads((HERE / "tiny-ssd.json").read_text())
    config["store"] = dict(backend="uring", qd=16)
    # a spill path of its own: the other files' tiny-ssd runs may spill
    # into the same work directory at the same time
    config["name"] = "tiny-ssd-pread"
    mix = json.loads((BENCH / "traffic" / "batch128.json").read_text())
    mix.update(rows=16, warmup_s=0.5)
    cell = Cell(name="tiny-ssd-pread.batch128", chips=1, config=config,
                traffic=mix,
                end_to_end=[dict(name="setup_s", unit="s"),
                            dict(name="qps", unit="queries/s")],
                per_layer=[])
    seen = {}

    def look(sysm):
        seen["store"] = sysm.external.store

    out = run.run_cell(cell, 2**33 + 17, 1.0, False, require_chip=False,
                       hook=look, cache=False)
    store = seen["store"]
    assert (store.name, store.submit, store.o_direct) == (
        "uring", "pread", True)
    assert store.stats.device_reads > 0 and store.stats.read_us > 0
    assert out["correct"] is True and out["failed"] == 0
    assert out["compared"]["mismatch_share"]["value"] == 0.0
