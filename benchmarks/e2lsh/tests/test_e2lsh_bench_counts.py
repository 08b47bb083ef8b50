"""Operations and bytes the kernels need, and the least time from peaks."""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from counts import (block_row_bytes, bucket_probe_need,  # noqa: E402
                    l2_distance_need, least_time_s)
from peaks import PEAKS, peaks_for  # noqa: E402


def test_block_row_bytes_pads_to_lanes():
    assert block_row_bytes(99) == 1024          # 128 int32 ids + 128 fps
    assert block_row_bytes(128) == 1024
    assert block_row_bytes(129) == 2048


def test_bucket_probe_needs_each_block_row_once():
    need = bucket_probe_need(nio_blocks=1000, block_objs=99)
    assert need == dict(flops=0.0, bytes=1000 * 1024.0)


def test_l2_distance_counts_per_candidate():
    need = l2_distance_need(cands_checked=500, d=128)
    assert need["flops"] == 2 * 128 * 500
    assert need["bytes"] == 4 * 128 * 500


def test_least_time_takes_the_binding_bound():
    peaks = peaks_for("TPU v5 lite")
    t, bound = least_time_s(l2_distance_need(10**6, 128), peaks)
    assert bound == "bytes"
    assert t == pytest.approx(4 * 128 * 10**6 / 819e9)
    t, bound = least_time_s(dict(flops=197e12, bytes=1.0), peaks)
    assert bound == "flops" and t == pytest.approx(1.0)


def test_unknown_device_is_an_error():
    assert "TPU v5 lite" in PEAKS
    with pytest.raises(KeyError):
        peaks_for("cpu")
