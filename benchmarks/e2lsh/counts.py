"""Operations and bytes each kernel needs for the rows it served, from the
served answers' own counters (``QueryResult.nio_blocks`` and
``cands_checked``), and the least time the chip could take for them."""
from __future__ import annotations

LANE = 128          # TPU lane width: a block row holds ceil(objs/128)*128 slots


def block_row_bytes(block_objs: int) -> int:
    """Bytes of one stored block row: int32 ids plus int32 fingerprints."""
    slots = -(-int(block_objs) // LANE) * LANE
    return 2 * 4 * slots


def bucket_probe_need(nio_blocks: int, block_objs: int) -> dict:
    """Every block row the chain walk reads, read once from HBM. The rows
    the kernel's grid adds as padding (spare row 0) are not needed."""
    return dict(flops=0.0,
                bytes=float(nio_blocks) * block_row_bytes(block_objs))


def l2_distance_need(cands_checked: int, d: int) -> dict:
    """One d-wide float32 dot product per candidate (2d operations), and
    the candidate's float32 coordinates read from HBM."""
    return dict(flops=2.0 * d * float(cands_checked),
                bytes=4.0 * d * float(cands_checked))


def least_time_s(need: dict, peaks: dict) -> tuple:
    """(seconds, bound): the larger of operations over the peak rate and
    bytes over the HBM bandwidth, and which of the two it is."""
    t_flops = need["flops"] / peaks["flops_bf16"]
    t_bytes = need["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
