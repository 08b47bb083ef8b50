"""bucket_probe's share of its roofline: the least time the block rows the
served answers read (sum of nio_blocks x one stored row) take at the HBM
bandwidth, over the kernel's summed device time in the traced window."""
from counts import bucket_probe_need, least_time_s


def read(run):
    kt = run["trace"]["kernel_s"].get("bucket_probe", 0.0)
    if kt <= 0 or run["traced"]["nio_blocks"] <= 0:
        return None
    need = bucket_probe_need(run["traced"]["nio_blocks"],
                             run["config"]["index"]["block_objs"])
    least, _ = least_time_s(need, run["peaks"])
    return 100.0 * least / kt
