"""l2_distance_gathered's share of its roofline: the least time for the
served answers' candidates (2d operations and 4d bytes each; the bytes
bound it on a TPU v5e) over the kernel's summed device time in the traced
window."""
from counts import l2_distance_need, least_time_s


def read(run):
    kt = run["trace"]["kernel_s"].get("l2_distance_gathered", 0.0)
    if kt <= 0 or run["traced"]["cands_checked"] <= 0:
        return None
    need = l2_distance_need(run["traced"]["cands_checked"],
                            run["config"]["dataset"]["d"])
    least, _ = least_time_s(need, run["peaks"])
    return 100.0 * least / kt
