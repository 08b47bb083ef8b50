"""Share of the traced window in which no operation ran on the device
(1 - union of device-op intervals / window), in the bulk-load cell."""
from devtrace import idle_share_pct


def read(run):
    return idle_share_pct(run["trace"])
