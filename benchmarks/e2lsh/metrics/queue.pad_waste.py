"""Share of the compiled tick rows that were padding, over the window's
ticks (BatchQueue.stats_summary()["pad_waste"])."""


def read(run):
    q = run["queue"]
    if not q or not q.get("ticks"):
        return None
    return 100.0 * q["pad_waste"]
