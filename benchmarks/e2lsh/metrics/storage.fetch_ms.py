"""Host chain-walk time of the external plan (block fetches and filtering,
ExternalPlanTotals.fetch_ms) summed over the window's ticks, per query row
those ticks served."""


def read(run):
    p, q = run["plan"], run["queue"]
    if p is None or not q or not q.get("rows_served"):
        return None
    return p.fetch_ms / q["rows_served"]
