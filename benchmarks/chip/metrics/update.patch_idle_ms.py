"""Device idle time per edge delta in the traced window while the host
patches the layout: the idle gaps that the trace reduction names by the
program's ``update.patch`` span or its child ``update.patch.rows`` (the
host rebuild of each SELL tier's rows), summed over the window, over its
deltas (refresh layer).  A gap is named by the innermost span open at its
midpoint."""

SPANS = ("update.patch", "update.patch.rows")


def read(rec: dict):
    if rec["end_to_end"] != "update_ms" or not rec["trace"]:
        return None
    gaps = [s for name, s in rec["trace"]["idle_gaps"] if name in SPANS]
    if not gaps or not rec["items"]:
        return None
    return 1e3 * sum(gaps) / len(rec["items"])
