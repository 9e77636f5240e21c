"""Device idle time per edge delta in the traced window while the host
plans the delta: the idle gaps that the trace reduction names by the
program's ``update.plan`` span or one of its children, ``update.plan.keys``
(the key-set arithmetic) and ``update.plan.rows`` (the rows each changed
column touches), summed over the window, over its deltas (refresh layer).
A gap is named by the innermost span open at its midpoint."""

SPANS = ("update.plan", "update.plan.keys", "update.plan.rows")


def read(rec: dict):
    if rec["end_to_end"] != "update_ms" or not rec["trace"]:
        return None
    gaps = [s for name, s in rec["trace"]["idle_gaps"] if name in SPANS]
    if not gaps or not rec["items"]:
        return None
    return 1e3 * sum(gaps) / len(rec["items"])
