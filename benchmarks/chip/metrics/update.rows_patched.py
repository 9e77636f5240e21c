"""Layout rows rewritten per edge delta in the traced window: the
program's ``update.rows_patched`` counter over the window, over its deltas
(refresh layer).  A program without the counter reads nothing."""


def read(rec: dict):
    if rec["op"] != "delta" or not rec["items"]:
        return None
    rows = rec["counters"].get("update.rows_patched")
    return None if rows is None else rows / len(rec["items"])
