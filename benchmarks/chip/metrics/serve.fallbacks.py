"""Query columns per served batch in the traced window that missed the
push's bound and were solved exactly: the program's
``landmarks.fallbacks`` counter, which appears with the first fallback,
over the window's batches (landmarks layer)."""


def read(rec: dict):
    if rec["op"] != "ppr_serve" or not rec["items"]:
        return None
    return rec["counters"].get("landmarks.fallbacks", 0) / len(rec["items"])
