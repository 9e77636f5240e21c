"""Batched (N, Q) sweeps per served batch in the traced window: the
program's ``ppr.sweeps`` counter (the landmark push's sweeps, plus the
fixed sweeps of any exact fallback) over the window's batches (landmarks
layer)."""


def read(rec: dict):
    if rec["op"] != "ppr_serve" or not rec["items"]:
        return None
    sweeps = rec["counters"].get("ppr.sweeps")
    return None if sweeps is None else sweeps / len(rec["items"])
