"""Idle share of the device in the traced window of a cell that serves
personalized PageRank: 1 - busy / window, busy being the union of the
device operations' intervals in the profiler trace (device layer)."""


def read(rec: dict):
    if rec["op"] != "ppr_serve" or not rec["trace"]:
        return None
    t = rec["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
