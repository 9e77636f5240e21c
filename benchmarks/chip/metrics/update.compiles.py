"""XLA compiles in the traced window of a cell that reports
``update_ms``: the program's ``compiles`` counter (compiles less loads
from the persistent cache), which the engine keeps from its first build;
its ``compile`` events name the span that compiled (refresh layer)."""


def read(rec: dict):
    if rec["op"] != "delta":
        return None
    return rec["counters"].get("compiles")
