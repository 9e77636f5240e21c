"""Mean ``UpdateInfo.iters`` per edge delta in the traced window: push
sweeps, or iterations of a warm or rebuilt solve (refresh layer)."""


def read(rec: dict):
    if rec["op"] != "delta":
        return None
    iters = [it["iters"] for it in rec["items"] if "iters" in it]
    return sum(iters) / len(iters) if iters else None
