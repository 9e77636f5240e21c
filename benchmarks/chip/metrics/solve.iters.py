"""Mean iterations per global solve in the traced window: the exact
``SolveInfo.iters`` of each ``run_tol`` (engine loops layer)."""


def read(rec: dict):
    if rec["op"] != "solve_tol":
        return None
    iters = [it["iters"] for it in rec["items"] if "iters" in it]
    return sum(iters) / len(iters) if iters else None
