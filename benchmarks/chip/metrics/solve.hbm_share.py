"""Share of the chip's HBM peak that the solves' needed bytes make of the
device's busy time in the traced window (layouts/matvec layer).

Needed bytes are ``work.sweep_bytes(n, nnz)`` per iteration, from the
graph's sizes alone, times the iterations the window's solves ran."""
from benchmarks.chip.work import sweep_bytes


def read(rec: dict):
    if rec["op"] not in ("solve_tol", "solve_fixed") or not rec["trace"]:
        return None
    iters = sum(it.get("iters", 0) for it in rec["items"])
    busy = rec["trace"]["busy_s"]
    if iters <= 0 or busy <= 0:
        return None
    need_s = iters * sweep_bytes(rec["n"], rec["nnz"]) \
        / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * need_s / busy
