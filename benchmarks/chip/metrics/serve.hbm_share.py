"""Share of the chip's HBM peak that the served batches' needed bytes make
of the device's busy time in the traced window (layouts/matvec layer).

Needed bytes are ``work_ppr.batched_sweep_bytes`` of the window's
``ppr.sweeps`` and ``ppr.column_sweeps`` counters, from the graph's sizes
alone; busy time holds every device operation of the window (estimate,
push, fallback, ranking)."""
from benchmarks.chip.work_ppr import batched_sweep_bytes


def read(rec: dict):
    if rec["op"] != "ppr_serve" or not rec["trace"]:
        return None
    sweeps = rec["counters"].get("ppr.sweeps")
    cols = rec["counters"].get("ppr.column_sweeps")
    busy = rec["trace"]["busy_s"]
    if not sweeps or not cols or busy <= 0:
        return None
    need_s = batched_sweep_bytes(rec["n"], rec["nnz"], sweeps, cols) \
        / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * need_s / busy
