"""The ``update.rows_patched`` reader on canned run records: the refresh
layer's rows rewritten per delta, from the program's counter."""
from __future__ import annotations

import pytest

from benchmarks.chip import cells


def _rec(op, e2e, counters, deltas=2):
    return {"cell": "c", "op": op, "end_to_end": e2e, "precision": "f32",
            "n": 1000, "nnz": 20000, "window_s": 10.0, "counters": counters,
            "items": [{"iters": 3, "ok": True}] * deltas, "trace": None,
            "peaks": {"hbm_bytes_per_s": 819e9}}


@pytest.mark.parametrize("rec,want", [
    (_rec("delta", "update_ms", {"update.rows_patched": 96,
                                 "compiles": 0}), 48.0),
    (_rec("delta", "update_ms", {"update.rows_patched": 0}), 0.0),
    # a program without the counter, as before it was added
    (_rec("delta", "update_ms", {"compiles": 0}), None),
    (_rec("delta", "update_ms", {"update.rows_patched": 5}, deltas=0), None),
    (_rec("solve_tol", "solve_ms", {"update.rows_patched": 96}), None),
    (_rec("solve_fixed", "solve_ms", {"update.rows_patched": 96}), None),
    (_rec("ppr_serve", "solve_ms", {"update.rows_patched": 96}), None),
], ids=["per-delta", "zero", "absent", "no-deltas", "solve_tol",
        "solve_fixed", "ppr_serve"])
def test_rows_patched_reads_the_counter_per_delta(rec, want):
    assert cells.metric_reader("update.rows_patched")(rec) == want


def test_rows_patched_is_a_refresh_metric_of_delta_stream():
    from pathlib import Path
    root = Path(__file__).resolve().parents[3]
    cell = cells.load_cell(root, "graph500-20.delta-stream")
    names = {m["name"] for m in cell.per_layer}
    assert "update.rows_patched" in names
    for name in ("graph500-20.pr-tol", "graph500-20.ppr-serve"):
        assert "update.rows_patched" not in {
            m["name"] for m in cells.load_cell(root, name).per_layer}
