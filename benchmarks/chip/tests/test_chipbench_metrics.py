"""Every per-layer metric reader on canned run records, and the pieces
``BENCHMARK.json`` names being there."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from benchmarks.chip import cells
from benchmarks.chip.work import sweep_bytes

ROOT = Path(__file__).resolve().parents[3]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
PEAKS = {"hbm_bytes_per_s": 819e9}
TRACE = {"busy_s": 8.0, "window_s": 10.0}


def _rec(op, e2e, items, trace=TRACE, n=1000, nnz=20000):
    return {"cell": "c", "op": op, "end_to_end": e2e, "precision": "f32",
            "n": n, "nnz": nnz, "items": items, "window_s": 10.0,
            "counters": {}, "trace": trace, "peaks": PEAKS}


SOLVE = _rec("solve_tol", "solve_ms", [{"iters": 19, "ok": True},
                                       {"iters": 21, "ok": True}])
FIXED = _rec("solve_fixed", "solve_ms", [{"iters": 100, "ok": True}] * 3)
DELTA = _rec("delta", "update_ms", [
    {"iters": 6, "strategy": "push", "coerced_from": None, "ok": True},
    {"iters": 9, "strategy": "push", "coerced_from": None, "ok": True}])

EXPECT = {
    ("solve.iters", "SOLVE"): 20.0,
    ("solve.iters", "FIXED"): None,
    ("solve.iters", "DELTA"): None,
    ("solve.hbm_share", "SOLVE"):
        100 * 40 * sweep_bytes(1000, 20000) / 819e9 / 8.0,
    ("solve.hbm_share", "FIXED"):
        100 * 300 * sweep_bytes(1000, 20000) / 819e9 / 8.0,
    ("solve.hbm_share", "DELTA"): None,
    ("device_idle.solve", "SOLVE"): 20.0,
    ("device_idle.solve", "FIXED"): 20.0,
    ("device_idle.solve", "DELTA"): None,
    ("update.sweeps", "SOLVE"): None,
    ("update.sweeps", "DELTA"): 7.5,
    ("device_idle.update", "SOLVE"): None,
    ("device_idle.update", "DELTA"): 20.0,
}


@pytest.mark.parametrize("name,rec", sorted(EXPECT), ids=str)
def test_metric_reader_on_canned_record(name, rec):
    got = cells.metric_reader(name)(globals()[rec])
    want = EXPECT[(name, rec)]
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("name", ["solve.hbm_share", "device_idle.solve",
                                  "device_idle.update"])
def test_trace_readers_read_nothing_without_a_trace(name):
    for rec in (SOLVE, FIXED, DELTA):
        assert cells.metric_reader(name)(dict(rec, trace=None)) is None


def test_every_named_piece_has_its_file():
    for m in SPEC["per_layer"]:
        assert (cells.BENCH_DIR / "metrics" / f"{m['name']}.py").is_file()
    for w in SPEC["workloads"]:
        cell = cells.load_cell(ROOT, w["name"])
        assert cell.limits and cell.end_to_end and cell.per_layer
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file()
