"""A new cell, configuration, traffic mix, kind of operation or per-layer
metric is a new file and an entry: the loader finds them by name."""
from __future__ import annotations

import json

import pytest

from benchmarks.chip import cells


def test_cell_written_to_a_temporary_checkout_is_loaded(tmp_path):
    bench = tmp_path / "benchmarks" / "chip"
    for d in ("configs", "traffic", "workloads", "metrics", "ops"):
        (bench / d).mkdir(parents=True)
    (bench / "configs" / "ring-9.json").write_text(json.dumps(
        {"generator": "kronecker", "scale": 9, "damping": 0.85}))
    (bench / "traffic" / "pr-loose.json").write_text(json.dumps(
        {"op": "count", "tol": 1e-4, "max_iters": 100}))
    (bench / "ops" / "count.py").write_text(
        "from benchmarks.chip.ops import ClosedLoop\n"
        "class Op(ClosedLoop):\n"
        "    e2e = 'solve_ms'\n"
        "    def __init__(self, cfg, traffic, graph, seed, precision,\n"
        "                 metrics, limits):\n"
        "        self.calls = 0\n"
        "    def call(self):\n"
        "        self.calls += 1\n"
        "        return {'ok': True}\n")
    (bench / "workloads" / "ring-9.pr-loose.json").write_text(json.dumps(
        {"limits": {"l1_vs_f64": 1e-3}}))
    (bench / "metrics" / "solve.count.py").write_text(
        "def read(rec):\n    return float(len(rec['items']))\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "ring-9",
                     "file": "benchmarks/chip/configs/ring-9.json"}],
        "workloads": [{"name": "ring-9.pr-loose", "config": "ring-9",
                       "traffic": "pr-loose", "chips": 1}],
        "end_to_end": [{"name": "setup_s", "unit": "s"},
                       {"name": "solve_ms", "unit": "ms",
                        "workloads": ["ring-9.pr-loose"]},
                       {"name": "update_ms", "unit": "ms",
                        "workloads": ["elsewhere"]}],
        "per_layer": [{"name": "solve.count", "unit": "solves",
                       "workloads": ["ring-9.pr-loose"]}]}))
    cell = cells.load_cell(tmp_path, "ring-9.pr-loose", bench_dir=bench)
    assert cell.config["scale"] == 9 and cell.config["name"] == "ring-9"
    assert cell.traffic["tol"] == 1e-4
    assert cell.limits == {"l1_vs_f64": 1e-3}
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "solve_ms"]
    read = cells.metric_reader("solve.count", bench_dir=bench)
    assert read({"items": [{}, {}]}) == 2.0
    op = cells.load_op(cell.traffic["op"], bench_dir=bench)(
        cell.config, cell.traffic, None, 1, "f32", None, cell.limits)
    items, window_s = op.window(0.0)
    assert items == [{"ok": True}] and op.calls == 1 and window_s >= 0
    with pytest.raises(KeyError):
        cells.load_cell(tmp_path, "ring-9.absent", bench_dir=bench)
