"""CPU tests of the chip benchmark's harness.

JAX stays on the CPU (a chip belongs to the benchmark's own process), and
the checkout root and the program's ``src`` are importable, as ``run.py``
makes them.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = Path(__file__).resolve().parents[3]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

# tiny stand-ins for each configuration's scale; every width of the
# source (edge factor, A/B/C, BA edges per node) is kept
TINY = {"kronecker": {"scale": 9}, "protein": {"nodes": 300}}


@pytest.fixture
def run_tiny(tmp_path, capsys, monkeypatch):
    """Runs a cell of ``BENCHMARK.json`` through ``run.run_cell`` on the
    CPU at a tiny scale, past the look for a chip, with JAX's persistent
    compile cache left off; returns the parsed result line and the
    standard error."""
    from benchmarks.chip import cells, run
    monkeypatch.setattr("repro.launch.compile_cache.use_compile_cache",
                        lambda: "off")

    def go(name: str, seed: int = 2**31 + 5, seconds: float = 0.3,
           precision: str | None = None):
        cell = cells.load_cell(ROOT, name)
        cfg = dict(cell.config, backend="ell")
        cfg.update(TINY[cfg["generator"]])
        cfg["name"] = f"{cfg['name']}-tiny"
        cell.config = cfg
        args = argparse.Namespace(workload=name, seed=seed, seconds=seconds,
                                  trace=0, precision=precision)
        device = {"platform": "cpu", "kind": "cpu", "count": 1}
        rc = run.run_cell(cell, args, device, {"hbm_bytes_per_s": 819e9},
                          graph_cache=tmp_path / "graphs")
        out, err = capsys.readouterr()
        assert rc == 0, err
        return json.loads(out.strip().splitlines()[-1]), err

    return go
