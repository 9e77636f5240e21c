"""Finds a cell's pieces by the names in ``BENCHMARK.json``.

- the configuration: the file the ``configs`` entry names;
- the traffic mix: ``traffic/<traffic>.json``, the parameters of the
  kind of operation it names;
- the kind of operation: ``ops/<op>.py``, whose ``Op`` builds the system
  under test and drives it (``ops/__init__.py``);
- the cell's limits: ``workloads/<cell>.json``, each number compared with
  the limit it is held to;
- the per-layer metrics: ``metrics/<name>.py``, each with a
  ``read(record)`` that returns a number or None.

Adding any of them is adding a file and an entry; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list[dict]      # the cell's entries of ``end_to_end``
    per_layer: list[dict]       # the cell's entries of ``per_layer``


def _for_cell(metrics: list[dict], cell: str) -> list[dict]:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_cell(root: Path, name: str, bench_dir: Path = BENCH_DIR) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``; ``KeyError`` when the
    file lists no such cell."""
    spec = json.loads((Path(root) / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((Path(root) / conf["file"]).read_text())
    config.setdefault("name", conf["name"])
    traffic = json.loads(
        (bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads(
        (bench_dir / "workloads" / f"{name}.json").read_text())["limits"]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits,
                end_to_end=_for_cell(spec["end_to_end"], name),
                per_layer=_for_cell(spec["per_layer"], name))


def _module(kind: str, name: str, bench_dir: Path):
    path = bench_dir / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"_bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench_dir: Path = BENCH_DIR):
    """The ``read(record)`` of ``metrics/<name>.py``."""
    return _module("metrics", name, bench_dir).read


def load_op(name: str, bench_dir: Path = BENCH_DIR):
    """The class ``Op`` of ``ops/<name>.py``."""
    return _module("ops", name, bench_dir).Op
