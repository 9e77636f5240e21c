"""Faults planted under the timed path: the harness's tests plant them at a
tiny size, and this script reads them on the chip at a cell's own size.

    python3 benchmarks/chip/tests/faults.py <fault> --workload <cell> \
        --seconds <s> --seeds <n> [<n> ...]

runs the cell once per seed in one process, through ``run.py``'s own path
(the look for a chip included), with ``<fault>`` planted underneath
(``none`` plants nothing; ``--precision`` gives the lower-precision
control).  Each
run's result line reads ``correct`` false where the benchmark catches the
fault, and its ``checks`` give the number the fault reads.

The faults are those the cells can have: a solve or refresh that returns
its state unchanged (``unchanged``), and an answer altered where it is
produced (``altered``).  The cells run on one chip and take no batch mean,
so a left-out half batch and a left-out exchange between chips have no
place to be planted.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def unchanged(mp, cell: str) -> None:
    """Every sweep or push returns its start."""
    import jax.numpy as jnp

    import repro.pagerank.dynamic as dyn
    import repro.pagerank.engine as eng
    if cell.endswith("delta-stream"):
        def push(self, x0, tol, max_iters):
            z = jnp.zeros((), jnp.int32)
            return x0, z, jnp.zeros((), jnp.float32), z, None
        mp.setattr(dyn.DynamicPageRankEngine, "_push", push)
    else:
        mp.setattr(eng, "sparse_step", lambda mv, pr, dang, d, n: pr)


def altered(mp, cell: str) -> None:
    """One entry of every answer moved by 1e-3 where it is produced."""
    import repro.pagerank.dynamic as dyn
    import repro.pagerank.engine as eng

    def bump(pr):
        return pr.at[3].add(1e-3)
    if cell.endswith("delta-stream"):
        real = dyn.DynamicPageRankEngine.update

        def update(self, delta, **kw):
            pr, info = real(self, delta, **kw)
            return bump(pr), info
        mp.setattr(dyn.DynamicPageRankEngine, "update", update)
    elif cell.endswith("pr-tol"):
        real = eng.PageRankEngine._finish_solve
        mp.setattr(eng.PageRankEngine, "_finish_solve",
                   lambda self, out, *a: real(self, (bump(out[0]),)
                                              + out[1:], *a))
    else:
        real = eng.PageRankEngine.run
        mp.setattr(eng.PageRankEngine, "run",
                   lambda self, n_iters=100: bump(real(self, n_iters)))


def none(mp, cell: str) -> None:
    """Nothing planted: the sound runs of many seeds in one process."""


FAULTS = {"unchanged": unchanged, "altered": altered, "none": none}


def main(argv=None) -> int:
    import pytest

    from benchmarks.chip import run
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("fault", choices=sorted(FAULTS))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--seeds", nargs="+", required=True)
    ap.add_argument("--precision", default=None)
    args = ap.parse_args(argv)
    mp = pytest.MonkeyPatch()
    FAULTS[args.fault](mp, args.workload)
    rc = 0
    try:
        for seed in args.seeds:
            print(f"fault={args.fault} seed={seed}", flush=True)
            rc = rc or run.main(
                ["--workload", args.workload, "--seed", seed,
                 "--seconds", args.seconds]
                + (["--precision", args.precision] if args.precision
                   else []))
    finally:
        mp.undo()
    return rc


if __name__ == "__main__":
    sys.exit(main())
