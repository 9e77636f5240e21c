"""Back-to-back fixed-schedule solves (``PageRankEngine.run``), each ended
by ``block_until_ready``; a seeded sample of their answers is compared."""
from __future__ import annotations

import numpy as np

from benchmarks.chip import reference
from benchmarks.chip.ops.solve_tol import Op as SolveTol


class Op(SolveTol):

    def __init__(self, cfg: dict, traffic: dict, graph, seed: int,
                 precision: str, metrics, limits: dict):
        super().__init__(cfg, traffic, graph, seed, precision, metrics,
                         limits)
        self.n_iters = int(traffic["n_iters"])

    def call(self, keep: bool = True) -> dict:
        pr = self.eng.run(n_iters=self.n_iters).block_until_ready()
        if keep:
            self.sample.offer(pr)
        return {"iters": self.n_iters, "ok": True}

    def _reference(self, g: reference.RefGraph, d: float) -> np.ndarray:
        return g.solve(d=d, n_iters=self.n_iters)
