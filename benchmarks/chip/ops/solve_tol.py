"""Back-to-back global solves from the uniform start to an L1 step of
``tol`` (``PageRankEngine.run_tol``), each ended by its host read."""
from __future__ import annotations

import numpy as np

from benchmarks.chip import reference
from benchmarks.chip.ops import ClosedLoop, Reservoir, rng_for


class Op(ClosedLoop):
    e2e = "solve_ms"

    def __init__(self, cfg: dict, traffic: dict, graph, seed: int,
                 precision: str, metrics, limits: dict):
        from repro.pagerank.engine import PageRankEngine
        src, dst, n = graph
        self.eng = PageRankEngine(src, dst, n, d=float(cfg["damping"]),
                                  backend=cfg["backend"],
                                  precision=precision, metrics=metrics)
        self.layout = self.eng.layout
        self.tol = float(traffic.get("tol", 0))
        self.max_iters = int(traffic.get("max_iters", 0))
        self.graph = graph
        self.limits = limits
        self.sample = Reservoir(int(traffic.get("sample", 1 << 30)),
                                rng_for(seed, 3))

    def warm(self) -> None:
        self.call(keep=False)

    def call(self, keep: bool = True) -> dict:
        res = self.eng.run_tol(tol=self.tol, max_iters=self.max_iters)
        pr = res[0].block_until_ready()
        item = {"iters": res.info.iters, "ok": bool(res.info.converged)}
        if keep:
            self.sample.offer(pr)
        return item

    def end_to_end(self, window_s: float, items: list) -> dict:
        return {"solve_ms": window_s * 1e3 / len(items)}

    def _reference(self, g: reference.RefGraph, d: float) -> np.ndarray:
        return g.solve(d=d, tol=1e-10)

    def checks(self) -> dict:
        got = [np.asarray(x, np.float64) for x in self.sample.items]
        d = self.eng.d
        del self.eng
        src, dst, n = self.graph
        ref = self._reference(reference.RefGraph(src, dst, n), d)
        l1 = max(float(np.abs(x - ref).sum()) for x in got)
        lim = self.limits["l1_vs_f64"]
        return {"l1_vs_f64": (l1, lim, l1 <= lim),
                "answers_compared": (len(got), ">=1", len(got) >= 1)}
