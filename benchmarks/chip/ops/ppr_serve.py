"""Batched personalized PageRank for cold users through
``PageRankQueryEngine.flush``: each call submits one batch of ``batch``
fresh seed sets and flushes it, and ends when the host holds every query's
top-``top_k`` ids and scores.

The engine is the static ``PageRankEngine`` on the configuration's layout;
the serving stack is a ``LandmarkIndex`` of ``hubs`` hub columns that
pushes every query to ``push_tol`` in at most ``max_pushes`` sweeps, a
``ResultCache()`` and ``ServeResilience()``.  Set-up builds the hub
columns, serves one batch of its own, compiles the exact fallback at the
push's width without running it (its 100 sweeps would cost more than the
window), and empties the cache; the window's cache only stores.

Seed sets of ``seeds_per_set`` vertices are drawn uniformly over the
structure's vertices with the mix's own ``draw_seed`` and relabelled as the
run's graph is, as ``delta.py`` draws its deltas.  A batch costs the push
sweeps of its slowest column, which varies from batch to batch by half or
more; so every ``--seed`` serves the same sets, in other labels, and does
the same work.  No set is drawn twice, so no query hits the cache.

A compared query's served vector is the (N,) entry the timed path wrote
to the cache, held to the float64 reference of its seed columns
(``reference.RefGraph.solve``), whose ``step`` teleports the dangling leak
to the seeds as the program does:

- ``l1_vs_f64``: the largest L1 distance of a served vector;
- ``top10_score_err``: the largest of |served score - reference score at
  that id| over the compared top-k, and of the reference's k-th score less
  the smallest served score (a vertex missed from the top-k);
- ``max_residual``: the largest per-column L1 residual at the push's exit
  over every query of the window (``LandmarkIndex.last_info``), held to
  ``push_tol``: a looser push cannot pass for the stated one.

The program has to report that residual: where it does not, the op fails
before it builds anything.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from benchmarks.chip import graphs, reference
from benchmarks.chip.ops import ClosedLoop, Reservoir, rng_for


class Op(ClosedLoop):
    e2e = "solve_ms"

    def __init__(self, cfg: dict, traffic: dict, graph, seed: int,
                 precision: str, metrics, limits: dict):
        from repro.pagerank.engine import PageRankEngine
        from repro.pagerank.landmarks import LandmarkIndex
        from repro.serve import (PageRankQueryEngine, ResultCache,
                                 ServeResilience)
        need = [f"{cls.__name__}.{name}" for cls, name in (
            (LandmarkIndex, "last_info"), (LandmarkIndex, "compile_fallback"))
            if not hasattr(cls, name)]
        if need:
            raise RuntimeError(f"the program lacks {', '.join(need)}: this "
                               "cell reads the push's per-column residual")
        src, dst, n = graph
        self.eng = PageRankEngine(src, dst, n, d=float(cfg["damping"]),
                                  backend=cfg["backend"],
                                  precision=precision, metrics=metrics)
        self.layout = self.eng.layout
        self.lm = LandmarkIndex(self.eng, n_hubs=int(traffic["hubs"]),
                                tol=float(traffic["push_tol"]),
                                max_pushes=int(traffic["max_pushes"]),
                                metrics=metrics)
        self.batch = int(traffic["batch"])
        self.top_k = int(traffic["top_k"])
        self.qe = PageRankQueryEngine(self.eng, max_batch=self.batch,
                                      resilience=ServeResilience(),
                                      cache=ResultCache(),
                                      landmarks=self.lm, metrics=metrics)
        self._ResultCache = ResultCache
        self.precision = precision
        self.tol = float(traffic["push_tol"])
        self.graph = graph
        self.limits = limits
        self.sets = SeedSets(traffic, graph, seed, stream=5)
        self.sample = Reservoir(int(traffic["sample"]), rng_for(seed, 3))
        self.residuals: list[float] = []    # max push residual per batch
        self.uid = 0

    def warm(self) -> None:
        t0 = time.perf_counter()
        self.lm.build(0)
        t1 = time.perf_counter()
        self.lm.compile_fallback(self.batch)
        t2 = time.perf_counter()
        warm_sets = SeedSets.like(self.sets, stream=6)
        self._serve(warm_sets.draw(self.batch))
        self.qe.cache = self._ResultCache()
        self.residuals.clear()
        print(f"warm hub_build_s={t1 - t0!r} fallback_compile_s={t2 - t1!r}"
              f" warm_batch_s={time.perf_counter() - t2!r}",
              file=sys.stderr, flush=True)

    def _serve(self, sets: list) -> list:
        self.lm.last_info = None
        qs = [self.qe.submit(self.uid + i, s, top_k=self.top_k)
              for i, s in enumerate(sets)]
        self.uid += len(qs)
        self.qe.flush()
        return qs

    def call(self) -> dict:
        t0 = time.perf_counter()
        qs = self._serve(self.sets.draw(self.batch))
        info = self.lm.last_info
        ok = info is not None and all(q.status == "fresh" for q in qs)
        if info is not None:
            self.residuals.append(float(np.max(info["residuals"])))
        for q in qs:
            key = self._ResultCache.key(q.seeds, self.precision)
            ranks = self.qe.cache.get(key, self.qe.graph_version)
            if ranks is not None and q.result is not None:
                self.sample.offer((q.seeds, *q.result, ranks))
        return {"ok": bool(ok), "s": time.perf_counter() - t0,
                "sweeps": info["sweeps"] if info is not None else None,
                "fallbacks": info["fallbacks"] if info is not None else None}

    def end_to_end(self, window_s: float, items: list) -> dict:
        # seconds and push sweeps of each batch, for reading a run whose
        # solve_ms stands apart
        print("batches " + " ".join(f"{it.get('s', 0.0):.3f}s/"
                                    f"{it.get('sweeps')}" for it in items),
              file=sys.stderr)
        return {"solve_ms": window_s * 1e3 / len(items)}

    def checks(self) -> dict:
        got = self.sample.items
        d = self.eng.d
        del self.qe, self.lm, self.eng
        src, dst, n = self.graph
        V = np.zeros((n, len(got)))
        for j, (seeds, _, _, _) in enumerate(got):
            np.add.at(V[:, j], seeds, 1.0 / len(seeds))
        ref = reference.RefGraph(src, dst, n).solve(v=V, d=d, tol=1e-10)
        l1 = top = 0.0
        for j, (_, idx, scores, ranks) in enumerate(got):
            x = ref[:, j]
            l1 = max(l1, float(np.abs(np.asarray(ranks, np.float64)
                                      - x).sum()))
            kth = float(np.partition(x, -len(idx))[-len(idx)])
            top = max(top, float(np.abs(scores - x[idx]).max()),
                      kth - float(np.min(scores)))
        res = max(self.residuals, default=float("inf"))
        lim, tlim = self.limits["l1_vs_f64"], self.limits["top10_score_err"]
        return {"l1_vs_f64": (l1, lim, l1 <= lim),
                "top10_score_err": (top, tlim, top <= tlim),
                "max_residual": (res, self.tol, res <= self.tol),
                "answers_compared": (len(got), ">=1", len(got) >= 1)}


class SeedSets:
    """Fresh seed sets of the mix, drawn on the configuration's structure
    with its ``draw_seed`` and relabelled as the run's graph is."""

    def __init__(self, traffic: dict, graph, seed: int, stream: int):
        self.traffic, self.graph, self.seed = traffic, graph, seed
        self.lo, self.hi = (int(k) for k in traffic["seeds_per_set"])
        self.distinct = bool(traffic["distinct"])
        self.perm = graphs.permutation(seed, graph[2])
        self.rng = rng_for(int(traffic["draw_seed"]), stream)
        self.seen: set = set()

    @classmethod
    def like(cls, other: "SeedSets", stream: int) -> "SeedSets":
        return cls(other.traffic, other.graph, other.seed, stream)

    def draw(self, count: int) -> list[np.ndarray]:
        """``count`` seed sets in the run's labels, none drawn before."""
        n, out = self.graph[2], []
        while len(out) < count:
            size = int(self.rng.integers(self.lo, self.hi + 1))
            s = np.sort(self.rng.choice(n, size=size, replace=False))
            key = s.tobytes()
            if self.distinct and key in self.seen:
                continue
            self.seen.add(key)
            out.append(self.perm[s].astype(np.int64))
        return out
