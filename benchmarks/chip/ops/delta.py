"""Back-to-back edge deltas through ``DynamicPageRankEngine.update``, from
a converged solve.

``deltas`` deltas are drawn in set-up: each ``inserts`` new undirected
edges with both ends drawn with probability proportional to degree + 1,
and ``deletes`` existing undirected edges drawn uniformly, none drawn
twice.  They are drawn on the configuration's structure with the mix's
own ``draw_seed`` and then relabelled as the run's graph is, so every
``--seed`` patches the same rows of the same degrees (the cost of a delta
follows the degrees of the rows it rewrites), in another order.  The cycle
is the deltas in order, then their inverses in reverse order, which brings
the graph back to where it started.  Set-up applies the cycle once and the
window repeats it, so each update in the window meets the same graph and
the same delta as one in set-up, and compiles nothing new (the program
compiles a scatter per number of chunks of rows it patches in each layout
tier).
"""
from __future__ import annotations

import numpy as np

from benchmarks.chip import graphs, reference
from benchmarks.chip.ops import ClosedLoop, rng_for


class Op(ClosedLoop):
    e2e = "update_ms"

    def __init__(self, cfg: dict, traffic: dict, graph, seed: int,
                 precision: str, metrics, limits: dict):
        from repro.graph.delta import GraphDelta
        from repro.pagerank.dynamic import DynamicPageRankEngine
        src, dst, n = graph
        self.eng = DynamicPageRankEngine(src, dst, n,
                                         d=float(cfg["damping"]),
                                         backend=cfg["backend"],
                                         precision=precision,
                                         metrics=metrics)
        self.layout = self.eng.layout
        self.graph = graph
        self.limits = limits
        self.tol = float(traffic["tol"])
        self.cycle = draw_cycle(traffic, graph, seed)
        self._GraphDelta = GraphDelta
        self.applied = 0
        self.answers: list = []     # (cycle position, ranks) per window call

    def warm(self) -> None:
        self.eng.run_tol(tol=self.tol)[0].block_until_ready()
        for d in self.cycle:
            self._apply(d)

    def _apply(self, d) -> tuple:
        self.applied += 1
        pr, info = self.eng.update(self._GraphDelta(*d), tol=self.tol)
        return pr.block_until_ready(), {
            "iters": info.iters, "strategy": info.strategy,
            "coerced_from": info.coerced_from, "ok": bool(info.healthy)}

    def call(self) -> dict:
        pos = self.applied % len(self.cycle)
        pr, item = self._apply(self.cycle[pos])
        self.answers.append((pos, pr))
        return item

    def end_to_end(self, window_s: float, items: list) -> dict:
        return {"update_ms": window_s * 1e3 / len(items)}

    def checks(self) -> dict:
        """Every window answer against the reference on the edges after
        the cycle up to and including its delta (the cycle starts from the
        set-up graph, its last position); the reference of each state
        warm-starts from the previous one's.

        - ``l1_vs_f64``: the largest L1 distance of an answer from the
          reference's ranks;
        - ``l1_over_change``: the largest such distance over the L1 norm of
          the change that the answer's delta makes to the reference's
          ranks.  A delta of a few edges in a large graph moves the ranks
          by little, so a refresh that returns its start can sit inside
          any absolute limit that sound answers need; it cannot sit inside
          this one.
        """
        got = [(pos, np.asarray(x, np.float64)) for pos, x in self.answers]
        d = self.eng.d
        del self.eng, self.answers
        src, dst, n = self.graph
        refs, ref = [], None
        for pos in range(len(self.cycle)):
            s2, d2 = reference.apply_deltas(src, dst, n,
                                            self.cycle[:pos + 1])
            ref = reference.RefGraph(s2, d2, n).solve(d=d, tol=1e-10,
                                                      x0=ref)
            refs.append(ref)
        err = [(float(np.abs(x - refs[p]).sum()),
                float(np.abs(refs[p] - refs[p - 1]).sum())) for p, x in got]
        l1 = max(e for e, _ in err)
        rel = max(e / c for e, c in err)
        lim, rlim = self.limits["l1_vs_f64"], self.limits["l1_over_change"]
        return {"l1_vs_f64": (l1, lim, l1 <= lim),
                "l1_over_change": (rel, rlim, rel <= rlim),
                "answers_compared": (len(got), ">=1", len(got) >= 1)}


def draw_cycle(traffic: dict, graph, seed: int) -> list:
    """The run's cycle of ``(ins_u, ins_v, del_u, del_v)``: the mix's
    deltas drawn on the structure, relabelled as the run's graph, then
    their inverses in reverse order."""
    src, dst, n = graph
    perm = graphs.permutation(seed, n)
    inv = np.argsort(perm).astype(np.int32)
    drawn = draw_deltas(rng_for(int(traffic["draw_seed"]), 4),
                        inv[src], inv[dst], n, int(traffic["deltas"]),
                        int(traffic["inserts"]), int(traffic["deletes"]))
    drawn = [tuple(perm[a] for a in d) for d in drawn]
    undo = [(du, dv, iu, iv) for iu, iv, du, dv in reversed(drawn)]
    return drawn + undo


def draw_deltas(rng: np.random.Generator, src: np.ndarray, dst: np.ndarray,
                n: int, count: int, inserts: int, deletes: int) -> list:
    """``count`` deltas of ``(ins_u, ins_v, del_u, del_v)`` int32 arrays
    over the undirected graph ``(src, dst)`` (both directions listed)."""
    keys = np.sort(np.asarray(src, np.int64) * n + dst)
    fwd = np.flatnonzero(src < dst)
    pick = rng.choice(fwd, size=count * deletes, replace=False)
    du, dv = src[pick].astype(np.int32), dst[pick].astype(np.int32)
    deg = np.bincount(src, minlength=n).astype(np.float64) + 1.0
    p = deg / deg.sum()
    want = count * inserts
    iu = np.empty(0, np.int64)
    iv = np.empty(0, np.int64)
    while len(iu) < want:
        u = rng.choice(n, size=4 * want, p=p)
        v = rng.choice(n, size=4 * want, p=p)
        lo, hi = np.minimum(u, v), np.maximum(u, v)
        k = lo * n + hi
        pos = np.searchsorted(keys, k)
        new = (lo != hi) & (keys[np.minimum(pos, len(keys) - 1)] != k)
        lo, hi = np.concatenate([iu, lo[new]]), np.concatenate([iv, hi[new]])
        _, first = np.unique(lo * n + hi, return_index=True)
        first.sort()
        iu, iv = lo[first], hi[first]
    iu, iv = iu[:want].astype(np.int32), iv[:want].astype(np.int32)
    return [(iu[i * inserts:(i + 1) * inserts],
             iv[i * inserts:(i + 1) * inserts],
             du[i * deletes:(i + 1) * deletes],
             dv[i * deletes:(i + 1) * deletes]) for i in range(count)]
