"""The plain float64 reference the benchmark holds the program to.

Independent of the program: a scipy CSR power iteration over the
deduplicated directed edges, with the program's semantics.  Transitions
are column-stochastic (an edge u -> v carries 1 / outdeg(u)), the mass of
dangling vertices goes to the teleport distribution, and the iteration
starts from the teleport vector itself.  It iterates one vector (n,) or a
batch of columns (n, Q).
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp


class RefGraph:
    def __init__(self, src: np.ndarray, dst: np.ndarray, n: int):
        keys = np.unique(np.asarray(src, np.int64) * n
                         + np.asarray(dst, np.int64))
        self.n = n
        src, dst = keys // n, keys % n
        outdeg = np.bincount(src, minlength=n).astype(np.float64)
        self.H = sp.csr_matrix((1.0 / outdeg[src], (dst, src)),
                               shape=(n, n))
        self.dang = outdeg == 0

    def step(self, x: np.ndarray, v: np.ndarray, d: float) -> np.ndarray:
        return d * (self.H @ x + x[self.dang].sum(axis=0) * v) \
            + (1.0 - d) * v

    def solve(self, v: np.ndarray | None = None, d: float = 0.85,
              n_iters: int | None = None, tol: float = 1e-10,
              max_iters: int = 10_000, x0: np.ndarray | None = None
              ) -> np.ndarray:
        """From ``x0`` (``v`` when None; ``v`` uniform when None):
        ``n_iters`` iterations, or until every column's L1 step is at most
        ``tol``."""
        v = np.full(self.n, 1.0 / self.n) if v is None else v
        x = (v if x0 is None else x0).copy()
        for _ in range(n_iters if n_iters is not None else max_iters):
            new = self.step(x, v, d)
            done = (n_iters is None
                    and np.abs(new - x).sum(axis=0).max() <= tol)
            x = new
            if done:
                break
        return x


def apply_deltas(src: np.ndarray, dst: np.ndarray, n: int, deltas
                 ) -> tuple[np.ndarray, np.ndarray]:
    """The undirected graph after each of ``deltas`` in turn, from the edge
    lists alone.  Each delta is ``(ins_u, ins_v, del_u, del_v)``; both
    directions of each listed edge change, deletions before insertions."""
    keys = np.unique(np.asarray(src, np.int64) * n + dst)

    def both(a, b):
        a, b = np.asarray(a, np.int64), np.asarray(b, np.int64)
        return np.concatenate([a * n + b, b * n + a])

    for iu, iv, du, dv in deltas:
        keys = np.union1d(np.setdiff1d(keys, both(du, dv)), both(iu, iv))
    return keys // n, keys % n
