"""Graph500 Kronecker generator, vectorised (numpy).

The edge model of the Graph500 reference generator: each of the
``edgefactor * 2**scale`` edges picks its quadrant at every one of the
``scale`` levels with probabilities A, B, C and D = 1 - A - B - C.  The
result is then cleaned as LDBC Graphalytics builds its ``graph500-*``
datasets: undirected, self-loops and duplicate edges dropped, vertices
with no edge dropped and the rest numbered 0..n-1 in id order.
"""
from __future__ import annotations

import numpy as np


def raw_edges(scale: int, edgefactor: int, A: float, B: float, C: float,
              rng: np.random.Generator) -> tuple[np.ndarray, np.ndarray]:
    """The ``edgefactor * 2**scale`` generated (i, j) pairs, int64, before
    any cleaning: self-loops and duplicates included."""
    m = edgefactor << scale
    ab = A + B
    c_norm = C / (1.0 - ab)
    a_norm = A / ab
    i = np.zeros(m, np.int64)
    j = np.zeros(m, np.int64)
    for bit in range(scale):
        i_bit = rng.random(m) > ab
        j_bit = rng.random(m) > np.where(i_bit, c_norm, a_norm)
        i |= i_bit.astype(np.int64) << bit
        j |= j_bit.astype(np.int64) << bit
    return i, j


def undirected_compact(i: np.ndarray, j: np.ndarray, n_ids: int
                       ) -> tuple[np.ndarray, np.ndarray, int]:
    """Both directions of every distinct non-loop edge, on vertices
    renumbered to drop those with no edge.  Returns int32 ``(src, dst)``
    sorted by ``(src, dst)`` and the vertex count."""
    keep = i != j
    lo = np.minimum(i[keep], j[keep])
    hi = np.maximum(i[keep], j[keep])
    und = np.unique(lo * n_ids + hi)
    lo, hi = und // n_ids, und % n_ids
    used = np.zeros(n_ids, bool)
    used[lo] = True
    used[hi] = True
    new_id = np.cumsum(used) - 1
    lo, hi = new_id[lo], new_id[hi]
    n = int(used.sum())
    keys = np.sort(np.concatenate([lo * n + hi, hi * n + lo]))
    return (keys // n).astype(np.int32), (keys % n).astype(np.int32), n


def generate(cfg: dict, seed: int) -> tuple[np.ndarray, np.ndarray, int]:
    rng = np.random.default_rng(seed)
    i, j = raw_edges(int(cfg["scale"]), int(cfg["edgefactor"]),
                     float(cfg["A"]), float(cfg["B"]), float(cfg["C"]), rng)
    return undirected_compact(i, j, 1 << int(cfg["scale"]))
