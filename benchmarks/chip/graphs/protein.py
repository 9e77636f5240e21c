"""The paper's protein-interaction stand-in: a Barabasi-Albert backbone
(mean degree about 8), 5% extra random edges, and 1% of the proteins
isolated, so their columns dangle.

A copy of the program's ``graph.generators.protein_network``, kept here so
that the benchmark's inputs do not change when the program does.
"""
from __future__ import annotations

import numpy as np


def _dedupe_symmetrize(src, dst, n):
    mask = src != dst
    src, dst = src[mask], dst[mask]
    a = np.concatenate([src, dst])
    b = np.concatenate([dst, src])
    _, idx = np.unique(a.astype(np.int64) * n + b, return_index=True)
    return a[idx].astype(np.int32), b[idx].astype(np.int32)


def barabasi_albert(n: int, m_edges: int, seed: int):
    rng = np.random.default_rng(seed)
    repeated: list[int] = []
    src: list[int] = []
    dst: list[int] = []
    for i in range(m_edges + 1):
        for j in range(i + 1, m_edges + 1):
            src.append(i)
            dst.append(j)
            repeated += [i, j]
    for v in range(m_edges + 1, n):
        targets: set[int] = set()
        while len(targets) < m_edges:
            if repeated and rng.random() < 0.9:
                targets.add(repeated[rng.integers(len(repeated))])
            else:
                targets.add(int(rng.integers(0, v)))
        for t in targets:
            src.append(v)
            dst.append(t)
            repeated += [v, t]
    return _dedupe_symmetrize(np.array(src, np.int64),
                              np.array(dst, np.int64), n)


def generate(cfg: dict, seed: int) -> tuple[np.ndarray, np.ndarray, int]:
    """``protein_network(cfg["nodes"], seed)``, edge for edge."""
    n = int(cfg["nodes"])
    rng = np.random.default_rng(seed)
    src, dst = barabasi_albert(n, int(cfg["ba_edges_per_node"]), seed)
    k = max(1, int(float(cfg["noise_edge_share"]) * len(src) / 2))
    ns = rng.integers(0, n, size=k, dtype=np.int64)
    nd = rng.integers(0, n, size=k, dtype=np.int64)
    src, dst = _dedupe_symmetrize(
        np.concatenate([src.astype(np.int64), ns]),
        np.concatenate([dst.astype(np.int64), nd]), n)
    iso = rng.choice(n, size=max(1, int(float(cfg["isolated_share"]) * n)),
                     replace=False)
    cut = np.isin(src, iso) | np.isin(dst, iso)
    return src[~cut], dst[~cut], n
