"""Graph inputs of the benchmark, made from a configuration and a seed.

A configuration names its generator (``graphs/<generator>.py``, with a
``generate(cfg, seed) -> (src, dst, n)``) and a fixed ``structure_seed``:
the graph itself is one dataset, as a Graphalytics graph is.  A run's
``--seed`` permutes the vertex labels, so every seed gets the same sizes
and the same work in another order.

The generated structure is kept as ``.npy`` files under
``<cache_dir>/<config>-<generator>-v<version>/`` so that the runs of a
cell after the first in a checkout load it instead of generating it.
"""
from __future__ import annotations

import importlib
from pathlib import Path

import numpy as np

# bump when a generator's output changes, so stale cached graphs are not read
GENERATOR_VERSION = 1


def _generate(cfg: dict) -> tuple[np.ndarray, np.ndarray, int]:
    mod = importlib.import_module(f"{__name__}.{cfg['generator']}")
    return mod.generate(cfg, int(cfg["structure_seed"]))


def structure(cfg: dict, cache_dir: Path | None
              ) -> tuple[np.ndarray, np.ndarray, int]:
    """The configuration's graph before relabelling: int32 directed
    ``(src, dst)`` holding both directions of each undirected edge, and
    the vertex count.  Read from ``cache_dir`` when it is there."""
    if cache_dir is None:
        return _generate(cfg)
    d = Path(cache_dir) / (f"{cfg['name']}-{cfg['generator']}"
                           f"-v{GENERATOR_VERSION}")
    files = [d / f for f in ("src.npy", "dst.npy", "n.npy")]
    if all(f.exists() for f in files):
        src, dst, n = (np.load(f) for f in files)
        return src, dst, int(n)
    src, dst, n = _generate(cfg)
    d.mkdir(parents=True, exist_ok=True)
    for f, a in zip(files, (src, dst, np.int64(n))):
        tmp = f.with_suffix(".tmp.npy")
        np.save(tmp, a)
        tmp.replace(f)
    return src, dst, n


def load(cfg: dict, seed: int, cache_dir: Path | None
         ) -> tuple[np.ndarray, np.ndarray, int]:
    """The run's graph: the configuration's structure with its vertices
    relabelled by a permutation drawn from ``seed``."""
    src, dst, n = structure(cfg, cache_dir)
    perm = permutation(seed, n)
    return perm[src], perm[dst], n


def permutation(seed: int, n: int) -> np.ndarray:
    """The relabelling of a run: vertex ``v`` of the structure is vertex
    ``permutation(seed, n)[v]`` of the run's graph."""
    return np.random.default_rng(seed).permutation(n).astype(np.int32)
