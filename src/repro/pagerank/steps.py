"""Canonical per-iteration PageRank step functions.

Every tier — the reference loops in :mod:`repro.pagerank.dense` /
:mod:`repro.pagerank.sparse` and the whole-loop-compiled
:class:`repro.pagerank.engine.PageRankEngine` — routes through these, so
the arithmetic (and therefore the floating-point result) is defined in
exactly one place.
"""
from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def dense_step(H: jax.Array, pr: jax.Array, d: float) -> jax.Array:
    """One power iteration against a dangling-fixed dense H."""
    n = H.shape[0]
    return d * (H @ pr) + (1.0 - d) / n


def sparse_step(matvec: Callable[[jax.Array], jax.Array], pr: jax.Array,
                dang: jax.Array, d: float, n: int) -> jax.Array:
    """One power iteration with the explicit dangling-leak correction.

    The vector passes (leak, damping) run under the ``pagerank.vector``
    scope; the ELL and SELL matvecs open their own scopes inside it."""
    with jax.named_scope("pagerank.vector"):
        leak = jnp.sum(pr * dang) / n
        return d * (matvec(pr) + leak) + (1.0 - d) / n


def ppr_step(matvec: Callable[[jax.Array], jax.Array], pr: jax.Array,
             v: jax.Array, dang: jax.Array, d: float) -> jax.Array:
    """One personalized step: teleport (and leak) flow to ``v``, not 1/n."""
    with jax.named_scope("pagerank.vector"):
        leak = jnp.sum(pr * dang)
        return d * (matvec(pr) + leak * v) + (1.0 - d) * v


def ppr_step_batched(matvec: Callable[[jax.Array], jax.Array],
                     PR: jax.Array, V: jax.Array, dang: jax.Array,
                     d: float) -> jax.Array:
    """Batched personalized step: ``PR``/``V`` are (N, Q); Q queries share
    the single sweep over H inside ``matvec``."""
    with jax.named_scope("pagerank.vector"):
        leak = jnp.sum(PR * dang[:, None], axis=0)        # (Q,)
        return d * (matvec(PR) + V * leak[None, :]) + (1.0 - d) * V


def seed_matrix(n: int, seed_sets: Sequence[np.ndarray]) -> np.ndarray:
    """Stack per-user seed index sets into the (N, Q) teleport matrix.
    Duplicate indices accumulate (multiplicity weighting), so every
    column is a proper distribution summing to 1."""
    V = np.zeros((n, len(seed_sets)), np.float32)
    for q, seeds in enumerate(seed_sets):
        idx = np.asarray(seeds, np.int64).ravel()
        if idx.size == 0:
            raise ValueError(f"query {q}: empty seed set")
        np.add.at(V[:, q], idx, 1.0 / idx.size)
    return V
