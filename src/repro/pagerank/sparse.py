"""Sparse PageRank — the production path for real protein networks.

Sparse H drops the dense dangling columns, so the update carries an explicit
dangling correction:

    PR' = d * (H_sparse @ PR + 1*sum(PR[dangling])/N) + (1-d)/N

which equals the dense-H update exactly (tests cross-check).  Works with any
container exposing ``.matvec`` (CSR / ELL / BSR / the Pallas-backed ops).

The per-iteration bodies are the shared steps from
:mod:`repro.pagerank.steps`, so these loops and the whole-loop-compiled
:class:`~repro.pagerank.engine.PageRankEngine` run the same arithmetic.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.pagerank.steps import ppr_step, sparse_step


def pagerank_sparse(matvec: Callable[[jax.Array], jax.Array], n: int,
                    dangling: jax.Array | None = None, d: float = 0.85,
                    n_iters: int = 100) -> jax.Array:
    """Fixed-iteration sparse power iteration.

    ``matvec``: y = H_sparse @ x (column-stochastic except dangling columns)
    ``dangling``: float32 (n,) mask of dangling nodes (1.0 where dangling).
    """
    pr0 = jnp.full((n,), 1.0 / n, jnp.float32)
    dang = (jnp.zeros((n,), jnp.float32) if dangling is None
            else jnp.asarray(dangling, jnp.float32))

    def body(pr, _):
        return sparse_step(matvec, pr, dang, d, n), None

    pr, _ = jax.lax.scan(body, pr0, None, length=n_iters)
    return pr


def pagerank_sparse_tol(matvec: Callable[[jax.Array], jax.Array], n: int,
                        dangling: jax.Array | None = None, d: float = 0.85,
                        tol: float = 1e-6, max_iters: int = 1000):
    """Tolerance-terminated variant; returns (pr, iters, residual)."""
    pr0 = jnp.full((n,), 1.0 / n, jnp.float32)
    dang = (jnp.zeros((n,), jnp.float32) if dangling is None
            else jnp.asarray(dangling, jnp.float32))

    def cond(state):
        _, i, res = state
        return (res > tol) & (i < max_iters)

    def body(state):
        pr, i, _ = state
        new = sparse_step(matvec, pr, dang, d, n)
        return new, i + 1, jnp.sum(jnp.abs(new - pr))

    return jax.lax.while_loop(cond, body,
                              (pr0, jnp.int32(0), jnp.float32(jnp.inf)))


def personalized_pagerank(matvec: Callable[[jax.Array], jax.Array], n: int,
                          seeds: jax.Array,
                          dangling: jax.Array | None = None,
                          d: float = 0.85, n_iters: int = 100) -> jax.Array:
    """Personalized PageRank: the teleport distribution is concentrated on
    ``seeds`` (protein-complex identification à la the paper's ref [7] —
    rank proteins by proximity to a seed set instead of globally).

    ``seeds``: int32 indices of the seed proteins.
    """
    v = jnp.zeros((n,), jnp.float32).at[seeds].set(1.0 / seeds.shape[0])
    pr0 = v
    dang = (jnp.zeros((n,), jnp.float32) if dangling is None
            else jnp.asarray(dangling, jnp.float32))

    def body(pr, _):
        return ppr_step(matvec, pr, v, dang, d), None

    pr, _ = jax.lax.scan(body, pr0, None, length=n_iters)
    return pr
