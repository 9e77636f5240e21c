"""MELOPPR-style landmark/hub PPR precomputation for the serve path.

On the power-law graphs this system serves, a small set of top-degree
hubs dominates random walks: most of any personalized-PageRank vector's
mass flows through them.  :class:`LandmarkIndex` exploits that by
precomputing the PPR vectors of the top-degree hubs ONCE (one batched
(N, H) dispatch through the existing engine solver, any backend /
precision tier) and answering arbitrary queries as a cheap linear
combination of those vectors plus a short, bounded Gauss–Southwell
residual push.

**The algebra.**  With the dangling leak teleported to the seed
distribution ``v``, the PPR fixed point satisfies
``x = d·H·x + (d·dangᵀx + (1−d))·v``, i.e. ``x(v) = normalize(R·v)``
with the resolvent ``R = (I − dH)⁻¹``.  ``R`` is *linear* in ``v``, so:

* per hub ``h`` the engine's solved ``x(e_h)`` gives the resolvent
  column ``R·e_h = x(e_h) / c_h`` with ``c_h = (1−d) + d·dangᵀx(e_h)``;
* a query over seeds S combines columns: ``R·v = Σ_s w_s·R·e_s``;
* for a non-hub seed, ``R = I + d·R·H`` expands one step exactly:
  ``R·e_s = e_s + (d/outdeg(s))·Σ_{t∈out(s)} R·e_t`` — hub
  out-neighbors use their stored columns, tail out-neighbors truncate to
  ``R·e_t ≈ e_t`` (the MELOPPR decomposition).

The combination is only the **warm start**: the answer then runs a
frontier push (the same masked-sweep Gauss–Southwell machinery as the
dynamic engine's delta refresh, on the batched personalized operator)
down to ``tol`` against the *current* layout operands.  That makes
correctness independent of hub quality — stale or truncated hub vectors
only cost extra sweeps, never accuracy — which is why the index can
tolerate graph deltas between rebuilds (`rebuild_every`).  Any column
whose residual bound is not met within ``max_pushes`` sweeps falls back
to an exact batched ``engine.ppr`` solve.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.common import upcast_f32
from repro.kernels.streaming_matvec import streaming_matvec
from repro.obs.registry import default_registry
from repro.obs.trace import instrumented_tol_loop
from repro.pagerank.distributed import sell_mv_sharded
from repro.pagerank.engine import SHARDED_BACKENDS, _matvec, _row_scale
from repro.pagerank.steps import ppr_step_batched, seed_matrix

__all__ = ["LandmarkIndex"]


def _key_slice(sorted_keys: np.ndarray, u: int, n: int) -> np.ndarray:
    """Out-neighbors of ``u`` from the engine's sorted src*n+dst keys."""
    lo = np.searchsorted(sorted_keys, u * np.int64(n))
    hi = np.searchsorted(sorted_keys, (u + 1) * np.int64(n))
    return (sorted_keys[lo:hi] % n).astype(np.int64)


# --------------------------------------------------------------------------- #
# batched Gauss–Southwell residual push on the personalized operator          #
#                                                                             #
# Same masked-sweep shape as repro.pagerank.dynamic._push_loop, lifted to     #
# the batched (N, Q) personalized affine operator                             #
# Ab(X) = d·(H·X + V·leak) + (1−d)·V, on the same instrumented while_loop     #
# driver.  The loop residual is the MAX per-column L1 residual, so exit       #
# means every query met the bound; per-column residuals come back so the      #
# caller can fall back per query when the loop exhausted max_pushes.          #
# --------------------------------------------------------------------------- #
def _batched_push(Ab, X0, tol, n, max_pushes):
    thresh = tol / n

    def step(state):
        X, R = state
        X = X + R * (jnp.abs(R) >= thresh).astype(X.dtype)
        R = Ab(X) - X
        return (X, R), jnp.max(jnp.sum(jnp.abs(R), axis=0))

    R0 = Ab(X0) - X0
    (X, R), iters, res, grow, _ = instrumented_tol_loop(
        step, (X0, R0), tol=tol, max_iters=max_pushes, watchdog=True,
        trace=False, res0=jnp.max(jnp.sum(jnp.abs(R0), axis=0)))
    return X, jnp.sum(jnp.abs(R), axis=0), iters, res, grow


@partial(jax.jit, static_argnames=("backend", "n", "max_pushes", "d",
                                   "mesh", "axes"))
def _hub_push(operands, dang, scales, V, X0, tol, *, backend: str, n: int,
              max_pushes: int, d: float, mesh=None, axes=()):
    if backend == "dense":
        # the f32 dense operand is dangling-FIXED; masking the dangling
        # columns reconstructs the unfixed H (a no-op on the reduced
        # tiers, which store H unfixed) — same trick as engine._run_ppr
        op_scales = operands[1] if len(operands) == 2 else None
        H = upcast_f32(operands[0]) * (1.0 - dang)[None, :]
        mv = lambda X: _row_scale(H @ X, op_scales)
    elif backend == "dense_sharded":
        # stored dangling-unfixed; GSPMD propagates the P(row, col) layout
        mv = lambda X: _row_scale(upcast_f32(operands[0]) @ X, scales)
    elif backend == "ell_sharded":
        # every device sweeps its own SELL rows for all query columns
        mv = lambda X: sell_mv_sharded(operands, X, mesh, axes)
    else:
        mv = lambda X: _matvec(backend, operands, X)

    def Ab(X):
        return ppr_step_batched(mv, X, V, dang, d)

    return _batched_push(Ab, X0, tol, n, max_pushes)


@partial(jax.jit, static_argnames=("n", "max_pushes", "d", "block_n",
                                   "block_m", "interpret"))
def _hub_push_pallas(Hp, dangp, scales, Vp, X0p, tol, *, n: int,
                     max_pushes: int, d: float, block_n: int, block_m: int,
                     interpret: bool):
    # pre-padded transposed (Q, Mp) layout like engine._run_ppr_pallas;
    # pad entries of H/dang/V/X0 are zero so their residual stays zero and
    # the frontier never touches the pad tail
    thresh = tol / n

    def Ab(Xp):
        leak = jnp.sum(Xp * dangp, axis=1)                 # (Q,)
        Y = streaming_matvec(Hp, Xp, block_n=block_n, block_m=block_m,
                             interpret=interpret)
        if scales is not None:
            Y = Y * scales
        return d * (Y + Vp * leak[:, None]) + (1.0 - d) * Vp

    def step(state):
        Xp, R = state
        Xp = Xp + R * (jnp.abs(R) >= thresh).astype(Xp.dtype)
        R = Ab(Xp) - Xp
        return (Xp, R), jnp.max(jnp.sum(jnp.abs(R), axis=1))

    R0 = Ab(X0p) - X0p
    (Xp, R), iters, res, grow, _ = instrumented_tol_loop(
        step, (X0p, R0), tol=tol, max_iters=max_pushes, watchdog=True,
        trace=False, res0=jnp.max(jnp.sum(jnp.abs(R0), axis=1)))
    return Xp[:, :n].T, jnp.sum(jnp.abs(R), axis=1), iters, res, grow


# --------------------------------------------------------------------------- #
# the index                                                                   #
# --------------------------------------------------------------------------- #
class LandmarkIndex:
    """Precomputed top-degree hub PPR + hub-combination query answering.

    ``build()`` solves the ``n_hubs`` top-(in+out)-degree hubs as ONE
    batched ``engine.ppr`` dispatch and stores their resolvent columns;
    ``answer(seed_sets)`` warm-starts from the hub combination and pushes
    the residual below ``tol`` (max per-column L1) in ``<= max_pushes``
    masked sweeps, falling back to an exact batched solve for any column
    that missed the bound.  ``ensure(version)`` rebuilds lazily — at
    first use and every ``rebuild_every`` graph versions; in between,
    stale hub vectors are safe (the push re-converges on the current
    operands) and only cost sweeps.
    """

    def __init__(self, engine, n_hubs: int = 64, tol: float = 1e-7,
                 max_pushes: int = 256, n_iters: int = 100,
                 rebuild_every: int = 16, metrics=None):
        self.engine = engine
        self.n_hubs = int(n_hubs)
        self.tol = float(tol)
        self.max_pushes = int(max_pushes)
        self.n_iters = int(n_iters)
        self.rebuild_every = max(1, int(rebuild_every))
        self.metrics = (metrics if metrics is not None
                        else getattr(engine, "metrics", None)
                        or default_registry())
        self.hubs: np.ndarray | None = None       # (H,) sorted node ids
        self._Y: np.ndarray | None = None         # (n, H) resolvent columns
        self._hub_pos: np.ndarray | None = None   # node -> column, -1 = tail
        self.built_version: int | None = None

    # ------------------------------ build ------------------------------ #
    @property
    def built(self) -> bool:
        return self._Y is not None

    def ensure(self, version: int = 0) -> None:
        if (self.built_version is not None
                and abs(int(version) - self.built_version)
                < self.rebuild_every):
            return
        self.build(version)

    def build(self, version: int = 0) -> None:
        e = self.engine
        k = min(self.n_hubs, e.n)
        with self.metrics.span("landmarks.build", hubs=k):
            deg = e._outdeg + e._indeg
            hubs = np.sort(np.argpartition(deg, -k)[-k:].astype(np.int64))
            X = np.asarray(e.ppr([[int(h)] for h in hubs],
                                 n_iters=self.n_iters), np.float64)
            # x(e_h) = c_h · R e_h with c_h = (1−d) + d·dangᵀx(e_h): divide
            # the normalization back out so columns combine linearly
            dang = np.asarray(e._dang, np.float64)[:e.n]
            c = (1.0 - e.d) + e.d * (dang @ X)                    # (H,)
            self._Y = (X / c[None, :]).astype(np.float32)
            self._hub_pos = np.full(e.n, -1, np.int64)
            self._hub_pos[hubs] = np.arange(k)
            self.hubs = hubs
            self.built_version = int(version)

    # ---------------------------- estimate ----------------------------- #
    def estimate(self, seed_sets) -> tuple[np.ndarray, list[float]]:
        """Hub-combination warm starts: the (n, Q) estimate matrix (each
        column a distribution) plus the per-query fraction of one-step
        walk mass covered by stored hub columns (1.0 = fully hub-resolved,
        0.0 = pure truncation)."""
        e, d = self.engine, self.engine.d
        n = e.n
        Y, pos = self._Y, self._hub_pos
        X0 = np.zeros((n, len(seed_sets)), np.float32)
        coverage = []
        for q, seeds in enumerate(seed_sets):
            idx = np.asarray(seeds, np.int64).ravel()
            w = 1.0 / idx.size
            y = X0[:, q]
            covered = total = 0.0
            for s in idx:
                s = int(s)
                j = pos[s]
                if j >= 0:
                    y += w * Y[:, j]
                    covered += w
                    total += w
                    continue
                total += w
                y[s] += w
                outdeg = int(e._outdeg[s])
                if outdeg == 0:
                    covered += w          # dangling: R·e_s = e_s exactly
                    continue
                nbrs = _key_slice(e._keys, s, n)
                ws = w * d / outdeg
                hub_n = nbrs[pos[nbrs] >= 0]
                tail_n = nbrs[pos[nbrs] < 0]
                if hub_n.size:
                    y += ws * Y[:, pos[hub_n]].sum(axis=1)
                if tail_n.size:
                    np.add.at(y, tail_n, ws)
                covered += w * (1.0 - d) + ws * hub_n.size
            X0[:, q] = np.maximum(y, 0.0) / max(float(y.sum()), 1e-30)
            coverage.append(covered / max(total, 1e-30))
        return X0, coverage

    # ----------------------------- answer ------------------------------ #
    def answer(self, seed_sets, tol: float | None = None,
               max_pushes: int | None = None) -> tuple[np.ndarray, dict]:
        """Serve ``seed_sets``: hub-combination warm start, bounded
        residual push, exact-solve fallback for any column over the bound.
        Returns ``(X, info)`` with ``X`` the (n, Q) PPR matrix (columns
        clipped + renormalized: exact fixed points are distributions, the
        push's leftover residual is below ``tol``) and ``info`` recording
        sweeps / fallbacks / paths / hub coverage."""
        if not self.built:
            self.build(self.built_version or 0)
        tol = self.tol if tol is None else float(tol)
        max_pushes = (self.max_pushes if max_pushes is None
                      else int(max_pushes))
        e = self.engine
        q = len(seed_sets)
        with self.metrics.span("landmarks.answer", q=q):
            X0, coverage = self.estimate(seed_sets)
            V = seed_matrix(e.n, seed_sets)
            # pad the query axis to the next power of two with zero
            # columns (V=0 keeps X=R=0 identically, so pad columns never
            # move the max-residual exit test) to bound recompiles
            q_pad = 1 << max(0, q - 1).bit_length()
            if q_pad != q:
                V = np.pad(V, ((0, 0), (0, q_pad - q)))
                X0 = np.pad(X0, ((0, 0), (0, q_pad - q)))
            X, res_col, sweeps = self._push(V, X0, tol, max_pushes)
            X, res_col = X[:, :q], res_col[:q]
            # NaN-safe: a poisoned column fails `<= tol` and falls back
            bad = np.flatnonzero(~(res_col <= tol))
            if bad.size:
                exact = np.asarray(e.ppr([seed_sets[j] for j in bad],
                                         n_iters=self.n_iters))
                X = np.array(X)         # device buffers are read-only
                X[:, bad] = exact
                self.metrics.counter("landmarks.fallbacks").inc(
                    int(bad.size))
            X = np.clip(X, 0.0, None)
            X /= X.sum(axis=0, keepdims=True)
        bad_set = set(int(j) for j in bad)
        return X, {"sweeps": int(sweeps), "fallbacks": int(bad.size),
                   "paths": ["exact" if j in bad_set else "hub"
                             for j in range(q)],
                   "coverage": coverage}

    # ------------------------- backend dispatch ------------------------ #
    def _push(self, V, X0, tol, max_pushes):
        e = self.engine
        if e.backend == "pallas_dense":
            Hp, dangp = e._operands
            Mp, q = Hp.shape[1], V.shape[1]
            Vp = np.zeros((q, Mp), np.float32)
            X0p = np.zeros((q, Mp), np.float32)
            Vp[:, :e.n], X0p[:, :e.n] = V.T, X0.T
            X, res_col, sweeps, _, _ = _hub_push_pallas(
                Hp, dangp, e._scales, jnp.asarray(Vp), jnp.asarray(X0p),
                tol, n=e.n, max_pushes=max_pushes, d=e.d,
                block_n=e._block[0], block_m=e._block[1],
                interpret=e.interpret)
            return np.asarray(X), np.asarray(res_col), int(sweeps)
        if e.backend in SHARDED_BACKENDS:
            n_pad, q = e._n_pad, V.shape[1]
            Vp = np.zeros((n_pad, q), np.float32)
            X0p = np.zeros((n_pad, q), np.float32)
            Vp[:e.n], X0p[:e.n] = V, X0
            X, res_col, sweeps, _, _ = _hub_push(
                e._operands, e._dang, e._scales, jnp.asarray(Vp),
                jnp.asarray(X0p), tol, backend=e.backend, n=e.n,
                max_pushes=max_pushes, d=e.d, mesh=e.mesh, axes=e._axes)
            return np.asarray(X)[:e.n], np.asarray(res_col), int(sweeps)
        X, res_col, sweeps, _, _ = _hub_push(
            e._operands, e._dang, None, jnp.asarray(V), jnp.asarray(X0),
            tol, backend=e._mv_backend, n=e.n, max_pushes=max_pushes,
            d=e.d)
        return np.asarray(X), np.asarray(res_col), int(sweeps)
