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
to an exact batched ``engine.ppr_columns`` solve.

**On the device.**  The hub columns stay on the device as one (N, H)
float32 array.  The estimate of a whole batch is one dispatch: one
batched sweep over the layout gives every non-hub seed's one-step term
(``d·H·e_s`` is ``d/outdeg(s)`` on each out-neighbor) and one (N, H) by
(H, Q) product the hub combination.  The pushed (N, Q) matrix stays there
through the fallback, the clip and the renormalization; the host reads
the per-column residuals, which decide the fallback, and nothing of
size N.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.common import F32_DOT
from repro.obs.registry import default_registry
from repro.pagerank.engine import push_loop
from repro.pagerank.steps import ppr_step_batched

__all__ = ["LandmarkIndex"]


@partial(jax.jit, static_argnames=("n",))
def _teleport(ids, w, *, n: int):
    """The (n, Q) teleport matrix of the (Q, S) seed ``ids`` and weights
    ``w``, made on the device; duplicates add up, weight-0 ids pad."""
    cols = jnp.arange(ids.shape[0])[:, None]
    return jnp.zeros((n, ids.shape[0]), jnp.float32).at[ids, cols].add(w)


def _seed_ids(n: int, seed_sets, width: int) -> tuple[np.ndarray, ...]:
    """``width`` rows of seed ids and weights 1/|set| for :func:`_teleport`
    (rows past the sets and slots past a set's size hold weight 0), each
    row as long as the next power of two of the largest set; checked
    here, since a device scatter drops an id out of range."""
    sets = [np.asarray(s, np.int64).ravel() for s in seed_sets]
    ids = np.zeros((width, _padded(max(len(s) for s in sets))), np.int32)
    w = np.zeros(ids.shape, np.float32)
    for j, s in enumerate(sets):
        if s.size == 0 or s.min() < 0 or s.max() >= n:
            raise ValueError(f"query {j}: seed set empty or out of "
                             f"[0, {n})")
        ids[j, :s.size], w[j, :s.size] = s, 1.0 / s.size
    return ids, w


@partial(jax.jit, static_argnames=("d",))
def _resolvent_columns(X, dang, *, d: float):
    """``x(e_h) = c_h · R e_h`` with ``c_h = (1−d) + d·dangᵀx(e_h)``: the
    normalization divided back out, so the columns combine linearly."""
    c = (1.0 - d) + d * jnp.sum(X * dang[:X.shape[0], None], axis=0)
    return X / c[None, :]


@partial(jax.jit, static_argnames=("d",))
def _hub_estimate(lay, d: float, V, Y, hubs):
    """Hub-combination warm starts of the (n, Q) query columns ``V`` from
    the (n, H) resolvent columns ``Y`` of the ``hubs``, on the engine's
    prepared layout ``lay``: each column a distribution (clipped at zero,
    renormalized)."""
    with jax.named_scope("pagerank.ppr_estimate"):
        n = V.shape[0]
        hub = jnp.zeros((n, 1), bool).at[hubs].set(True)
        # a non-hub seed expands one step, R·e_s = e_s + d·R·H·e_s; a
        # dangling one has no step (R·e_s = e_s exactly)
        tail = jnp.where(hub, 0.0, V)
        Z = d * lay.mv(lay.pad(tail * (1.0 - lay.dang[:n, None])))[:n]
        # hub out-neighbors (and hub seeds) take their stored columns; tail
        # out-neighbors truncate to R·e_t ≈ e_t
        coef = V[hubs] + Z[hubs]                                 # (H, Q)
        y = tail + jnp.where(hub, 0.0, Z) + jnp.dot(Y, coef,
                                                    precision=F32_DOT)
        return jnp.maximum(y, 0.0) / jnp.maximum(jnp.sum(y, axis=0), 1e-30)


@partial(jax.jit, static_argnames=("d", "max_pushes"))
def _hub_push(lay, d: float, max_pushes: int, V, X0, tol):
    """Pushes the (n, Q) warm starts ``X0`` of the teleport columns ``V``
    below ``tol`` on the batched personalized operator
    ``Ab(X) = d·(H·X + V·leak) + (1−d)·V`` of the engine's prepared layout
    ``lay``; returns the (n, Q) matrix, the per-column residuals (so the
    caller can fall back per query when the push exhausted
    ``max_pushes``) and the sweeps run.  Pad rows (the sharded tiers' N
    padding) and zero columns keep a zero residual, so they never move
    the exit test."""
    n = V.shape[0]
    Vp = lay.pad(V)
    X, R, iters, *_ = push_loop(
        lambda X: ppr_step_batched(lay.mv, X, Vp, lay.dang, d),
        lay.pad(X0), tol, n, max_pushes)
    return X[:n], jnp.sum(jnp.abs(R), axis=0), iters


@partial(jax.jit, static_argnames=("q",))
def _served(X, exact, bad, *, q: int):
    """The first ``q`` columns of the pushed ``X``, those flagged ``bad``
    taken from the ``exact`` solve, clipped and renormalized: exact fixed
    points are distributions, and the push's leftover residual is below
    its bound."""
    X = jnp.maximum(jnp.where(bad[None, :], exact, X)[:, :q], 0.0)
    return X / jnp.sum(X, axis=0, keepdims=True)


# --------------------------------------------------------------------------- #
# the index                                                                   #
# --------------------------------------------------------------------------- #
class LandmarkIndex:
    """Precomputed top-degree hub PPR + hub-combination query answering.

    ``build()`` solves the ``n_hubs`` top-(in+out)-degree hubs as ONE
    batched ``engine.ppr`` dispatch and keeps their resolvent columns on
    the device; ``answer(seed_sets)`` warm-starts from the hub combination
    and pushes the residual below ``tol`` (max per-column L1) in
    ``<= max_pushes`` masked sweeps, falling back to an exact batched solve
    for any column that missed the bound.  ``ensure(version)`` rebuilds
    lazily — at first use and every ``rebuild_every`` graph versions; in
    between, stale hub vectors are safe (the push re-converges on the
    current operands) and only cost sweeps.

    Every answer counts its batched sweeps in ``ppr.sweeps`` and, per query
    column, in ``ppr.column_sweeps`` (padding excluded), and fallback
    columns in ``landmarks.fallbacks``; :attr:`last_info` keeps its info.
    """

    #: the ``info`` of the last :meth:`answer` (None before the first)
    last_info: dict | None = None

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
        self._Y: jax.Array | None = None          # (n, H) resolvent columns
        self._hub_ids: jax.Array | None = None    # the hubs, on the device
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
        """Solve the hubs' columns; returns once they are on the device."""
        e = self.engine
        k = min(self.n_hubs, e.n)
        with self.metrics.span("landmarks.build", hubs=k):
            deg = e._outdeg + e._indeg
            hubs = np.sort(np.argpartition(deg, -k)[-k:].astype(np.int64))
            X = e.ppr([[int(h)] for h in hubs], n_iters=self.n_iters)
            self._Y = _resolvent_columns(X, e.prepared.dang,
                                         d=e.d).block_until_ready()
            self._hub_ids = jnp.asarray(hubs, jnp.int32)
            self.hubs = hubs
            self.built_version = int(version)

    def compile_fallback(self, q: int) -> None:
        """Compile, without running it, the exact fallback that an answer
        of ``q`` queries would run: the batched solve at the push's padded
        width."""
        self.engine.lower_ppr(_padded(q), self.n_iters).compile()

    # ---------------------------- estimate ----------------------------- #
    def estimate(self, seed_sets) -> jax.Array:
        """Hub-combination warm starts: the (n, Q) device matrix, each
        column a distribution."""
        if not self.built:
            self.build(self.built_version or 0)
        with self.metrics.span("landmarks.estimate", q=len(seed_sets)):
            return self._estimate(self._teleport(seed_sets, len(seed_sets)))

    def _teleport(self, seed_sets, width: int) -> jax.Array:
        ids, w = _seed_ids(self.engine.n, seed_sets, width)
        return _teleport(jnp.asarray(ids), jnp.asarray(w), n=self.engine.n)

    def _estimate(self, V: jax.Array) -> jax.Array:
        e = self.engine
        return _hub_estimate(e.prepared, e.d, V, self._Y, self._hub_ids)

    # ----------------------------- answer ------------------------------ #
    def answer(self, seed_sets, tol: float | None = None,
               max_pushes: int | None = None) -> tuple[jax.Array, dict]:
        """Serve ``seed_sets``: hub-combination warm start, bounded
        residual push, exact-solve fallback for any column over the bound.
        Returns ``(X, info)`` with ``X`` the (n, Q) device PPR matrix
        (columns clipped + renormalized: exact fixed points are
        distributions, the push's leftover residual is below ``tol``) and
        ``info`` recording the push's ``sweeps``, the per-column
        ``residuals`` at its exit, ``fallbacks`` and each column's path."""
        if not self.built:
            self.build(self.built_version or 0)
        tol = self.tol if tol is None else float(tol)
        max_pushes = (self.max_pushes if max_pushes is None
                      else int(max_pushes))
        e, m = self.engine, self.metrics
        q = len(seed_sets)
        q_pad = _padded(q)
        with m.span("landmarks.answer", q=q):
            with m.span("landmarks.estimate", q=q):
                # the query axis padded with zero columns (V=0 keeps X=R=0
                # identically, so pad columns never move the max-residual
                # exit test): one program per power of two.  Only the seed
                # ids go up; the (n, Q) matrix is made on the device
                V = self._teleport(seed_sets, q_pad)
                X0 = self._estimate(V)
            with m.span("landmarks.push", q=q):
                X, res_col, sweeps = _hub_push(e.prepared, e.d, max_pushes,
                                               V, X0, tol)
                res = np.asarray(res_col)[:q]
                sweeps = int(sweeps)
            m.counter("ppr.sweeps").inc(sweeps)
            m.counter("ppr.column_sweeps").inc(sweeps * q)
            # NaN-safe: a poisoned column fails `<= tol` and falls back
            bad = np.pad(~(res <= tol), (0, q_pad - q))
            exact = X
            if bad.any():
                exact = e.ppr_columns(
                    np.where(bad[None, :], np.asarray(V), 0.0),
                    n_iters=self.n_iters)
                m.counter("landmarks.fallbacks").inc(int(bad.sum()))
            X = _served(X, exact, jnp.asarray(bad), q=q)
        self.last_info = {
            "sweeps": sweeps, "residuals": res,
            "fallbacks": int(bad.sum()),
            "paths": ["exact" if b else "hub" for b in bad[:q]]}
        return X, self.last_info


def _padded(q: int) -> int:
    """The query axis of an answer of ``q`` queries: the next power of
    two, which bounds the programs compiled."""
    return 1 << max(0, q - 1).bit_length()
