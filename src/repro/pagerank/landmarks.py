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

from repro.kernels.common import F32_DOT, upcast_f32
from repro.kernels.streaming_matvec import streaming_matvec
from repro.obs.registry import default_registry
from repro.obs.trace import instrumented_tol_loop
from repro.pagerank.distributed import sell_mv_sharded
from repro.pagerank.engine import SHARDED_BACKENDS, _matvec, _row_scale
from repro.pagerank.steps import ppr_step_batched

__all__ = ["LandmarkIndex"]

# the static arguments that pick a layout's batched matvec
_LAYOUT = ("backend", "mesh", "axes", "block", "interpret")


def _batched_mv(operands, dang, scales, *, backend, mesh, axes, block,
                interpret):
    """``X -> H·X`` for a (rows, Q) batch on an engine's prepared layout,
    ``H`` the transition matrix without the dangling fix (the PPR leak
    teleports to the seeds, not 1/n); ``rows`` is the layout's row count,
    the padded N on the sharded tiers."""
    if backend == "dense":
        # the f32 dense operand is dangling-FIXED; masking the dangling
        # columns reconstructs the unfixed H (a no-op on the reduced
        # tiers, which store H unfixed) — same trick as engine._run_ppr
        op_scales = operands[1] if len(operands) == 2 else None
        H = upcast_f32(operands[0]) * (1.0 - dang)[None, :]
        return lambda X: _row_scale(H @ X, op_scales)
    if backend == "dense_sharded":
        # stored dangling-unfixed; GSPMD propagates the P(row, col) layout
        return lambda X: _row_scale(upcast_f32(operands[0]) @ X, scales)
    if backend == "ell_sharded":
        # every device sweeps its own SELL rows for all query columns
        return lambda X: sell_mv_sharded(operands, X, mesh, axes)
    if backend == "pallas_dense":
        # the pre-padded unfixed Hp streams the transposed, zero-padded
        # (Q, Mp) batch, as engine._run_ppr_pallas does
        Hp = operands[0]

        def mv(X):
            rows = X.shape[0]
            Y = streaming_matvec(Hp, jnp.pad(X.T, ((0, 0),
                                                   (0, Hp.shape[1] - rows))),
                                 block_n=block[0], block_m=block[1],
                                 interpret=interpret)
            if scales is not None:
                Y = Y * scales
            return Y[:, :rows].T
        return mv
    return lambda X: _matvec(backend, operands, X)


def _pad_rows(X, rows: int):
    return jnp.pad(X, ((0, rows - X.shape[0]), (0, 0)))


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


@partial(jax.jit, static_argnames=("d",) + _LAYOUT)
def _hub_estimate(operands, dang, scales, V, Y, hubs, *, d: float, backend,
                  mesh=None, axes=(), block=(0, 0), interpret=False):
    """Hub-combination warm starts of the (n, Q) query columns ``V`` from
    the (n, H) resolvent columns ``Y`` of the ``hubs``: each column a
    distribution (clipped at zero, renormalized)."""
    with jax.named_scope("pagerank.ppr_estimate"):
        n = V.shape[0]
        mv = _batched_mv(operands, dang, scales, backend=backend, mesh=mesh,
                         axes=axes, block=block, interpret=interpret)
        hub = jnp.zeros((n, 1), bool).at[hubs].set(True)
        # a non-hub seed expands one step, R·e_s = e_s + d·R·H·e_s; a
        # dangling one has no step (R·e_s = e_s exactly)
        tail = jnp.where(hub, 0.0, V)
        Z = d * mv(_pad_rows(tail * (1.0 - dang[:n, None]),
                             dang.shape[0]))[:n]
        # hub out-neighbors (and hub seeds) take their stored columns; tail
        # out-neighbors truncate to R·e_t ≈ e_t
        coef = V[hubs] + Z[hubs]                                 # (H, Q)
        y = tail + jnp.where(hub, 0.0, Z) + jnp.dot(Y, coef,
                                                    precision=F32_DOT)
        return jnp.maximum(y, 0.0) / jnp.maximum(jnp.sum(y, axis=0), 1e-30)


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


@partial(jax.jit, static_argnames=("max_pushes", "d") + _LAYOUT)
def _hub_push(operands, dang, scales, V, X0, tol, *, max_pushes: int,
              d: float, backend, mesh=None, axes=(), block=(0, 0),
              interpret=False):
    """Pushes the (n, Q) warm starts ``X0`` of the teleport columns ``V``
    below ``tol``; returns the (n, Q) matrix, the per-column residuals and
    the sweeps run.  Pad rows (the sharded tiers' N padding) and zero
    columns keep a zero residual, so they never move the exit test."""
    n = V.shape[0]
    mv = _batched_mv(operands, dang, scales, backend=backend, mesh=mesh,
                     axes=axes, block=block, interpret=interpret)
    Vp = _pad_rows(V, dang.shape[0])

    def Ab(X):
        return ppr_step_batched(mv, X, Vp, dang, d)

    X, res_col, iters, _, _ = _batched_push(
        Ab, _pad_rows(X0, dang.shape[0]), tol, n, max_pushes)
    return X[:n], res_col, iters


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
            self._Y = _resolvent_columns(X, e._dang,
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
        return _hub_estimate(e._operands, e._dang, e._scales, V, self._Y,
                             self._hub_ids, d=e.d, **self._layout())

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
                X, res_col, sweeps = _hub_push(
                    e._operands, e._dang, e._scales, V, X0, tol,
                    max_pushes=max_pushes, d=e.d, **self._layout())
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

    def _layout(self) -> dict:
        """The static arguments that pick the engine's batched matvec."""
        e = self.engine
        sharded_or_pallas = SHARDED_BACKENDS + ("pallas_dense",)
        return dict(backend=(e.backend if e.backend in sharded_or_pallas
                             else e._mv_backend),
                    mesh=e.mesh, axes=e._axes, block=tuple(e._block),
                    interpret=e.interpret)


def _padded(q: int) -> int:
    """The query axis of an answer of ``q`` queries: the next power of
    two, which bounds the programs compiled."""
    return 1 << max(0, q - 1).bit_length()
