"""Incremental PageRank over a streaming graph.

:class:`DynamicPageRankEngine` extends the whole-loop-compiled
:class:`~repro.pagerank.engine.PageRankEngine` with an ``update()`` path
that folds a :class:`~repro.graph.delta.GraphDelta` into the *prepared*
device layouts in place and re-solves from the previous rank vector —
turning "rebuild every layout and re-run the full power iteration" into
"patch a few rows/columns and spend exactly the work the staleness budget
requires" (the MELOPPR-style low-latency regime).

Three refresh strategies, picked automatically by delta size:

* **push** — a Gauss–Southwell frontier sweep: the residual
  ``r = A·x + b − x`` of the *new* operator at the *old* ranks is nonzero
  only near the changed edges; a ``lax.while_loop`` repeatedly pushes every
  entry of the frontier mask ``|r| ≥ tol/n`` into the iterate and refreshes
  the residual, terminating on ``‖r‖₁ ≤ tol``.  One device dispatch, a
  handful of sweeps.
* **warm-start** — the layouts are patched in place and the existing
  tolerance loop re-runs with ``x0 =`` previous ranks (the new ``x0``
  threading through every ``run_tol`` backend).
* **rebuild** — deltas too large (or structurally too disruptive: an ELL
  row outgrowing its capacity slack, a BSR block materializing outside the
  prepared block structure) fall back to a full layout rebuild, still
  warm-starting the solve.

Layout patches are in-place in the functional-JAX sense — a scatter into
the prepared arrays, never a rebuild:

* **dense / pallas_dense** — the changed transition *columns* are
  recomputed host-side and written with one ``H.at[:, cols].set`` scatter
  (the pre-padded Pallas layout keeps its padding; the dangling row mask is
  patched alongside).
* **ell / ell_sharded** — both are a *sliced* ELLPACK
  (:mod:`repro.pagerank.sell`): rows grouped into degree tiers of doubling
  width, each padded to its width plus ``slack``, one device-local SELL
  per shard on ``ell_sharded``.  The sweep is one dense gather per tier
  and **no** ``segment_sum``, and every affected row is rewritten with one
  row-scatter per tier; on ``ell_sharded`` the scatter output is pinned to
  the tier's row ``NamedSharding`` (a ``with_sharding_constraint``), so
  each write lands on the device owning the row.  The capacity slack means
  small deltas never change any array shape; a row outgrowing its tier
  triggers the rebuild fallback.
* **dense_sharded** — the changed columns are scattered under the 2-D
  fabric ``P(row, col)`` sharding, so each write lands on the mesh column
  that owns it; the padded tail rows/columns stay zero.
* **bsr** — value patches inside the *existing* block structure: a host-
  side sorted (block-row, block-col) -> slot map (reconstructed from the
  edge set, matching ``BSRMatrix.from_dense``'s row-major block order)
  addresses every changed entry as ``blocks[br, slot, r%bs, c%bs]``, and
  one chunked scatter rewrites them.  Deletes zero entries in place (the
  block stays, harmlessly); only an insert that *materializes a new block*
  escalates to the rebuild fallback.

The push strategy runs shard-local on the sharded tiers
(:func:`repro.pagerank.distributed.push_distributed_tol` /
``push_distributed_sparse_tol``): the frontier update is elementwise on
each device's shard of the rank vector and the residual L1 norm costs a
single psum per sweep, inside the same ``instrumented_tol_loop`` driver —
watchdogs, ``SolveResult.info`` and the residual trace ring work on the
mesh exactly as they do single-device, and the auto push/warm/rebuild
policy picks the same strategies sharded as it does single-device.

Host-side bookkeeping is a sorted int64 edge-key set (plus its reverse for
in-neighbor queries) and the degree vectors, so computing affected
columns/rows for a Δ-edge delta costs ``O(Δ·maxdeg + log E)``, not
``O(E)``.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.graph import transition as tr
from repro.graph.delta import GraphDelta, edge_keys
from repro.kernels.streaming_matvec import streaming_matvec
from repro.obs.trace import SolveTrace, instrumented_tol_loop
from repro.pagerank import distributed as dist
from repro.pagerank import sell
from repro.pagerank.engine import PageRankEngine, _dedupe_edges, _matvec
from repro.pagerank.resilience import EngineSnapshot, make_solve_info

__all__ = ["DynamicPageRankEngine", "UpdateInfo", "PATCHABLE_BACKENDS"]

# every backend's prepared layout now accepts in-place edge-delta patches
# (sharded scatters land on the owning devices under the existing
# NamedShardings; BSR patches values inside the prepared block structure).
# Capacity overflow — an ELL/SELL row outgrowing its slack, a BSR insert
# needing a block the layout doesn't hold — still escalates to rebuild.
# Reduced-precision tiers patch too: recomputed rows/columns are cast to
# the layout's storage dtype before the scatter, never widening the
# prepared arrays.  int8 is the exception — a changed row invalidates its
# per-row quantization scale, so a value patch alone would dequantize the
# row's untouched entries wrong; every int8 delta coerces to rebuild
# (recorded on ``coerced_from``, same as capacity overflow).
PATCHABLE_BACKENDS = ("dense", "ell", "pallas_dense", "bsr",
                      "dense_sharded", "ell_sharded")


@dataclasses.dataclass(frozen=True)
class UpdateInfo:
    """What one ``update()`` actually did."""
    strategy: str                 # "push" | "warm" | "rebuild" | "noop"
    n_inserted: int               # effective directed inserts
    n_deleted: int                # effective directed deletes
    cols_patched: int
    rows_patched: int
    iters: int                    # push sweeps or warm/rebuild iterations
    residual: float
    overflow: bool                # layout capacity exceeded: an ELL/SELL
    #                               row outgrew its slack, or a BSR insert
    #                               needs a block outside the structure
    # convergence-watchdog verdict of the refresh solve (defaults keep
    # positional construction of the original eight fields working)
    diverged: bool = False
    nonfinite: bool = False
    # the auto policy wanted this strategy but capacity overflow forced a
    # rebuild instead — ``strategy`` always reports what actually RAN, and
    # a coercion is recorded here (plus an ``update.coerced`` counter and
    # ``update_coerced`` metrics event) instead of silently relabelling
    coerced_from: str | None = None

    @property
    def healthy(self) -> bool:
        """The refresh solve's rank vector is trustworthy (no watchdog
        abort).  A committed-but-unhealthy update is what escalates the
        resilient refresh ladder to a full rebuild."""
        return not (self.diverged or self.nonfinite)


def _in_sorted(sorted_keys: np.ndarray, vals: np.ndarray) -> np.ndarray:
    """Membership of ``vals`` in a sorted unique key array (searchsorted —
    no O(E) scan per delta)."""
    if len(vals) == 0 or len(sorted_keys) == 0:
        return np.zeros(len(vals), bool)
    idx = np.searchsorted(sorted_keys, vals)
    idx = np.minimum(idx, len(sorted_keys) - 1)
    return sorted_keys[idx] == vals


def _key_slice(sorted_keys: np.ndarray, u: int, n: int) -> np.ndarray:
    """All partners of ``u`` in a sorted key array (``u*n .. (u+1)*n``)."""
    lo = np.searchsorted(sorted_keys, u * np.int64(n))
    hi = np.searchsorted(sorted_keys, (u + 1) * np.int64(n))
    return (sorted_keys[lo:hi] % n).astype(np.int64)


def _chunks(idx: np.ndarray, *arrs: np.ndarray, cap: int):
    """Split a scatter into fixed-``cap``-sized chunks, padding the last by
    repeating its final element (duplicate indices write identical content,
    so the scatter result is unchanged).  Scatter shapes are therefore
    keyed on the chunk COUNT k alone — a small discrete set (k=1 for
    nearly every stream delta) — instead of one XLA compile per distinct
    patch size."""
    for s in range(0, len(idx), cap):
        i = idx[s:s + cap]
        a = [x[s:s + cap] for x in arrs]
        pad = cap - len(i)
        if pad:
            i = np.concatenate([i, np.repeat(i[-1:], pad)])
            a = [np.concatenate([x, np.repeat(x[-1:], pad, axis=0)], axis=0)
                 for x in a]
        yield (i, *a)


def _stack_chunks(idx: np.ndarray, *arrs: np.ndarray, cap: int):
    """Stack the fixed-shape chunks along a leading axis, so one jitted
    scan applies them all: the target buffer is copied ONCE per patch (the
    scatters fuse in-place inside the program), not once per chunk.  The
    jitted scatters still recompile per distinct chunk count k (the
    stacked leading axis) — bounded and tiny in practice; the benchmark
    warms the shapes it will meet."""
    groups = list(zip(*_chunks(idx, *arrs, cap=cap)))
    return tuple(np.stack(g) for g in groups)


@partial(jax.jit, static_argnames=("sharding",))
def _scatter_rows(A, pos, rows, *, sharding=None):
    """A[pos_c] = rows_c for every chunk c; pos (k, cap), rows (k, cap, K).
    ``sharding`` (a hashable ``NamedSharding``, static) pins the scatter
    output to the operand's existing placement, so on the sharded tiers
    each row write lands on the device that owns the row instead of XLA
    inventing a reshard."""
    def body(A, args):
        p, r = args
        return A.at[p].set(r), None

    A, _ = jax.lax.scan(body, A, (pos, rows))
    return (A if sharding is None
            else jax.lax.with_sharding_constraint(A, sharding))


@partial(jax.jit, static_argnames=("n", "sharding"))
def _scatter_cols(H, ci, mats, *, n: int, sharding=None):
    """H[:n, ci_c] = mats_c.T for every chunk c; ci (k, cap), mats
    (k, cap, n).  ``n`` bounds the row slice (== H rows for the unpadded
    dense operand, the real-node prefix for the padded Pallas/sharded
    ones).  ``sharding`` keeps the patched H on its fabric-mesh
    ``P(row, col)`` placement for the ``dense_sharded`` tier."""
    def body(H, args):
        i, m = args
        return H.at[:n, i].set(m.T), None

    H, _ = jax.lax.scan(body, H, (ci, mats))
    return (H if sharding is None
            else jax.lax.with_sharding_constraint(H, sharding))


@jax.jit
def _scatter_block_vals(B, br, sl, lr, lc, vals):
    """B[br_c, sl_c, lr_c, lc_c] = vals_c for every chunk c (all (k, cap)):
    the BSR in-block value patch — entries addressed by (block-row, slot,
    local row, local col), never touching the block structure."""
    def body(B, args):
        b, s, r, c, v = args
        return B.at[b, s, r, c].set(v), None

    B, _ = jax.lax.scan(body, B, (br, sl, lr, lc, vals))
    return B


# --------------------------------------------------------------------------- #
# Gauss–Southwell push: frontier-masked residual sweeps in one while_loop     #
#                                                                             #
# The SELL layout itself needs no runners of its own: engine._matvec knows    #
# the "sell" tag, so the engine's generic whole-loop dispatchers (run /       #
# run_tol / ppr) drive it unchanged via self._mv_backend.                     #
# --------------------------------------------------------------------------- #
def _push_loop(Ab, x0, tol, n, max_pushes, trace=False):
    """Shared frontier loop.  ``Ab(x) = A·x + b`` is the damped PageRank
    affine operator; the invariant solved for is the fixed point
    ``x = Ab(x)``.  Every sweep pushes the whole frontier mask
    ``|r| ≥ tol/n`` (whenever ``‖r‖₁ > tol`` at least one entry qualifies,
    so the loop cannot stall) and refreshes the residual from scratch —
    one operator sweep per push round, same cost as an incremental
    residual update but immune to float drift in the bookkeeping.

    Runs on the same instrumented driver as the engine's tolerance loops
    (:func:`repro.obs.trace.instrumented_tol_loop`: NaN/Inf and
    sustained-growth watchdog — a corrupted layout makes the push residual
    *grow* every sweep, so without it the loop spins all ``max_pushes`` —
    plus the optional residual-trajectory ring).  The real initial
    residual seeds the loop, so an already-converged frontier exits in
    zero sweeps.  Returns ``(x, iters, residual, grow, ring)``."""
    thresh = tol / n

    def step(state):
        x, r = state
        x = x + r * (jnp.abs(r) >= thresh).astype(x.dtype)
        r = Ab(x) - x
        return (x, r), jnp.sum(jnp.abs(r))

    r0 = Ab(x0) - x0
    (x, _), iters, res, grow, ring = instrumented_tol_loop(
        step, (x0, r0), tol=tol, max_iters=max_pushes, watchdog=True,
        trace=trace, res0=jnp.sum(jnp.abs(r0)))
    return x, iters, res, grow, ring


@partial(jax.jit, static_argnames=("backend", "n", "max_pushes", "trace"))
def _push_tol(operands, dang, d, tol, x0, *, backend: str, n: int,
              max_pushes: int, trace: bool = False):
    if (backend == "dense" and len(operands) == 1
            and operands[0].dtype == jnp.float32):
        # the f32 dense operand is dangling-FIXED: the uniform leak columns
        # are already folded in, so A·x is just d·H·x.  Reduced-precision
        # dense tiers store H *unfixed* (and int8 appends a scale operand),
        # so they take the generic explicit-leak branch below — the arity/
        # dtype test is static under jit, so the f32 program is unchanged.
        def Ab(x):
            return d * (operands[0] @ x) + (1.0 - d) / n
    else:
        def Ab(x):
            return d * (_matvec(backend, operands, x)
                        + jnp.sum(x * dang) / n) + (1.0 - d) / n

    return _push_loop(Ab, x0, tol, n, max_pushes, trace=trace)


@partial(jax.jit, static_argnames=("n", "block_n", "block_m", "interpret",
                                   "max_pushes", "trace"))
def _push_pallas(Hp, dangp, d, tol, x0, *, n: int, block_n: int,
                 block_m: int, interpret: bool, max_pushes: int,
                 trace: bool = False):
    # state lives in the pre-padded (1, Mp) layout; pad entries of H, dang
    # and x0 are zero, so the residual is identically zero on the pad tail
    # and the frontier never touches it
    Mp = Hp.shape[1]
    real = (jnp.arange(Mp) < n).astype(jnp.float32)[None, :]
    xp0 = jnp.pad(x0, (0, Mp - n))[None, :]

    def Ab(xp):
        y = streaming_matvec(Hp, xp, block_n=block_n, block_m=block_m,
                             interpret=interpret)
        leak = jnp.sum(xp * dangp)
        return d * (y + leak / n * real) + (1.0 - d) / n * real

    xp, iters, res, grow, ring = _push_loop(Ab, xp0, tol, n, max_pushes,
                                            trace=trace)
    return xp[0, :n], iters, res, grow, ring


@partial(jax.jit, static_argnames=("mesh", "axes", "n_true", "max_pushes",
                                   "d", "trace"))
def _push_dense_sharded(H, dang, tol, x0, *, mesh, axes, n_true, max_pushes,
                        d, trace: bool = False):
    x, sweeps, res, grow, ring = dist.push_distributed_tol(
        H, mesh, x0, tol=tol, max_pushes=max_pushes, d=d, row_axis=axes[0],
        col_axis=axes[1], dangling=dang, n_true=n_true, trace=trace)
    return x[:n_true], sweeps, res, grow, ring


@partial(jax.jit, static_argnames=("mesh", "axes", "n_true", "max_pushes",
                                   "d", "trace"))
def _push_ell_sharded(layout, dang, tol, x0, *, mesh, axes, n_true,
                      max_pushes, d, trace: bool = False):
    x, sweeps, res, grow, ring = dist.push_distributed_sparse_tol(
        layout, mesh, x0, tol=tol, max_pushes=max_pushes, d=d,
        dangling=dang, axes=axes, n_true=n_true, trace=trace)
    return x[:n_true], sweeps, res, grow, ring


# --------------------------------------------------------------------------- #
# the dynamic engine                                                          #
# --------------------------------------------------------------------------- #
class DynamicPageRankEngine(PageRankEngine):
    """A :class:`PageRankEngine` over a *live* graph.

    Same constructor, same ``run`` / ``run_tol`` / ``ppr`` surface (the
    ``ell`` backend transparently swaps in the patchable SELL layout; the
    ``ell_sharded`` SELL tiers are built with ``slack`` slots of row
    headroom; ``bsr`` keeps a host block-structure map for in-block value
    patches), plus:

    * ``update(delta)`` — fold a :class:`~repro.graph.delta.GraphDelta`
      into the prepared layouts and refresh the ranks; returns
      ``(pr, UpdateInfo)``.  Strategy is picked automatically (push for
      tiny deltas, warm-started ``run_tol`` for patchable mid-size ones,
      full rebuild beyond ``rebuild_frac`` or on capacity overflow);
      ``strategy=`` forces one.
    * ``ranks`` — the latest solved rank vector (refreshed by every
      ``run`` / ``run_tol`` / ``update``), what the serving layer reads.

    ``update``'s default ``tol=1e-6`` is the serving-grade budget: the L1
    error of the refreshed ranks is bounded by ``‖r‖₁ / (1 − d·λ₂)`` —
    a small multiple of the push residual — which keeps incremental and
    from-scratch ranks within 1e-5 of each other while spending an order
    of magnitude less work than a cold 1e-8 solve.
    """

    def __init__(self, src: np.ndarray, dst: np.ndarray, n: int, *,
                 slack: int = 8, push_max_changed: int = 64,
                 rebuild_frac: float = 0.05, symmetric: bool = True, **kw):
        self._slack = int(slack)
        self.push_max_changed = int(push_max_changed)
        self.rebuild_frac = float(rebuild_frac)
        self.symmetric = bool(symmetric)
        self._pr: jax.Array | None = None
        super().__init__(src, dst, n, **kw)
        src, dst = _dedupe_edges(np.asarray(src), np.asarray(dst), self.n)
        self._keys = edge_keys(src, dst, self.n)
        self._rkeys = np.sort(np.asarray(dst, np.int64) * self.n
                              + np.asarray(src, np.int64))
        self._outdeg = np.bincount(src, minlength=self.n).astype(np.int64)
        self._indeg = np.bincount(dst, minlength=self.n).astype(np.int64)

    # --------------------------- layout prep --------------------------- #
    def _prepare_layout(self, src: np.ndarray, dst: np.ndarray) -> None:
        if self.backend == "bsr":
            super()._prepare_layout(src, dst)
            self._bsr_index(src, dst)
            return
        if self.backend != "ell":
            # ell_sharded builds its SELL with this engine's ``_slack``
            super()._prepare_layout(src, dst)
            return
        n = self.n
        self._dang = jnp.asarray(tr.dangling_mask(src, n).astype(np.float32))
        self.mesh = None
        self._axes = ()
        self._n_pad = n
        self._scales = None
        self._mv_backend = "sell"     # engine._matvec's tag for this layout
        # the capacity slack is what lets a delta patch rows in place — a
        # row outgrowing its tier is what escalates update() to rebuild
        self._operands, self._sell = sell.build(
            tr.build_transition_csr(src, dst, n), n, slack=self._slack,
            precision=self.precision)
        self.layout = self._sell.describe(self._slack)
        if self.precision != "f32":
            self.layout = f"{self.layout}[{self.precision}]"
        self._record_layout_bytes()

    def _bsr_index(self, src: np.ndarray, dst: np.ndarray) -> None:
        """Host map of the prepared BSR block structure: sorted int64
        ``(block-row * nb_c + block-col)`` keys plus each block's slot
        within its block-row.  ``BSRMatrix.from_dense`` lays blocks out in
        np.nonzero row-major order with slot = rank since the row start, so
        the map is reconstructible from the edge set alone — value patches
        address ``blocks[brow, slot]`` without ever reading device arrays
        back.  Patches only zero/overwrite entries of existing blocks
        (structure never changes between rebuilds), so the map stays valid
        until the next ``_prepare_layout``."""
        bsr = self._operands[0]
        bs = int(bsr.block_size)
        self._bsr_nbc = -(-self.n // bs)
        pairs = np.unique((np.asarray(dst, np.int64) // bs)
                          * np.int64(self._bsr_nbc)
                          + np.asarray(src, np.int64) // bs)
        brows = pairs // self._bsr_nbc
        counts = np.bincount(brows, minlength=bsr.blocks.shape[0])
        starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
        self._bsr_pairs = pairs
        self._bsr_slots = (np.arange(len(pairs))
                           - starts[brows]).astype(np.int64)

    # ----------------------- solver front doors ------------------------ #
    @property
    def ranks(self) -> jax.Array | None:
        """Latest solved rank vector (``None`` until the first solve)."""
        return self._pr

    def run(self, n_iters: int = 100) -> jax.Array:
        # the engine's generic runners drive the SELL layout through
        # _mv_backend — these overrides only stash the latest ranks
        pr = super().run(n_iters)
        self._pr = pr
        return pr

    def run_tol(self, tol: float = 1e-6, max_iters: int = 1000,
                x0: np.ndarray | jax.Array | None = None, **kw):
        out = super().run_tol(tol, max_iters, x0, **kw)
        self._pr = out[0]
        return out

    # ------------------- snapshots & recovery hooks -------------------- #
    def snapshot(self) -> EngineSnapshot:
        """Host-side copy of everything needed to rebuild this engine: the
        sorted edge-key set and the latest ranks.  Device layouts are
        derived state — :meth:`restore` reconstructs them — so a snapshot
        taken *before* device-side corruption restores a healthy engine."""
        return EngineSnapshot(
            keys=np.asarray(self._keys, np.int64).copy(),
            ranks=(None if self._pr is None
                   else np.asarray(self._pr, np.float32).copy()),
            residual=0.0)

    def restore(self, snap: EngineSnapshot) -> None:
        """Roll the engine back to ``snap``: rebuild the host bookkeeping
        and every prepared device layout from the snapshot's edge keys and
        reinstate its ranks.  The escalation ladder's last rung."""
        n = self.n
        keys = np.sort(np.asarray(snap.keys, np.int64))
        src = (keys // n).astype(np.int32)
        dst = (keys % n).astype(np.int32)
        self._keys = keys
        self._rkeys = np.sort((keys % n) * np.int64(n) + keys // n)
        self._outdeg = np.bincount(src, minlength=n).astype(np.int64)
        self._indeg = np.bincount(dst, minlength=n).astype(np.int64)
        self.n_edges = len(keys)
        self.density = self.n_edges / float(n * n)
        self._prepare_layout(src, dst)
        self._pr = (None if snap.ranks is None
                    else jnp.asarray(snap.ranks, jnp.float32))

    def rebuild_and_solve(self, tol: float = 1e-6, max_iters: int = 1000,
                          x0: np.ndarray | jax.Array | None = None, **kw):
        """Rebuild every prepared device layout from the (authoritative)
        host edge keys and re-solve — the recovery path for device-side
        layout corruption, where the edge set is still correct but the
        prepared arrays are not.  ``x0`` warm-starts from known-good ranks
        (e.g. the last snapshot).  Returns the ``run_tol`` result."""
        with self.metrics.span("rebuild", backend=self.backend):
            self._rebuild()
        return self.run_tol(tol=tol, max_iters=max_iters, x0=x0, **kw)

    # --------------------------- the update ---------------------------- #
    def update(self, delta: GraphDelta, *, tol: float = 1e-6,
               max_iters: int = 1000, strategy: str = "auto"
               ) -> tuple[jax.Array, UpdateInfo]:
        """Fold ``delta`` into the prepared layouts and refresh the ranks.

        Returns ``(pr, UpdateInfo)``.  ``strategy``: ``"auto"`` (default
        policy by delta size), or force ``"push"`` / ``"warm"`` /
        ``"rebuild"``.

        Every update lands in the engine's metrics registry: an
        ``update.<strategy>`` counter (``noop`` included), the overall
        ``span.update`` latency histogram, per-strategy
        ``span.update.patch`` / ``span.update.rebuild`` layout timings,
        and one ``update`` event with the delta size and solve verdict.
        When capacity overflow forces the auto policy to rebuild where the
        size policy wanted a patch, the coercion is recorded on
        ``UpdateInfo.coerced_from`` plus an ``update.coerced`` counter and
        an ``update_coerced`` event — ``.strategy`` never lies about what
        ran.
        """
        with self.metrics.span("update"):
            pr, info = self._update(delta, tol=tol, max_iters=max_iters,
                                    strategy=strategy)
        self.metrics.counter(f"update.{info.strategy}").inc()
        if info.coerced_from is not None:
            self.metrics.counter("update.coerced").inc()
            self.metrics.event("update_coerced",
                               requested=info.coerced_from,
                               ran=info.strategy, overflow=info.overflow)
        self.metrics.event("update", strategy=info.strategy,
                           n_ins=info.n_inserted, n_del=info.n_deleted,
                           iters=info.iters, residual=info.residual,
                           overflow=info.overflow, healthy=info.healthy)
        return pr, info

    def _update(self, delta: GraphDelta, *, tol: float,
                max_iters: int, strategy: str
                ) -> tuple[jax.Array, UpdateInfo]:
        if strategy not in ("auto", "push", "warm", "rebuild"):
            raise ValueError(f"unknown strategy {strategy!r}")
        plan = self._plan(delta)
        if plan is None:
            if self._pr is None:
                self.run_tol(tol=tol, max_iters=max_iters)
            return self._pr, UpdateInfo("noop", 0, 0, 0, 0, 0, 0.0, False)
        # validate BEFORE committing any bookkeeping, so a raise leaves the
        # engine exactly as it was (no half-applied delta).  int8 layouts
        # never patch: a changed row needs a new quantization scale, and
        # re-scaling re-quantizes the whole row — a rebuild in disguise.
        patchable = (self.backend in PATCHABLE_BACKENDS
                     and not plan["overflow"]
                     and self.precision != "int8")
        coerced_from = None
        if strategy == "auto":
            if (plan["n_changed"] > self.rebuild_frac
                    * max(plan["n_edges_before"], 1)):
                strategy = "rebuild"
            else:
                want = ("push" if self._pr is not None
                        and plan["n_changed"] <= self.push_max_changed
                        else "warm")
                if patchable:
                    strategy = want
                else:
                    # the size policy wanted a patch but the layout can't
                    # take one (capacity overflow / block-structure change)
                    # — record the coercion instead of relabelling it
                    strategy, coerced_from = "rebuild", want
        elif strategy in ("push", "warm") and not patchable:
            raise ValueError(
                f"strategy {strategy!r} needs a patchable layout "
                f"(backend in {PATCHABLE_BACKENDS}, no capacity overflow "
                f"or BSR block-structure change, precision != 'int8')")
        elif strategy == "push" and self._pr is None:
            raise ValueError("push needs previous ranks; run/run_tol first")

        # apply atomically: if the layout change or solve fails partway
        # (allocation, device error), roll the whole engine back so the
        # host bookkeeping and the device layout never describe different
        # graphs.  A shallow attribute snapshot suffices — every field is
        # replaced, never mutated in place, on the update path.
        state = dict(self.__dict__)
        try:
            self._commit(plan)
            if strategy == "rebuild":
                with self.metrics.span("update.rebuild"):
                    self._rebuild()
                rows = cols = 0
            else:
                with self.metrics.span("update.patch"):
                    rows, cols = self._patch(plan)
            x0 = self._pr
            if strategy == "push":
                with self.metrics.span("solve", backend=self.backend,
                                       strategy="push"):
                    pr, iters, res, grow, ring = self._push(
                        x0, tol, max_iters)
                    self.last_solve_info = make_solve_info(
                        iters, res, grow, tol=tol, max_iters=max_iters,
                        trace=(SolveTrace(ring, iters)
                               if ring is not None else None))
                self.metrics.counter("engine.solves").inc()
                self.metrics.counter(
                    f"engine.solve.{self.last_solve_info.status}").inc()
                self._pr = pr
            else:
                pr, iters, res = self.run_tol(tol=tol, max_iters=max_iters,
                                              x0=x0)
        except BaseException:
            self.__dict__.clear()
            self.__dict__.update(state)
            raise
        solve = self.last_solve_info
        return pr, UpdateInfo(strategy, plan["n_ins"], plan["n_del"],
                              cols, rows, int(iters), float(res),
                              bool(plan["overflow"]),
                              diverged=solve.diverged,
                              nonfinite=solve.nonfinite,
                              coerced_from=coerced_from)

    # ------------------------ host bookkeeping ------------------------- #
    def _plan(self, delta: GraphDelta) -> dict | None:
        """Canonicalize the delta against the current edge set and compute
        the patch plan (affected rows/columns, post-delta key sets and
        degrees, overflow flag) WITHOUT touching any engine state — or
        return ``None`` for an effective no-op.  ``_commit`` applies it."""
        n = self.n
        delta = delta.canonical(n, symmetric=self.symmetric)
        ins = edge_keys(delta.insert_src, delta.insert_dst, n)
        dels = edge_keys(delta.delete_src, delta.delete_dst, n)
        eff_ins = ins[~_in_sorted(self._keys, ins)]
        eff_del = dels[_in_sorted(self._keys, dels)]
        eff_del = eff_del[~_in_sorted(ins, eff_del)]   # delete-then-insert
        changed = np.concatenate([eff_ins, eff_del])
        if len(changed) == 0:
            return None
        new_keys = np.union1d(
            np.setdiff1d(self._keys, eff_del, assume_unique=True), eff_ins)
        rkey = lambda k: (k % n) * np.int64(n) + k // n
        new_rkeys = np.union1d(
            np.setdiff1d(self._rkeys, rkey(eff_del), assume_unique=True),
            rkey(eff_ins))
        outdeg, indeg = self._outdeg.copy(), self._indeg.copy()
        np.add.at(outdeg, (eff_ins // n), 1)
        np.add.at(outdeg, (eff_del // n), -1)
        np.add.at(indeg, (eff_ins % n), 1)
        np.add.at(indeg, (eff_del % n), -1)

        cols = np.unique(changed // n)
        rows = np.empty(0, np.int64)
        overflow = False
        extra: dict = {}
        if self.backend in ("ell", "ell_sharded"):
            # only the row-major layouts patch rows (dense tiers rewrite
            # whole columns, BSR individual block entries), so only they
            # pay the neighbor scans
            parts = [changed % n]
            for u in cols:
                parts.append(_key_slice(self._keys, int(u), n))
                parts.append(_key_slice(new_keys, int(u), n))
            rows = np.unique(np.concatenate(parts))
            cap = np.asarray(self._sell.widths)[self._sell.tier[rows]]
            overflow = bool((indeg[rows] > cap).any())
        elif self.backend == "bsr":
            # per changed column: its old and new out-neighbor sets (both
            # sorted — _key_slice walks the sorted keys).  Every entry the
            # patch touches lives in block (v//bs, u//bs); old entries are
            # in existing blocks by construction, so only the post-delta
            # sets can demand a block the structure doesn't hold — that is
            # the genuine structure change that forces a rebuild.
            bs = int(self._operands[0].block_size)
            old_nbrs = [_key_slice(self._keys, int(u), n) for u in cols]
            new_nbrs = [_key_slice(new_keys, int(u), n) for u in cols]
            need = [(vv // bs) * np.int64(self._bsr_nbc) + int(u) // bs
                    for u, vv in zip(cols, new_nbrs) if len(vv)]
            if need:
                need = np.unique(np.concatenate(need))
                overflow = not bool(_in_sorted(self._bsr_pairs, need).all())
            extra = {"bsr_old": old_nbrs, "bsr_new": new_nbrs}
        return {"cols": cols, "rows": rows, "overflow": overflow,
                "n_ins": len(eff_ins), "n_del": len(eff_del),
                "n_changed": len(changed),
                "n_edges_before": len(self._keys),
                "keys": new_keys, "rkeys": new_rkeys,
                "outdeg": outdeg, "indeg": indeg, **extra}

    def _commit(self, plan: dict) -> None:
        """Swap in the post-delta bookkeeping computed by ``_plan`` (only
        after strategy validation passed, so no raise path can leave the
        host state and the device layout describing different graphs)."""
        self._keys = plan["keys"]
        self._rkeys = plan["rkeys"]
        self._outdeg = plan["outdeg"]
        self._indeg = plan["indeg"]
        self.n_edges = len(self._keys)
        self.density = self.n_edges / float(self.n * self.n)

    def _rebuild(self) -> None:
        src = (self._keys // self.n).astype(np.int32)
        dst = (self._keys % self.n).astype(np.int32)
        self._prepare_layout(src, dst)

    # -------------------------- layout patches ------------------------- #
    def _column(self, u: int, fix_dangling: bool) -> np.ndarray:
        """Recompute transition column ``u`` from the current edge set."""
        col = np.zeros(self.n, np.float32)
        nbrs = _key_slice(self._keys, u, self.n)
        if len(nbrs):
            col[nbrs] = 1.0 / len(nbrs)
        elif fix_dangling:
            col[:] = 1.0 / self.n
        return col

    def _patch(self, plan: dict) -> tuple[int, int]:
        """Scatter the recomputed rows/columns into the prepared layout.
        Returns ``(rows_patched, cols_patched)``."""
        n = self.n
        cols = plan["cols"]
        flags = (self._outdeg[cols] == 0).astype(np.float32)
        dang = self._dang
        for ci, f in _chunks(cols, flags, cap=32):
            dang = dang.at[jnp.asarray(ci)].set(jnp.asarray(f))
        if self.mesh is not None:
            # the sharded tiers keep the dangling mask replicated; pin the
            # patched copy back to P() so no runner pays a reshard
            dang = jax.device_put(dang, NamedSharding(self.mesh, P()))
        self._dang = dang
        if self.backend in ("dense", "dense_sharded"):
            # the sharded and reduced-precision H are stored dangling-
            # UNFIXED (explicit leak), the single-device f32 dense operand
            # dangling-fixed; patch columns are cast to the layout's
            # storage dtype (a no-op on f32) so the scatter never widens it
            H0 = self._operands[0]
            mat = np.stack([self._column(int(u), fix_dangling=self.backend
                                         == "dense"
                                         and self.precision == "f32")
                            for u in cols], axis=0)        # (C, n)
            ci, mats = _stack_chunks(cols, mat, cap=32)
            sharding = (None if self.mesh is None
                        else NamedSharding(self.mesh, P(*self._axes)))
            H = _scatter_cols(H0, jnp.asarray(ci),
                              jnp.asarray(mats).astype(H0.dtype), n=n,
                              sharding=sharding)
            self._operands = (H,)
            return 0, len(cols)
        if self.backend == "bsr":
            self._patch_bsr(plan)
            return 0, len(cols)
        if self.backend == "pallas_dense":
            Hp, dangp = self._operands
            mat = np.stack([self._column(int(u), fix_dangling=False)
                            for u in cols], axis=0)        # (C, n)
            ci, mats = _stack_chunks(cols, mat, cap=32)
            Hp = _scatter_cols(Hp, jnp.asarray(ci),
                               jnp.asarray(mats).astype(Hp.dtype), n=n)
            for ci, f in _chunks(cols, flags, cap=32):
                dangp = dangp.at[0, jnp.asarray(ci)].set(jnp.asarray(f))
            self._operands = (Hp, dangp)
            return 0, len(cols)
        # ell / ell_sharded: rewrite every affected SELL row in its tier
        # (vectorized: one gather over the reverse key set builds all rows
        # at once); on the mesh each scatter stays on its tier's row
        # sharding, so every write lands on the device owning the row
        rows = plan["rows"]
        inv, tiers = self._operands
        tiers = list(tiers)
        for t, k in enumerate(self._sell.widths):
            sel = rows[self._sell.tier[rows] == t]
            if len(sel) == 0:
                continue
            data, idx = self._rebuild_rows(sel, k)
            pos, dat, ix = _stack_chunks(self._sell.pos[sel], data, idx,
                                         cap=512 if t == 0 else 64)
            pos = jnp.asarray(pos)
            d, i = tiers[t]
            sharding = None if self.mesh is None else d.sharding
            tiers[t] = (_scatter_rows(d, pos,
                                      jnp.asarray(dat).astype(d.dtype),
                                      sharding=sharding),
                        _scatter_rows(i, pos, jnp.asarray(ix),
                                      sharding=sharding))
        self._operands = (inv, tuple(tiers))
        return len(rows), len(cols)

    def _patch_bsr(self, plan: dict) -> None:
        """Rewrite every changed entry inside the existing BSR block
        structure with one chunked scatter.  For each changed column ``u``
        the union of its old and new out-neighbors is touched: entries in
        ``new`` get the recomputed ``1/outdeg`` value, entries only in
        ``old`` are zeroed in place (their block stays — harmless, the
        padded slots already accumulate zeros).  ``_plan`` guaranteed every
        touched block exists (a miss is the structure change that forces a
        rebuild), so the host (block-row, block-col) -> slot map resolves
        every coordinate."""
        bsr = self._operands[0]
        bs = int(bsr.block_size)
        parts = []
        for u, old, new in zip(plan["cols"], plan["bsr_old"],
                               plan["bsr_new"]):
            vs = np.union1d(old, new)
            if len(vs) == 0:
                continue
            val = np.zeros(len(vs), np.float32)
            if len(new):
                val[_in_sorted(new, vs)] = 1.0 / len(new)
            key = (vs // bs) * np.int64(self._bsr_nbc) + int(u) // bs
            slot = self._bsr_slots[np.searchsorted(self._bsr_pairs, key)]
            parts.append((vs // bs, slot, vs % bs,
                          np.full(len(vs), int(u) % bs, np.int64), val))
        if not parts:
            return
        br, sl, lr, lc, vals = (np.concatenate(a) for a in zip(*parts))
        b, s, r, c, v = _stack_chunks(br, sl, lr, lc, vals, cap=256)
        blocks = _scatter_block_vals(
            bsr.blocks, jnp.asarray(b), jnp.asarray(s), jnp.asarray(r),
            jnp.asarray(c), jnp.asarray(v).astype(bsr.blocks.dtype))
        self._operands = (dataclasses.replace(bsr, blocks=blocks),)

    def _rebuild_rows(self, sel: np.ndarray, k: int
                      ) -> tuple[np.ndarray, np.ndarray]:
        """Recompute the SELL rows ``sel`` (width ``k``) from the current
        edge set — no per-row Python loop: one vectorized slice-gather over
        the sorted reverse keys yields every (row, slot, col, val) at
        once."""
        n = self.n
        sel64 = sel.astype(np.int64)
        lo = np.searchsorted(self._rkeys, sel64 * n)
        hi = np.searchsorted(self._rkeys, (sel64 + 1) * n)
        cnt = hi - lo
        total = int(cnt.sum())
        data = np.zeros((len(sel), k), np.float32)
        idx = np.zeros((len(sel), k), np.int32)
        if total:
            starts = np.concatenate([[0], np.cumsum(cnt)[:-1]])
            slot = np.arange(total) - np.repeat(starts, cnt)
            flat = np.repeat(lo, cnt) + slot
            j = np.repeat(np.arange(len(sel)), cnt)
            u = self._rkeys[flat] % n
            data[j, slot] = 1.0 / self._outdeg[u]
            idx[j, slot] = u
        return data, idx

    # ------------------------------ push -------------------------------- #
    def _push(self, x0: jax.Array, tol: float, max_pushes: int,
              trace: bool = True):
        if self.backend == "dense_sharded":
            return _push_dense_sharded(
                self._operands[0], self._dang, jnp.float32(tol),
                self._pad_x0(jnp.asarray(x0, jnp.float32)),
                mesh=self.mesh, axes=self._axes, n_true=self.n,
                max_pushes=max_pushes, d=self.d, trace=trace)
        if self.backend == "ell_sharded":
            return _push_ell_sharded(
                self._operands, self._dang, jnp.float32(tol),
                self._pad_x0(jnp.asarray(x0, jnp.float32)),
                mesh=self.mesh, axes=self._axes, n_true=self.n,
                max_pushes=max_pushes, d=self.d, trace=trace)
        if self.backend == "pallas_dense":
            Hp, dangp = self._operands
            return _push_pallas(Hp, dangp, self.d, jnp.float32(tol),
                                jnp.asarray(x0), n=self.n,
                                block_n=self._block[0],
                                block_m=self._block[1],
                                interpret=self.interpret,
                                max_pushes=max_pushes, trace=trace)
        return _push_tol(self._operands, self._dang, self.d,
                         jnp.float32(tol), jnp.asarray(x0),
                         backend=self._mv_backend, n=self.n,
                         max_pushes=max_pushes, trace=trace)
