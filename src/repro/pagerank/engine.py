"""Fused on-device PageRank engine: prepare once, run the whole loop compiled.

The seed drove its fastest tier from a host Python loop — one kernel
dispatch *per iteration*, a host sync between iterations, H re-padded
inside every call, and a separate full pass over the rank vector for the
dangling leak.  The paper's headline number (213.6 ms for 5k nodes x 100
iterations) comes from keeping the entire power iteration on the fabric
with no host intervention; :class:`PageRankEngine` is the JAX analogue:

* **Prepare once** — the backend's layout is built at construction into
  one :class:`Layout`, a pytree of its device arrays whose static fields
  (node count, mesh, Pallas blocks) key the compiled programs; nothing in
  the hot loop pads or reshapes.  The layout decision lives in
  ``PageRankEngine._prepare_layout`` alone: every other caller asks the
  layout for its matvec ``mv`` and its global step.
* **Four loop drivers, written once** — :func:`fixed_loop` (one
  ``lax.scan``), :func:`tol_loop` (one ``lax.while_loop`` with the
  convergence watchdog), :func:`ppr_loop` (the batched (N, Q) personalized
  scan) and :func:`push_loop` (the Gauss–Southwell frontier push of an
  (N,) vector or an (N, Q) batch), each over any layout, so 100
  iterations are one dispatch, not 100 dispatches + syncs.
* **In-kernel dangling fusion** — the Pallas tier's step is
  :func:`repro.kernels.pagerank_step.pagerank_step_fused`, which emits
  ``sum(y * dangling)`` from the same epilogue that applies the affine
  term; the loop carries it as the next iteration's scalar ``t``, deleting
  the per-iteration extra pass over the rank vector.
* **Backend auto-selection** — by graph density and the active JAX
  device (``interpret`` for the Pallas tiers is derived from the device,
  not an import-time constant).
* **Batched personalized PageRank** — Q personalization queries propagate
  as one (N, Q) rank matrix sharing a single sweep over H per iteration
  (the MELOPPR-style batching).
* **Sharded multi-device tiers** — ``dense_sharded`` steps the paper's
  fabric schedule (:func:`repro.pagerank.distributed.fabric_step` over
  :mod:`repro.core.fabric_matvec`) with H blocked ``P(row, col)`` over a
  2-D device mesh; ``ell_sharded`` row-shards a sliced-ELL layout
  (:mod:`repro.pagerank.sell`) over the flattened mesh with one
  ``all_gather`` per sweep (:func:`repro.pagerank.distributed
  .sell_mv_sharded`).  Both build their ``NamedSharding`` layouts once at
  construction, zero-pad N to the mesh, and keep tolerance-based early
  exit working across the mesh (the residual is a replicated scalar).

The canonical per-iteration step functions live in
:mod:`repro.pagerank.steps` and are shared with ``repro.pagerank.dense`` /
``repro.pagerank.sparse``, so every tier (and every test oracle) runs
literally the same arithmetic; the engine's f32 dense tier steps the
reference's own ``dense_step``, making the two bit-identical.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import warnings
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.graph import delta as delta_mod
from repro.graph import transition as tr
from repro.graph.sparse import BSRMatrix
from repro.kernels import ops as kops
from repro.kernels.common import upcast_f32
from repro.kernels.pagerank_step import (pad_pagerank_operands,
                                         pagerank_step_fused)
from repro.kernels.streaming_matvec import streaming_matvec
from repro.launch.mesh import make_mesh
from repro.pagerank import distributed as dist
from repro.pagerank import sell
from repro.obs.registry import default_registry
from repro.obs.trace import SolveTrace, instrumented_tol_loop
from repro.pagerank.precision import (PRECISIONS, STORAGE_DTYPES,
                                      layout_nbytes, quantize_int8,
                                      resolve_precision, rowmax_scales,
                                      solve_dtype)
from repro.pagerank.resilience import (ConvergenceError, SolveResult,
                                       make_solve_info)
from repro.pagerank.steps import (dense_step, ppr_step, ppr_step_batched,
                                  seed_matrix, sparse_step)

__all__ = ["PageRankEngine", "select_backend", "dense_step", "sparse_step",
           "ppr_step", "ppr_step_batched", "seed_matrix", "PRECISIONS",
           "Layout", "fixed_loop", "tol_loop", "ppr_loop", "push_loop",
           "global_push"]

BACKENDS = ("dense", "ell", "bsr", "pallas_dense", "dense_sharded",
            "ell_sharded")
SHARDED_BACKENDS = ("dense_sharded", "ell_sharded")

# auto-selection threshold on nnz / n^2: at/above it, blocked-dense sweeps
# beat index chasing; below it every device takes the ELL gather
DENSE_DENSITY = 0.25


def select_backend(n: int, density: float, device: str | None = None,
                   n_devices: int | None = None,
                   precision: str = "auto") -> str:
    """Pick an execution backend from graph density and the device topology.

    ``device`` defaults to ``jax.default_backend()`` so the same code picks
    the Mosaic-compiled Pallas tier on TPU and the XLA tiers elsewhere;
    ``n_devices`` defaults to ``jax.device_count()`` so a multi-device
    process auto-picks the sharded tiers (the single-device heuristics only
    apply on one chip).

    ``precision`` is accepted (and validated) so callers can route the
    engine's full configuration through one chooser, but it deliberately
    does **not** alter the choice: every backend supports every storage
    tier, and ``"auto"`` precision always resolves to ``"f32"`` — reduced
    precision is an explicit accuracy trade, never an auto-policy pick.
    """
    resolve_precision(precision)
    device = device or jax.default_backend()
    n_devices = jax.device_count() if n_devices is None else n_devices
    if n_devices > 1:
        return ("dense_sharded" if density >= DENSE_DENSITY
                else "ell_sharded")
    if density >= DENSE_DENSITY:
        return "pallas_dense" if device == "tpu" else "dense"
    # sparse graphs go to ELL on every device: the bsr tier builds and
    # stores N^2 values (graph.transition.build_transition_bsr), so it is
    # selectable by name only
    return "ell"


def _default_mesh(backend: str) -> Mesh:
    """All visible devices: a near-square 2-D (row, col) mesh for the dense
    fabric schedule, a flat 1-D mesh for the row-sharded ELL tier."""
    ndev = jax.device_count()
    if backend == "ell_sharded":
        return make_mesh((ndev,), ("shard",))
    r = int(math.isqrt(ndev))
    while ndev % r:
        r -= 1
    return make_mesh((r, ndev // r), ("row", "col"))


def _dedupe_edges(src: np.ndarray, dst: np.ndarray,
                  n: int) -> tuple[np.ndarray, np.ndarray]:
    """Collapse duplicate directed edges.  The engine's contract is a *set*
    of edges: without this, a repeated (u, v) inflates outdeg(u) in the
    dense builder but contributes multiple summed entries in CSR/ELL, and
    the tiers silently disagree.  Delegates to the shared canonicalizer in
    :mod:`repro.graph.delta`; self-loops are kept — the transition
    builders support them."""
    return delta_mod.dedupe_directed(src, dst, n, drop_self_loops=False)


def _row_scale(y: jax.Array, scales: jax.Array | None) -> jax.Array:
    """Fold an int8 layout's per-row f32 dequantization scales into the
    accumulated f32 row sums (vector or batched-matrix shaped)."""
    if scales is None:
        return y
    return y * (scales if y.ndim == 1 else scales[:, None])


def _real(v: jax.Array, n: int) -> jax.Array:
    """The first ``n`` rows of a padded vector or batch: the graph's
    nodes (no slice where nothing is padded)."""
    return v if v.shape[0] == n else v[:n]


def inv_outdeg(outdeg: np.ndarray, dtype) -> np.ndarray:
    """``1/outdeg`` per column in ``dtype``, 0 for a dangling column: the
    float32 quotient the transition builders store, then rounded as a
    layout rounds its values."""
    deg = np.asarray(outdeg, np.float32)
    return np.divide(np.float32(1.0), deg, out=np.zeros_like(deg),
                     where=deg > 0).astype(dtype)


def _stored(H: np.ndarray, precision: str, put, put_scales) -> tuple:
    """A dense H's operands in its storage precision: ``(H,)``, or
    ``(Hq, scales)`` for int8 with its per-row f32 dequantization scales."""
    if precision == "int8":
        scales = rowmax_scales(np.abs(H).max(axis=1, initial=0.0))
        return put(quantize_int8(H, scales[:, None])), put_scales(scales)
    return (put(jnp.asarray(H).astype(STORAGE_DTYPES[precision])),)


# --------------------------------------------------------------------------- #
# prepared layouts: one pytree per backend family                             #
# --------------------------------------------------------------------------- #
def _static():
    return dataclasses.field(metadata={"static": True})


def _pytree(cls):
    """A frozen dataclass registered as a pytree: its array fields are the
    leaves, its ``_static()`` fields ride in the treedef, the jit key."""
    return jax.tree_util.register_dataclass(
        dataclasses.dataclass(frozen=True, kw_only=True)(cls))


@_pytree
class Layout:
    """A prepared transition matrix H over ``rows`` rows, the first ``n``
    of them the graph's nodes.  Pad rows and columns of H are zero, so pad
    entries never feed back into real ones.

    The leaves are the device arrays: ``operands`` (int8 scales among
    them) and the (rows,) dangling mask ``dang``.  ``mv`` is H·X without
    the dangling fix, for a (rows,) vector or a (rows, Q) batch.  The
    global iteration runs ``start`` → ``step``… and reads its iterate with
    ``vector``; by default a step is one ``sparse_step`` of ``mv`` (an
    engine-module global, looked up when a solve is traced).  ``affine`` is
    the same damped map as the push sweeps it."""
    operands: tuple
    dang: jax.Array
    n: int = _static()

    @property
    def rows(self) -> int:
        return self.dang.shape[0]

    def mv(self, X: jax.Array) -> jax.Array:
        raise NotImplementedError

    def pad(self, X: jax.Array) -> jax.Array:
        """Zero rows of a vector or batch up to ``rows``."""
        if X.shape[0] == self.rows:
            return X
        return jnp.pad(X, ((0, self.rows - X.shape[0]),)
                       + ((0, 0),) * (X.ndim - 1))

    def start(self, x0: jax.Array | None):
        """The loop state of ``x0`` (the uniform vector for ``None``)."""
        return self.pad(jnp.full((self.n,), 1.0 / self.n, jnp.float32)
                        if x0 is None else x0)

    def step(self, state, d):
        return sparse_step(self.mv, state, self.dang, d, self.n)

    def vector(self, state) -> jax.Array:
        """The (rows,) iterate of a loop state."""
        return state

    def affine(self, x: jax.Array, d) -> jax.Array:
        """``x -> A·x + b`` of a (rows,) vector, as the push sweeps it:
        the matvec before the leak, the operand order of the compiled
        refresh push."""
        with jax.named_scope("pagerank.vector"):
            return (d * (self.mv(x) + jnp.sum(x * self.dang) / self.n)
                    + (1.0 - d) / self.n)


@_pytree
class SellLayout(Layout):
    """The sliced ELLPACK of :mod:`repro.pagerank.sell` (the ``ell``
    tier): one gather per degree tier.

    ``col_scale``, where the layout has one, is the (rows,) column scale
    ``1/outdeg`` in the storage dtype (0 for dangling and pad columns),
    and the rows hold the structure alone, 1 per edge: the dynamic
    engine's layout, where a changed out-degree rewrites one entry of the
    scale instead of every row that holds the column.  ``mv`` scales the
    iterate once, then gathers it as the valued rows would."""
    col_scale: jax.Array | None = None

    def scaled(self, X: jax.Array) -> jax.Array:
        """``X`` with row ``u`` times ``col_scale[u]`` (``X`` itself where
        the rows hold their values)."""
        if self.col_scale is None:
            return X
        s = upcast_f32(self.col_scale)
        return X * (s if X.ndim == 1 else s[:, None])

    def mv(self, X):
        return sell.sell_rows(self.operands, self.scaled(X))


@_pytree
class ShardedSellLayout(SellLayout):
    """A SELL row-sharded over the flattened mesh (``ell_sharded``): each
    device sweeps its own rows and one tiled ``all_gather`` re-assembles
    the replicated image."""
    mesh: Mesh = _static()
    axes: tuple = _static()

    def mv(self, X):
        return dist.sell_mv_sharded(self.operands, self.scaled(X), self.mesh,
                                    self.axes)


@_pytree
class BsrLayout(Layout):
    """MXU-aligned block-sparse rows; ``BSRMatrix`` upcasts its own blocks
    and owns its row scales."""

    def mv(self, X):
        bsr = self.operands[0]
        return bsr.matvec(X) if X.ndim == 1 else bsr.matmat(X)


@_pytree
class DenseLayout(Layout):
    """Dense H stored dangling-UNFIXED in a reduced precision (the fix
    would densify the dangling columns with 1/n values that quantize
    poorly): ``(H,)``, or ``(Hq, scales)`` for int8."""

    def mv(self, X):
        H, *scales = self.operands
        return _row_scale(upcast_f32(H) @ X, scales[0] if scales else None)


@_pytree
class FixedDenseLayout(DenseLayout):
    """f32 dense H stored dangling-FIXED (the uniform leak folded into the
    dangling columns): the global step is the reference's ``dense_step``
    itself.  Zeroing the dangling columns gives back the unfixed H that
    the personalized operator needs."""

    def mv(self, X):
        return (self.operands[0] * (1.0 - self.dang)[None, :]) @ X

    def step(self, x, d):
        return dense_step(self.operands[0], x, d)

    affine = step


@_pytree
class DenseShardedLayout(DenseLayout):
    """Dense H stored dangling-unfixed, blocked ``P(row, col)`` over a 2-D
    mesh (``dense_sharded``): the global step is the paper's fabric
    iteration; the batched matvec is GSPMD's."""
    mesh: Mesh = _static()
    axes: tuple = _static()

    def start(self, x0):
        return jax.lax.with_sharding_constraint(
            super().start(x0), NamedSharding(self.mesh, P(self.axes[1])))

    def step(self, x, d):
        H, *scales = self.operands
        return dist.fabric_step(H, x, self.dang, self.mesh, *self.axes, d,
                                self.n, scales[0] if scales else None)

    affine = step


@_pytree
class PallasLayout(Layout):
    """The pre-padded dense layout of the fused Pallas kernel
    (``pallas_dense``): ``(Hp,)`` or ``(Hp, scales)`` with (1, rows) int8
    scales.  The global step carries ``(xp, t)``, the (1, rows) iterate
    and the next step's leak term, which the kernel's epilogue emits; the
    kernel compiles ``d`` in, so this layout carries it."""
    d: float = _static()
    block: tuple = _static()
    interpret: bool = _static()

    def _t(self, leak):
        return self.d * leak / self.n + (1.0 - self.d) / self.n

    def mv(self, X):
        Hp, *scales = self.operands
        Y = streaming_matvec(Hp, X[None, :] if X.ndim == 1 else X.T,
                             block_n=self.block[0], block_m=self.block[1],
                             interpret=self.interpret)
        if scales:
            Y = Y * scales[0]
        return Y[0] if X.ndim == 1 else Y.T

    def start(self, x0):
        xp = super().start(x0)[None, :]
        return xp, self._t(jnp.sum(xp * self.dang))

    def step(self, state, d):
        xp, t = state
        yp, leak = pagerank_step_fused(
            self.operands[0], xp, self.dang[None, :], t, *self.operands[1:],
            d=self.d, block_n=self.block[0], block_m=self.block[1],
            interpret=self.interpret)
        return yp, self._t(leak)

    def vector(self, state):
        return state[0][0]


# --------------------------------------------------------------------------- #
# the loop drivers                                                            #
# --------------------------------------------------------------------------- #
@partial(jax.jit, static_argnames=("n_iters",))
def fixed_loop(lay: Layout, d, *, n_iters: int) -> jax.Array:
    """``n_iters`` global iterations from the uniform start; the ranks."""
    state, _ = jax.lax.scan(lambda s, _: (lay.step(s, d), None),
                            lay.start(None), None, length=n_iters)
    return _real(lay.vector(state), lay.n)


@partial(jax.jit, static_argnames=("max_iters", "watchdog", "trace"))
def tol_loop(lay: Layout, d, tol, x0, *, max_iters: int,
             watchdog: bool = True, trace: bool = False):
    """Global iterations from ``x0`` (uniform for ``None``) until the L1
    step over the real nodes is at most ``tol``.  Returns ``(pr, iters,
    residual, grow, ring)`` — ``grow`` is the convergence watchdog's
    consecutive-growth counter at exit (0 with ``watchdog=False``) and
    ``ring`` the on-device residual-trajectory ring (``None`` with
    ``trace=False``)."""
    def step(state):
        new = lay.step(state, d)
        with jax.named_scope("pagerank.vector"):
            return new, jnp.sum(jnp.abs(_real(
                lay.vector(new) - lay.vector(state), lay.n)))

    state, iters, res, grow, ring = instrumented_tol_loop(
        step, lay.start(x0), tol=tol, max_iters=max_iters,
        watchdog=watchdog, trace=trace)
    return _real(lay.vector(state), lay.n), iters, res, grow, ring


@partial(jax.jit, static_argnames=("n_iters",))
def ppr_loop(lay: Layout, V: jax.Array, d, *, n_iters: int) -> jax.Array:
    """``n_iters`` batched personalized iterations of the (n, Q) teleport
    columns ``V``, starting from ``V``; the (n, Q) ranks."""
    Vp = lay.pad(V)
    PR, _ = jax.lax.scan(
        lambda PR, _: (ppr_step_batched(lay.mv, PR, Vp, lay.dang, d), None),
        Vp, None, length=n_iters)
    return _real(PR, lay.n)


def push_loop(Ab, x0: jax.Array, tol, n: int, max_pushes: int,
              trace: bool = False):
    """Gauss–Southwell frontier push to the fixed point ``x = Ab(x)`` of
    an affine map, from ``x0``: an (N,) vector or an (N, Q) batch.  Every
    sweep pushes the whole frontier mask ``|r| ≥ tol/n`` (whenever the
    residual exceeds ``tol`` at least one entry qualifies, so the loop
    cannot stall) and refreshes the residual ``r = Ab(x) − x`` from
    scratch — one operator sweep per round, immune to float drift in the
    bookkeeping.  The residual is the L1 norm over the first ``n`` rows,
    for a batch the largest column's, so exit means every column met
    ``tol``.  The (N,) push of the refresh runs under the
    ``pagerank.push`` scope.

    Runs on :func:`repro.obs.trace.instrumented_tol_loop` (NaN/Inf and
    sustained-growth watchdog — a corrupted layout makes the residual
    *grow* every sweep — plus the optional residual ring); the real
    initial residual seeds it, so an already-converged start exits in zero
    sweeps.  Returns ``(x, r, iters, residual, grow, ring)``."""
    thresh = tol / n
    scope = ((lambda: jax.named_scope("pagerank.push")) if x0.ndim == 1
             else contextlib.nullcontext)

    def l1(r):
        r = jnp.abs(_real(r, n))
        return jnp.sum(r) if r.ndim == 1 else jnp.max(jnp.sum(r, axis=0))

    def step(state):
        x, r = state
        with scope():
            x = x + r * (jnp.abs(r) >= thresh).astype(x.dtype)
            r = Ab(x) - x
            return (x, r), l1(r)

    with scope():
        r0 = Ab(x0) - x0
    (x, r), iters, res, grow, ring = instrumented_tol_loop(
        step, (x0, r0), tol=tol, max_iters=max_pushes, watchdog=True,
        trace=trace, res0=l1(r0))
    return x, r, iters, res, grow, ring


@partial(jax.jit, static_argnames=("max_pushes", "trace"))
def global_push(lay: Layout, d, tol, x0, *, max_pushes: int,
                trace: bool = False):
    """:func:`push_loop` of the global PageRank map from the (n,) ranks
    ``x0``; returns ``(x, sweeps, residual, grow, ring)``."""
    x, _, iters, res, grow, ring = push_loop(
        lambda x: lay.affine(x, d), lay.pad(x0), tol, lay.n, max_pushes,
        trace)
    return _real(x, lay.n), iters, res, grow, ring


# --------------------------------------------------------------------------- #
# the engine                                                                  #
# --------------------------------------------------------------------------- #
class PageRankEngine:
    """Prepared, whole-loop-compiled PageRank over one graph.

    Build it once per graph from the COO edge list; every ``run`` /
    ``run_tol`` / ``ppr`` call is a single device dispatch.  Backends:

    * ``"dense"``        — dangling-fixed dense H, XLA matmul sweep.
    * ``"ell"``          — sliced ELLPACK (:mod:`repro.pagerank.sell`):
      rows in degree tiers of doubling width, each row padded to under
      twice its degree plus 4, one gather per tier.
    * ``"bsr"``          — MXU-aligned block-sparse rows, explicit leak.
    * ``"pallas_dense"`` — pre-padded dense layout through the fused
      Pallas kernel with the in-kernel dangling reduction.
    * ``"dense_sharded"``— dangling-unfixed dense H blocked P(row, col)
      over a 2-D device mesh, iterated with the paper's fabric schedule
      (one psum + one re-injection per iteration); explicit scalar leak.
    * ``"ell_sharded"``  — sliced-ELL rows (degree tiers, no row padded
      to the maximum degree) sharded over the flattened mesh, rank vector
      replicated, one tiled all_gather per iteration.
    * ``"auto"``         — :func:`select_backend` by density + device
      topology (multi-device processes pick the sharded tiers).

    The sharded and Pallas tiers zero-pad N at construction; pad entries
    never feed back into real ranks and results are sliced back to N.
    Duplicate directed edges are collapsed up front so every tier sees the
    same graph.  ``prepared`` is the :class:`Layout`.
    """

    # row headroom of each SELL tier (``ell``, ``ell_sharded``); the
    # dynamic engine reserves more so edge deltas patch rows in place
    _slack = 0
    # whether those SELL rows hold the structure alone and the layout the
    # column scale (``SellLayout.col_scale``): the dynamic engine's, so a
    # changed out-degree patches one entry, not every row of its column
    _scale_cols = False

    def __init__(self, src: np.ndarray, dst: np.ndarray, n: int, *,
                 d: float = 0.85, backend: str = "auto",
                 block_n: int = 256, block_m: int = 256,
                 bsr_block_size: int = 128,
                 interpret: bool | None = None, mesh: Mesh | None = None,
                 metrics=None, precision: str = "auto"):
        self.n = int(n)
        self.d = float(d)
        src, dst = _dedupe_edges(np.asarray(src), np.asarray(dst), self.n)
        self.n_edges = int(len(src))
        self.density = self.n_edges / float(self.n * self.n)
        # host edge-set bookkeeping (sorted src*n+dst keys + degree
        # vectors): the landmark/hub subsystem
        # (repro.pagerank.landmarks) reads hub degrees and
        # out-neighborhoods off any prepared engine; the dynamic engine
        # keeps these fresh across deltas
        self._keys = delta_mod.edge_keys(src, dst, self.n)
        self._outdeg = np.bincount(src, minlength=self.n).astype(np.int64)
        self._indeg = np.bincount(dst, minlength=self.n).astype(np.int64)
        self.interpret = (kops.default_interpret() if interpret is None
                          else bool(interpret))
        # storage precision of the prepared layout's value arrays; the
        # solve itself (rank vectors, residuals, accumulation) is always
        # f32, and "auto" resolves to "f32" — bit-identical to the
        # pre-precision engine
        self.precision = resolve_precision(precision)
        self.storage_dtype = STORAGE_DTYPES[self.precision]
        self.backend = (select_backend(self.n, self.density)
                        if backend == "auto" else backend)
        if self.backend not in BACKENDS:
            raise ValueError(
                f"backend {self.backend!r} not in {BACKENDS + ('auto',)}")
        self._block_arg = (block_n, block_m)
        self._bsr_block_size = bsr_block_size
        self._mesh_arg = mesh
        # resilience bookkeeping: the last run_tol's SolveInfo and the
        # warn-once latch for silently-exhausted solves
        self.last_solve_info = None
        self._warned_nonconverged = False
        # metrics sink: the process default registry unless injected (a
        # NullRegistry records nothing)
        self.metrics = metrics if metrics is not None else default_registry()
        self.metrics.watch_compiles()
        with self.metrics.span("prepare", backend=self.backend):
            self._prepare_layout(src, dst)

    def _prepare_layout(self, src: np.ndarray, dst: np.ndarray) -> None:
        """Build (or rebuild) the backend's :class:`Layout` from a
        deduplicated COO edge list.  Split out of ``__init__`` so the
        dynamic-graph subsystem (:mod:`repro.pagerank.dynamic`) can fall
        back to a full layout rebuild when an edge delta is too large — or
        structurally too disruptive — to patch in place."""
        n, backend, precision = self.n, self.backend, self.precision
        dang = tr.dangling_mask(src, n).astype(np.float32)
        self._sell = None
        self.layout = backend
        self.mesh = None
        if backend in SHARDED_BACKENDS:
            self.mesh = (self._mesh_arg if self._mesh_arg is not None
                         else _default_mesh(backend))
            axes = tuple(self.mesh.axis_names)
            replicated = NamedSharding(self.mesh, P())
        if backend == "dense":
            H = np.asarray(tr.build_transition_dense(
                src, dst, n, fix_dangling=precision == "f32"))
            cls = FixedDenseLayout if precision == "f32" else DenseLayout
            lay = cls(operands=_stored(H, precision, jnp.asarray,
                                       jnp.asarray),
                      dang=jnp.asarray(dang), n=n)
        elif backend in ("ell", "ell_sharded"):
            shards = 1 if self.mesh is None else self.mesh.size
            n_pad = -(-n // shards) * shards
            # sliced ELL, not one block padded to the maximum degree; on
            # the mesh each device holds a self-contained SELL of its own
            # rows, so it sweeps them with one gather per degree tier.
            # int8 rows keep their values: a patch would re-quantize them,
            # so they never patch
            csr = tr.build_transition_csr(src, dst, n)
            scaled = self._scale_cols and precision != "int8"
            if scaled:
                csr = dataclasses.replace(csr,
                                          data=np.ones(csr.nnz, np.float32))
            operands, self._sell = sell.build(
                csr, n_pad, shards=shards, slack=self._slack,
                precision=precision,
                sharding=(None if self.mesh is None
                          else NamedSharding(self.mesh, P(axes))))
            self.layout = self._sell.describe(self._slack)
            col_scale = (np.pad(inv_outdeg(np.bincount(src, minlength=n),
                                           self.storage_dtype),
                                (0, n_pad - n)) if scaled else None)
            if self.mesh is None:
                lay = SellLayout(
                    operands=operands, dang=jnp.asarray(dang), n=n,
                    col_scale=(None if col_scale is None
                               else jnp.asarray(col_scale)))
            else:
                put = lambda a: jax.device_put(a, replicated)
                lay = ShardedSellLayout(
                    operands=operands, n=n, mesh=self.mesh, axes=axes,
                    dang=put(np.pad(dang, (0, n_pad - n))),
                    col_scale=None if col_scale is None else put(col_scale))
                self.layout = (f"ell_sharded({self.layout}, "
                               f"shards={shards}, n_pad={n_pad})")
        elif backend == "bsr":
            bsr = tr.build_transition_bsr(src, dst, n,
                                          bs=self._bsr_block_size)
            if precision == "int8":
                blocks = np.asarray(bsr.blocks)
                nb_r, _, bs, _ = blocks.shape
                # per-row abs-max across the block budget: axis 2 is the
                # row within a block, so reduce over (slot, in-block col)
                absmax = np.abs(blocks).max(axis=(1, 3))    # (nb_r, bs)
                scales = rowmax_scales(absmax.reshape(-1))  # (nb_r*bs,)
                bsr = BSRMatrix(
                    jnp.asarray(quantize_int8(
                        blocks, scales.reshape(nb_r, 1, bs, 1))),
                    bsr.block_cols, shape=bsr.shape,
                    row_scales=jnp.asarray(scales))
            elif precision != "f32":
                bsr = BSRMatrix(bsr.blocks.astype(self.storage_dtype),
                                bsr.block_cols, shape=bsr.shape)
            lay = BsrLayout(operands=(bsr,), dang=jnp.asarray(dang), n=n)
        elif backend == "dense_sharded":
            if len(axes) != 2:
                raise ValueError("dense_sharded needs a 2-D mesh, got axes "
                                 f"{axes}")
            r, c = (self.mesh.shape[a] for a in axes)
            n_pad = -(-n // math.lcm(r, c)) * math.lcm(r, c)
            Hp = np.zeros((n_pad, n_pad), np.float32)
            Hp[:n, :n] = np.asarray(tr.build_transition_dense(
                src, dst, n, fix_dangling=False))
            blk = NamedSharding(self.mesh, P(*axes))
            # int8 scales stay replicated: fabric_step folds them into
            # the P(row)-sharded accumulated row sums
            lay = DenseShardedLayout(
                operands=_stored(Hp, precision,
                                 lambda a: jax.device_put(a, blk),
                                 lambda s: jax.device_put(s, replicated)),
                dang=jax.device_put(np.pad(dang, (0, n_pad - n)),
                                    replicated),
                n=n, mesh=self.mesh, axes=axes)
            self.layout = f"dense_sharded({r}x{c} mesh, n_pad={n_pad})"
        else:                                   # pallas_dense
            H = tr.build_transition_dense(src, dst, n, fix_dangling=False)
            Hp, dangp, bn, bm = pad_pagerank_operands(
                H, jnp.asarray(dang), block_n=self._block_arg[0],
                block_m=self._block_arg[1])
            # int8 scales are (1, Np): the fused kernel applies them per
            # row-block in the same drain epilogue as the affine term
            lay = PallasLayout(
                operands=_stored(np.asarray(Hp), precision, jnp.asarray,
                                 lambda s: jnp.asarray(s)[None, :]),
                dang=dangp[0], n=n, d=self.d, block=(bn, bm),
                interpret=self.interpret)
        if precision != "f32":
            self.layout = f"{self.layout}[{precision}]"
        self.prepared = lay
        self._record_layout_bytes()

    def _record_layout_bytes(self) -> None:
        """Operand-byte accounting of the prepared layout (value vs index
        bytes — precision tiers shrink only the former), exported as the
        ``layout.bytes`` gauge and kept as ``self.layout_bytes``; its stored
        value slots, padding included, as the ``layout.slots`` gauge (over
        ``n_edges``, the padding a sweep gathers)."""
        self.layout_bytes = layout_nbytes(tuple(self.operands))
        self.metrics.gauge("layout.bytes").set(
            self.layout_bytes["total_bytes"])
        # a SELL's first leaf is its int32 row order; every other layout's
        # is its value array (H, the BSR blocks, the padded Pallas H)
        self.metrics.gauge("layout.slots").set(
            self._sell.slots if self._sell is not None
            else int(jax.tree.leaves(self.operands)[0].size))

    @property
    def operands(self) -> tuple:
        """The prepared (already padded/sharded) layout arrays — read-only
        access for inspection (shard shapes, memory accounting)."""
        return self.prepared.operands

    def lower_run(self, n_iters: int = 100):
        """AOT-lower the fixed-schedule ``run`` without executing it, for
        collective audits / HLO dumps of the sharded tiers (e.g. counting
        all-reduces in ``.compile().as_text()``)."""
        return fixed_loop.lower(self.prepared, self.d, n_iters=n_iters)

    # ------------------------------ queries ------------------------------ #
    def run(self, n_iters: int = 100) -> jax.Array:
        """Fixed-schedule power iteration; one compiled dispatch."""
        return fixed_loop(self.prepared, self.d, n_iters=n_iters)

    def run_tol(self, tol: float = 1e-6, max_iters: int = 1000,
                x0: np.ndarray | jax.Array | None = None, *,
                watchdog: bool = True, raise_on_fail: bool = False,
                trace: bool = True):
        """Tolerance-terminated power iteration; one compiled dispatch.
        Returns a :class:`~repro.pagerank.resilience.SolveResult` — still
        the classic ``(pr, n_iters, residual)`` 3-tuple, now carrying the
        full :class:`~repro.pagerank.resilience.SolveInfo` as ``.info``
        (also recorded as ``self.last_solve_info``).

        ``x0`` warm-starts the loop from a previous rank vector (shape
        ``(n,)``); ``None`` keeps the classic uniform cold start.  After a
        small graph change the previous ranks are an excellent initial
        state, so the dynamic-graph refresh path converges in a fraction
        of the cold iteration count.

        ``watchdog`` (default on) arms the in-loop convergence watchdog:
        NaN/Inf residuals and sustained residual growth abort the loop
        early instead of spinning to ``max_iters``, at two scalar ops per
        iteration inside the existing ``while_loop``.  A solve that did
        not converge used to return an unconverged vector
        indistinguishable from a converged one; now it warns once per
        engine — or raises
        :class:`~repro.pagerank.resilience.ConvergenceError` with
        ``raise_on_fail=True``.

        ``trace`` (default on) records the per-iteration residual ring on
        device (:class:`~repro.obs.trace.SolveTrace`, surfaced as
        ``result.info.trace`` — zero host syncs until its ``residuals``
        are read); ``trace=False`` compiles the ring out entirely."""
        # THE single coercion point for user solve inputs: float32 passes
        # through untouched, float64 gets one explicit warned downcast
        # (checked on the host dtype — with x64 disabled, asarray would
        # downcast silently), everything else is cast to the solve dtype
        x0 = solve_dtype(x0, name="x0")
        tol_f32 = solve_dtype(tol, name="tol")
        with self.metrics.span("solve", backend=self.backend):
            out = tol_loop(self.prepared, self.d, tol_f32, x0,
                           max_iters=max_iters, watchdog=watchdog,
                           trace=trace)
            return self._finish_solve(out, tol, max_iters, raise_on_fail)

    def _finish_solve(self, out, tol: float, max_iters: int,
                      raise_on_fail: bool) -> SolveResult:
        """Host-side epilogue of every tolerance solve: build the
        :class:`SolveInfo` from the loop's exit scalars, record it (plus
        the solve counters and event in the metrics registry), and apply
        the raise/warn-once policy for non-converged solves."""
        pr, iters, res, grow, ring = out
        trace = SolveTrace(ring, iters) if ring is not None else None
        info = make_solve_info(iters, res, grow, tol=tol,
                               max_iters=max_iters, trace=trace)
        self.last_solve_info = info
        m = self.metrics
        m.counter("engine.solves").inc()
        m.counter(f"engine.solve.{info.status}").inc()
        m.event("solve", backend=self.backend, precision=self.precision,
                iters=info.iters, residual=info.residual,
                status=info.status)
        if info.failed:
            m.event("watchdog", backend=self.backend, iters=info.iters,
                    residual=info.residual, status=info.status)
        if not info.converged:
            if raise_on_fail:
                raise ConvergenceError(info)
            if not self._warned_nonconverged:
                self._warned_nonconverged = True
                reason = ("nonfinite residual" if info.nonfinite else
                          "diverging residual" if info.diverged else
                          f"max_iters={max_iters} exhausted")
                warnings.warn(
                    f"run_tol did not converge ({reason}; iters="
                    f"{info.iters}, residual={info.residual:.3e}, tol="
                    f"{tol:.1e}); check run_tol(...).info — further "
                    f"non-converged solves on this engine stay silent",
                    RuntimeWarning, stacklevel=3)
        return SolveResult(pr, iters, res, info)

    def ppr(self, seed_sets: Sequence[np.ndarray],
            n_iters: int = 100) -> jax.Array:
        """Batched personalized PageRank: one (N, Q) propagation for Q
        per-user seed sets; returns the (N, Q) rank matrix
        (:meth:`ppr_columns` of their :func:`seed_matrix`)."""
        return self.ppr_columns(seed_matrix(self.n, seed_sets), n_iters)

    def ppr_columns(self, V: np.ndarray, n_iters: int = 100) -> jax.Array:
        """Batched personalized PageRank of the host (N, Q) teleport matrix
        ``V``, one distribution per column; returns the (N, Q) rank matrix.
        A zero column of ``V`` comes back an exact zero column, so a caller
        pads its query axis with zero columns to run one program per width.
        On the sharded tiers every device sweeps its share of H for all
        queries, so a multi-user serve flush spreads over devices.

        Counts ``ppr.sweeps`` (``n_iters``) and ``ppr.column_sweeps``
        (``n_iters`` per non-zero column).  The result is not waited for,
        so its ``ppr.dispatch`` span times the dispatch alone; the caller's
        host read ends the solve."""
        V = np.asarray(V, np.float32)
        queries = int(np.count_nonzero(V.any(axis=0)))
        m = self.metrics
        with m.span("ppr.dispatch", backend=self.backend, q=queries):
            m.counter("engine.ppr_queries").inc(queries)
            m.counter("ppr.sweeps").inc(n_iters)
            m.counter("ppr.column_sweeps").inc(n_iters * queries)
            return ppr_loop(self.prepared, jnp.asarray(V), self.d,
                            n_iters=n_iters)

    def lower_ppr(self, q: int, n_iters: int = 100):
        """AOT-lower the batched personalized PageRank of ``q`` columns
        without running it; its ``.compile()`` leaves a later
        :meth:`ppr_columns` of that width nothing to compile."""
        return ppr_loop.lower(
            self.prepared, jax.ShapeDtypeStruct((self.n, q), jnp.float32),
            self.d, n_iters=n_iters)
