"""Fused on-device PageRank engine: prepare once, run the whole loop compiled.

The seed drove its fastest tier from a host Python loop — one kernel
dispatch *per iteration*, a host sync between iterations, H re-padded
inside every call, and a separate full pass over the rank vector for the
dangling leak.  The paper's headline number (213.6 ms for 5k nodes x 100
iterations) comes from keeping the entire power iteration on the fabric
with no host intervention; :class:`PageRankEngine` is the JAX analogue:

* **Prepare once** — the padded/blocked layout (dense, ELL, BSR, or the
  Pallas pre-padded dense layout) is built at construction; nothing in the
  hot loop pads or reshapes.
* **Whole-loop compilation** — fixed schedules run as a single
  ``lax.scan`` and tolerance-terminated runs as a single
  ``lax.while_loop``, so 100 iterations are one dispatch, not 100
  dispatches + syncs.
* **In-kernel dangling fusion** — the Pallas tier uses
  :func:`repro.kernels.pagerank_step.pagerank_step_fused`, which emits
  ``sum(y * dangling)`` from the same epilogue that applies the affine
  term; the scan carries it as the next iteration's scalar ``t``, deleting
  the per-iteration extra pass over the rank vector.
* **Backend auto-selection** — by graph density and the active JAX
  device (``interpret`` for the Pallas tiers is derived from the device,
  not an import-time constant).
* **Batched personalized PageRank** — Q personalization queries propagate
  as one (N, Q) rank matrix sharing a single sweep over H per iteration
  (the MELOPPR-style batching; the Pallas tier rides the already-batched
  ``streaming_matvec``).
* **Sharded multi-device tiers** — ``dense_sharded`` runs the paper's
  fabric schedule (:mod:`repro.pagerank.distributed` over
  :mod:`repro.core.fabric_matvec`) with H blocked ``P(row, col)`` over a
  2-D device mesh; ``ell_sharded`` row-shards a sliced-ELL layout
  (:mod:`repro.pagerank.sell`) over the flattened mesh with one
  ``all_gather`` per iteration.  Both build their ``NamedSharding``
  layouts once at construction and keep tolerance-based early exit
  working across the mesh (the residual is a replicated scalar); batched
  (N, Q) PPR shards the query axis (``dense_sharded``) or the rows, as the
  global solve does (``ell_sharded``).

The canonical per-iteration step functions live in
:mod:`repro.pagerank.steps` and are shared with ``repro.pagerank.dense`` /
``repro.pagerank.sparse``, so every tier (and every test oracle) runs
literally the same arithmetic; the engine's dense tier dispatches the very
same jitted ``pagerank_dense_fixed`` program as the reference, making the
two bit-identical.
"""
from __future__ import annotations

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
from repro.kernels.common import ell_rows, upcast_f32
from repro.kernels.pagerank_step import (pad_pagerank_operands,
                                         pagerank_step_fused)
from repro.kernels.streaming_matvec import streaming_matvec
from repro.launch.mesh import make_mesh
from repro.pagerank import distributed as dist
from repro.pagerank import sell
from repro.obs.registry import default_registry
from repro.obs.trace import SolveTrace, instrumented_tol_loop
from repro.pagerank.dense import pagerank_dense, pagerank_dense_fixed
from repro.pagerank.precision import (PRECISIONS, STORAGE_DTYPES,
                                      layout_nbytes, quantize_int8,
                                      resolve_precision, rowmax_scales,
                                      solve_dtype)
from repro.pagerank.resilience import (ConvergenceError, SolveResult,
                                       make_solve_info)
from repro.pagerank.steps import (dense_step, ppr_step, ppr_step_batched,
                                  seed_matrix, sparse_step)

__all__ = ["PageRankEngine", "select_backend", "dense_step", "sparse_step",
           "ppr_step", "ppr_step_batched", "seed_matrix", "PRECISIONS"]

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


# --------------------------------------------------------------------------- #
# whole-loop compiled runners (XLA backends)                                  #
# --------------------------------------------------------------------------- #
def _split_ell(src: np.ndarray, dst: np.ndarray, n: int,
               k0: int | None = None):
    """Engine-prepared ELL layout: a tight per-row budget ``k0`` (the 90th
    degree percentile by default) plus a COO overflow tail for the
    power-law hub rows.  Classic full-k ELLPACK pads every row to the max
    degree — on scale-free protein networks that is ~15x more
    multiply-adds than the nnz; the split keeps the vectorized gather for
    ~90% of entries and routes the tail through one ``segment_sum``."""
    csr = tr.build_transition_csr(src, dst, n)
    counts = np.diff(np.asarray(csr.indptr))
    if k0 is None:
        k0 = max(4, int(np.percentile(counts, 90))) if len(counts) else 4
    cols = np.asarray(csr.indices)
    vals = np.asarray(csr.data)
    rows, pos = csr.row_positions()
    in_ell = pos < k0
    data = np.zeros((n, k0), np.float32)
    idx = np.zeros((n, k0), np.int32)
    data[rows[in_ell], pos[in_ell]] = vals[in_ell]
    idx[rows[in_ell], pos[in_ell]] = cols[in_ell]
    ov = ~in_ell
    return (jnp.asarray(data), jnp.asarray(idx),
            jnp.asarray(rows[ov], jnp.int32), jnp.asarray(cols[ov],
                                                          jnp.int32),
            jnp.asarray(vals[ov], jnp.float32)), k0, int(ov.sum())


def _row_scale(y: jax.Array, scales: jax.Array | None) -> jax.Array:
    """Fold an int8 layout's per-row f32 dequantization scales into the
    accumulated f32 row sums (vector or batched-matrix shaped)."""
    if scales is None:
        return y
    return y * (scales if y.ndim == 1 else scales[:, None])


def _matvec(backend: str, operands, x: jax.Array) -> jax.Array:
    """Dispatch y = H @ x on the prepared layout tag.

    Value arrays may be stored reduced-precision (bf16/f16/int8); they are
    upcast at the multiply (a trace-time no-op on f32 layouts, keeping the
    f32 tier's program bit-identical) and accumulated in f32.  int8
    layouts append their per-row f32 scale vectors to the operand tuple —
    the tuple length is static under jit, so the scaled variants trace to
    their own programs and the float tiers never pay a branch.
    """
    if backend == "dense":
        scales = operands[1] if len(operands) == 2 else None
        return _row_scale(upcast_f32(operands[0]) @ x, scales)
    if backend == "ell":
        data, idx, ov_r, ov_c, ov_v = operands[:5]
        scales = operands[5] if len(operands) == 6 else None
        ov_v = upcast_f32(ov_v)
        y = ell_rows(data, idx, x)
        tail = jax.ops.segment_sum(
            (ov_v if x.ndim == 1 else ov_v[:, None]) * x[ov_c], ov_r,
            num_segments=data.shape[0])
        return _row_scale(y + tail, scales)
    if backend == "sell":
        # sliced ELLPACK, the dynamic engine's patchable ELL tier: the
        # layout's own module builds and reads it
        return sell.sell_rows(operands, x)
    if backend == "bsr":
        # BSRMatrix upcasts its own blocks and owns its row_scales field
        bsr = operands[0]
        return bsr.matvec(x) if x.ndim == 1 else bsr.matmat(x)
    raise ValueError(f"unknown backend {backend!r}")


@partial(jax.jit, static_argnames=("backend", "n", "n_iters"))
def _run_fixed(operands, dang, d, *, backend: str, n: int, n_iters: int):
    pr0 = jnp.full((n,), 1.0 / n, jnp.float32)

    def body(pr, _):
        return sparse_step(lambda v: _matvec(backend, operands, v),
                           pr, dang, d, n), None

    pr, _ = jax.lax.scan(body, pr0, None, length=n_iters)
    return pr


@partial(jax.jit, static_argnames=("backend", "n", "max_iters", "watchdog",
                                   "trace"))
def _run_tol(operands, dang, d, tol, x0, *, backend: str, n: int,
             max_iters: int, watchdog: bool = True, trace: bool = False):
    """Returns ``(pr, iters, residual, grow, ring)`` — ``grow`` is the
    convergence watchdog's consecutive-growth counter at exit (0 with
    ``watchdog=False``, the overhead-measurement baseline) and ``ring``
    the on-device residual-trajectory ring (``None`` with
    ``trace=False``)."""
    pr0 = jnp.full((n,), 1.0 / n, jnp.float32) if x0 is None else x0

    def step(pr):
        new = sparse_step(lambda v: _matvec(backend, operands, v),
                          pr, dang, d, n)
        return new, jnp.sum(jnp.abs(new - pr))

    return instrumented_tol_loop(step, pr0, tol=tol, max_iters=max_iters,
                                 watchdog=watchdog, trace=trace)


@partial(jax.jit, static_argnames=("backend", "n", "n_iters"))
def _run_ppr(operands, dang, V, d, *, backend: str, n: int, n_iters: int):
    if backend == "dense":
        # the f32 dense operand is the dangling-FIXED H (uniform 1/n leak
        # folded into the dangling columns — right for global PageRank,
        # wrong for PPR where the leak teleports to V).  Zeroing those
        # columns reconstructs the unfixed H exactly; hoisted out of the
        # scan as a loop invariant.  Reduced-precision dense tiers store H
        # *unfixed* (their dangling columns are already zero), so the same
        # masking is a mathematical no-op and one program serves both.
        scales = operands[1] if len(operands) == 2 else None
        H = upcast_f32(operands[0]) * (1.0 - dang)[None, :]
        mv = lambda X: _row_scale(H @ X, scales)
    else:
        mv = lambda X: _matvec(backend, operands, X)

    def body(PR, _):
        return ppr_step_batched(mv, PR, V, dang, d), None

    PR, _ = jax.lax.scan(body, V, None, length=n_iters)
    return PR


# --------------------------------------------------------------------------- #
# whole-loop compiled runners (sharded multi-device tiers)                    #
#                                                                             #
# The mesh, axis names, true node count, and schedule length are all static: #
# one compiled program per (mesh, schedule), every call one dispatch.  The   #
# distributed schedules themselves live in repro.pagerank.distributed.       #
# --------------------------------------------------------------------------- #
@partial(jax.jit, static_argnames=("mesh", "axes", "n_true", "n_iters", "d"))
def _run_fixed_dense_sharded(H, dang, scales=None, *, mesh, axes, n_true,
                             n_iters, d):
    pr = dist.pagerank_distributed(H, mesh, n_iters=n_iters, d=d,
                                   row_axis=axes[0], col_axis=axes[1],
                                   dangling=dang, n_true=n_true,
                                   scales=scales)
    return pr[:n_true]


@partial(jax.jit, static_argnames=("mesh", "axes", "n_true", "max_iters",
                                   "d", "watchdog", "trace"))
def _run_tol_dense_sharded(H, dang, tol, x0, scales=None, *, mesh, axes,
                           n_true, max_iters, d, watchdog: bool = True,
                           trace: bool = False):
    pr, iters, res, grow, ring = dist.pagerank_distributed_tol(
        H, mesh, tol=tol, max_iters=max_iters, d=d, row_axis=axes[0],
        col_axis=axes[1], dangling=dang, n_true=n_true, x0=x0,
        watchdog=watchdog, trace=trace, scales=scales)
    return pr[:n_true], iters, res, grow, ring


@partial(jax.jit, static_argnames=("mesh", "axes", "n_true", "n_iters", "d"))
def _run_ppr_dense_sharded(H, dang, V, scales=None, *, mesh, axes, n_true,
                           n_iters, d):
    # H is stored dangling-UNFIXED for this tier, so the PPR schedule can
    # teleport the leak to V directly — no column reconstruction needed.
    PR = dist.ppr_distributed_dense(H, dang, V, mesh, n_iters=n_iters, d=d,
                                    row_axis=axes[0], col_axis=axes[1],
                                    scales=scales)
    return PR[:n_true]


@partial(jax.jit, static_argnames=("mesh", "axes", "n_true", "n_iters", "d"))
def _run_fixed_ell_sharded(layout, dang, *, mesh, axes, n_true, n_iters, d):
    pr = dist.pagerank_distributed_sparse(layout, mesh, n_iters=n_iters,
                                          d=d, dangling=dang, axes=axes,
                                          n_true=n_true)
    return pr[:n_true]


@partial(jax.jit, static_argnames=("mesh", "axes", "n_true", "max_iters",
                                   "d", "watchdog", "trace"))
def _run_tol_ell_sharded(layout, dang, tol, x0, *, mesh, axes, n_true,
                         max_iters, d, watchdog: bool = True,
                         trace: bool = False):
    pr, iters, res, grow, ring = dist.pagerank_distributed_sparse_tol(
        layout, mesh, tol=tol, max_iters=max_iters, d=d, dangling=dang,
        axes=axes, n_true=n_true, x0=x0, watchdog=watchdog, trace=trace)
    return pr[:n_true], iters, res, grow, ring


@partial(jax.jit, static_argnames=("mesh", "axes", "n_true", "n_iters", "d"))
def _run_ppr_ell_sharded(layout, dang, V, *, mesh, axes, n_true, n_iters,
                         d):
    PR = dist.ppr_distributed_sparse(layout, dang, V, mesh,
                                     n_iters=n_iters, d=d, axes=axes)
    return PR[:n_true]


# --------------------------------------------------------------------------- #
# whole-loop compiled runners (Pallas pre-padded dense tier)                  #
# --------------------------------------------------------------------------- #
@partial(jax.jit, static_argnames=("n", "n_iters", "d", "block_n",
                                   "block_m", "interpret"))
def _run_fixed_pallas(Hp, dangp, scales=None, *, n: int, n_iters: int,
                      d: float, block_n: int, block_m: int,
                      interpret: bool):
    Mp = Hp.shape[1]
    xp0 = jnp.pad(jnp.full((n,), 1.0 / n, jnp.float32), (0, Mp - n))[None, :]
    t0 = d * jnp.sum(xp0 * dangp) / n + (1.0 - d) / n

    def body(carry, _):
        xp, t = carry
        yp, leak = pagerank_step_fused(Hp, xp, dangp, t, scales, d=d,
                                       block_n=block_n, block_m=block_m,
                                       interpret=interpret)
        return (yp, d * leak / n + (1.0 - d) / n), None

    (yp, _), _ = jax.lax.scan(body, (xp0, t0), None, length=n_iters)
    return yp[0, :n]


@partial(jax.jit, static_argnames=("n", "max_iters", "d", "block_n",
                                   "block_m", "interpret", "watchdog",
                                   "trace"))
def _run_tol_pallas(Hp, dangp, tol, x0, scales=None, *, n: int,
                    max_iters: int, d: float, block_n: int, block_m: int,
                    interpret: bool, watchdog: bool = True,
                    trace: bool = False):
    Mp = Hp.shape[1]
    x0 = jnp.full((n,), 1.0 / n, jnp.float32) if x0 is None else x0
    xp0 = jnp.pad(x0, (0, Mp - n))[None, :]
    t0 = d * jnp.sum(xp0 * dangp) / n + (1.0 - d) / n

    def step(carry):
        xp, t = carry
        yp, leak = pagerank_step_fused(Hp, xp, dangp, t, scales, d=d,
                                       block_n=block_n, block_m=block_m,
                                       interpret=interpret)
        res = jnp.sum(jnp.abs(yp[0, :n] - xp[0, :n]))
        return (yp, d * leak / n + (1.0 - d) / n), res

    (xp, _), iters, res, grow, ring = instrumented_tol_loop(
        step, (xp0, t0), tol=tol, max_iters=max_iters, watchdog=watchdog,
        trace=trace)
    return xp[0, :n], iters, res, grow, ring


@partial(jax.jit, static_argnames=("n", "n_iters", "d", "block_n",
                                   "block_m", "interpret"))
def _run_ppr_pallas(Hp, dangp, Vp, scales=None, *, n: int, n_iters: int,
                    d: float, block_n: int, block_m: int, interpret: bool):
    # Vp: (Q, Np) — queries ride the batch axis of streaming_matvec, so all
    # Q teleport distributions share one sweep over Hp per iteration.  The
    # kernel upcasts reduced-precision Hp tiles in-register; an int8
    # layout's (1, Np) row scales fold into the f32 output here (Y's
    # column axis is Hp's row axis).
    def body(PR, _):
        leak = jnp.sum(PR * dangp, axis=1)                # (Q,)
        Y = streaming_matvec(Hp, PR, block_n=block_n, block_m=block_m,
                             interpret=interpret)
        if scales is not None:
            Y = Y * scales
        return d * (Y + Vp * leak[:, None]) + (1.0 - d) * Vp, None

    PR, _ = jax.lax.scan(body, Vp, None, length=n_iters)
    return PR[:, :n].T                                    # (n, Q)


# --------------------------------------------------------------------------- #
# the engine                                                                  #
# --------------------------------------------------------------------------- #
class PageRankEngine:
    """Prepared, whole-loop-compiled PageRank over one graph.

    Build it once per graph from the COO edge list; every ``run`` /
    ``run_tol`` / ``ppr`` call is a single device dispatch.  Backends:

    * ``"dense"``        — dangling-fixed dense H, XLA matmul sweep.
    * ``"ell"``          — engine-prepared split ELLPACK: a tight per-row
      budget (``ell_k``, default 90th degree percentile) + a COO overflow
      tail for hub rows, so the hot loop doesn't pay max-degree padding.
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

    The sharded tiers zero-pad N (and the PPR query axis) up to the mesh
    divisibility requirement at construction; pad entries never feed back
    into real ranks and results are sliced back to N.  Duplicate directed
    edges are collapsed up front so every tier sees the same graph.
    """

    # row headroom of each SELL tier (``ell_sharded``); the dynamic engine
    # reserves more so edge deltas patch rows in place
    _slack = 0

    def __init__(self, src: np.ndarray, dst: np.ndarray, n: int, *,
                 d: float = 0.85, backend: str = "auto",
                 block_n: int = 256, block_m: int = 256,
                 bsr_block_size: int = 128, ell_k: int | None = None,
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
        self._ell_k = ell_k
        self._mesh_arg = mesh
        # resilience bookkeeping: the last run_tol's SolveInfo and the
        # warn-once latch for silently-exhausted solves
        self.last_solve_info = None
        self._warned_nonconverged = False
        # metrics sink: the process default registry unless injected (a
        # NullRegistry injects the uninstrumented overhead baseline)
        self.metrics = metrics if metrics is not None else default_registry()
        with self.metrics.span("prepare", backend=self.backend):
            self._prepare_layout(src, dst)

    def _prepare_layout(self, src: np.ndarray, dst: np.ndarray) -> None:
        """Build (or rebuild) the backend's prepared device layout from a
        deduplicated COO edge list.  Split out of ``__init__`` so the
        dynamic-graph subsystem (:mod:`repro.pagerank.dynamic`) can fall
        back to a full layout rebuild when an edge delta is too large — or
        structurally too disruptive — to patch in place."""
        n = self.n
        block_n, block_m = self._block_arg
        bsr_block_size, ell_k, mesh = (self._bsr_block_size, self._ell_k,
                                       self._mesh_arg)
        self._dang = jnp.asarray(tr.dangling_mask(src, n).astype(np.float32))
        self._block = self._block_arg
        self.mesh = None
        self._axes: tuple[str, ...] = ()
        self._n_pad = self.n
        # int8 per-row dequantization scales of the pallas/dense_sharded
        # tiers (the XLA tiers append theirs to the operand tuple, SELL
        # keeps one per tier); always None for float precisions
        self._scales = None
        # the layout tag the generic jitted runners dispatch _matvec on —
        # normally the backend itself; the dynamic engine's patchable SELL
        # tier overrides it while keeping backend == "ell"
        self._mv_backend = self.backend
        self.layout = self.backend
        if self.backend == "dense":
            if self.precision == "f32":
                self._operands = (tr.build_transition_dense(src, dst, n),)
            else:
                # reduced tiers store H dangling-UNFIXED (the fix would
                # densify the dangling columns with 1/n values that
                # quantize poorly) and pay the explicit scalar leak via
                # the generic runners' sparse_step
                H = np.asarray(tr.build_transition_dense(
                    src, dst, n, fix_dangling=False))
                if self.precision == "int8":
                    scales = rowmax_scales(
                        np.abs(H).max(axis=1, initial=0.0))
                    self._operands = (
                        jnp.asarray(quantize_int8(H, scales[:, None])),
                        jnp.asarray(scales))
                else:
                    self._operands = (
                        jnp.asarray(H).astype(self.storage_dtype),)
        elif self.backend == "ell":
            self._operands, k0, ov_nnz = _split_ell(src, dst, n, k0=ell_k)
            self.layout = f"ell(k0={k0})+overflow(nnz={ov_nnz})"
            if self.precision != "f32":
                self._operands = self._quantize_split_ell(self._operands)
        elif self.backend == "bsr":
            bsr = tr.build_transition_bsr(src, dst, n, bs=bsr_block_size)
            if self.precision == "int8":
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
            elif self.precision != "f32":
                bsr = BSRMatrix(bsr.blocks.astype(self.storage_dtype),
                                bsr.block_cols, shape=bsr.shape)
            self._operands = (bsr,)
        elif self.backend == "dense_sharded":
            self.mesh = mesh if mesh is not None else _default_mesh(
                self.backend)
            self._axes = tuple(self.mesh.axis_names)
            if len(self._axes) != 2:
                raise ValueError("dense_sharded needs a 2-D mesh, got axes "
                                 f"{self._axes}")
            r, c = (self.mesh.shape[a] for a in self._axes)
            self._n_pad = -(-self.n // math.lcm(r, c)) * math.lcm(r, c)
            Hp = np.zeros((self._n_pad, self._n_pad), np.float32)
            Hp[:n, :n] = np.asarray(tr.build_transition_dense(
                src, dst, n, fix_dangling=False))
            blk = NamedSharding(self.mesh, P(*self._axes))
            if self.precision == "int8":
                scales = rowmax_scales(np.abs(Hp).max(axis=1, initial=0.0))
                self._operands = (jax.device_put(
                    quantize_int8(Hp, scales[:, None]), blk),)
                # replicated: _dense_iter folds it into the P(row)-sharded
                # accumulated row sums
                self._scales = jax.device_put(
                    scales, NamedSharding(self.mesh, P()))
            elif self.precision != "f32":
                self._operands = (jax.device_put(
                    jnp.asarray(Hp).astype(self.storage_dtype), blk),)
            else:
                self._operands = (jax.device_put(Hp, blk),)
            self._dang = self._pad_replicated(self._dang)
            self.layout = (f"dense_sharded({r}x{c} mesh, "
                           f"n_pad={self._n_pad})")
        elif self.backend == "ell_sharded":
            self.mesh = mesh if mesh is not None else _default_mesh(
                self.backend)
            self._axes = tuple(self.mesh.axis_names)
            ndev = self.mesh.size
            self._n_pad = -(-self.n // ndev) * ndev
            # sliced ELL, not one block padded to the maximum degree: each
            # device holds a self-contained SELL of its own rows, so it
            # sweeps them with one gather per degree tier
            self._operands, self._sell = sell.build(
                tr.build_transition_csr(src, dst, n), self._n_pad,
                shards=ndev, slack=self._slack, precision=self.precision,
                sharding=NamedSharding(self.mesh, P(self._axes)))
            self._dang = self._pad_replicated(self._dang)
            self.layout = (f"ell_sharded({self._sell.describe(self._slack)}"
                           f", shards={ndev}, n_pad={self._n_pad})")
        else:                                   # pallas_dense
            H = tr.build_transition_dense(src, dst, n, fix_dangling=False)
            Hp, dangp, bn, bm = pad_pagerank_operands(
                H, self._dang, block_n=block_n, block_m=block_m)
            if self.precision == "int8":
                Hp_np = np.asarray(Hp)
                scales = rowmax_scales(
                    np.abs(Hp_np).max(axis=1, initial=0.0))
                Hp = jnp.asarray(quantize_int8(Hp_np, scales[:, None]))
                # (1, Np): the fused kernel applies it per row-block in
                # the same drain epilogue as the affine term
                self._scales = jnp.asarray(scales)[None, :]
            elif self.precision != "f32":
                Hp = Hp.astype(self.storage_dtype)
            self._operands = (Hp, dangp)
            self._block = (bn, bm)
        if self.precision != "f32":
            self.layout = f"{self.layout}[{self.precision}]"
        self._record_layout_bytes()

    def _quantize_split_ell(self, operands: tuple) -> tuple:
        """Cast a prepared split-ELL layout's value arrays to the reduced
        storage dtype.  int8 scales are computed over the FULL row — the
        ELL block's entries and the COO overflow tail share the row's
        abs-max — and appended as a sixth operand."""
        data, idx, ov_r, ov_c, ov_v = operands
        if self.precision != "int8":
            return (data.astype(self.storage_dtype), idx, ov_r, ov_c,
                    ov_v.astype(self.storage_dtype))
        data_np, ov_v_np = np.asarray(data), np.asarray(ov_v)
        ov_r_np = np.asarray(ov_r)
        absmax = np.abs(data_np).max(axis=1, initial=0.0)
        np.maximum.at(absmax, ov_r_np, np.abs(ov_v_np))
        scales = rowmax_scales(absmax)
        return (jnp.asarray(quantize_int8(data_np, scales[:, None])), idx,
                ov_r, ov_c,
                jnp.asarray(quantize_int8(ov_v_np, scales[ov_r_np])),
                jnp.asarray(scales))

    def _record_layout_bytes(self) -> None:
        """Operand-byte accounting of the prepared layout (value vs index
        bytes — precision tiers shrink only the former), exported as the
        ``layout.bytes`` gauge and kept as ``self.layout_bytes``."""
        extras = () if self._scales is None else (self._scales,)
        self.layout_bytes = layout_nbytes(tuple(self._operands) + extras)
        self.metrics.gauge("layout.bytes").set(
            self.layout_bytes["total_bytes"])

    def _pad_replicated(self, dang: jax.Array) -> jax.Array:
        padded = np.zeros((self._n_pad,), np.float32)
        padded[:self.n] = np.asarray(dang)
        return jax.device_put(padded, NamedSharding(self.mesh, P()))

    @property
    def operands(self) -> tuple:
        """The prepared (already padded/sharded) layout arrays — read-only
        access for inspection (shard shapes, memory accounting)."""
        return self._operands

    def lower_run(self, n_iters: int = 100):
        """AOT-lower the fixed-schedule ``run`` without executing it, for
        collective audits / HLO dumps of the sharded tiers (e.g. counting
        all-reduces in ``.compile().as_text()``)."""
        if self.backend == "dense_sharded":
            return _run_fixed_dense_sharded.lower(
                self._operands[0], self._dang, self._scales,
                mesh=self.mesh, axes=self._axes, n_true=self.n,
                n_iters=n_iters, d=self.d)
        if self.backend == "ell_sharded":
            return _run_fixed_ell_sharded.lower(
                self._operands, self._dang, mesh=self.mesh, axes=self._axes,
                n_true=self.n, n_iters=n_iters, d=self.d)
        if self.backend == "dense" and self.precision == "f32":
            return pagerank_dense_fixed.lower(
                self._operands[0], n_iters=n_iters, d=self.d)
        if self.backend == "pallas_dense":
            return _run_fixed_pallas.lower(
                *self._operands, self._scales, n=self.n, n_iters=n_iters,
                d=self.d, block_n=self._block[0], block_m=self._block[1],
                interpret=self.interpret)
        return _run_fixed.lower(self._operands, self._dang, self.d,
                                backend=self._mv_backend, n=self.n,
                                n_iters=n_iters)

    # ------------------------------ queries ------------------------------ #
    def run(self, n_iters: int = 100) -> jax.Array:
        """Fixed-schedule power iteration; one compiled dispatch."""
        if self.backend == "dense_sharded":
            return _run_fixed_dense_sharded(
                self._operands[0], self._dang, self._scales,
                mesh=self.mesh, axes=self._axes, n_true=self.n,
                n_iters=n_iters, d=self.d)
        if self.backend == "ell_sharded":
            return _run_fixed_ell_sharded(
                self._operands, self._dang, mesh=self.mesh, axes=self._axes,
                n_true=self.n, n_iters=n_iters, d=self.d)
        if self.backend == "pallas_dense":
            Hp, dangp = self._operands
            return _run_fixed_pallas(
                Hp, dangp, self._scales, n=self.n, n_iters=n_iters,
                d=self.d, block_n=self._block[0], block_m=self._block[1],
                interpret=self.interpret)
        if self.backend == "dense" and self.precision == "f32":
            # the reference program itself -> bit-identical to it; the
            # reduced-precision dense tiers store H unfixed and take the
            # generic explicit-leak runner below instead
            return pagerank_dense_fixed(self._operands[0], n_iters=n_iters,
                                        d=self.d)
        return _run_fixed(self._operands, self._dang, self.d,
                          backend=self._mv_backend, n=self.n,
                          n_iters=n_iters)

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
            if self.backend == "dense_sharded":
                out = _run_tol_dense_sharded(
                    self._operands[0], self._dang, tol_f32,
                    self._pad_x0(x0), self._scales, mesh=self.mesh,
                    axes=self._axes, n_true=self.n, max_iters=max_iters,
                    d=self.d, watchdog=watchdog, trace=trace)
            elif self.backend == "ell_sharded":
                out = _run_tol_ell_sharded(
                    self._operands, self._dang, tol_f32, self._pad_x0(x0),
                    mesh=self.mesh, axes=self._axes, n_true=self.n,
                    max_iters=max_iters, d=self.d, watchdog=watchdog,
                    trace=trace)
            elif self.backend == "pallas_dense":
                Hp, dangp = self._operands
                out = _run_tol_pallas(
                    Hp, dangp, tol_f32, x0, self._scales, n=self.n,
                    max_iters=max_iters, d=self.d, block_n=self._block[0],
                    block_m=self._block[1], interpret=self.interpret,
                    watchdog=watchdog, trace=trace)
            elif self.backend == "dense" and self.precision == "f32":
                out = pagerank_dense(self._operands[0], d=self.d,
                                     tol=tol_f32, max_iters=max_iters,
                                     x0=x0, watchdog=watchdog, trace=trace)
            else:
                out = _run_tol(self._operands, self._dang, self.d,
                               tol_f32, x0,
                               backend=self._mv_backend, n=self.n,
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

    def _pad_x0(self, x0: jax.Array | None) -> jax.Array | None:
        """Zero-pad a warm-start vector up to the sharded tiers' padded N
        (pad entries never feed back into real ranks)."""
        if x0 is None or self._n_pad == self.n:
            return x0
        return jnp.pad(x0, (0, self._n_pad - self.n))

    def ppr(self, seed_sets: Sequence[np.ndarray],
            n_iters: int = 100) -> jax.Array:
        """Batched personalized PageRank: one (N, Q) propagation for Q
        per-user seed sets; returns the (N, Q) rank matrix.

        On ``dense_sharded`` the query axis is sharded across the mesh
        (padded up to the shard count with zero columns, sliced back); on
        ``ell_sharded`` every device sweeps its own rows for all queries,
        so a multi-user serve flush spreads over devices either way."""
        with self.metrics.span("ppr", backend=self.backend,
                               q=len(seed_sets)):
            self.metrics.counter("engine.ppr_queries").inc(len(seed_sets))
            return self._ppr(seed_sets, n_iters)

    def _ppr(self, seed_sets: Sequence[np.ndarray],
             n_iters: int) -> jax.Array:
        V = seed_matrix(self.n, seed_sets)
        if self.backend == "dense_sharded":
            q = V.shape[1]
            q_shards = self.mesh.shape[self._axes[1]]
            q_pad = -(-q // q_shards) * q_shards
            Vp = np.zeros((self._n_pad, q_pad), np.float32)
            Vp[:self.n, :q] = V
            PR = _run_ppr_dense_sharded(
                self._operands[0], self._dang, jnp.asarray(Vp),
                self._scales, mesh=self.mesh, axes=self._axes,
                n_true=self.n, n_iters=n_iters, d=self.d)
            return PR[:, :q]
        if self.backend == "ell_sharded":
            Vp = np.zeros((self._n_pad, V.shape[1]), np.float32)
            Vp[:self.n] = V
            return _run_ppr_ell_sharded(
                self._operands, self._dang, jnp.asarray(Vp), mesh=self.mesh,
                axes=self._axes, n_true=self.n, n_iters=n_iters, d=self.d)
        if self.backend == "pallas_dense":
            Hp, dangp = self._operands
            Vp = np.zeros((V.shape[1], Hp.shape[1]), np.float32)
            Vp[:, :self.n] = V.T
            return _run_ppr_pallas(
                Hp, dangp, jnp.asarray(Vp), self._scales, n=self.n,
                n_iters=n_iters, d=self.d, block_n=self._block[0],
                block_m=self._block[1], interpret=self.interpret)
        return _run_ppr(self._operands, self._dang, jnp.asarray(V), self.d,
                        backend=self._mv_backend, n=self.n,
                        n_iters=n_iters)
