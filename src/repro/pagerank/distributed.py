"""Pod-scale distributed PageRank — the paper's workload on the TPU mesh.

Two production layouts, each in a fixed-schedule and a tolerance-terminated
variant, plus the batched-PPR schedules that back the ``dense_sharded`` /
``ell_sharded`` tiers of :class:`repro.pagerank.engine.PageRankEngine`:

* :func:`pagerank_distributed` / :func:`pagerank_distributed_tol` — dense H
  sharded ``P(row, col)`` over the 2-D mesh, iterating the paper's fabric
  schedule (vertical-bus all-gather -> local MV -> horizontal-bus psum ->
  diagonal re-injection).  This is the direct pod-scale analogue of
  Fig. 3/Fig. 4 and what the dry-run lowers for the ``pagerank_65k`` config.

* :func:`pagerank_distributed_sparse` /
  :func:`pagerank_distributed_sparse_tol` — a sliced-ELL layout
  (:mod:`repro.pagerank.sell`) row-sharded over the flattened mesh (1-D
  row distribution), rank vector replicated, one ``all_gather`` per
  iteration.  This is the realistic layout for sparse interactomes where
  N >> nnz/N: each device sweeps its own rows in degree tiers, so no row
  is padded to the maximum degree.

* :func:`push_distributed_tol` / :func:`push_distributed_sparse_tol` — the
  Gauss–Southwell frontier push of the dynamic-refresh path run shard-local
  on the same two layouts: the frontier update is elementwise on each
  device's shard and the residual L1 norm costs one psum per sweep (the
  dense variant reuses the fabric matvec's collectives; the sparse variant
  computes it replicated after the per-sweep all_gather, no extra
  collective at all).

* :func:`ppr_distributed_dense` / :func:`ppr_distributed_sparse` — the
  batched (N, Q) personalized-PageRank matrix.  The dense variant shards
  it over the **query** axis and row-parallelizes the sweep (one row-axis
  ``all_gather`` per iteration); the sparse variant leaves the layout
  row-sharded where it is, sweeps every query over each device's own rows
  and re-assembles the (N, Q) image with one ``all_gather`` per iteration
  (:func:`sell_mv_sharded`), so no device holds the whole layout.

Uneven shapes are handled by zero-padding: every entry point takes
``n_true`` (the real node count) and keeps the PageRank arithmetic —
``1/n`` teleports, the dangling leak, residuals — on the real nodes only.
Padded rows/columns of H are zero, so pad entries never feed back into real
ranks; callers slice ``[:n_true]``.

All loops run under a single ``jit`` with ``lax.scan`` / ``lax.while_loop``
over iterations so XLA can pipeline collectives across iterations.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import fabric_matvec as fm
from repro.obs.trace import instrumented_tol_loop
from repro.pagerank.sell import n_rows, sell_rows
from repro.pagerank.steps import ppr_step_batched


def _shard_map(kernel, mesh: Mesh, in_specs, out_specs):
    # replication checks off: the kernels mix all_gather'd and per-device
    # values
    return jax.shard_map(kernel, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _pr0(n: int, n_true: int, dtype=jnp.float32) -> jax.Array:
    """Uniform 1/n_true on the real nodes, exactly 0 on the pad tail."""
    return jnp.where(jnp.arange(n) < n_true,
                     jnp.asarray(1.0 / n_true, dtype), 0).astype(dtype)


def _real_mask(n: int, n_true: int, dtype=jnp.float32) -> jax.Array:
    return (jnp.arange(n) < n_true).astype(dtype)


# --------------------------------------------------------------------------- #
# dense fabric schedule (2-D mesh)                                            #
# --------------------------------------------------------------------------- #
def _dense_iter(H, pr, dangling, mesh, row_axis, col_axis, d, nt,
                scales=None):
    """The canonical fabric-schedule iteration, shared by the fixed and
    tolerance-terminated variants so the arithmetic (and hence the float
    result) is defined in one place.  The leak term is the fabric analogue
    of the adder-column epilogue; ``dangling`` is a proper argument now —
    the seed closed over a name assigned *after* the closure def (it
    worked only because tracing happened later, and no caller ever
    exercised the dangling branch; tests/test_engine_sharded.py does).
    ``H`` may be stored reduced-precision (the fabric matvec upcasts each
    shard tile in-register and accumulates in f32); ``scales`` is the
    optional replicated per-row f32 dequantization vector of an int8
    layout, folded into the accumulated row sums here."""
    y = fm.matvec(H, pr, mesh, row_axis, col_axis)
    if scales is not None:
        y = y * scales
    leak = 0.0 if dangling is None else jnp.sum(pr * dangling) / nt
    y = d * (y + leak) + (1.0 - d) / nt
    return fm.matvec_iterated_reshard(y, mesh, row_axis, col_axis)


def pagerank_distributed(H: jax.Array, mesh: Mesh, n_iters: int = 100,
                         d: float = 0.85, row_axis: str = "data",
                         col_axis: str = "model",
                         dangling: jax.Array | None = None,
                         n_true: int | None = None,
                         scales: jax.Array | None = None) -> jax.Array:
    """Dense fabric-schedule PageRank.  H: (N, N) sharded P(row, col);
    returns PR (N,) sharded P(col) (vertical-bus layout).

    With ``dangling`` given, H must be the *unfixed* transition matrix and
    the leak is applied as an explicit scalar (the fabric analogue of the
    adder-column epilogue); with ``dangling=None`` H must be dangling-fixed.
    ``H`` may be stored reduced-precision; the iterate is always f32, and
    ``scales`` carries an int8 layout's per-row dequantization vector.
    """
    n = H.shape[0]
    nt = int(n if n_true is None else n_true)

    def one_iter(pr, _):
        return _dense_iter(H, pr, dangling, mesh, row_axis, col_axis,
                           d, nt, scales), None

    pr0 = jax.lax.with_sharding_constraint(
        _pr0(n, nt), NamedSharding(mesh, P(col_axis)))
    pr, _ = jax.lax.scan(one_iter, pr0, None, length=n_iters)
    return pr


def pagerank_distributed_tol(H: jax.Array, mesh: Mesh, tol: float = 1e-6,
                             max_iters: int = 1000, d: float = 0.85,
                             row_axis: str = "data", col_axis: str = "model",
                             dangling: jax.Array | None = None,
                             n_true: int | None = None,
                             x0: jax.Array | None = None,
                             watchdog: bool = True, trace: bool = False,
                             scales: jax.Array | None = None):
    """Tolerance-terminated fabric-schedule PageRank; the L1 residual is a
    replicated scalar, so every device exits the ``while_loop`` on the same
    iteration — and so the convergence watchdog's abort decision (NaN/Inf
    or sustained residual growth, armed by default) is identical on every
    device too.  Returns ``(pr, n_iters, residual, grow, ring)`` with
    ``grow`` the watchdog's consecutive-growth counter at exit and ``ring``
    the on-device residual-trajectory ring (``None`` with ``trace=False``;
    replicated — every device records the same residuals).  ``x0`` (padded
    to N, zeros on the pad tail) warm-starts the loop."""
    n = H.shape[0]
    nt = int(n if n_true is None else n_true)
    mask = jax.lax.with_sharding_constraint(
        _real_mask(n, nt), NamedSharding(mesh, P(col_axis)))

    def step(pr):
        new = _dense_iter(H, pr, dangling, mesh, row_axis, col_axis, d, nt,
                          scales)
        return new, jnp.sum(jnp.abs(new - pr) * mask)

    pr0 = jax.lax.with_sharding_constraint(
        _pr0(n, nt) if x0 is None else x0.astype(jnp.float32),
        NamedSharding(mesh, P(col_axis)))

    return instrumented_tol_loop(step, pr0, tol=tol, max_iters=max_iters,
                                 watchdog=watchdog, trace=trace)


# --------------------------------------------------------------------------- #
# sparse row-sharded schedule (flattened mesh)                                #
#                                                                             #
# ``layout`` is a SELL operand pytree built with ``shards`` = the mesh size   #
# (repro.pagerank.sell): every leaf is row-sharded ``P(axes)``, and each      #
# device's block is a self-contained SELL of its own rows.                    #
# --------------------------------------------------------------------------- #
def _sell_block_iter(layout_blk, pr, dang_full, axes, d, nt):
    """Canonical row-sharded iteration (local rows -> leak -> damp ->
    tiled all_gather), shared by the fixed and tolerance variants and the
    shard-local push.  Reduced-precision tiers are upcast in the row sums,
    where an int8 layout's per-row scales are folded in too."""
    y_blk = sell_rows(layout_blk, pr)
    leak = jnp.sum(pr * dang_full) / nt
    y_blk = d * (y_blk + leak) + (1.0 - d) / nt
    return jax.lax.all_gather(y_blk, axes, tiled=True)


def _dangling(dangling: jax.Array | None, n: int) -> jax.Array:
    return (jnp.zeros((n,), jnp.float32) if dangling is None
            else jnp.asarray(dangling, jnp.float32))


def pagerank_distributed_sparse(layout, mesh: Mesh, n_iters: int = 100,
                                d: float = 0.85,
                                dangling: jax.Array | None = None,
                                axes: tuple[str, ...] = ("data", "model"),
                                n_true: int | None = None) -> jax.Array:
    """Row-sharded SELL PageRank; PR replicated.  One tiled ``all_gather``
    of the fresh row-shards per iteration."""
    n = n_rows(layout)
    nt = int(n if n_true is None else n_true)

    def kernel(layout_blk, dang_full):
        def one_iter(pr, _):
            return _sell_block_iter(layout_blk, pr, dang_full, axes, d,
                                    nt), None

        pr, _ = jax.lax.scan(one_iter, _pr0(n, nt), None, length=n_iters)
        return pr

    return _shard_map(kernel, mesh, in_specs=(P(axes), P()),
                      out_specs=P())(layout, _dangling(dangling, n))


def pagerank_distributed_sparse_tol(layout, mesh: Mesh, tol: float = 1e-6,
                                    max_iters: int = 1000, d: float = 0.85,
                                    dangling: jax.Array | None = None,
                                    axes: tuple[str, ...] = ("data", "model"),
                                    n_true: int | None = None,
                                    x0: jax.Array | None = None,
                                    watchdog: bool = True,
                                    trace: bool = False):
    """Tolerance-terminated row-sharded SELL PageRank.  After each
    iteration's ``all_gather`` every device holds the full fresh vector, so
    the residual (and the exit decision — including the convergence
    watchdog's abort on NaN/Inf or sustained residual growth, armed by
    default) is computed identically everywhere without an extra
    collective.  Returns ``(pr, n_iters, residual, grow, ring)`` with
    ``grow`` the watchdog's consecutive-growth counter at exit and ``ring``
    the residual-trajectory ring (``None`` with ``trace=False``; computed
    from the replicated residual, so it is identical — and replicated —
    across devices).  ``x0`` (padded to N, zeros on the pad tail)
    warm-starts the loop; it rides into the kernel as a replicated operand
    like the dangling mask."""
    n = n_rows(layout)
    nt = int(n if n_true is None else n_true)
    pr0 = _pr0(n, nt) if x0 is None else jnp.asarray(x0, jnp.float32)

    def kernel(layout_blk, dang_full, pr0_full):
        mask = _real_mask(n, nt)

        def step(pr):
            new = _sell_block_iter(layout_blk, pr, dang_full, axes, d, nt)
            return new, jnp.sum(jnp.abs(new - pr) * mask)

        pr, iters, res, grow, ring = instrumented_tol_loop(
            step, pr0_full, tol=tol, max_iters=max_iters,
            watchdog=watchdog, trace=trace)
        return ((pr, iters, res, grow, ring) if trace
                else (pr, iters, res, grow))

    out = _shard_map(kernel, mesh, in_specs=(P(axes), P(), P()),
                     out_specs=(P(),) * (5 if trace else 4))(
        layout, _dangling(dangling, n), pr0)
    return out if trace else (*out, None)


# --------------------------------------------------------------------------- #
# shard-local Gauss–Southwell push (the dynamic-refresh primitive)            #
# --------------------------------------------------------------------------- #
def push_distributed_tol(H: jax.Array, mesh: Mesh, x0: jax.Array,
                         tol: float = 1e-6, max_pushes: int = 1000,
                         d: float = 0.85, row_axis: str = "data",
                         col_axis: str = "model",
                         dangling: jax.Array | None = None,
                         n_true: int | None = None,
                         watchdog: bool = True, trace: bool = False,
                         scales: jax.Array | None = None):
    """Frontier push on the dense fabric layout.  Each sweep pushes every
    entry of the frontier mask ``|r| >= tol/n`` into the iterate — a purely
    elementwise update on the P(col)-sharded vector, so the only
    per-sweep collectives are the ones ``_dense_iter`` already pays (the
    fabric matvec's psum + re-injection) plus the single psum XLA emits
    for the replicated residual L1 norm.  The residual is masked to the
    real nodes, so the pad tail never enters the frontier and stays
    exactly zero.  Runs under :func:`instrumented_tol_loop` — the
    convergence watchdog and residual-trajectory ring work on the mesh
    exactly as they do single-device.  ``x0`` must be padded to N (zeros
    on the pad tail).  Returns ``(x, sweeps, residual, grow, ring)``."""
    n = H.shape[0]
    nt = int(n if n_true is None else n_true)
    spec = NamedSharding(mesh, P(col_axis))
    mask = jax.lax.with_sharding_constraint(_real_mask(n, nt), spec)
    thresh = jnp.float32(tol) / nt

    def residual(x):
        new = _dense_iter(H, x, dangling, mesh, row_axis, col_axis, d, nt,
                          scales)
        return (new - x) * mask

    def step(state):
        x, r = state
        x = x + r * (jnp.abs(r) >= thresh).astype(x.dtype)
        r = residual(x)
        return (x, r), jnp.sum(jnp.abs(r))

    x0 = jax.lax.with_sharding_constraint(x0.astype(jnp.float32), spec)
    r0 = residual(x0)
    (x, _), sweeps, res, grow, ring = instrumented_tol_loop(
        step, (x0, r0), tol=tol, max_iters=max_pushes, watchdog=watchdog,
        trace=trace, res0=jnp.sum(jnp.abs(r0)))
    return x, sweeps, res, grow, ring


def push_distributed_sparse_tol(layout, mesh: Mesh, x0: jax.Array,
                                tol: float = 1e-6, max_pushes: int = 1000,
                                d: float = 0.85,
                                dangling: jax.Array | None = None,
                                axes: tuple[str, ...] = ("data", "model"),
                                n_true: int | None = None,
                                watchdog: bool = True, trace: bool = False):
    """Frontier push on the row-sharded SELL layout, as a ``shard_map``
    kernel mirroring :func:`pagerank_distributed_sparse_tol`: each device
    sweeps its own row block and the per-sweep ``all_gather`` re-assembles
    the fresh operator image — after which the residual (and the frontier
    mask, the watchdog verdict and the while_loop exit) is computed
    identically on every device from the replicated vector, with no extra
    collective.  Pad rows have zero data and a zero x0 tail, so their
    masked residual is identically zero and the frontier never touches
    them.  Returns ``(x, sweeps, residual, grow, ring)``."""
    n = n_rows(layout)
    nt = int(n if n_true is None else n_true)

    def kernel(layout_blk, dang_full, x0_full):
        mask = _real_mask(n, nt)
        thresh = jnp.float32(tol) / nt

        def residual(x):
            new = _sell_block_iter(layout_blk, x, dang_full, axes, d, nt)
            return (new - x) * mask

        def step(state):
            x, r = state
            x = x + r * (jnp.abs(r) >= thresh).astype(x.dtype)
            r = residual(x)
            return (x, r), jnp.sum(jnp.abs(r))

        r0 = residual(x0_full)
        (x, _), sweeps, res, grow, ring = instrumented_tol_loop(
            step, (x0_full, r0), tol=tol, max_iters=max_pushes,
            watchdog=watchdog, trace=trace, res0=jnp.sum(jnp.abs(r0)))
        return ((x, sweeps, res, grow, ring) if trace
                else (x, sweeps, res, grow))

    out = _shard_map(kernel, mesh, in_specs=(P(axes), P(), P()),
                     out_specs=(P(),) * (5 if trace else 4))(
        layout, _dangling(dangling, n), jnp.asarray(x0, jnp.float32))
    return out if trace else (*out, None)


# --------------------------------------------------------------------------- #
# batched personalized PageRank                                               #
# --------------------------------------------------------------------------- #
def ppr_distributed_dense(H: jax.Array, dang: jax.Array, V: jax.Array,
                          mesh: Mesh, n_iters: int = 100, d: float = 0.85,
                          row_axis: str = "data", col_axis: str = "model",
                          scales: jax.Array | None = None) -> jax.Array:
    """Batched PPR with the (N, Q) rank matrix sharded over the query axis.

    H is the *unfixed* transition matrix (the PPR leak teleports to V, not
    1/n), resharded by the in_spec to row blocks on ``row_axis`` and
    replicated along ``col_axis``; V rides ``P(None, col_axis)``.  Each
    mesh column owns Q/C queries; each mesh row owns N/R rows of the sweep,
    re-assembled by one row-axis ``all_gather`` per iteration.  Returns the
    (N, Q) rank matrix sharded like V.
    """

    def kernel(h_blk, dang_full, v_blk, *rest):
        scale_blk = rest[0] if rest else None

        def mv(PR):                     # local row-block MV, re-assembled
            y_blk = h_blk.astype(jnp.float32) @ PR
            if scale_blk is not None:
                y_blk = y_blk * scale_blk[:, None]
            return jax.lax.all_gather(y_blk, row_axis, axis=0, tiled=True)

        def one_iter(pr_blk, _):
            return ppr_step_batched(mv, pr_blk, v_blk, dang_full, d), None

        pr, _ = jax.lax.scan(one_iter, v_blk, None, length=n_iters)
        return pr

    in_specs = (P(row_axis, None), P(), P(None, col_axis))
    operands = (H, dang, V)
    if scales is not None:
        in_specs += (P(row_axis),)
        operands += (scales,)
    return _shard_map(kernel, mesh, in_specs=in_specs,
                      out_specs=P(None, col_axis))(*operands)


def sell_mv_sharded(layout, X: jax.Array, mesh: Mesh,
                    axes: tuple[str, ...]) -> jax.Array:
    """``H @ X`` over a row-sharded SELL layout for a replicated (N, Q)
    batch: each device sweeps its own rows for every query, and one tiled
    ``all_gather`` re-assembles the replicated (N, Q) image.  Callable
    inside ``jit`` — the batched PPR below and the landmark hub push
    (:mod:`repro.pagerank.landmarks`) both iterate it."""
    return _shard_map(
        lambda blk, X: jax.lax.all_gather(sell_rows(blk, X), axes,
                                          tiled=True),
        mesh, in_specs=(P(axes), P()), out_specs=P())(layout, X)


def ppr_distributed_sparse(layout, dang: jax.Array, V: jax.Array,
                           mesh: Mesh, n_iters: int = 100, d: float = 0.85,
                           axes: tuple[str, ...] = ("data", "model")
                           ) -> jax.Array:
    """Batched PPR over the row-sharded SELL layout: the (N, Q) iterate is
    replicated, and every iteration each device sweeps its own rows for
    all Q queries (:func:`sell_mv_sharded`).  The layout is never copied:
    each device holds its 1/D share of it, as the global solve does."""
    def one_iter(PR, _):
        return ppr_step_batched(lambda X: sell_mv_sharded(layout, X, mesh,
                                                          axes),
                                PR, V, dang, d), None

    PR, _ = jax.lax.scan(one_iter, V, None, length=n_iters)
    return PR


def make_sharded_inputs_dense(H, mesh: Mesh, row_axis="data",
                              col_axis="model"):
    """Host -> device placement helper for the dense layout."""
    return jax.device_put(H, NamedSharding(mesh, P(row_axis, col_axis)))
