"""The paper's fabric MV schedule mapped onto a TPU device mesh.

This is the production-scale adaptation (DESIGN.md §2): the R x C site grid
becomes the 2-D device mesh, and the paper's buses become collectives —

* matrix stationary in the fabric      ->  A sharded ``P(row_axis, col_axis)``
* vector broadcast on the vertical bus ->  x sharded ``P(col_axis)`` (GSPMD
  replicates it across the row axis — the broadcast), or an explicit
  ``all_gather`` when starting from fully-sharded x
* products summed on the horizontal bus -> ``psum`` / ``psum_scatter`` along
  ``col_axis``
* result in the adder column           ->  y sharded ``P(row_axis)``
* re-injection for iterative algorithms (PageRank) -> mesh-transpose
  ``all_to_all`` exchanging the (row, col) block layout back to vector layout.

All entry points are ``jax.shard_map``-ed so the collective schedule is
explicit and auditable in the lowered HLO (the roofline harness counts those
bytes).  Replication checks are off (``check_vma=False``): the kernels mix
psum'd and per-device values.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def matvec(A: jax.Array, x: jax.Array, mesh: Mesh,
           row_axis: str = "data", col_axis: str = "model") -> jax.Array:
    """y = A @ x with the fabric schedule.  A: (N, M) sharded over
    (row_axis, col_axis); x: (M,) sharded over col_axis (vertical-bus
    layout); returns y: (N,) sharded over row_axis (adder-column layout).
    """

    def kernel(a_blk, x_blk):
        # A shards may be stored reduced-precision (bf16/f16/int8); the
        # site multiply upcasts in-register (a trace-time no-op on f32)
        # and the horizontal-bus reduction stays f32.
        partial_y = a_blk.astype(jnp.float32) @ x_blk   # site multiplies
        return jax.lax.psum(partial_y, col_axis)        # horizontal bus

    return jax.shard_map(
        kernel, mesh=mesh, check_vma=False,
        in_specs=(P(row_axis, col_axis), P(col_axis)),
        out_specs=P(row_axis))(A, x)


def matvec_scatter(A: jax.Array, x: jax.Array, mesh: Mesh,
                   row_axis: str = "data", col_axis: str = "model") -> jax.Array:
    """Bandwidth-optimal variant: ``psum_scatter`` leaves y jointly sharded
    over (row_axis, col_axis) — 1/C the horizontal-bus traffic of ``matvec``
    (reduce-scatter vs all-reduce), at the cost of a blocked y layout."""

    def kernel(a_blk, x_blk):
        partial_y = a_blk @ x_blk
        return jax.lax.psum_scatter(
            partial_y, col_axis, scatter_dimension=0, tiled=True)

    return jax.shard_map(
        kernel, mesh=mesh, check_vma=False,
        in_specs=(P(row_axis, col_axis), P(col_axis)),
        out_specs=P((row_axis, col_axis)))(A, x)


def matvec_iterated_reshard(y_rowrep: jax.Array, mesh: Mesh,
                            row_axis: str = "data",
                            col_axis: str = "model") -> jax.Array:
    """Mesh-transpose: convert y sharded ``P(row_axis)`` (adder-column
    layout) into ``P(col_axis)`` (vertical-bus layout) so it can feed the
    next iteration's :func:`matvec`.

    On a square mesh, global column-shard ``c`` of the vector *is* row-block
    ``r = c``, so the exchange is a within-column broadcast from the diagonal
    device — realized as a masked ``psum`` along ``row_axis`` (the TPU analogue
    of the fabric re-injecting the adder column onto the vertical bus)."""
    R = mesh.shape[row_axis]
    C = mesh.shape[col_axis]
    if R != C:
        # Fall back to a global reshard (GSPMD inserts the all-to-all).
        return jax.lax.with_sharding_constraint(
            y_rowrep, NamedSharding(mesh, P(col_axis)))

    def kernel(y_blk):
        c = jax.lax.axis_index(col_axis)
        r = jax.lax.axis_index(row_axis)
        contrib = jnp.where(r == c, y_blk, jnp.zeros_like(y_blk))
        return jax.lax.psum(contrib, row_axis)

    return jax.shard_map(
        kernel, mesh=mesh, check_vma=False,
        in_specs=P(row_axis),
        out_specs=P(col_axis))(y_rowrep)


def fabric_gemv_batched(W: jax.Array, X: jax.Array, mesh: Mesh,
                        row_axis: str = "model",
                        col_axis: str | None = None) -> jax.Array:
    """Decode-path batched GEMV: Y = X @ W^T with W (out, in) stationary,
    sharded over ``row_axis`` on its output dim; X (batch, in) replicated on
    the model axis.  The fabric schedule degenerates to: local GEMV +
    all-gather of the output shards (the adder column is distributed).

    Used by ``serve/engine.py`` for single-token decode where every matmul
    is vector-like (batch << in/out dims).
    """

    def kernel(w_blk, x_full):
        y_blk = x_full @ w_blk.T
        return jax.lax.all_gather(y_blk, row_axis, axis=1, tiled=True)

    return jax.shard_map(
        kernel, mesh=mesh, check_vma=False,
        in_specs=(P(row_axis, None), P(None, None)),
        out_specs=P(None, None))(W, X)
