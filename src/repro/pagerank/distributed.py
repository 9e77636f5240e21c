"""Pod-scale distributed PageRank — the paper's workload on the TPU mesh.

The two sharded layouts of :class:`repro.pagerank.engine.PageRankEngine`
keep here what is particular to the mesh; the engine's loop drivers
iterate them like any other layout:

* :func:`fabric_step` — one iteration of the paper's fabric schedule on a
  dense H sharded ``P(row, col)`` over the 2-D mesh (vertical-bus
  all-gather -> local MV -> horizontal-bus psum -> diagonal re-injection),
  the ``dense_sharded`` tier's global step.  This is the direct pod-scale
  analogue of Fig. 3/Fig. 4.
* :func:`sell_mv_sharded` — ``H @ X`` over a sliced-ELL layout
  (:mod:`repro.pagerank.sell`) row-sharded over the flattened mesh: each
  device sweeps its own rows in degree tiers (no row padded to the maximum
  degree) and one ``all_gather`` re-assembles the replicated image.  This
  is the ``ell_sharded`` tier's matvec, for a vector or an (N, Q) batch.

:func:`pagerank_distributed` and :func:`pagerank_distributed_sparse` run
the fixed schedule on each layout from bare arrays.  Uneven shapes are
zero-padded: ``n_true`` (the real node count) keeps the PageRank
arithmetic — ``1/n`` teleports, the dangling leak — on the real nodes.
Padded rows and columns of H are zero, so pad entries never feed back into
real ranks; the result is the real nodes' ranks.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import fabric_matvec as fm
from repro.pagerank.sell import n_rows, sell_rows


def fabric_step(H, pr, dangling, mesh, row_axis, col_axis, d, nt,
                scales=None):
    """The canonical fabric-schedule iteration.  The leak term is the
    fabric analogue of the adder-column epilogue; ``dangling`` is the
    padded (N,) mask of the unfixed H (zeros for a dangling-fixed one).
    ``H`` may be stored reduced-precision (the fabric matvec upcasts each
    shard tile in-register and accumulates in f32); ``scales`` is the
    optional replicated per-row f32 dequantization vector of an int8
    layout, folded into the accumulated row sums here."""
    y = fm.matvec(H, pr, mesh, row_axis, col_axis)
    if scales is not None:
        y = y * scales
    leak = jnp.sum(pr * dangling) / nt
    y = d * (y + leak) + (1.0 - d) / nt
    return fm.matvec_iterated_reshard(y, mesh, row_axis, col_axis)


def sell_mv_sharded(layout, X: jax.Array, mesh: Mesh,
                    axes: tuple[str, ...]) -> jax.Array:
    """``H @ X`` over a row-sharded SELL layout for a replicated (N,)
    vector or (N, Q) batch: each device sweeps its own rows, and one tiled
    ``all_gather`` re-assembles the replicated image.  ``layout`` is a SELL
    operand pytree built with ``shards`` = the mesh size: every leaf is
    row-sharded ``P(axes)``, and each device's block is a self-contained
    SELL of its own rows (replication checks off: the kernel mixes
    all_gather'd and per-device values)."""
    return jax.shard_map(
        lambda blk, X: jax.lax.all_gather(sell_rows(blk, X), axes,
                                          tiled=True),
        mesh=mesh, in_specs=(P(axes), P()), out_specs=P(),
        check_vma=False)(layout, X)


def _zeros_if_none(dangling: jax.Array | None, n: int) -> jax.Array:
    return (jnp.zeros((n,), jnp.float32) if dangling is None
            else jnp.asarray(dangling, jnp.float32))


def pagerank_distributed(H: jax.Array, mesh: Mesh, n_iters: int = 100,
                         d: float = 0.85, row_axis: str = "data",
                         col_axis: str = "model",
                         dangling: jax.Array | None = None,
                         n_true: int | None = None,
                         scales: jax.Array | None = None) -> jax.Array:
    """Dense fabric-schedule PageRank.  H: (N, N) sharded P(row, col);
    returns the ranks of the first ``n_true`` nodes.

    With ``dangling`` given, H must be the *unfixed* transition matrix and
    the leak is applied as an explicit scalar (the fabric analogue of the
    adder-column epilogue); with ``dangling=None`` H must be dangling-fixed.
    ``H`` may be stored reduced-precision; the iterate is always f32, and
    ``scales`` carries an int8 layout's per-row dequantization vector.
    """
    # the engine builds on this module's steps, so it is imported here
    from repro.pagerank.engine import DenseShardedLayout, fixed_loop
    n = H.shape[0]
    lay = DenseShardedLayout(
        operands=(H,) if scales is None else (H, scales),
        dang=_zeros_if_none(dangling, n),
        n=int(n if n_true is None else n_true), mesh=mesh,
        axes=(row_axis, col_axis))
    return fixed_loop(lay, d, n_iters=n_iters)


def pagerank_distributed_sparse(layout, mesh: Mesh, n_iters: int = 100,
                                d: float = 0.85,
                                dangling: jax.Array | None = None,
                                axes: tuple[str, ...] = ("data", "model"),
                                n_true: int | None = None) -> jax.Array:
    """Row-sharded SELL PageRank of a SELL operand pytree built with
    ``shards`` = the mesh size; PR replicated.  One tiled ``all_gather``
    of the fresh row-shards per iteration."""
    from repro.pagerank.engine import ShardedSellLayout, fixed_loop
    n = n_rows(layout)
    lay = ShardedSellLayout(operands=layout,
                            dang=_zeros_if_none(dangling, n),
                            n=int(n if n_true is None else n_true),
                            mesh=mesh, axes=tuple(axes))
    return fixed_loop(lay, d, n_iters=n_iters)


def make_sharded_inputs_dense(H, mesh: Mesh, row_axis="data",
                              col_axis="model"):
    """Host -> device placement helper for the dense layout."""
    return jax.device_put(H, NamedSharding(mesh, P(row_axis, col_axis)))
