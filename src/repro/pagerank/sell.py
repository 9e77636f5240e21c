"""Sliced ELLPACK (SELL): the patchable, row-shardable sparse layout.

Rows are grouped into tiers by in-degree: the base tier holds the rows up
to the 90th degree percentile, and each further tier doubles the width up
to the maximum degree.  Every tier is a dense ``(rows, width)`` ELL block
padded to its width plus ``slack``, so a sweep is one gather per tier and
no ``segment_sum``, and a small edge delta rewrites rows in place without
changing any array shape.  On a 2^20-node protein network the doubling
tiers hold 3x the edges in padded slots; a base tier plus one hub tier
padded to the maximum degree holds 26x, and one block padded to the
maximum degree 262x.

Row-sharded over ``shards`` devices, each device holds a self-contained
SELL of its own contiguous block of rows: every tier array stacks
``shards`` equal blocks (each padded to the largest shard's row count in
that tier), and ``inv`` maps each row to its place in the device-local
concatenation of the tiers.  With one shard this is the plain
single-device SELL.

The operand pytree is ``(inv, tiers)``: ``inv`` is ``(n_pad,)`` int32 and
``tiers`` holds one ``(data, idx)`` pair per tier, or ``(data, idx,
scale)`` for int8 storage with its per-row float32 dequantization scales.
:func:`sell_rows` is the one reader of that structure.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.common import ell_rows
from repro.pagerank.precision import (STORAGE_DTYPES, quantize_int8,
                                      rowmax_scales)


@dataclasses.dataclass(frozen=True)
class SellIndex:
    """Host map of a SELL layout, what in-place row patches address."""
    widths: tuple[int, ...]       # row capacity of each tier
    tier: np.ndarray              # row -> tier
    pos: np.ndarray               # row -> row of its tier's array
    rows: tuple[int, ...]         # rows of each tier's array

    def describe(self, slack: int) -> str:
        return f"sell(k={list(self.widths)}, rows={list(self.rows)}, " \
               f"slack={slack})"


def build(csr, n_pad: int, *, shards: int = 1, slack: int = 0,
          precision: str = "f32", sharding=None):
    """The SELL operand pytree of ``csr`` (rows zero-padded to ``n_pad``,
    a multiple of ``shards``) and its :class:`SellIndex`.  Values are
    stored in ``precision``; ``sharding`` (row-wise, over ``shards``
    devices) places every array, else they go to the default device."""
    n = csr.shape[0]
    counts = np.zeros(n_pad, np.int64)
    counts[:n] = np.diff(np.asarray(csr.indptr))
    # tier bounds: the 90th degree percentile, then doubling up to the
    # maximum degree; capacities sit ``slack`` above each bound (the widest
    # tier >= 16 above, rounded to 32), so every row has patch headroom
    bounds = [max(4, int(np.percentile(counts[:n], 90)) if n else 0)]
    maxdeg = int(counts.max()) if n_pad else 0
    while bounds[-1] < maxdeg:
        bounds.append(min(2 * bounds[-1], maxdeg))
    caps = [b + slack for b in bounds]
    if len(caps) > 1:
        caps[-1] = -(-(maxdeg + max(16, slack)) // 32) * 32
    tier = np.searchsorted(bounds, counts)
    used = np.unique(tier)                  # drop tiers no row falls in
    tier = np.searchsorted(used, tier)
    widths = tuple(caps[u] for u in used)
    n_t = len(widths)
    # rank of every row among the rows of its (shard, tier) group, in row
    # order; each tier gives every shard the largest group's row count
    group = np.arange(n_pad) // (n_pad // shards) * n_t + tier
    sizes = np.bincount(group, minlength=shards * n_t)
    start = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    order = np.argsort(group, kind="stable")
    rank = np.empty(n_pad, np.int64)
    rank[order] = np.arange(n_pad) - start[group[order]]
    per_shard = sizes.reshape(shards, n_t).max(axis=0)
    pos = group // n_t * per_shard[tier] + rank
    offset = np.concatenate([[0], np.cumsum(per_shard)[:-1]])
    inv = (offset[tier] + rank).astype(np.int32)
    rows, slot = csr.row_positions()
    cols, vals = np.asarray(csr.indices), np.asarray(csr.data)
    put = jnp.asarray if sharding is None else (
        lambda a: jax.device_put(a, sharding))
    tiers = []
    for t, k in enumerate(widths):
        data = np.zeros((shards * per_shard[t], k), np.float32)
        idx = np.zeros((shards * per_shard[t], k), np.int32)
        sel = tier[rows] == t
        data[pos[rows[sel]], slot[sel]] = vals[sel]
        idx[pos[rows[sel]], slot[sel]] = cols[sel]
        if precision == "int8":
            scale = rowmax_scales(np.abs(data).max(axis=1, initial=0.0))
            tiers.append((put(quantize_int8(data, scale[:, None])),
                          put(idx), put(scale)))
        else:
            tiers.append((put(data.astype(STORAGE_DTYPES[precision])),
                          put(idx)))
    index = SellIndex(widths, tier, pos,
                      tuple(int(shards * r) for r in per_shard))
    return (put(inv), tuple(tiers)), index


def n_rows(layout) -> int:
    """Rows of a SELL layout, pad rows included."""
    return layout[0].shape[0]


def sell_rows(layout, x: jax.Array) -> jax.Array:
    """``y = H @ x`` over a SELL layout (one device's block of a sharded
    one), for a vector ``x`` (n,) or a batch (n, Q): one ELL row sum per
    tier, accumulated in float32, then ``inv`` puts the rows in order."""
    inv, tiers = layout
    ys = []
    for data, idx, *scale in tiers:
        y = ell_rows(data, idx, x)
        if scale:
            y = y * (scale[0] if y.ndim == 1 else scale[0][:, None])
        ys.append(y)
    return jnp.concatenate(ys, axis=0)[inv]
