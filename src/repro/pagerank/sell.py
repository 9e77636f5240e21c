"""Sliced ELLPACK (SELL): the patchable, row-shardable sparse layout.

Rows are grouped into tiers by in-degree: tier bounds double from a width
of 4 up to the maximum degree, and each row goes to the narrowest tier
that holds it.  Every tier is a dense ``(rows, width)`` ELL block padded
to its width plus ``slack``, so a row of degree ``deg`` keeps fewer than
``2·deg + 4 + slack`` slots (an isolated row keeps ``4 + slack``), a sweep
is one gather per tier and no ``segment_sum``, and a small edge delta
rewrites rows in place without changing any array shape.  The tiers
follow the graph's own degree histogram, so no width is tuned per graph.

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

    @property
    def slots(self) -> int:
        """Stored slots of the layout, padding included: what each sweep
        gathers."""
        return sum(r * w for r, w in zip(self.rows, self.widths))

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
    # tier bounds double from 4 up to the maximum degree; capacities sit
    # ``slack`` above each bound.  The widest tier rounds up to a multiple
    # of 32 with >= 16 slots of patch headroom, but stays under
    # 2·deg + 4 + slack for its narrowest row (deg > the bound below it,
    # and maxdeg <= twice that bound)
    maxdeg = int(counts.max()) if n_pad else 0
    bounds = [4]
    while bounds[-1] < maxdeg:
        bounds.append(min(2 * bounds[-1], maxdeg))
    caps = [b + slack for b in bounds]
    if len(caps) > 1:
        caps[-1] = min(-(-(maxdeg + max(16, slack)) // 32) * 32,
                       2 * bounds[-2] + 5 + slack)
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
    # every tier's array is one slice of a flat buffer, so each edge is
    # placed in one pass: its row's first slot plus its place in the row
    w = np.asarray(widths)
    base = np.concatenate([[0], np.cumsum(shards * per_shard * w)])
    first = base[tier] + pos * w[tier]
    indptr = np.asarray(csr.indptr).astype(np.int64)
    dest = (np.repeat(first[:n] - indptr[:-1], np.diff(indptr))
            + np.arange(indptr[-1]))
    flat_data = np.zeros(base[-1], np.float32)
    flat_idx = np.zeros(base[-1], np.int32)
    flat_data[dest] = np.asarray(csr.data)
    flat_idx[dest] = np.asarray(csr.indices)
    put = jnp.asarray if sharding is None else (
        lambda a: jax.device_put(a, sharding))
    tiers = []
    for t, k in enumerate(widths):
        data = flat_data[base[t]:base[t + 1]].reshape(-1, k)
        idx = put(flat_idx[base[t]:base[t + 1]].reshape(-1, k))
        if precision == "int8":
            scale = rowmax_scales(np.abs(data).max(axis=1, initial=0.0))
            tiers.append((put(quantize_int8(data, scale[:, None])), idx,
                          put(scale)))
        else:
            tiers.append((put(data.astype(STORAGE_DTYPES[precision])), idx))
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
    with jax.named_scope("pagerank.sell_order"):
        return jnp.concatenate(ys, axis=0)[inv]
