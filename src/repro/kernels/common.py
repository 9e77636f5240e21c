"""Shared kernel-layer helpers (dependency-free leaf module).

Importable from anywhere — the graph containers, the Pallas kernels and the
engine all use :func:`upcast_f32` for the mixed-precision contract: operand
tiles may be stored in a reduced dtype (bf16 / f16 / int8), but every
multiply-accumulate happens in float32.  :data:`F32_DOT` is the precision
of the kernels' dots; :func:`ell_rows` is the one ELL row-sum every ELL
layout (split, sliced, row-sharded) sweeps with.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Precision of every f32 dot inside a Pallas kernel.  Mosaic's default
# feeds the MXU bf16-rounded operands: on a TPU v5e that left the fused
# PageRank tier 1.2e-5 off a float64 reference after 100 iterations and
# stalled its tolerance loop at an L1 step of 3.3e-4.  HIGHEST keeps the
# operands f32 (the CPU, where kernels are interpreted, is unaffected).
F32_DOT = jax.lax.Precision.HIGHEST


def upcast_f32(x: jax.Array) -> jax.Array:
    """Upcast a (possibly reduced-precision) operand to float32 for
    accumulation.  On a float32 input this is a trace-time no-op —
    ``astype`` short-circuits on a matching dtype — so the float32 tiers
    keep emitting bit-identical programs through the shared code paths."""
    return x.astype(jnp.float32)



# device bytes of one gathered ELL block that ell_rows materializes at once
ELL_GATHER_BUDGET = 1 << 30


def _tile(m: int, t: int) -> int:
    return -(-m // t) * t


def ell_rows(data: jax.Array, idx: jax.Array, x: jax.Array) -> jax.Array:
    """Row sums of an ELL block: ``y[r] = sum_j data[r, j] * x[idx[r, j]]``
    for a vector ``x`` (n,) or a batch (n, Q), accumulated in float32.

    The one-shot form gathers a whole (rows, slots[, Q]) block, which XLA
    keeps in device memory, its two minor axes padded to the TPU's
    (8, 128) tile: a batch of Q = 16 queries takes 128 lanes.  On a wide
    layout (hub rows padded to the maximum degree) that block outgrows the
    chip, so past :data:`ELL_GATHER_BUDGET` bytes the rows are swept in
    chunks that each fit it.  Below the budget the program is the one-shot
    form, unchanged."""
    rows, k = data.shape

    def part(d, i):
        d = upcast_f32(d)
        return jnp.sum((d if x.ndim == 1 else d[..., None]) * x[i], axis=1)

    row_bytes = 4 * (_tile(k, 128) if x.ndim == 1
                     else _tile(k, 8) * _tile(x.shape[1], 128))
    r = max(8, ELL_GATHER_BUDGET // max(row_bytes, 1) // 8 * 8)
    if r >= rows:
        return part(data, idx)
    n_full = rows // r

    def body(b, y):
        d = jax.lax.dynamic_slice_in_dim(data, b * r, r)
        i = jax.lax.dynamic_slice_in_dim(idx, b * r, r)
        return jax.lax.dynamic_update_slice_in_dim(y, part(d, i), b * r, 0)

    y = jax.lax.fori_loop(0, n_full, body,
                          jnp.zeros((rows,) + x.shape[1:], jnp.float32))
    if rows % r:
        y = y.at[n_full * r:].set(part(data[n_full * r:], idx[n_full * r:]))
    return y
