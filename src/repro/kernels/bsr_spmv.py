"""Block-sparse-row SpMV Pallas kernel (scalar-prefetch indexed gather).

The TPU-native adaptation of the paper's sparse workload: protein networks
are sparse, so streaming the *dense* N x N transition matrix (as the paper's
fabric does) wastes bandwidth on zero tiles.  Here H is stored as BSR —
MXU-aligned dense (bs x bs) blocks, a fixed per-block-row budget — and the
rank-vector blocks are gathered via **scalar prefetch**: the block-column
index array rides in SMEM ahead of the grid so the ``x`` BlockSpec's
``index_map`` can select which VMEM tile of ``x`` to stage for each step.
This is the TPU equivalent of the paper's content-addressed message routing:
the *index data* steers the dataflow, no host intervention.

Layout (built by ``graph.sparse.BSRMatrix``):
  ``blocks``     (nb_r, mb, bs, bs) f32 — zero-padded block budget
  ``block_cols`` (nb_r, mb) i32        — padded entries -> block-col 0, zero block
  ``x``          (nb_c * bs,)          -> viewed (nb_c, 1, bs)
  ``y``          (nb_r * bs,)          <- computed (nb_r, 1, bs)

The vectors carry a unit middle axis so every block is ``(1, 1, bs)``: its
last two dimensions are the array's full size-1 axis and a multiple of 128,
the tiling Mosaic accepts (a ``(1, bs)`` block of an ``(nb, bs)`` array is
not).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import F32_DOT


def _kernel(cols_ref, blk_ref, x_ref, y_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    # (1, bs) x (bs, bs)^T -> (1, bs); padded blocks are all-zero => safe
    # accumulate.  Blocks may be stored reduced-precision (bf16/f16/int8):
    # upcast in-register, accumulate f32 (no-op on f32 blocks).
    y_ref[0] += jax.lax.dot_general(
        x_ref[0], blk_ref[0, 0].astype(jnp.float32),
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=F32_DOT,
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bsr_spmv(blocks: jax.Array, block_cols: jax.Array, x: jax.Array, *,
             interpret: bool = True) -> jax.Array:
    """y = H_bsr @ x.  ``x`` length must be a multiple of the block size
    (``BSRMatrix`` guarantees the padded layout)."""
    nb_r, mb, bs, _ = blocks.shape
    xp = x
    if x.shape[0] % bs:
        xp = jnp.pad(x, (0, bs - x.shape[0] % bs))
    xb = xp.reshape(-1, 1, bs)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb_r, mb),
        in_specs=[
            pl.BlockSpec((1, 1, bs, bs), lambda i, j, cols: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, bs), lambda i, j, cols: (cols[i, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bs), lambda i, j, cols: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nb_r, 1, bs), jnp.float32),
        interpret=interpret,
    )(block_cols, blocks, xb)
    return out.reshape(nb_r * bs)
