"""Public jit'd entry points for the kernel layer.

``interpret`` is resolved **per call** by :func:`default_interpret`: the
Pallas kernels compile to Mosaic on TPU and run in interpret mode
everywhere else, so the same process can mix compiled and interpreted
paths (e.g. a TPU engine next to a CPU unit test).  Pass ``interpret=``
explicitly to choose a mode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.graph.sparse import BSRMatrix
from repro.kernels.bsr_spmv import bsr_spmv
from repro.kernels.common import upcast_f32
from repro.kernels.pagerank_step import pagerank_step
from repro.kernels.streaming_matvec import streaming_matvec


def default_interpret() -> bool:
    """Interpret-mode default for this call, derived from the active
    device: compiled on TPU, interpreted everywhere else."""
    return jax.default_backend() != "tpu"


def matvec(W: jax.Array, x: jax.Array, **kw) -> jax.Array:
    """y = W @ x via the streaming kernel (paper's MV, B=1)."""
    kw.setdefault("interpret", default_interpret())
    return streaming_matvec(W, x[None, :], **kw)[0]


def gemv_batched(W: jax.Array, X: jax.Array, **kw) -> jax.Array:
    """Y = X @ W^T — the decode-path batched GEMV."""
    kw.setdefault("interpret", default_interpret())
    return streaming_matvec(W, X, **kw)


def spmv(bsr: BSRMatrix, x: jax.Array, **kw) -> jax.Array:
    """y = H_bsr @ x, trimmed to the logical (unpadded) length.  Reduced-
    precision blocks (bf16/f16/int8) are upcast tile-by-tile inside the
    kernel; an int8 layout's per-row scales fold into the accumulated f32
    row sums here — never into the stored operand."""
    kw.setdefault("interpret", default_interpret())
    y = bsr_spmv(bsr.blocks, bsr.block_cols, upcast_f32(x), **kw)
    if bsr.row_scales is not None:
        y = y * bsr.row_scales
    return y[:bsr.shape[0]]


def pagerank_iteration(H: jax.Array, pr: jax.Array,
                       dangling: jax.Array | None = None,
                       d: float = 0.85, **kw) -> jax.Array:
    """Fused PageRank step with dangling correction.

    One-shot convenience path: the leak is a separate pass over ``pr`` and
    the kernel re-pads per call.  Loops should use
    ``repro.pagerank.engine.PageRankEngine``, which prepares the layout
    once and carries the in-kernel leak reduction between iterations.
    """
    kw.setdefault("interpret", default_interpret())
    n = H.shape[0]
    leak = 0.0 if dangling is None else jnp.sum(pr * dangling) / n
    t = d * leak + (1.0 - d) / n
    return pagerank_step(H, pr, t, d=d, **kw)
