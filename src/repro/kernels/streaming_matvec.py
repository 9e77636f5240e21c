"""Streaming matrix-vector multiply — the paper's N+3 schedule as a Pallas
TPU kernel.

The paper streams the *matrix* through a fabric holding the *vector*
stationary per column, then reduces along rows.  On TPU the memory hierarchy
inverts the roles: VMEM is scarce, HBM bandwidth is the stream — so the
activation block (small) stays VMEM-stationary while weight tiles stream
HBM -> VMEM, one (block_n x block_m) tile per grid step.  A grid step is the
TPU analogue of the paper's "time step": after sweeping the ``M`` axis the
row-block's partial products have been accumulated (the horizontal-bus add),
mirroring the N+3 pipeline with MXU-sized tiles instead of scalar sites.

Shapes: ``W`` (N, M) weights, ``X`` (B, M) activations -> ``Y`` (B, N).
``B = 1`` is the paper's MV; decode GEMV uses B = decode batch.

Grid: ``(N / bn, M / bm)`` with the M axis innermost so each output block is
revisited across the reduction — the canonical accumulate-in-place pattern.
Accumulation always in float32 (``preferred_element_type``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import F32_DOT


def _kernel(x_ref, w_ref, y_ref, *, n_steps_m: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    # (B, bm) @ (bn, bm)^T -> (B, bn), f32 accumulation on the MXU.  The
    # weight tile may arrive in a reduced storage dtype (bf16/f16/int8);
    # it is upcast in-register — a trace-time no-op on f32 tiles.
    y_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...].astype(jnp.float32),
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=F32_DOT,
        preferred_element_type=jnp.float32).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "block_m",
                                             "interpret"))
def streaming_matvec(W: jax.Array, X: jax.Array, *, block_n: int = 256,
                     block_m: int = 256, interpret: bool = True) -> jax.Array:
    """Y = X @ W^T with weight tiles streamed through VMEM.

    Pads every axis up to the block grid; strips padding on return.
    ``interpret=True`` runs the kernel body on CPU (this container); on real
    TPU pass ``interpret=False``.
    """
    N, M = W.shape
    B = X.shape[0]
    assert X.shape[1] == M
    bn = min(block_n, _next_multiple(N, 128))
    bm = min(block_m, _next_multiple(M, 128))
    Np = _next_multiple(N, bn)
    Mp = _next_multiple(M, bm)
    Wp = jnp.pad(W, ((0, Np - N), (0, Mp - M)))
    Xp = jnp.pad(X, ((0, 0), (0, Mp - M)))
    grid = (Np // bn, Mp // bm)

    out = pl.pallas_call(
        functools.partial(_kernel, n_steps_m=grid[1]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((B, bm), lambda i, j: (0, j)),     # activations
            pl.BlockSpec((bn, bm), lambda i, j: (i, j)),    # weight tile
        ],
        out_specs=pl.BlockSpec((B, bn), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((B, Np), jnp.float32),
        interpret=interpret,
    )(Xp, Wp)
    return out[:, :N]


def _next_multiple(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
