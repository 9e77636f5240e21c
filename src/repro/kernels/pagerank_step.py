"""Fused PageRank iteration kernel: y = d * (H @ x) + t, in one pass.

The paper executes the MV, the scalar-d multiply, and the teleport add as
*separate* fabric phases (N+3, +1, +1 steps).  On TPU the affine epilogue is
free ALU work while the final MXU tile drains, so we fuse all three into the
matvec's last reduction step — removing two full passes over the rank vector
(the beyond-paper optimization benchmarked in EXPERIMENTS.md §Perf).

``t`` carries the teleport term plus the dangling-leak correction, computed
by the caller: ``t = d * sum(pr[dangling]) / n + (1 - d) / n`` — a scalar,
staged through SMEM.

Two variants:

* :func:`pagerank_step` — convenience entry: pads on every call, trims on
  return.  Fine for one-shot use; wasteful inside a loop.
* :func:`pagerank_step_fused` — the engine's hot-loop kernel.  Operates on
  a *pre-padded* layout (no ``jnp.pad``/reshape per iteration) and emits a
  **second output**: the dangling-leak reduction ``sum(y_new * dangling)``
  accumulated in the same epilogue that applies the affine term.  The
  caller carries it as the next iteration's scalar ``t``, deleting the
  separate full pass over the rank vector that
  ``ops.pagerank_iteration`` pays every step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import F32_DOT


def _kernel(t_ref, h_ref, x_ref, y_ref, *, d: float, m_steps: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    # H tiles may be stored reduced-precision; upcast in-register (a
    # trace-time no-op on f32) and accumulate in f32.
    y_ref[...] += jax.lax.dot_general(
        x_ref[...], h_ref[...].astype(jnp.float32),
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=F32_DOT,
        preferred_element_type=jnp.float32)

    @pl.when(j == m_steps - 1)
    def _epilogue():
        y_ref[...] = jnp.float32(d) * y_ref[...] + t_ref[0]


@functools.partial(jax.jit,
                   static_argnames=("d", "block_n", "block_m", "interpret"))
def pagerank_step(H: jax.Array, pr: jax.Array, t: jax.Array, *,
                  d: float = 0.85, block_n: int = 256, block_m: int = 256,
                  interpret: bool = True) -> jax.Array:
    """One fused iteration: returns d * (H @ pr) + t.  H: (N, N), pr: (N,)."""
    N, M = H.shape
    bn = min(block_n, _mult(N, 128))
    bm = min(block_m, _mult(M, 128))
    Np, Mp = _mult(N, bn), _mult(M, bm)
    Hp = jnp.pad(H, ((0, Np - N), (0, Mp - M)))
    xp = jnp.pad(pr, (0, Mp - M))[None, :]          # (1, Mp)
    grid = (Np // bn, Mp // bm)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bm), lambda i, j, t: (i, j)),
            pl.BlockSpec((1, bm), lambda i, j, t: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, bn), lambda i, j, t: (0, i)),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, d=d, m_steps=grid[1]),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, Np), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(t, jnp.float32).reshape(1), Hp, xp)
    return out[0, :N]


def _fused_kernel(t_ref, h_ref, x_ref, dang_ref, *rest,
                  d: float, m_steps: int, has_scales: bool):
    # ``rest`` is (s_ref, y_ref, leak_ref) for int8 layouts carrying a
    # per-row dequantization scale, (y_ref, leak_ref) otherwise — the
    # two variants trace to different programs, selected statically.
    if has_scales:
        s_ref, y_ref, leak_ref = rest
    else:
        y_ref, leak_ref = rest
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _init_leak():
        leak_ref[...] = jnp.zeros_like(leak_ref)

    @pl.when(j == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    # H tiles may be stored reduced-precision (bf16/f16/int8); upcast
    # in-register (a trace-time no-op on f32) and accumulate in f32.
    y_ref[...] += jax.lax.dot_general(
        x_ref[...], h_ref[...].astype(jnp.float32),
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=F32_DOT,
        preferred_element_type=jnp.float32)

    @pl.when(j == m_steps - 1)
    def _epilogue():
        acc = y_ref[...]
        if has_scales:
            # int8 dequant: fold the per-row scale into the accumulated
            # f32 row sums, in the same drain epilogue as the affine term.
            acc = s_ref[...] * acc
        y = jnp.float32(d) * acc + t_ref[0]
        y_ref[...] = y
        # dangling-leak reduction over the *new* rank block, while the
        # block is still resident — the second pass ops.pagerank_iteration
        # pays per step happens here for free.  Accumulated as a (1, 1)
        # vector: Mosaic cannot store a scalar into VMEM.
        leak_ref[...] += jnp.sum(y * dang_ref[...], keepdims=True)


@functools.partial(jax.jit,
                   static_argnames=("d", "block_n", "block_m", "interpret"))
def pagerank_step_fused(Hp: jax.Array, xp: jax.Array, dangp: jax.Array,
                        t: jax.Array, scales: jax.Array | None = None, *,
                        d: float = 0.85,
                        block_n: int = 256, block_m: int = 256,
                        interpret: bool = True
                        ) -> tuple[jax.Array, jax.Array]:
    """One fused iteration on the **pre-padded** layout.

    ``Hp``: (Np, Mp) transition matrix, both axes already multiples of the
    block sizes (zero padding).  ``xp``: (1, Mp) rank vector, ``dangp``:
    (1, Np) dangling mask (zero in the padded tail).  ``Hp`` may be stored
    in a reduced dtype (bf16/f16/int8) — tiles are upcast in-register and
    accumulated in f32.  ``scales``: optional (1, Np) f32 per-row
    dequantization scales for int8 layouts, applied in the drain epilogue;
    ``None`` traces the exact pre-existing program (bit-identical f32
    path).  Returns ``(yp, leak)`` where ``yp = d * (Hp @ xp) + t`` (still
    padded — the padded tail holds ``t``, harmless because Hp's padded
    columns and ``dangp``'s padded tail are zero) and
    ``leak = sum(yp * dangp)``, the scalar the caller folds into the next
    iteration's ``t``.
    """
    Np, Mp = Hp.shape
    bn = min(block_n, Np)
    bm = min(block_m, Mp)
    assert Np % bn == 0 and Mp % bm == 0, "inputs must be pre-padded"
    grid = (Np // bn, Mp // bm)
    has_scales = scales is not None

    in_specs = [
        pl.BlockSpec((bn, bm), lambda i, j, t: (i, j)),
        pl.BlockSpec((1, bm), lambda i, j, t: (0, j)),
        pl.BlockSpec((1, bn), lambda i, j, t: (0, i)),
    ]
    operands = [Hp, xp, dangp]
    if has_scales:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, t: (0, i)))
        operands.append(scales)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bn), lambda i, j, t: (0, i)),
            pl.BlockSpec((1, 1), lambda i, j, t: (0, 0)),
        ],
    )
    yp, leak = pl.pallas_call(
        functools.partial(_fused_kernel, d=d, m_steps=grid[1],
                          has_scales=has_scales),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((1, Np), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(jnp.asarray(t, jnp.float32).reshape(1), *operands)
    return yp, leak[0, 0]


def pad_pagerank_operands(H: jax.Array, dangling: jax.Array | None = None, *,
                          block_n: int = 256, block_m: int = 256
                          ) -> tuple[jax.Array, jax.Array, int, int]:
    """One-time layout prep for :func:`pagerank_step_fused`.

    Returns ``(Hp, dangp, bn, bm)`` with zero padding up to the block grid;
    do this once per graph so nothing in the hot loop re-pads.
    """
    N, M = H.shape
    bn = min(block_n, _mult(N, 128))
    bm = min(block_m, _mult(M, 128))
    Np, Mp = _mult(N, bn), _mult(M, bm)
    Hp = jnp.pad(H, ((0, Np - N), (0, Mp - M)))
    dang = (jnp.zeros((N,), jnp.float32) if dangling is None
            else jnp.asarray(dangling, jnp.float32))
    dangp = jnp.pad(dang, (0, Np - N))[None, :]
    return Hp, dangp, bn, bm


def _mult(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
