"""Static-shape sparse matrix containers (JAX-friendly).

Three formats, each chosen for a different execution tier:

* :class:`CSRMatrix` — host/reference format; SpMV via ``segment_sum``.
* :class:`ELLMatrix` — fixed nonzeros-per-row padding; SpMV is a dense
  gather + rowwise reduce, vectorizes cleanly (and shards row-wise).
* :class:`BSRMatrix` — block-sparse rows with MXU-aligned dense blocks; the
  layout consumed by the ``bsr_spmv`` Pallas kernel (blocks stream through
  VMEM, block-column indices ride in scalar-prefetch memory).

All containers are registered pytrees with static structural metadata so
they pass through ``jit``/``shard_map`` unmodified.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CSRMatrix:
    data: jax.Array      # (nnz,) f32
    indices: jax.Array   # (nnz,) i32 column ids
    indptr: jax.Array    # (n_rows+1,) i32
    row_ids: jax.Array   # (nnz,) i32 — precomputed row of each nnz
    shape: tuple[int, int] = dataclasses.field(metadata=dict(static=True),
                                               default=(0, 0))

    @staticmethod
    def from_dense(A: np.ndarray) -> "CSRMatrix":
        A = np.asarray(A)
        rows, cols = np.nonzero(A)
        order = np.lexsort((cols, rows))
        rows, cols = rows[order], cols[order]
        data = A[rows, cols].astype(np.float32)
        indptr = np.zeros(A.shape[0] + 1, np.int32)
        np.add.at(indptr, rows + 1, 1)
        indptr = np.cumsum(indptr).astype(np.int32)
        return CSRMatrix(jnp.asarray(data), jnp.asarray(cols, jnp.int32),
                         jnp.asarray(indptr), jnp.asarray(rows, jnp.int32),
                         shape=A.shape)

    @staticmethod
    def from_coo(src: np.ndarray, dst: np.ndarray, vals: np.ndarray,
                 shape: tuple[int, int]) -> "CSRMatrix":
        order = np.lexsort((dst, src))
        rows = np.asarray(src)[order]
        cols = np.asarray(dst)[order]
        data = np.asarray(vals)[order].astype(np.float32)
        indptr = np.zeros(shape[0] + 1, np.int32)
        np.add.at(indptr, rows + 1, 1)
        indptr = np.cumsum(indptr).astype(np.int32)
        return CSRMatrix(jnp.asarray(data), jnp.asarray(cols, jnp.int32),
                         jnp.asarray(indptr), jnp.asarray(rows, jnp.int32),
                         shape=shape)

    @property
    def nnz(self) -> int:
        return self.data.shape[0]

    def row_positions(self) -> tuple[np.ndarray, np.ndarray]:
        """Host-side (row, position-within-row) of every nnz — the scatter
        coordinates of :meth:`ELLMatrix.from_csr`."""
        indptr = np.asarray(self.indptr)
        counts = np.diff(indptr)
        rows = np.repeat(np.arange(self.shape[0]), counts)
        pos = np.arange(rows.size) - np.repeat(indptr[:-1], counts)
        return rows, pos

    def matvec(self, x: jax.Array) -> jax.Array:
        return self.matmat(x[:, None])[:, 0]

    def matmat(self, X: jax.Array) -> jax.Array:
        """Y = A @ X for (M, Q) X — Q columns share one pass over the nnz."""
        prod = self.data[:, None] * X[self.indices]
        return jax.ops.segment_sum(prod, self.row_ids,
                                   num_segments=self.shape[0])

    def todense(self) -> jax.Array:
        out = jnp.zeros(self.shape, jnp.float32)
        return out.at[self.row_ids, self.indices].add(self.data)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ELLMatrix:
    """ELLPACK: ``data``/``indices`` are (n_rows, K) with zero padding."""

    data: jax.Array      # (n_rows, K) f32, 0 padded
    indices: jax.Array   # (n_rows, K) i32, 0 padded (data==0 masks)
    shape: tuple[int, int] = dataclasses.field(metadata=dict(static=True),
                                               default=(0, 0))

    @staticmethod
    def from_csr(csr: CSRMatrix, k: int | None = None) -> "ELLMatrix":
        indptr = np.asarray(csr.indptr)
        counts = np.diff(indptr)
        kk = int(counts.max()) if k is None else k
        n = csr.shape[0]
        data = np.zeros((n, kk), np.float32)
        idx = np.zeros((n, kk), np.int32)
        cols = np.asarray(csr.indices)
        vals = np.asarray(csr.data)
        # bulk scatter: position of each nnz within its row, rows truncated
        # at the K budget (no per-row Python loop)
        rows, pos = csr.row_positions()
        keep = pos < kk
        data[rows[keep], pos[keep]] = vals[keep]
        idx[rows[keep], pos[keep]] = cols[keep]
        return ELLMatrix(jnp.asarray(data), jnp.asarray(idx), shape=csr.shape)

    @property
    def k(self) -> int:
        return self.data.shape[1]

    def matvec(self, x: jax.Array) -> jax.Array:
        return self.matmat(x[:, None])[:, 0]

    def matmat(self, X: jax.Array) -> jax.Array:
        """Y = A @ X for (M, Q) X — one gather serves all Q columns.
        ``data`` may be stored reduced-precision (bf16/f16); products and
        the rowwise reduce run in f32 (upcast is a no-op on f32 data)."""
        data = self.data.astype(jnp.float32)
        return jnp.sum(data[..., None] * X[self.indices], axis=1)

    def todense(self) -> jax.Array:
        n, _ = self.shape
        rows = jnp.repeat(jnp.arange(n), self.k).reshape(n, self.k)
        out = jnp.zeros(self.shape, jnp.float32)
        return out.at[rows, self.indices].add(self.data)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BSRMatrix:
    """Block-sparse rows: for each block-row, a fixed budget of ``max_blocks``
    dense (bs x bs) blocks (zero-padded), with their block-column indices.

    ``blocks``:    (n_block_rows, max_blocks, bs, bs) f32 — or a reduced
                   storage dtype (bf16/f16/int8); matvecs upcast per tile
                   and accumulate in f32.
    ``block_cols``:(n_block_rows, max_blocks) i32 — padded entries point at
                   block-column 0 with an all-zero block (safe to accumulate).
    ``row_scales``:(n_block_rows * bs,) f32 per-row dequantization scales
                   for int8 blocks, folded into the accumulated row sums;
                   ``None`` for float layouts.
    """

    blocks: jax.Array
    block_cols: jax.Array
    shape: tuple[int, int] = dataclasses.field(metadata=dict(static=True),
                                               default=(0, 0))
    row_scales: jax.Array | None = None

    @staticmethod
    def from_dense(A: np.ndarray, bs: int = 128,
                   max_blocks: int | None = None) -> "BSRMatrix":
        A = np.asarray(A, np.float32)
        n, m = A.shape
        nb_r = -(-n // bs)
        nb_c = -(-m // bs)
        Ap = np.zeros((nb_r * bs, nb_c * bs), np.float32)
        Ap[:n, :m] = A
        blk = Ap.reshape(nb_r, bs, nb_c, bs).transpose(0, 2, 1, 3)
        nz = np.abs(blk).sum(axis=(2, 3)) > 0          # (nb_r, nb_c)
        counts = nz.sum(axis=1)
        mb = int(counts.max()) if max_blocks is None else max_blocks
        mb = max(mb, 1)
        blocks = np.zeros((nb_r, mb, bs, bs), np.float32)
        bcols = np.zeros((nb_r, mb), np.int32)
        # bulk scatter of nonzero blocks: np.nonzero is row-major, so the
        # slot of each block within its row is its rank since the row start
        r_idx, c_idx = np.nonzero(nz)
        starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
        slot = np.arange(len(r_idx)) - np.repeat(starts, counts)
        keep = slot < mb
        blocks[r_idx[keep], slot[keep]] = blk[r_idx[keep], c_idx[keep]]
        bcols[r_idx[keep], slot[keep]] = c_idx[keep]
        return BSRMatrix(jnp.asarray(blocks), jnp.asarray(bcols),
                         shape=(n, m))

    @property
    def block_size(self) -> int:
        return self.blocks.shape[-1]

    @property
    def max_blocks(self) -> int:
        return self.blocks.shape[1]

    def matvec(self, x: jax.Array) -> jax.Array:
        """Reference BSR SpMV (pure jnp; the Pallas kernel mirrors this)."""
        return self.matmat(x[:, None])[:, 0]

    def matmat(self, X: jax.Array) -> jax.Array:
        """Y = A @ X for (M, Q) X — blocks are gathered once per sweep."""
        bs = self.block_size
        nb_r = self.blocks.shape[0]
        q = X.shape[1]
        m_pad = self.shape[1] if self.shape[1] % bs == 0 else (
            (self.shape[1] // bs + 1) * bs)
        Xp = jnp.zeros((m_pad, q), X.dtype).at[:self.shape[1]].set(X)
        xb = Xp.reshape(-1, bs, q)                    # (nb_c, bs, Q)
        gathered = xb[self.block_cols]                # (nb_r, mb, bs, Q)
        y = jnp.einsum("rbij,rbjq->riq", self.blocks.astype(jnp.float32),
                       gathered.astype(jnp.float32))
        y = y.reshape(nb_r * bs, q)
        if self.row_scales is not None:
            y = y * self.row_scales[:, None]
        return y[:self.shape[0]]
