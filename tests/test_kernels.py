"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.graph.sparse import BSRMatrix
from repro.kernels import common, ops, ref
from repro.kernels.bsr_spmv import bsr_spmv
from repro.kernels.pagerank_step import pagerank_step
from repro.kernels.streaming_matvec import streaming_matvec

TOL = dict(rtol=2e-3, atol=2e-3)        # bf16 inputs, f32 accumulation
# f32: blocked kernel accumulation order differs from the oracle's single
# dot; 512-length reductions land ~1.5e-5 apart on CPU, so atol > 1e-5
TOL32 = dict(rtol=1e-5, atol=5e-5)


# --------------------------------------------------------------------------- #
# streaming_matvec                                                            #
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("N,M,B", [
    (128, 128, 1), (256, 128, 1), (128, 384, 4), (512, 512, 8),
    (100, 90, 1),               # non-aligned (padding path)
    (37, 129, 3),               # very ragged
    (1024, 256, 2),
])
def test_streaming_matvec_sweep(N, M, B, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(N + M + B))
    W = jax.random.normal(k1, (N, M), dtype)
    X = jax.random.normal(k2, (B, M), dtype)
    got = streaming_matvec(W, X, block_n=128, block_m=128)
    want = ref.streaming_matvec_ref(W, X)
    tol = TOL if dtype == jnp.bfloat16 else TOL32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **tol)


@pytest.mark.parametrize("bn,bm", [(128, 128), (256, 256), (128, 512)])
def test_streaming_matvec_block_shapes(bn, bm):
    W = jax.random.normal(jax.random.PRNGKey(0), (512, 512))
    X = jax.random.normal(jax.random.PRNGKey(1), (2, 512))
    got = streaming_matvec(W, X, block_n=bn, block_m=bm)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.streaming_matvec_ref(W, X)),
                               **TOL32)


@given(n=st.integers(1, 300), m=st.integers(1, 300), b=st.integers(1, 4))
@settings(max_examples=15, deadline=None)
def test_streaming_matvec_property(n, m, b):
    W = jax.random.normal(jax.random.PRNGKey(n * m), (n, m))
    X = jax.random.normal(jax.random.PRNGKey(b), (b, m))
    got = streaming_matvec(W, X, block_n=128, block_m=128)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.streaming_matvec_ref(W, X)),
                               rtol=1e-4, atol=1e-4)


def test_ops_matvec_matches_paper_mv():
    """ops.matvec == the fabric schedule's result (same math, three tiers)."""
    from repro.core import schedule
    A = jax.random.normal(jax.random.PRNGKey(5), (64, 48))
    x = jax.random.normal(jax.random.PRNGKey(6), (48,))
    fabric_y = schedule.matvec(A, x).result
    kernel_y = ops.matvec(A, x)
    np.testing.assert_allclose(np.asarray(kernel_y), np.asarray(fabric_y),
                               rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------- #
# bsr_spmv                                                                    #
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("n,bs,density", [
    (256, 128, 0.3), (384, 128, 0.1), (512, 128, 0.05),
    (200, 128, 0.2),            # padded rows
    (256, 256, 0.3),
])
def test_bsr_spmv_sweep(n, bs, density):
    rng = np.random.default_rng(n)
    A = rng.normal(size=(n, n)).astype(np.float32)
    A[rng.random(size=A.shape) > density] = 0.0
    bsr = BSRMatrix.from_dense(A, bs=bs)
    x = jnp.asarray(rng.normal(size=n).astype(np.float32))
    got = ops.spmv(bsr, x)
    np.testing.assert_allclose(np.asarray(got), A @ np.asarray(x),
                               rtol=2e-4, atol=2e-4)


def test_bsr_spmv_matches_ref_and_container():
    rng = np.random.default_rng(0)
    A = rng.normal(size=(384, 384)).astype(np.float32)
    A[rng.random(size=A.shape) > 0.15] = 0.0
    bsr = BSRMatrix.from_dense(A, bs=128)
    x = jnp.asarray(rng.normal(size=384).astype(np.float32))
    kernel_y = bsr_spmv(bsr.blocks, bsr.block_cols, x)
    ref_y = ref.bsr_spmv_ref(bsr.blocks, bsr.block_cols, x)
    np.testing.assert_allclose(np.asarray(kernel_y), np.asarray(ref_y),
                               **TOL32)
    np.testing.assert_allclose(np.asarray(kernel_y[:384]),
                               np.asarray(bsr.matvec(x)), rtol=1e-4,
                               atol=1e-4)


def test_bsr_empty_rows():
    """Block-rows with zero stored blocks produce exact zeros."""
    A = np.zeros((256, 256), np.float32)
    A[:128, :128] = 1.0          # only the first block-row populated
    bsr = BSRMatrix.from_dense(A, bs=128)
    x = jnp.ones((256,))
    y = ops.spmv(bsr, x)
    np.testing.assert_allclose(np.asarray(y[:128]), 128.0, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(y[128:]), 0.0, atol=0)


# --------------------------------------------------------------------------- #
# pagerank_step                                                               #
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("n", [128, 256, 500, 1000])
def test_pagerank_step_sweep(n):
    from repro.graph import generators as gen, transition as tr
    src, dst = gen.protein_network(n, seed=n)
    H = tr.build_transition_dense(src, dst, n)
    pr = jnp.full((n,), 1.0 / n)
    t = jnp.float32(0.15 / n)
    got = pagerank_step(H, pr, t, d=0.85)
    want = ref.pagerank_step_ref(H, pr, t, d=0.85)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-7)


def test_fused_iteration_matches_three_phase():
    """Fused kernel == the paper's separate MV/scale/add phases, and the
    dangling-leak epilogue matches pagerank.sparse semantics."""
    from repro.graph import generators as gen, transition as tr
    n = 300
    src, dst = gen.protein_network(n, seed=3)
    H = tr.build_transition_dense(src, dst, n, fix_dangling=False)
    dang = jnp.asarray(tr.dangling_mask(src, n).astype(np.float32))
    pr = jax.random.uniform(jax.random.PRNGKey(0), (n,))
    pr = pr / jnp.sum(pr)
    fused = ops.pagerank_iteration(H, pr, dangling=dang)
    leak = jnp.sum(pr * dang) / n
    unfused = 0.85 * (H @ pr + leak) + 0.15 / n
    np.testing.assert_allclose(np.asarray(fused), np.asarray(unfused),
                               rtol=1e-5, atol=1e-7)


def test_full_pagerank_via_kernel_matches_dense():
    from repro.graph import generators as gen, transition as tr
    from repro.pagerank import pagerank_dense_fixed
    n = 256
    src, dst = gen.protein_network(n, seed=1)
    H = tr.build_transition_dense(src, dst, n)
    pr = jnp.full((n,), 1.0 / n)
    for _ in range(30):
        pr = ops.pagerank_iteration(H, pr)
    want = pagerank_dense_fixed(H, n_iters=30)
    np.testing.assert_allclose(np.asarray(pr), np.asarray(want), rtol=1e-4,
                               atol=1e-7)


# --------------------------------------------------------------------------- #
# ell_rows: the chunked sweep past the gather budget                          #
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("q", [None, 3])
@pytest.mark.parametrize("storage", [jnp.float32, jnp.bfloat16, jnp.int8])
def test_ell_rows_chunked_matches_one_shot(monkeypatch, storage, q):
    """Past ``ELL_GATHER_BUDGET`` the rows are swept in chunks plus a
    remainder; every row sum must equal the one-shot gather's, for a
    vector and for an (n, Q) batch, in every storage dtype."""
    rows, k, n = 100, 5, 70
    rng = np.random.default_rng(q or 0)
    data = rng.integers(-9, 10, (rows, k)) if storage == jnp.int8 \
        else rng.normal(size=(rows, k))
    data = jnp.asarray(data).astype(storage)
    idx = jnp.asarray(rng.integers(0, n, (rows, k)), jnp.int32)
    x = jnp.asarray(rng.normal(size=(n,) if q is None else (n, q)),
                    jnp.float32)
    def sweep():
        # a fresh function: jit's trace cache must not hand back the
        # program traced under the other budget
        return jax.jit(lambda *a: common.ell_rows(*a))

    one_shot = sweep()(data, idx, x)
    # 12 KiB holds 24 vector rows or 8 batched rows: 100 rows leave a
    # remainder either way
    monkeypatch.setattr(common, "ELL_GATHER_BUDGET", 12 * 1024)
    chunked = sweep()(data, idx, x)
    assert "while" in sweep().lower(data, idx, x).as_text()
    d64 = np.asarray(data.astype(jnp.float32), np.float64)
    x64 = np.asarray(x, np.float64)
    want = (d64 if q is None else d64[..., None]) * x64[np.asarray(idx)]
    np.testing.assert_allclose(np.asarray(chunked), want.sum(axis=1),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(chunked), np.asarray(one_shot))
