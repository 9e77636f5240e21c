"""PageRankEngine: whole-loop compilation, dangling fusion, batched PPR,
backend auto-selection, and the serve-layer multi-user query path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.graph import generators as gen
from repro.graph import transition as tr
from repro.pagerank import PageRankEngine, pagerank_dense_fixed, select_backend
from repro.pagerank.sparse import personalized_pagerank


@pytest.fixture(scope="module")
def net():
    n = 200
    src, dst = gen.protein_network(n, seed=7)
    assert int(tr.dangling_mask(src, n).sum()) > 0    # dangling nodes present
    H = tr.build_transition_dense(src, dst, n)
    return n, src, dst, H


def test_engine_dense_bitwise_matches_reference(net):
    """The fused-scan dense tier dispatches the same compiled program as
    ``pagerank_dense_fixed`` — results must be bit-identical."""
    n, src, dst, H = net
    eng = PageRankEngine(src, dst, n, d=0.85, backend="dense")
    pr = eng.run(n_iters=100)
    ref = pagerank_dense_fixed(H, n_iters=100, d=0.85)
    assert np.array_equal(np.asarray(pr), np.asarray(ref))


@pytest.mark.parametrize("backend", ["ell", "bsr"])
def test_engine_sparse_backends_match_dense(net, backend):
    n, src, dst, H = net
    eng = PageRankEngine(src, dst, n, backend=backend)
    pr = eng.run(n_iters=100)
    ref = pagerank_dense_fixed(H, n_iters=100)
    np.testing.assert_allclose(np.asarray(pr), np.asarray(ref), rtol=1e-4,
                               atol=1e-7)


def test_engine_pallas_fused_matches_dense(net):
    """Whole loop inside one scan around the fused kernel, leak carried
    in-kernel — must agree with the dense reference."""
    n, src, dst, H = net
    eng = PageRankEngine(src, dst, n, backend="pallas_dense")
    pr = eng.run(n_iters=15)            # interpret mode on CPU: keep short
    ref = pagerank_dense_fixed(H, n_iters=15, d=0.85)
    np.testing.assert_allclose(np.asarray(pr), np.asarray(ref), rtol=1e-5,
                               atol=1e-7)


def test_engine_tolerance_terminates(net):
    n, src, dst, H = net
    eng = PageRankEngine(src, dst, n, backend="ell")
    pr, iters, res = eng.run_tol(tol=1e-7, max_iters=500)
    assert 0 < int(iters) < 500
    assert float(res) <= 1e-7
    assert float(jnp.sum(pr)) == pytest.approx(1.0, abs=1e-3)


def test_batched_ppr_matches_per_query_loop(net):
    """Q=8 queries in one (N, Q) propagation == 8 independent
    personalized_pagerank runs."""
    n, src, dst, H = net
    ell = tr.build_transition_ell(src, dst, n)
    dang = jnp.asarray(tr.dangling_mask(src, n).astype(np.float32))
    rng = np.random.default_rng(0)
    seed_sets = [rng.choice(n, size=3, replace=False) for _ in range(8)]

    eng = PageRankEngine(src, dst, n, backend="ell")
    PPR = eng.ppr(seed_sets, n_iters=60)
    assert PPR.shape == (n, 8)
    for q, seeds in enumerate(seed_sets):
        ref = personalized_pagerank(ell.matvec, n,
                                    jnp.asarray(seeds, jnp.int32),
                                    dangling=dang, n_iters=60)
        np.testing.assert_allclose(np.asarray(PPR[:, q]), np.asarray(ref),
                                   rtol=1e-5, atol=1e-7)
        assert float(jnp.sum(PPR[:, q])) == pytest.approx(1.0, abs=1e-3)


def test_batched_ppr_pallas_matches_xla(net):
    n, src, dst, _ = net
    seed_sets = [np.array([1, 2]), np.array([5])]
    eng_p = PageRankEngine(src, dst, n, backend="pallas_dense")
    eng_e = PageRankEngine(src, dst, n, backend="ell")
    got = eng_p.ppr(seed_sets, n_iters=10)
    want = eng_e.ppr(seed_sets, n_iters=10)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-7)


def test_backend_auto_selection():
    """Density/device routing: ELL for every sparse graph on every device
    (bsr is selectable by name only), dense tiers for dense graphs.
    n_devices pinned to 1 — the suite runs under 8 virtual devices
    (conftest), where auto picks the sharded tiers
    (tests/test_engine_sharded.py)."""
    # very sparse graphs on TPU -> ELL, never the N^2-sized BSR layout
    assert select_backend(5000, 0.004, device="tpu", n_devices=1) == "ell"
    assert select_backend(5000, 0.019, device="tpu", n_devices=1) == "ell"
    # below the sparsity threshold (denser): ELL
    assert select_backend(5000, 0.05, device="tpu", n_devices=1) == "ell"
    # CPU: the block einsum loses to the ELL gather
    assert select_backend(5000, 0.004, device="cpu", n_devices=1) == "ell"
    # dense graphs: fused Pallas on TPU, XLA matmul elsewhere
    assert select_backend(1000, 0.4, device="tpu",
                          n_devices=1) == "pallas_dense"
    assert select_backend(1000, 0.4, device="cpu", n_devices=1) == "dense"
    # tiny graphs never pick BSR
    assert select_backend(100, 0.001, device="tpu", n_devices=1) == "ell"
    # any multi-device topology routes to the sharded tiers
    assert select_backend(5000, 0.004, device="tpu",
                          n_devices=4) == "ell_sharded"
    assert select_backend(1000, 0.4, n_devices=4) == "dense_sharded"


def test_engine_auto_uses_selector(net):
    n, src, dst, _ = net
    eng = PageRankEngine(src, dst, n)     # auto
    assert eng.backend == select_backend(n, eng.density)
    with pytest.raises(ValueError):
        PageRankEngine(src, dst, n, backend="nope")


def test_interpret_derived_from_device(net):
    n, src, dst, _ = net
    assert PageRankEngine(src, dst, n).interpret == (
        jax.default_backend() != "tpu")


def test_containers_matmat_matches_matvec_columns(net):
    n, src, dst, _ = net
    csr = tr.build_transition_csr(src, dst, n)
    ell = tr.build_transition_ell(src, dst, n)
    bsr = tr.build_transition_bsr(src, dst, n)
    X = jax.random.uniform(jax.random.PRNGKey(0), (n, 4))
    for c in (csr, ell, bsr):
        Y = c.matmat(X)
        assert Y.shape == (n, 4)
        for q in range(4):
            np.testing.assert_allclose(np.asarray(Y[:, q]),
                                       np.asarray(c.matvec(X[:, q])),
                                       rtol=1e-5, atol=1e-6)


def test_serve_query_engine_batches(net):
    from repro.serve import PageRankQueryEngine
    n, src, dst, _ = net
    eng = PageRankEngine(src, dst, n, backend="ell")
    qe = PageRankQueryEngine(eng, n_iters=40, max_batch=4)
    rng = np.random.default_rng(1)
    seed_sets = [rng.choice(n, size=2, replace=False) for _ in range(6)]
    results = qe.query_batch(seed_sets, top_k=5)
    assert len(results) == 6 and not qe._queue
    ell = tr.build_transition_ell(src, dst, n)
    dang = jnp.asarray(tr.dangling_mask(src, n).astype(np.float32))
    for (idx, scores), seeds in zip(results, seed_sets):
        assert len(idx) == 5
        ref = personalized_pagerank(ell.matvec, n,
                                    jnp.asarray(seeds, jnp.int32),
                                    dangling=dang, n_iters=40)
        ref_top = int(jnp.argmax(ref))
        assert idx[0] == ref_top
        assert scores[0] == pytest.approx(float(ref[ref_top]), rel=1e-4)


def test_seed_matrix_rejects_empty():
    from repro.pagerank.steps import seed_matrix
    with pytest.raises(ValueError):
        seed_matrix(10, [np.array([1]), np.array([], np.int64)])
    V = seed_matrix(10, [np.array([0, 1]), np.array([5])])
    assert V.shape == (10, 2)
    np.testing.assert_allclose(V.sum(axis=0), 1.0)
    # duplicate seeds accumulate: the column stays a distribution
    Vd = seed_matrix(10, [np.array([3, 3, 5])])
    np.testing.assert_allclose(Vd.sum(axis=0), 1.0)
    assert Vd[3, 0] == pytest.approx(2.0 / 3)


def test_dense_ppr_handles_dangling(net):
    """Regression: the dense operand folds the uniform dangling fix into
    H; PPR must undo it (the leak teleports to V, not 1/n) or mass is
    double-counted and the iteration diverges."""
    n, src, dst, _ = net
    seed_sets = [np.array([1, 2]), np.array([5])]
    ppr_d = PageRankEngine(src, dst, n, backend="dense").ppr(
        seed_sets, n_iters=80)
    ppr_e = PageRankEngine(src, dst, n, backend="ell").ppr(
        seed_sets, n_iters=80)
    np.testing.assert_allclose(np.asarray(ppr_d.sum(axis=0)), 1.0,
                               atol=1e-3)
    np.testing.assert_allclose(np.asarray(ppr_d), np.asarray(ppr_e),
                               rtol=1e-4, atol=1e-7)
