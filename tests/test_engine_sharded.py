"""Sharded engine tiers on the 8-virtual-device CPU mesh (see conftest).

Every sharded backend must agree with the single-device dense reference to
<= 1e-5 on fixed seeds — including dangling nodes, tolerance-based early
exit across the mesh, uneven N padding, and the sharded batched
PPR path that backs ``serve.PageRankQueryEngine``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.graph import generators as gen
from repro.graph import transition as tr
from repro.launch.mesh import make_mesh
from repro.pagerank import (PageRankEngine, pagerank_dense_fixed,
                            select_backend)
from repro.pagerank.engine import SHARDED_BACKENDS

TOL = 1e-5


@pytest.fixture(scope="module")
def net(multi_device):
    n = 200
    src, dst = gen.protein_network(n, seed=7)
    assert int(tr.dangling_mask(src, n).sum()) > 0    # dangling nodes present
    H = tr.build_transition_dense(src, dst, n)
    return n, src, dst, H


@pytest.mark.parametrize("backend", SHARDED_BACKENDS)
def test_sharded_matches_dense_reference(net, backend):
    n, src, dst, H = net
    eng = PageRankEngine(src, dst, n, backend=backend)
    pr = eng.run(n_iters=100)
    ref = pagerank_dense_fixed(H, n_iters=100)
    assert eng.mesh is not None and eng.mesh.size > 1
    assert float(jnp.max(jnp.abs(pr - ref))) <= TOL
    assert float(jnp.sum(pr)) == pytest.approx(1.0, abs=1e-3)


@pytest.mark.parametrize("backend", SHARDED_BACKENDS)
def test_sharded_early_exit_across_mesh(net, backend):
    """The residual is a replicated scalar, so the while_loop exits on the
    same iteration on every device — and at the same count the
    single-device dense reference needs."""
    n, src, dst, H = net
    eng = PageRankEngine(src, dst, n, backend=backend)
    pr, iters, res = eng.run_tol(tol=1e-7, max_iters=500)
    assert 0 < int(iters) < 500
    assert float(res) <= 1e-7
    from repro.pagerank import pagerank_dense
    ref, ref_iters, _, _, _ = pagerank_dense(H, tol=1e-7, max_iters=500)
    assert abs(int(iters) - int(ref_iters)) <= 2
    assert float(jnp.max(jnp.abs(pr - ref))) <= TOL


@pytest.mark.parametrize("backend", SHARDED_BACKENDS)
def test_sharded_uneven_n_pads_and_slices(multi_device, backend):
    """N not divisible by the shard count: zero-padding must not leak into
    real ranks."""
    n = 203
    src, dst = gen.protein_network(n, seed=5)
    eng = PageRankEngine(src, dst, n, backend=backend)
    assert eng.prepared.rows > n               # padding actually exercised
    pr = eng.run(n_iters=80)
    ref = pagerank_dense_fixed(tr.build_transition_dense(src, dst, n),
                               n_iters=80)
    assert pr.shape == (n,)
    assert float(jnp.max(jnp.abs(pr - ref))) <= TOL


@pytest.mark.parametrize("backend", SHARDED_BACKENDS)
def test_sharded_batched_ppr_matches_single_device(net, backend):
    """Query-sharded (N, Q) propagation == the single-device ELL engine,
    with Q chosen indivisible by the shard count to exercise Q-padding."""
    n, src, dst, _ = net
    rng = np.random.default_rng(0)
    seed_sets = [rng.choice(n, size=3, replace=False) for _ in range(5)]
    want = PageRankEngine(src, dst, n, backend="ell").ppr(seed_sets,
                                                         n_iters=60)
    eng = PageRankEngine(src, dst, n, backend=backend)
    got = eng.ppr(seed_sets, n_iters=60)
    assert got.shape == (n, 5)
    assert float(jnp.max(jnp.abs(got - want))) <= TOL
    np.testing.assert_allclose(np.asarray(got.sum(axis=0)), 1.0, atol=1e-3)


def test_dense_sharded_explicit_square_mesh(net):
    """A square mesh takes the diagonal re-injection path of
    ``matvec_iterated_reshard`` (the non-square default falls back to a
    GSPMD reshard) — both must agree with the reference."""
    n, src, dst, H = net
    mesh = make_mesh((2, 2), ("data", "model"))
    eng = PageRankEngine(src, dst, n, backend="dense_sharded", mesh=mesh)
    pr = eng.run(n_iters=100)
    ref = pagerank_dense_fixed(H, n_iters=100)
    assert float(jnp.max(jnp.abs(pr - ref))) <= TOL


def test_ell_sharded_on_2d_mesh_flattens_axes(net):
    n, src, dst, H = net
    mesh = make_mesh((2, 4), ("data", "model"))
    eng = PageRankEngine(src, dst, n, backend="ell_sharded", mesh=mesh)
    assert eng.prepared.axes == ("data", "model")
    pr = eng.run(n_iters=100)
    ref = pagerank_dense_fixed(H, n_iters=100)
    assert float(jnp.max(jnp.abs(pr - ref))) <= TOL


def test_dense_sharded_rejects_1d_mesh(net):
    n, src, dst, _ = net
    with pytest.raises(ValueError, match="2-D mesh"):
        PageRankEngine(src, dst, n, backend="dense_sharded",
                       mesh=make_mesh((jax.device_count(),), ("shard",)))


def test_select_backend_device_count_dimension(multi_device):
    """Multi-device processes auto-pick the sharded tiers; the
    single-device heuristics are preserved under n_devices=1."""
    assert select_backend(5000, 0.004, n_devices=8) == "ell_sharded"
    assert select_backend(1000, 0.4, n_devices=8) == "dense_sharded"
    assert select_backend(1000, 0.4, device="tpu", n_devices=2) == \
        "dense_sharded"
    assert select_backend(5000, 0.004, device="tpu", n_devices=1) == "ell"
    # default n_devices follows jax.device_count() (8 under conftest)
    assert select_backend(5000, 0.004) == "ell_sharded"


def test_auto_engine_picks_sharded_tier(net):
    n, src, dst, _ = net
    eng = PageRankEngine(src, dst, n)          # auto, 8 devices
    assert eng.backend in SHARDED_BACKENDS
    assert eng.backend == select_backend(n, eng.density)


def test_distributed_dangling_regression_2d_mesh(net):
    """The ``dangling`` branch of ``pagerank_distributed`` was never
    exercised before this PR (the seed's ``dangling_col`` closure read a
    name assigned after the ``one_iter`` def — functional only because
    tracing is deferred, and untested).  Pin it down: unfixed H + explicit
    leak on a 2-D mesh must match the dangling-fixed dense reference."""
    from repro.pagerank.distributed import (make_sharded_inputs_dense,
                                            pagerank_distributed)
    n, src, dst, H = net
    mesh = make_mesh((2, 4), ("data", "model"))
    Hu = tr.build_transition_dense(src, dst, n, fix_dangling=False)
    dang = jnp.asarray(tr.dangling_mask(src, n).astype(np.float32))
    Hd = make_sharded_inputs_dense(Hu, mesh)
    pr = jax.jit(lambda Hd: pagerank_distributed(
        Hd, mesh, n_iters=80, dangling=dang))(Hd)
    ref = pagerank_dense_fixed(H, n_iters=80)
    assert float(jnp.max(jnp.abs(pr - ref))) <= TOL


@pytest.mark.parametrize("backend", SHARDED_BACKENDS)
def test_serve_query_engine_on_sharded_backend(net, backend):
    """serve.PageRankQueryEngine flushes multi-user batches onto the mesh
    unchanged — the flush is one sharded device dispatch."""
    from repro.serve import PageRankQueryEngine
    n, src, dst, _ = net
    eng = PageRankEngine(src, dst, n, backend=backend)
    qe = PageRankQueryEngine(eng, n_iters=40, max_batch=4)
    rng = np.random.default_rng(1)
    seed_sets = [rng.choice(n, size=2, replace=False) for _ in range(6)]
    results = qe.query_batch(seed_sets, top_k=5)
    assert len(results) == 6 and not qe._queue

    ref_eng = PageRankEngine(src, dst, n, backend="ell")
    ref_qe = PageRankQueryEngine(ref_eng, n_iters=40, max_batch=4)
    ref_results = ref_qe.query_batch(seed_sets, top_k=5)
    for (idx, scores), (ridx, rscores) in zip(results, ref_results):
        np.testing.assert_array_equal(idx, ridx)
        np.testing.assert_allclose(scores, rscores, rtol=1e-4, atol=1e-7)
