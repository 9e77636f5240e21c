"""PageRank correctness across all tiers: dense / sparse / fabric / distributed."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.graph import generators as gen
from repro.graph import transition as tr
from repro.pagerank import (pagerank_dense, pagerank_dense_fixed,
                            pagerank_on_fabric, pagerank_sparse)
from repro.pagerank.sparse import pagerank_sparse_tol
from repro.serve.engine import top_k_proteins


def _numpy_pagerank(H, n_iters=100, d=0.85):
    n = H.shape[0]
    pr = np.full((n,), 1.0 / n, np.float64)
    for _ in range(n_iters):
        pr = d * (H.astype(np.float64) @ pr) + (1.0 - d) / n
    return pr


@pytest.fixture(scope="module")
def small_net():
    n = 120
    src, dst = gen.protein_network(n, seed=7)
    H = np.asarray(tr.build_transition_dense(src, dst, n))
    return n, src, dst, H


def test_dense_fixed_matches_numpy(small_net):
    n, _, _, H = small_net
    pr = pagerank_dense_fixed(jnp.asarray(H), n_iters=100)
    np.testing.assert_allclose(np.asarray(pr), _numpy_pagerank(H), rtol=1e-4)


def test_dense_converges_and_sums_to_one(small_net):
    n, _, _, H = small_net
    pr, iters, res, _, _ = pagerank_dense(jnp.asarray(H), tol=1e-6)
    assert float(jnp.sum(pr)) == pytest.approx(1.0, abs=1e-4)
    assert int(iters) < 1000 and float(res) <= 1e-6
    # fixed point: one more application changes nothing
    pr2 = 0.85 * (H @ np.asarray(pr)) + 0.15 / n
    np.testing.assert_allclose(pr2, np.asarray(pr), atol=1e-6)


def test_sparse_matches_dense_with_dangling(small_net):
    n, src, dst, H = small_net
    ell = tr.build_transition_ell(src, dst, n)
    dang = tr.dangling_mask(src, n).astype(np.float32)
    pr_sparse = pagerank_sparse(ell.matvec, n, dangling=jnp.asarray(dang),
                                n_iters=100)
    pr_dense = pagerank_dense_fixed(jnp.asarray(H), n_iters=100)
    np.testing.assert_allclose(np.asarray(pr_sparse), np.asarray(pr_dense),
                               rtol=1e-4, atol=1e-7)


def test_sparse_tol_variant(small_net):
    n, src, dst, H = small_net
    ell = tr.build_transition_ell(src, dst, n)
    dang = tr.dangling_mask(src, n).astype(np.float32)
    pr, iters, res = pagerank_sparse_tol(ell.matvec, n,
                                         dangling=jnp.asarray(dang),
                                         tol=1e-7)
    assert float(res) <= 1e-7
    assert float(jnp.sum(pr)) == pytest.approx(1.0, abs=1e-3)


def test_fabric_tier_matches_dense():
    """The fabric simulator (paper-faithful tier) agrees with native JAX."""
    n = 24
    src, dst = gen.erdos_renyi(n, avg_degree=5.0, seed=9)
    H = np.asarray(tr.build_transition_dense(src, dst, n))
    pr_fab, steps, secs = pagerank_on_fabric(jnp.asarray(H), n_iters=50)
    pr_ref = pagerank_dense_fixed(jnp.asarray(H), n_iters=50)
    np.testing.assert_allclose(np.asarray(pr_fab), np.asarray(pr_ref),
                               rtol=1e-4)
    assert steps == 50 * (n + 6)
    assert secs == pytest.approx(steps * 5e-9)


def test_top_k():
    pr = jnp.asarray([0.1, 0.5, 0.2, 0.15, 0.05])
    idx, scores = top_k_proteins(pr, k=2)
    assert idx.tolist() == [1, 2]


def test_hub_nodes_rank_highest():
    """A star graph's hub must get the top PageRank score."""
    n = 50
    src = np.array([0] * (n - 1) + list(range(1, n)), np.int32)
    dst = np.array(list(range(1, n)) + [0] * (n - 1), np.int32)
    H = tr.build_transition_dense(src, dst, n)
    pr = pagerank_dense_fixed(H, n_iters=100)
    assert int(jnp.argmax(pr)) == 0


_DIST_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys; sys.path.insert(0, {src!r})
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.graph import generators as gen, transition as tr
    from repro.pagerank.dense import pagerank_dense_fixed
    from repro.pagerank.distributed import (pagerank_distributed,
                                            pagerank_distributed_sparse,
                                            make_sharded_inputs_dense)

    n = 128
    src, dst = gen.protein_network(n, seed=11)
    H = np.asarray(tr.build_transition_dense(src, dst, n))
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 2), ("data", "model"))
    Hd = make_sharded_inputs_dense(jnp.asarray(H), mesh)
    pr = pagerank_distributed(Hd, mesh, n_iters=60)
    ref = pagerank_dense_fixed(jnp.asarray(H), n_iters=60)
    np.testing.assert_allclose(np.asarray(pr), np.asarray(ref), rtol=2e-4,
                               atol=1e-7)

    from repro.pagerank import sell
    dang = jnp.asarray(tr.dangling_mask(src, n).astype(np.float32))
    spec = NamedSharding(mesh, P(("data", "model")))
    layout, _ = sell.build(tr.build_transition_csr(src, dst, n), n,
                           shards=4, sharding=spec)
    pr2 = pagerank_distributed_sparse(layout, mesh, n_iters=60,
                                      dangling=dang)
    np.testing.assert_allclose(np.asarray(pr2), np.asarray(ref), rtol=2e-4,
                               atol=1e-7)
    print("DIST_PAGERANK_OK")
""")


@pytest.mark.slow
def test_distributed_pagerank_4dev_subprocess():
    """Fabric and row-sharded schedules on a 2x2 mesh of four virtual CPU
    devices (the shape of a four-chip host) vs the dense reference."""
    src_dir = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _DIST_SCRIPT.format(src=src_dir)], env=env,
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "DIST_PAGERANK_OK" in out.stdout


def test_personalized_pagerank_localizes():
    """PPR mass concentrates near the seed set; global PR does not."""
    from repro.pagerank.sparse import personalized_pagerank
    n = 150
    src, dst = gen.barabasi_albert(n, m_edges=3, seed=13)
    ell = tr.build_transition_ell(src, dst, n)
    dang = jnp.asarray(tr.dangling_mask(src, n).astype(np.float32))
    seeds = jnp.asarray([5, 6, 7], jnp.int32)
    ppr = personalized_pagerank(ell.matvec, n, seeds, dangling=dang,
                                n_iters=60)
    assert float(jnp.sum(ppr)) == pytest.approx(1.0, abs=1e-3)
    # seeds hold far more mass than under uniform teleport
    pr_global = pagerank_sparse(ell.matvec, n, dangling=dang, n_iters=60)
    assert float(jnp.sum(ppr[seeds])) > 3 * float(jnp.sum(pr_global[seeds]))
    # teleport-only sanity: d=0 gives exactly the seed distribution
    ppr0 = personalized_pagerank(ell.matvec, n, seeds, dangling=dang,
                                 d=0.0, n_iters=5)
    np.testing.assert_allclose(np.asarray(ppr0[seeds]), 1.0 / 3, rtol=1e-5)
    assert float(jnp.sum(ppr0)) == pytest.approx(1.0, abs=1e-5)
