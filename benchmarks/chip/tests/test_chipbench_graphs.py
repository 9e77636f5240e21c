"""The benchmark's graph generators and graph cache."""
from __future__ import annotations

import numpy as np

from benchmarks.chip import graphs
from benchmarks.chip.graphs import kronecker, protein

KRON = {"name": "k", "generator": "kronecker", "scale": 8, "edgefactor": 16,
        "A": 0.57, "B": 0.19, "C": 0.19, "structure_seed": 7}


def test_kronecker_raw_edges_count_range_and_determinism():
    a = kronecker.raw_edges(8, 16, 0.57, 0.19, 0.19,
                            np.random.default_rng(3))
    b = kronecker.raw_edges(8, 16, 0.57, 0.19, 0.19,
                            np.random.default_rng(3))
    c = kronecker.raw_edges(8, 16, 0.57, 0.19, 0.19,
                            np.random.default_rng(4))
    assert len(a[0]) == len(a[1]) == 16 * 2**8
    assert a[0].min() >= 0 and max(a[0].max(), a[1].max()) < 2**8
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])


def test_kronecker_quadrant_skew():
    # the top bit of i is set with probability C + D = 1 - A - B
    i, _ = kronecker.raw_edges(10, 16, 0.57, 0.19, 0.19,
                               np.random.default_rng(0))
    share = ((i >> 9) & 1).mean()
    assert abs(share - 0.24) < 0.01


def test_kronecker_cleaned_graph_is_simple_symmetric_and_compact():
    src, dst, n = kronecker.generate(KRON, 7)
    assert (src != dst).all()
    keys = src.astype(np.int64) * n + dst
    assert len(np.unique(keys)) == len(keys)
    rev = np.sort(dst.astype(np.int64) * n + src)
    np.testing.assert_array_equal(np.sort(keys), rev)
    assert np.bincount(src, minlength=n).min() >= 1     # no vertex dropped
    assert n < 2**8


def test_protein_copy_matches_the_program_generator():
    from repro.graph.generators import protein_network
    cfg = {"generator": "protein", "nodes": 400, "ba_edges_per_node": 4,
           "noise_edge_share": 0.05, "isolated_share": 0.01}
    src, dst, n = protein.generate(cfg, 5)
    ps, pd = protein_network(400, seed=5)
    assert n == 400
    np.testing.assert_array_equal(src, ps)
    np.testing.assert_array_equal(dst, pd)


def test_load_relabels_by_seed_and_caches(tmp_path):
    s1, d1, n = graphs.load(KRON, 2**31 + 11, tmp_path)
    cached = list(tmp_path.rglob("*.npy"))
    assert len(cached) == 3
    s2, d2, n2 = graphs.load(KRON, 2**31 + 11, tmp_path)   # from the cache
    s3, d3, _ = graphs.load(KRON, 12, None)
    assert n == n2
    np.testing.assert_array_equal(s1, s2)
    np.testing.assert_array_equal(d1, d2)
    assert not np.array_equal(s1, s3)
    # every seed gets the same graph up to labels: same degree multiset
    deg = lambda s: np.sort(np.bincount(s, minlength=n))
    np.testing.assert_array_equal(deg(s1), deg(s3))
    assert len(s1) == len(s3)
