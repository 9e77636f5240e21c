"""The float64 reference against the program's dense reference, the delta
replay, and the bytes function."""
from __future__ import annotations

import numpy as np

from benchmarks.chip import reference, work
from benchmarks.chip.ops import Reservoir
from benchmarks.chip.ops.delta import draw_cycle, draw_deltas


def _graph(n=60, seed=0):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, 300)
    dst = rng.integers(0, n, 300)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    src[src == 0] = 1          # vertex 0 has no out-edge: it dangles
    keys = np.unique(src * n + dst)
    return keys // n, keys % n, n


def test_reference_matches_program_dense_reference_at_tiny_n():
    import jax
    jax.config.update("jax_enable_x64", True)
    try:
        from repro.graph.transition import build_transition_dense
        from repro.pagerank.dense import pagerank_dense_fixed
        src, dst, n = _graph()
        H = build_transition_dense(src, dst, n).astype(np.float64)
        want = np.asarray(pagerank_dense_fixed(H, n_iters=50, d=0.85))
    finally:
        jax.config.update("jax_enable_x64", False)
    got = reference.RefGraph(src, dst, n).solve(d=0.85, n_iters=50)
    # the program builds H in float32 (entries off by up to 2**-24
    # relative) before the float64 iteration
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=0)
    assert abs(got.sum() - 1.0) < 1e-12


def test_reference_tolerance_solve_is_the_fixed_point():
    src, dst, n = _graph(seed=1)
    g = reference.RefGraph(src, dst, n)
    x = g.solve(d=0.85, tol=1e-13)
    v = np.full(n, 1.0 / n)
    np.testing.assert_allclose(g.step(x, v, 0.85), x, atol=1e-13)


def test_apply_deltas_replays_inserts_and_deletes():
    src = np.array([0, 1, 1, 2])
    dst = np.array([1, 0, 2, 1])
    ins = (np.array([0]), np.array([2]), np.array([1]), np.array([2]))
    s, d = reference.apply_deltas(src, dst, 3, [ins])
    assert sorted(zip(s.tolist(), d.tolist())) == [(0, 1), (0, 2), (1, 0),
                                                   (2, 0)]


def test_draw_deltas_sizes_and_validity():
    from benchmarks.chip.graphs import kronecker
    src, dst, n = kronecker.generate(
        {"scale": 8, "edgefactor": 16, "A": 0.57, "B": 0.19, "C": 0.19}, 1)
    ds = draw_deltas(np.random.default_rng(9), src, dst, n, 5, 16, 8)
    keys = set((src.astype(np.int64) * n + dst).tolist())
    assert len(ds) == 5
    seen = set()
    for iu, iv, du, dv in ds:
        assert len(iu) == 16 and len(du) == 8
        assert (iu != iv).all()
        for u, v in zip(iu.tolist(), iv.tolist()):
            assert u * n + v not in keys and (min(u, v), max(u, v)) not in seen
            seen.add((min(u, v), max(u, v)))
        for u, v in zip(du.tolist(), dv.tolist()):
            assert u * n + v in keys


def test_reservoir_keeps_a_seeded_sample_and_the_last():
    r = Reservoir(3, np.random.default_rng(0))
    for i in range(100):
        r.offer(i)
    items = r.items
    assert len(items) == 4 and items[-1] == 99
    r2 = Reservoir(3, np.random.default_rng(0))
    for i in range(100):
        r2.offer(i)
    assert r2.items == items


def test_sweep_bytes_counts_value_index_gather_and_vectors():
    assert work.sweep_bytes(0, 1) == 12
    assert work.sweep_bytes(1, 0) == 16
    assert work.sweep_bytes(646_465, 31_399_760) == 31_399_760 * 12 \
        + 646_465 * 16


def test_delta_stream_draws_one_delta_for_every_seed_up_to_labels():
    from benchmarks.chip import graphs
    cfg = {"name": "k", "generator": "kronecker", "scale": 8,
           "edgefactor": 16, "A": 0.57, "B": 0.19, "C": 0.19,
           "structure_seed": 1}
    traffic = {"tol": 1e-6, "deltas": 2, "inserts": 16, "deletes": 8,
               "draw_seed": 0}
    back = []
    for seed in (3, 2**31 + 7):
        graph = graphs.load(cfg, seed, None)
        cycle = draw_cycle(traffic, graph, seed)
        inv = np.argsort(graphs.permutation(seed, graph[2]))
        assert len(cycle) == 4
        back.append([[inv[a] for a in d] for d in cycle])
    for d1, d2 in zip(*back):
        for a1, a2 in zip(d1, d2):
            np.testing.assert_array_equal(a1, a2)
