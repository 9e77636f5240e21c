"""Serve acceleration: result cache + landmark (hub) PPR index.

Three layers, mirroring the serve-path design:

* :class:`repro.serve.cache.ResultCache` unit behavior — canonical keys
  (precision tiers never alias), LRU eviction, version-mismatch misses,
  and the first-order delta-aware invalidation score.
* End-to-end delta-aware invalidation on a ring graph, where PPR mass
  decays exponentially with hop distance: a delta at node ``u`` must
  drop cached entries seeded NEXT to ``u`` (they re-solve and match the
  post-delta cold solve) while entries seeded far away survive AND
  still match the post-delta cold solve within the parity gate.
* :class:`repro.pagerank.landmarks.LandmarkIndex` properties on every
  backend tier: hub-combination answers are distributions (non-negative,
  sum-to-1) and match the exact batched solver within the fidelity
  gates; exhausting the push budget falls back to the exact solver
  rather than serving an unconverged answer.
* The device-resident serve path on seeded Kronecker graphs: served top-k
  and full vectors against float64 references, the device estimate
  against the host formula it replaced, the padded fallback against the
  unpadded solve, and the one-step ranking against per-column host code.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.graph import generators as gen
from repro.graph.delta import GraphDelta
from repro.pagerank.dynamic import DynamicPageRankEngine
from repro.pagerank.engine import BACKENDS, SHARDED_BACKENDS, PageRankEngine
from repro.pagerank.fidelity import kendall_tau, topk_overlap
from repro.graph import transition as tr
from repro.obs.registry import MetricsRegistry
from repro.pagerank.landmarks import LandmarkIndex
from repro.pagerank.steps import seed_matrix
from repro.serve import PageRankQueryEngine, ResultCache, ServeResilience
from repro.serve.engine import _rank_batch, top_k_proteins

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))
from benchmarks.chip import reference  # noqa: E402
from benchmarks.chip.graphs import kronecker  # noqa: E402


def _kronecker(scale: int, seed: int = 0):
    """The benchmark's Graph500 Kronecker graph (edge factor 16), cleaned
    as Graphalytics does, at a small scale."""
    return kronecker.generate({"scale": scale, "edgefactor": 16, "A": 0.57,
                               "B": 0.19, "C": 0.19}, seed)


def _seed_sets(n: int, count: int, seed: int) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    return [np.sort(rng.choice(n, size=int(rng.integers(1, 5)),
                               replace=False)) for _ in range(count)]


def _exact_ppr(src, dst, n: int, seed_sets, d: float = 0.85) -> np.ndarray:
    """float64 dense solve of x = d·(H·x + v·dangᵀx) + (1−d)·v per column:
    the program's PPR semantics, the dangling leak teleported to v."""
    H = np.asarray(tr.build_transition_dense(src, dst, n,
                                             fix_dangling=False),
                   np.float64)
    dang = (np.bincount(src, minlength=n) == 0).astype(np.float64)
    V = seed_matrix(n, seed_sets).astype(np.float64)
    out = np.empty_like(V)
    for j in range(V.shape[1]):
        A = np.eye(n) - d * (H + np.outer(V[:, j], dang))
        out[:, j] = np.linalg.solve(A, (1.0 - d) * V[:, j])
    return out


# --------------------------------------------------------------------- #
# ResultCache unit behavior
# --------------------------------------------------------------------- #
def test_cache_key_is_canonical_over_seed_order_and_dupes():
    a = ResultCache.key([5, 9, 5], "f32")
    b = ResultCache.key(np.asarray([9, 5]), "f32")
    assert a == b == ("f32", (5, 9))


def test_cache_key_precision_tiers_never_alias():
    seeds = [3, 1, 4]
    keys = {ResultCache.key(seeds, p) for p in ("f32", "bf16", "f16",
                                                "int8")}
    assert len(keys) == 4
    cache = ResultCache(capacity=8)
    cache.put(ResultCache.key(seeds, "f32"), np.ones(4), 0)
    assert cache.get(ResultCache.key(seeds, "bf16"), 0) is None
    assert cache.get(ResultCache.key(seeds, "f32"), 0) is not None


def test_cache_lru_eviction_order_and_counter():
    cache = ResultCache(capacity=2)
    k = [ResultCache.key([i], "f32") for i in range(3)]
    cache.put(k[0], np.zeros(2), 0)
    cache.put(k[1], np.zeros(2), 0)
    assert cache.get(k[0], 0) is not None   # touch k0: k1 becomes LRU
    assert cache.put(k[2], np.zeros(2), 0) == 1
    assert cache.evictions == 1 and len(cache) == 2
    assert k[1] not in cache and k[0] in cache and k[2] in cache


def test_cache_version_mismatch_is_a_miss_and_drops_the_entry():
    cache = ResultCache(capacity=4)
    key = ResultCache.key([7], "f32")
    cache.put(key, np.ones(3), version=0)
    assert cache.get(key, version=1) is None
    assert cache.misses == 1 and key not in cache


def test_cache_invalidate_scores_first_order_impact():
    cache = ResultCache(capacity=4, keep_eps=1e-6)
    hot = np.zeros(10)
    hot[4] = 0.3                            # parks mass on the delta column
    cold = np.zeros(10)
    cold[9] = 0.3                           # mass far from the delta
    cache.put(ResultCache.key([4], "f32"), hot, 0)
    cache.put(ResultCache.key([9], "f32"), cold, 0)
    dropped, kept = cache.invalidate(np.asarray([4]), np.asarray([0.5]),
                                     version=1)
    assert (dropped, kept) == (1, 1)
    assert cache.invalidations == 1
    # the survivor was re-stamped: it hits at the NEW version
    assert cache.get(ResultCache.key([9], "f32"), 1) is not None
    assert cache.get(ResultCache.key([4], "f32"), 1) is None


def test_cache_invalidate_none_cols_flushes_everything():
    cache = ResultCache(capacity=4)
    for i in range(3):
        cache.put(ResultCache.key([i], "f32"), np.zeros(2), 0)
    assert cache.invalidate(None, None, version=1) == (3, 0)
    assert len(cache) == 0 and cache.invalidations == 3


# --------------------------------------------------------------------- #
# Delta-aware invalidation end to end (ring graph: exponential decay)
# --------------------------------------------------------------------- #
def _ring(n: int) -> tuple[np.ndarray, np.ndarray]:
    i = np.arange(n, dtype=np.int32)
    return (np.concatenate([i, i]),
            np.concatenate([(i + 1) % n, (i - 1) % n]).astype(np.int32))


def test_delta_aware_invalidation_on_ring():
    n = 400
    src, dst = _ring(n)
    eng = DynamicPageRankEngine(src, dst, n, backend="ell")
    eng.run_tol(1e-8)
    cache = ResultCache(capacity=16)
    qe = PageRankQueryEngine(eng, n_iters=200, max_batch=4, cache=cache)

    near, far = [199, 201], [10, 50]
    q_near = qe.submit(0, near)
    q_far = qe.submit(1, far)
    qe.flush()
    assert q_near.cache_outcome == "miss" and q_far.cache_outcome == "miss"
    assert len(cache) == 2

    # a chord at node 200: its transition column is rewritten, so the
    # entry seeded right next to it is perturbed; seeds 150+ hops away
    # park ~(d/2)^150 mass there — far below any gate
    qe.push_update(GraphDelta.inserts(np.asarray([200, 210]),
                                      np.asarray([210, 200])))
    q_near2 = qe.submit(2, near)
    q_far2 = qe.submit(3, far)
    qe.flush()
    assert qe.graph_version == 1
    assert q_near2.cache_outcome == "miss", "perturbed entry must re-solve"
    assert q_far2.cache_outcome == "hit", "distant entry must survive"

    # BOTH answers must match a post-delta cold solve of the new graph
    exact = np.asarray(eng.ppr([near, far], n_iters=300))
    key_near = ResultCache.key(near, "f32")
    key_far = ResultCache.key(far, "f32")
    got_near = cache._entries[key_near].ranks
    got_far = cache._entries[key_far].ranks
    assert float(np.abs(got_near - exact[:, 0]).sum()) <= 1e-5
    assert float(np.abs(got_far - exact[:, 1]).sum()) <= 1e-5


def test_cached_top_k_matches_uncached_serve():
    n = 300
    src, dst = gen.protein_network(n, seed=3)
    eng = DynamicPageRankEngine(src, dst, n, backend="ell")
    eng.run_tol(1e-7)
    qe = PageRankQueryEngine(eng, n_iters=100, max_batch=4,
                             cache=ResultCache(capacity=8))
    plain = PageRankQueryEngine(DynamicPageRankEngine(src, dst, n,
                                                      backend="ell"),
                                n_iters=100, max_batch=4)
    seeds = [4, 17, 99]
    a = qe.submit(0, seeds)
    qe.flush()
    b = qe.submit(1, seeds)                 # repeat: served from cache
    qe.flush()
    c = plain.submit(0, seeds)
    plain.flush()
    assert b.cache_outcome == "hit" and c.cache_outcome is None
    np.testing.assert_array_equal(a.result[0], b.result[0])
    np.testing.assert_array_equal(b.result[0], c.result[0])
    np.testing.assert_allclose(b.result[1], c.result[1], atol=1e-6)


# --------------------------------------------------------------------- #
# LandmarkIndex properties across every backend tier
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("backend", BACKENDS)
def test_landmark_answers_are_faithful_distributions(backend):
    if backend in SHARDED_BACKENDS and jax.device_count() < 2:
        pytest.skip("sharded tiers need >1 device")
    n, seed = 200, 7
    src, dst = gen.protein_network(n, seed=seed)
    eng = PageRankEngine(src, dst, n, backend=backend)
    lm = LandmarkIndex(eng, n_hubs=16, tol=1e-7, n_iters=60)
    lm.build(0)
    rng = np.random.default_rng(0)
    seed_sets = [np.sort(rng.choice(n, size=3, replace=False))
                 for _ in range(4)]
    X, info = lm.answer(seed_sets)
    assert X.shape == (n, 4)
    assert float(X.min()) >= 0.0
    np.testing.assert_allclose(X.sum(axis=0), 1.0, atol=1e-5)
    oracle = np.asarray(eng.ppr(seed_sets, n_iters=200))
    for j in range(4):
        assert float(np.abs(X[:, j] - oracle[:, j]).max()) <= 1e-5
        assert topk_overlap(X[:, j], oracle[:, j], k=50) >= 0.99
        assert kendall_tau(X[:, j], oracle[:, j], k=50) >= 0.99


@pytest.mark.parametrize("seed_sets", [[[3, 50], [120]],
                                       [[3, 50], [120], [7, 8, 9]]],
                         ids=["q2", "q3-padded-to-4"])
def test_landmark_exhausted_push_budget_falls_back_to_exact(seed_sets):
    n = 200
    src, dst = gen.protein_network(n, seed=7)
    eng = PageRankEngine(src, dst, n, backend="ell")
    lm = LandmarkIndex(eng, n_hubs=8, tol=1e-9, max_pushes=1, n_iters=100)
    lm.build(0)
    X, info = lm.answer(seed_sets)
    q = len(seed_sets)
    assert info["fallbacks"] == q, "1-push budget cannot converge to 1e-9"
    assert info["paths"] == ["exact"] * q
    assert X.shape == (n, q)
    # the fallback runs at the push's padded width, its pad columns zero;
    # the answer equals the unpadded exact solve
    oracle = np.asarray(eng.ppr(seed_sets, n_iters=100))
    np.testing.assert_allclose(X, oracle, atol=1e-6)
    V = seed_matrix(n, seed_sets)
    padded = np.asarray(eng.ppr_columns(
        np.pad(V, ((0, 0), (0, 4 - q))), n_iters=100))
    np.testing.assert_array_equal(padded[:, q:], 0.0)
    np.testing.assert_allclose(padded[:, :q], oracle, atol=1e-7)


def test_landmark_rebuild_policy_tracks_graph_version():
    n = 200
    src, dst = gen.protein_network(n, seed=1)
    eng = PageRankEngine(src, dst, n, backend="ell")
    lm = LandmarkIndex(eng, n_hubs=8, rebuild_every=4, n_iters=40)
    assert not lm.built
    lm.ensure(0)
    assert lm.built and lm.built_version == 0
    lm.ensure(3)                            # within the rebuild window
    assert lm.built_version == 0
    lm.ensure(4)                            # drift budget exceeded
    assert lm.built_version == 4


@pytest.mark.parametrize("engine_cls,resilient", [
    (PageRankEngine, False), (PageRankEngine, True),
    (DynamicPageRankEngine, True)],
    ids=["static-legacy", "static-resilient", "dynamic-resilient"])
def test_serve_uses_landmarks_when_attached(engine_cls, resilient):
    """A batch of cold users through flush: every served top-10 and every
    cached vector against a float64 dense solve and the benchmark's
    RefGraph, in legacy and in resilient mode."""
    src, dst, n = _kronecker(9, seed=4)
    eng = engine_cls(src, dst, n, backend="ell")
    lm = LandmarkIndex(eng, n_hubs=16, tol=1e-7, n_iters=100)
    qe = PageRankQueryEngine(eng, max_batch=16, cache=ResultCache(),
                             landmarks=lm,
                             resilience=ServeResilience() if resilient
                             else None)
    seed_sets = _seed_sets(n, 16, seed=11)
    qs = [qe.submit(i, s) for i, s in enumerate(seed_sets)]
    qe.flush()
    assert lm.built, "cold solve must go through the landmark index"
    assert lm.last_info["fallbacks"] == 0
    assert float(np.max(lm.last_info["residuals"])) <= 1e-7
    exact = _exact_ppr(src, dst, n, seed_sets)
    V = seed_matrix(n, seed_sets).astype(np.float64)
    ref = reference.RefGraph(src, dst, n).solve(v=V, tol=1e-12)
    # exact and the reference agree to the reference's own tolerance
    np.testing.assert_allclose(ref, exact, atol=1e-10)
    for j, q in enumerate(qs):
        assert q.status == ("fresh" if resilient else "unserved")
        assert q.cache_outcome == "miss"
        got = qe.cache.get(ResultCache.key(q.seeds, "f32"), 0)
        # push residual 1e-7 (L1) bounds the error by 1e-7 / (1 - d),
        # plus float32 rounding of the stored vector
        assert float(np.abs(got - exact[:, j]).sum()) <= 2e-6
        idx, scores = q.result
        assert idx.shape == scores.shape == (10,)
        x = exact[:, j]
        np.testing.assert_allclose(scores, x[idx], atol=1e-6)
        # no vertex of the true top-10 is missed by more than the error
        assert np.sort(x)[-10] - scores.min() <= 1e-6
        np.testing.assert_array_equal(scores, np.sort(scores)[::-1])


@pytest.mark.parametrize("backend", BACKENDS)
def test_device_estimate_matches_host_formula(backend):
    """The device warm start equals, column by column, the host loop it
    replaced (kept here as the reference), on a directed graph with hubs
    and dangling vertices, seeds on each kind and a duplicated seed."""
    if backend in SHARDED_BACKENDS and jax.device_count() < 2:
        pytest.skip("sharded tiers need >1 device")
    rng = np.random.default_rng(3)
    i, j = kronecker.raw_edges(8, 8, 0.57, 0.19, 0.19, rng)
    n = 256
    keep = i != j
    src, dst = i[keep].astype(np.int32), j[keep].astype(np.int32)
    eng = PageRankEngine(src, dst, n, backend=backend)
    lm = LandmarkIndex(eng, n_hubs=8, n_iters=60)
    lm.build(0)
    outdeg = eng._outdeg
    dangling = np.flatnonzero(outdeg == 0)
    hubs = lm.hubs
    tail = np.setdiff1d(np.flatnonzero(outdeg > 0), hubs)
    assert dangling.size and tail.size
    seed_sets = [[int(hubs[0])], [int(tail[0])], [int(dangling[0])],
                 [int(hubs[1]), int(tail[1]), int(dangling[1])],
                 [int(tail[2]), int(tail[2]), int(tail[3])]]
    got = np.asarray(lm.estimate(seed_sets))
    want = _host_estimate(eng, lm, seed_sets)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)


def _host_estimate(eng, lm, seed_sets) -> np.ndarray:
    """The former host formula of ``LandmarkIndex.estimate``: per seed,
    a hub seed adds its stored column; any other seed adds ``e_s`` and,
    unless dangling, one step: ``d/outdeg(s)`` times each out-neighbor's
    stored column (a hub) or ``e_t`` (any other vertex)."""
    n, d = eng.n, eng.d
    Y = np.asarray(lm._Y, np.float32)
    pos = np.full(n, -1, np.int64)
    pos[lm.hubs] = np.arange(len(lm.hubs))
    X0 = np.zeros((n, len(seed_sets)), np.float32)
    for q, seeds in enumerate(seed_sets):
        idx = np.asarray(seeds, np.int64).ravel()
        w = 1.0 / idx.size
        y = X0[:, q]
        for s in idx:
            if pos[s] >= 0:
                y += w * Y[:, pos[s]]
                continue
            y[s] += w
            if eng._outdeg[s] == 0:
                continue
            nbrs = eng._keys[(eng._keys // n) == s] % n
            ws = w * d / eng._outdeg[s]
            hub_n, tail_n = nbrs[pos[nbrs] >= 0], nbrs[pos[nbrs] < 0]
            if hub_n.size:
                y += ws * Y[:, pos[hub_n]].sum(axis=1)
            np.add.at(y, tail_n, ws)
        X0[:, q] = np.maximum(y, 0.0) / max(float(y.sum()), 1e-30)
    return X0


def test_rank_batch_matches_per_column_host():
    """The one device step that ranks a served batch: each column's top-k
    ids and scores as a per-column host sort gives them, and its health
    flag as a per-column host check gives it."""
    rng = np.random.default_rng(0)
    n, q, k = 300, 6, 10
    P = rng.random((n, q)).astype(np.float32)
    P /= P.sum(axis=0, keepdims=True)
    P[5, 1] = np.nan                        # poisoned
    P[7, 2] = -1e-3                         # negative
    P[:, 3] *= 1.01                         # mass off by 1e-2
    P[:, 4] *= 1.0005                       # mass off by 5e-4: healthy
    idx, scores, ok = (np.asarray(a) for a in _rank_batch(
        jnp.asarray(P), 1e-3, k=k))
    assert idx.shape == scores.shape == (q, k) and ok.shape == (q,)
    for j in range(q):
        col = P[:, j]
        host_ok = bool(np.isfinite(col).all() and (col >= -1e-6).all()
                       and abs(float(col.sum()) - 1.0) <= 1e-3)
        assert bool(ok[j]) == host_ok, j
        if not np.isnan(col).any():
            want = np.argsort(-col, kind="stable")[:k]
            np.testing.assert_array_equal(idx[j], want)
            np.testing.assert_array_equal(scores[j], col[want])
            vi, vs = top_k_proteins(col, k)
            np.testing.assert_array_equal(np.asarray(vi), want)
    assert ok.tolist() == [True, False, False, False, True, True]


def test_compiled_fallback_compiles_nothing_and_counts_sweeps():
    """After one answer and ``compile_fallback``, an answer whose every
    column falls back compiles nothing; the counters hold the push's
    sweeps and the fallback's fixed sweeps, per column without padding."""
    src, dst, n = _kronecker(9, seed=1)
    reg = MetricsRegistry()
    eng = PageRankEngine(src, dst, n, backend="ell", metrics=reg)
    lm = LandmarkIndex(eng, n_hubs=16, max_pushes=8, n_iters=30)
    lm.build(0)
    lm.answer(_seed_sets(n, 3, seed=1))
    lm.compile_fallback(3)
    c0 = dict(reg.as_dict()["counters"])
    X, info = lm.answer(_seed_sets(n, 3, seed=2), tol=1e-30)
    c1 = reg.as_dict()["counters"]
    assert info["fallbacks"] == 3 and info["sweeps"] == 8
    assert c1["compiles"] == c0["compiles"], "the window compiled"
    assert c1["landmarks.fallbacks"] - c0.get("landmarks.fallbacks", 0) == 3
    assert c1["ppr.sweeps"] - c0["ppr.sweeps"] == 8 + 30
    assert c1["ppr.column_sweeps"] - c0["ppr.column_sweeps"] == 3 * 8 + 3 * 30
    assert X.shape == (n, 3)
    np.testing.assert_allclose(np.asarray(X).sum(axis=0), 1.0, atol=1e-5)


def test_serve_spans_nest_under_the_flush():
    src, dst, n = _kronecker(9, seed=2)
    reg = MetricsRegistry()
    eng = PageRankEngine(src, dst, n, backend="ell", metrics=reg)
    qe = PageRankQueryEngine(eng, max_batch=4, cache=ResultCache(),
                             landmarks=LandmarkIndex(eng, n_hubs=8),
                             resilience=ServeResilience())
    for i, s in enumerate(_seed_sets(n, 4, seed=3)):
        qe.submit(i, s)
    parents = {e["name"]: e["parent"] for e in reg.events
               if e["kind"] == "span"}
    assert parents["serve"] is None
    assert parents["landmarks.answer"] == "serve"
    assert parents["landmarks.estimate"] == "landmarks.answer"
    assert parents["landmarks.push"] == "landmarks.answer"
    assert parents["serve.topk"] == "serve"
