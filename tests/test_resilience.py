"""Resilience layer end-to-end: every injected fault class — malformed
delta, NaN/Inf layout, diverging solve, backend-step exception — must be
*detected* (structured status, not a crash) and *recovered* (the serve path
returns finite sum-to-1 ranks tagged with the right staleness/degradation
status, and parity with a clean engine is restored after the next
successful refresh).

Layered like the subsystem itself:

* watchdog / ``SolveInfo`` semantics on the engine's tolerance loops;
* ``validate_delta`` quarantine / reject / clip policies;
* snapshot-restore and the ``ResilientRefresher`` escalation ladder;
* the resilient ``PageRankQueryEngine`` serve path (fresh/stale/degraded);
* a noisy-stream regression: valid ticks interleaved with every delta
  fault class, served continuously without a single raise.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.graph import generators as gen
from repro.graph.delta import (EdgeStream, GraphDelta, apply_delta,
                               edge_keys)
from repro.graph.validate import (DeadLetterQueue, DeltaRejected,
                                  ValidationPolicy, validate_delta)
from repro.pagerank import (ConvergenceError, DynamicPageRankEngine,
                            FaultInjector, PageRankEngine, RankStore,
                            ResilientRefresher, RetryPolicy, SolveResult)
from repro.pagerank.engine import SHARDED_BACKENDS
from repro.pagerank.resilience import (ppr_health, ranks_healthy, raw_delta)
from repro.serve import PageRankQueryEngine, ServeResilience

DYN_BACKENDS = ["dense", "ell", "pallas_dense"]   # patchable layouts


def _l1(a, b):
    return float(jnp.sum(jnp.abs(jnp.asarray(a) - jnp.asarray(b))))


def _scratch_ranks(src, dst, n, delta=None):
    if delta is not None:
        src, dst = apply_delta(src, dst, delta, n)
    return PageRankEngine(src, dst, n, backend="dense").run_tol(
        1e-8, max_iters=1000)[0]


def _absent_pairs(src, dst, n, k, seed=0):
    """k undirected pairs NOT in the edge set — inserts guaranteed to be
    effective, so the engine really solves (no silent no-op deltas)."""
    have = set(edge_keys(src, dst, n).tolist())
    rng = np.random.default_rng(seed)
    out = []
    while len(out) < k:
        u, v = (int(x) for x in rng.integers(0, n, size=2))
        if u != v and u * n + v not in have and (u, v) not in out:
            out.append((u, v))
    a = np.array(out, np.int64)
    return a[:, 0], a[:, 1]


@pytest.fixture(scope="module")
def net():
    n = 64
    src, dst = gen.protein_network(n, seed=5)
    return n, src, dst


# --------------------------------------------------------------------------- #
# SolveInfo / SolveResult semantics                                           #
# --------------------------------------------------------------------------- #
def test_solveresult_is_a_plain_tuple_with_info(net):
    n, src, dst = net
    eng = PageRankEngine(src, dst, n, backend="dense")
    res = eng.run_tol(tol=1e-6, max_iters=500)
    # every pre-existing call-site shape still works
    pr, iters, residual = res
    assert res[0] is pr and int(res[1]) == int(iters)
    assert isinstance(res, SolveResult) and len(res) == 3
    # and the new structured status rides along
    assert res.info.converged and not res.info.failed
    assert res.info is eng.last_solve_info
    assert res.info.iters == int(iters)
    assert res.info.residual == pytest.approx(float(residual))
    assert float(jnp.sum(pr)) == pytest.approx(1.0, abs=1e-4)


def test_exhausted_solve_warns_once_and_flags(net):
    """Silent max_iters exhaustion is gone: the first non-converged solve
    warns (once per engine), every one records ``info.exhausted``."""
    n, src, dst = net
    eng = PageRankEngine(src, dst, n, backend="dense")
    with pytest.warns(RuntimeWarning, match="did not converge"):
        res = eng.run_tol(tol=1e-30, max_iters=5)
    assert res.info.exhausted and not res.info.failed
    assert res.info.iters == 5
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        res2 = eng.run_tol(tol=1e-30, max_iters=6)
    assert not any("did not converge" in str(w.message) for w in rec)
    assert res2.info.exhausted


def test_raise_on_fail_raises_convergence_error(net):
    n, src, dst = net
    eng = PageRankEngine(src, dst, n, backend="dense")
    with pytest.raises(ConvergenceError, match="max_iters=5 exhausted"):
        eng.run_tol(tol=1e-30, max_iters=5, raise_on_fail=True)
    assert eng.last_solve_info.exhausted


def test_watchdog_disarmed_matches_armed(net):
    """``watchdog=False`` compiles the pre-resilience loop: identical
    ranks, iterations, and residual on a healthy graph."""
    n, src, dst = net
    eng = PageRankEngine(src, dst, n, backend="ell")
    pr_w, it_w, res_w = eng.run_tol(tol=1e-7, max_iters=500, watchdog=True)
    pr_o, it_o, res_o = eng.run_tol(tol=1e-7, max_iters=500, watchdog=False)
    assert int(it_w) == int(it_o)
    assert float(res_w) == pytest.approx(float(res_o), rel=1e-6)
    np.testing.assert_array_equal(np.asarray(pr_w), np.asarray(pr_o))


@pytest.mark.parametrize("backend", SHARDED_BACKENDS)
def test_sharded_backends_report_solve_info(net, backend, multi_device):
    n, src, dst = net
    eng = PageRankEngine(src, dst, n, backend=backend)
    res = eng.run_tol(tol=1e-6, max_iters=500)
    assert res.info.converged and res.info.iters == int(res[1])
    assert ranks_healthy(res[0])


# --------------------------------------------------------------------------- #
# watchdog: NaN/Inf layouts and diverging operators abort early               #
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("backend", DYN_BACKENDS)
def test_nan_layout_flags_nonfinite_and_aborts_early(net, backend):
    n, src, dst = net
    dyn = DynamicPageRankEngine(src, dst, n, backend=backend)
    FaultInjector(seed=3).corrupt_layout(dyn, kind="nan")
    res = dyn.run_tol(tol=1e-7, max_iters=500)
    assert res.info.nonfinite and res.info.failed
    assert res.info.iters < 50                  # aborted, not 500 spins
    assert not ranks_healthy(res[0])


@pytest.mark.parametrize("backend", DYN_BACKENDS)
def test_scaled_layout_flags_diverged_and_aborts_early(net, backend):
    """A uniformly scaled operator (spectral radius >> 1) trips the
    residual-growth counter — ``diverged``, not ``nonfinite``."""
    n, src, dst = net
    dyn = DynamicPageRankEngine(src, dst, n, backend=backend)
    FaultInjector(seed=3).corrupt_layout(dyn, kind="scale")
    res = dyn.run_tol(tol=1e-7, max_iters=500)
    assert res.info.diverged and not res.info.nonfinite
    assert res.info.iters < 50


def test_inf_layout_on_sharded_backend_flags_failed(net, multi_device):
    n, src, dst = net
    eng = PageRankEngine(src, dst, n, backend="dense_sharded")
    FaultInjector(seed=1).corrupt_layout(eng, kind="inf")
    res = eng.run_tol(tol=1e-7, max_iters=500)
    assert res.info.failed
    assert res.info.iters < 50


def test_push_loop_watchdog_flags_corrupt_update(net):
    """The Gauss–Southwell push refresh carries the same watchdog: a
    corrupted layout surfaces as ``UpdateInfo.diverged/nonfinite`` instead
    of a silently poisoned rank vector."""
    n, src, dst = net
    dyn = DynamicPageRankEngine(src, dst, n, backend="ell")
    dyn.run_tol(1e-7, max_iters=500)
    FaultInjector(seed=4).corrupt_layout(dyn, kind="nan")
    (u,), (v,) = _absent_pairs(src, dst, n, 1, seed=4)
    _, info = dyn.update(GraphDelta.inserts([u], [v]), strategy="push")
    assert info.strategy == "push"
    assert (info.nonfinite or info.diverged) and not info.healthy


# --------------------------------------------------------------------------- #
# validate_delta: quarantine / reject / clip                                  #
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("kind,reason", [
    ("out_of_range", "out_of_range"),
    ("negative", "negative_id"),
    ("self_loop", "self_loop"),
    ("nan", "nonfinite"),
    ("dup_flood", "duplicate_flood"),
])
def test_quarantine_catches_every_delta_fault_class(kind, reason):
    n = 64
    inj = FaultInjector(seed=7)
    bad = inj.corrupt_delta(n, kind=kind)
    result = validate_delta(bad, n)
    assert reason in result.reasons
    assert result.n_dropped > 0 and not result.clean
    assert sum(let.n_edges for let in result.dead_letters) == result.n_dropped
    # whatever survived is safe for the engine
    if result.delta is not None:
        c = result.delta.canonical(n)
        assert (np.asarray(c.insert_src) != np.asarray(c.insert_dst)).all()


def test_quarantine_oversized_batch_truncates():
    n = 64
    inj = FaultInjector(seed=8)
    bad = inj.corrupt_delta(n, kind="oversized", size=4)   # 256 edges
    policy = ValidationPolicy(max_batch_edges=64)
    result = validate_delta(bad, n, policy)
    assert "oversized_batch" in result.reasons
    assert result.n_accepted == 64


def test_reject_policy_raises_structured_error():
    n = 64
    bad = FaultInjector(seed=9).corrupt_delta(n, kind="out_of_range")
    with pytest.raises(DeltaRejected, match="out_of_range") as exc:
        validate_delta(bad, n, ValidationPolicy(on_invalid="reject"))
    assert exc.value.n_bad > 0 and "out_of_range" in exc.value.reasons


def test_clip_policy_rescues_range_errors():
    n = 64
    result = validate_delta(raw_delta([5, n + 7], [n + 3, 2]), n,
                            ValidationPolicy(on_invalid="clip"))
    assert result.delta is not None and result.n_accepted == 2
    assert "out_of_range_clipped" in result.reasons
    c = result.delta
    assert np.asarray(c.insert_src).max() < n
    assert np.asarray(c.insert_dst).max() < n


def test_valid_delta_passes_clean():
    n = 64
    result = validate_delta(GraphDelta.inserts([1, 2], [3, 4]), n)
    assert result.clean and result.n_accepted == 2
    assert result.reasons == () and result.delta is not None


def test_dead_letter_queue_is_bounded_audit_trail():
    q = DeadLetterQueue(maxlen=4)
    n = 64
    inj = FaultInjector(seed=11)
    for _ in range(6):
        q.extend(validate_delta(inj.corrupt_delta(n, "self_loop"),
                                n).dead_letters)
    assert len(q) == 4 and q.total_seen == 6
    assert set(q.counts()) == {"self_loop"}


# --------------------------------------------------------------------------- #
# snapshots, retries, and the escalation ladder                               #
# --------------------------------------------------------------------------- #
def test_retry_policy_backoff_schedule():
    delays = list(RetryPolicy(max_retries=3, base_delay_s=0.5).delays())
    assert delays == [0.0, 0.5, 1.0, 2.0]
    assert list(RetryPolicy(max_retries=0).delays()) == [0.0]


def test_rank_store_is_bounded_and_versioned(net):
    n, src, dst = net
    dyn = DynamicPageRankEngine(src, dst, n, backend="dense")
    dyn.run_tol(1e-7, max_iters=500)
    store = RankStore(maxlen=2)
    for _ in range(5):
        store.record(dyn)
    assert len(store) == 2 and store.latest().version == 5
    assert ranks_healthy(store.latest().ranks)


def test_snapshot_restore_roundtrip(net):
    """restore() rebuilds host bookkeeping AND device layouts from edge
    keys alone — after an update the engine equals its pre-update self."""
    n, src, dst = net
    dyn = DynamicPageRankEngine(src, dst, n, backend="ell")
    dyn.run_tol(1e-7, max_iters=500)
    snap = dyn.snapshot()
    before = [np.asarray(o) for o in jax.tree.leaves(dyn.operands)]
    edges_before = dyn.n_edges
    iu, iv = _absent_pairs(src, dst, n, 2, seed=5)
    dyn.update(GraphDelta.inserts(iu, iv))
    dyn.restore(snap)
    assert dyn.n_edges == edges_before
    for a, b in zip(before, jax.tree.leaves(dyn.operands)):
        np.testing.assert_array_equal(a, np.asarray(b))
    assert _l1(dyn.ranks, snap.ranks) == 0.0


@pytest.mark.parametrize("backend", DYN_BACKENDS)
def test_refresher_recovers_from_corrupt_layout(net, backend):
    """Ladder rung 2: update returns but the solve is poisoned → rebuild
    from host keys, warm-started from the last snapshot → 'recovered',
    delta applied, parity with the from-scratch oracle."""
    n, src, dst = net
    dyn = DynamicPageRankEngine(src, dst, n, backend=backend)
    dyn.run_tol(1e-7, max_iters=500)
    ref = ResilientRefresher()
    assert ref.baseline(dyn) is not None
    FaultInjector(seed=5).corrupt_layout(dyn, kind="nan")
    (u,), (v,) = _absent_pairs(src, dst, n, 1, seed=6)
    delta = GraphDelta.inserts([u], [v])
    outcome = ref.refresh(dyn, delta, tol=1e-7, max_iters=500)
    assert outcome.status == "recovered" and outcome.delta_applied
    assert ranks_healthy(dyn.ranks)
    assert _l1(dyn.ranks, _scratch_ranks(src, dst, n, delta)) <= 1e-5


def test_refresher_survives_update_exceptions(net):
    """Ladder rung 1: raised updates are retried with backoff; when every
    attempt raises the engine is untouched and the outcome is 'failed'."""
    n, src, dst = net
    dyn = DynamicPageRankEngine(src, dst, n, backend="ell")
    dyn.run_tol(1e-7, max_iters=500)
    pr_before = np.asarray(dyn.ranks).copy()
    ref = ResilientRefresher(retry=RetryPolicy(max_retries=2))
    ref.baseline(dyn)
    inj = FaultInjector(seed=6)
    (u,), (v,) = _absent_pairs(src, dst, n, 1, seed=7)
    delta = GraphDelta.inserts([u], [v])
    # 5 injected raises > 3 attempts: first refresh fails cleanly
    inj.fail_next_updates(dyn, times=5)
    outcome = ref.refresh(dyn, delta, tol=1e-7, max_iters=500)
    assert outcome.status == "failed" and not outcome.delta_applied
    assert outcome.attempts == 3 and "injected" in outcome.error
    np.testing.assert_array_equal(pr_before, np.asarray(dyn.ranks))
    # the next refresh burns the remaining 2 faults in its retries and lands
    outcome2 = ref.refresh(dyn, delta, tol=1e-7, max_iters=500)
    assert outcome2.status == "ok" and outcome2.attempts == 3
    assert _l1(dyn.ranks, _scratch_ranks(src, dst, n, delta)) <= 1e-5


def test_refresher_restores_snapshot_when_rebuild_fails(net):
    """Ladder rung 3: rebuild raising too rolls the engine back to the
    last-known-good snapshot; the delta is NOT applied."""
    n, src, dst = net
    dyn = DynamicPageRankEngine(src, dst, n, backend="ell")
    dyn.run_tol(1e-7, max_iters=500)
    ref = ResilientRefresher()
    snap = ref.baseline(dyn)
    FaultInjector(seed=12).corrupt_layout(dyn, kind="nan")
    dyn.rebuild_and_solve = lambda *a, **kw: (_ for _ in ()).throw(
        RuntimeError("injected rebuild failure"))
    (u,), (v,) = _absent_pairs(src, dst, n, 1, seed=8)
    outcome = ref.refresh(dyn, GraphDelta.inserts([u], [v]),
                          tol=1e-7, max_iters=500)
    assert outcome.status == "restored" and not outcome.delta_applied
    assert "injected rebuild" in outcome.error
    assert dyn.n_edges == len(snap.keys)
    assert _l1(dyn.ranks, snap.ranks) == 0.0 and ranks_healthy(dyn.ranks)


# --------------------------------------------------------------------------- #
# the resilient serve path                                                    #
# --------------------------------------------------------------------------- #
def _resilient_qe(src, dst, n, **kw):
    dyn = DynamicPageRankEngine(src, dst, n, backend="ell")
    dyn.run_tol(1e-7, max_iters=500)
    return dyn, PageRankQueryEngine(dyn, n_iters=50, max_batch=8,
                                    resilience=ServeResilience(**kw))


def _seed_sets(n, q=3, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.choice(n, size=2, replace=False) for _ in range(q)]


def test_serve_quarantines_bad_delta_and_stays_fresh(net):
    n, src, dst = net
    dyn, qe = _resilient_qe(src, dst, n)
    inj = FaultInjector(seed=20)
    res = qe.push_update(inj.corrupt_delta(n, kind="out_of_range"))
    assert res.delta is None and len(qe.dead_letters) > 0
    assert "out_of_range" in qe.dead_letters.counts()
    (u,), (v,) = _absent_pairs(src, dst, n, 1, seed=9)
    good = GraphDelta.inserts([u], [v])
    assert qe.push_update(good).clean
    queries = [qe.submit(uid, s, top_k=5)
               for uid, s in enumerate(_seed_sets(n))]
    qe.flush()
    assert all(q.status == "fresh" for q in queries)
    assert qe.last_refresh_outcome.status == "ok"
    # parity: the quarantined delta left no trace; only the good one landed
    assert _l1(dyn.ranks, _scratch_ranks(src, dst, n, good)) <= 1e-5


def test_serve_tags_stale_on_failed_refresh_then_recovers(net):
    n, src, dst = net
    dyn, qe = _resilient_qe(src, dst, n)
    inj = FaultInjector(seed=21)
    (u,), (v,) = _absent_pairs(src, dst, n, 1, seed=10)
    delta = GraphDelta.inserts([u], [v])
    qe.push_update(delta)
    inj.fail_next_updates(dyn, times=5)       # > 3 attempts: refresh fails
    queries = [qe.submit(uid, s, top_k=5)
               for uid, s in enumerate(_seed_sets(n, seed=1))]
    served = qe.flush()                        # never raises
    assert qe.last_refresh_outcome.status == "failed"
    assert all(q.status == "stale" for q in served)
    for q in served:
        assert np.isfinite(q.result[1]).all()
    # delta re-queued: the next flush retries, succeeds, serves fresh
    q2 = qe.submit(99, _seed_sets(n, seed=2)[0], top_k=5)
    qe.flush()
    assert qe.last_refresh_outcome.status == "ok" and q2.status == "fresh"
    assert _l1(dyn.ranks, _scratch_ranks(src, dst, n, delta)) <= 1e-5


def test_serve_recovers_poisoned_batch_in_one_flush(net):
    """Layout corruption between refreshes: the health-checked flush spots
    the poisoned PPR batch, runs one recovery, re-serves — queries come
    back 'fresh' and match a clean engine."""
    n, src, dst = net
    dyn, qe = _resilient_qe(src, dst, n)
    want = PageRankQueryEngine(
        PageRankEngine(src, dst, n, backend="ell"),
        n_iters=50).query_batch(_seed_sets(n, seed=3), top_k=5)
    FaultInjector(seed=22).corrupt_layout(dyn, kind="nan")
    queries = [qe.submit(uid, s, top_k=5)
               for uid, s in enumerate(_seed_sets(n, seed=3))]
    served = qe.flush()
    assert all(q.status == "fresh" for q in served)
    for q, (widx, wscores) in zip(queries, want):
        np.testing.assert_array_equal(q.result[0], widx)
        np.testing.assert_allclose(q.result[1], wscores, rtol=1e-4,
                                   atol=1e-6)


def test_serve_degrades_to_global_ranks_when_unrecoverable(net):
    """A static engine can't rebuild: the flush falls back to last-known-
    good global ranks (uniform here — no snapshot exists), tags the batch
    'degraded', and still never raises."""
    n, src, dst = net
    eng = PageRankEngine(src, dst, n, backend="ell")
    qe = PageRankQueryEngine(eng, n_iters=50, max_batch=8,
                             resilience=ServeResilience())
    FaultInjector(seed=23).corrupt_layout(eng, kind="nan")
    queries = [qe.submit(uid, s, top_k=5)
               for uid, s in enumerate(_seed_sets(n, seed=4))]
    served = qe.flush()
    assert all(q.status == "degraded" for q in served)
    for q in served:
        assert np.isfinite(q.result[1]).all() and (q.result[1] >= 0).all()


def test_serve_reject_policy_still_raises(net):
    n, src, dst = net
    _, qe = _resilient_qe(src, dst, n,
                          validation=ValidationPolicy(on_invalid="reject"))
    with pytest.raises(DeltaRejected):
        qe.push_update(FaultInjector(seed=24).corrupt_delta(n, "negative"))


# --------------------------------------------------------------------------- #
# the noisy-stream regression (every fault class, one live session)           #
# --------------------------------------------------------------------------- #
def test_noisy_stream_serves_through_every_fault_class(net):
    """EdgeStream ticks interleaved with one fault of each class: the
    resilient serving path never raises, quarantines all malformed deltas,
    and ends in parity with a clean engine on the edges that were actually
    accepted."""
    n, src, dst = net
    dyn, qe = _resilient_qe(src, dst, n)
    stream = EdgeStream(n, m_edges=3, seed=4, insert_per_step=3,
                        delete_per_step=0)
    cur = stream.base()
    dyn2 = DynamicPageRankEngine(cur[0], cur[1], n, backend="ell")
    dyn2.run_tol(1e-7, max_iters=500)
    qe2 = PageRankQueryEngine(dyn2, n_iters=50, max_batch=8,
                              resilience=ServeResilience())
    inj = FaultInjector(seed=25)
    faults = ["out_of_range", "negative", "self_loop", "nan", "dup_flood"]
    for step, kind in enumerate(faults):
        res = qe2.push_update(inj.corrupt_delta(n, kind=kind))
        assert not res.clean                               # quarantined...
        if res.delta is not None:                          # ...but any valid
            cur = apply_delta(cur[0], cur[1], res.delta, n)   # remainder lands
        good = stream.step()
        qe2.push_update(good)                              # accepted
        cur = apply_delta(cur[0], cur[1], good, n)
        if kind == "nan":
            inj.corrupt_layout(dyn2, kind="scale")         # mid-stream fault
        if kind == "self_loop":
            inj.fail_next_updates(dyn2, times=1)           # transient raise
        for q in qe2.query_batch(_seed_sets(n, seed=step), top_k=5):
            assert np.isfinite(q[1]).all()
    assert qe2.dead_letters.total_seen >= len(faults)
    assert set(qe2.dead_letters.counts()) >= {
        "out_of_range", "negative_id", "self_loop", "nonfinite",
        "duplicate_flood"}
    # every accepted delta is in the graph; parity with a clean engine
    assert ranks_healthy(dyn2.ranks)
    assert _l1(dyn2.ranks, _scratch_ranks(cur[0], cur[1], n)) <= 1e-5
    assert bool(ppr_health(
        dyn2.ppr(_seed_sets(n, seed=99), n_iters=50)).all())
