"""Dynamic-graph subsystem: GraphDelta/EdgeStream semantics, in-place
layout patches, push/warm-start/rebuild refresh strategies, x0 threading
through every run_tol backend, and the serve-layer refresh path.

The load-bearing oracle throughout: an incremental update must match a
from-scratch engine built on the post-delta edge list (``apply_delta``)
to ≤1e-5 L1 — the acceptance bound for the whole subsystem.
"""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.graph import generators as gen
from repro.graph.delta import (EdgeStream, GraphDelta, apply_delta, compose,
                               edge_keys)
from repro.pagerank import DynamicPageRankEngine, PageRankEngine

DYN_BACKENDS = ["dense", "ell", "pallas_dense", "bsr"]  # patchable layouts
ALL_LOCAL = ["dense", "ell", "bsr", "pallas_dense"]
SHARDED = ["dense_sharded", "ell_sharded"]        # patchable on the mesh


def _scratch_ranks(src, dst, n, delta=None):
    if delta is not None:
        src, dst = apply_delta(src, dst, delta, n)
    return PageRankEngine(src, dst, n, backend="dense").run_tol(
        1e-8, max_iters=500)[0]


def _l1(a, b):
    return float(jnp.sum(jnp.abs(jnp.asarray(a) - jnp.asarray(b))))


def _absent_pairs(src, dst, n, k, seed=0):
    """k undirected pairs NOT in the edge set — inserts that are
    guaranteed to be effective (not silent no-ops)."""
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
# GraphDelta / apply_delta / EdgeStream                                       #
# --------------------------------------------------------------------------- #
def test_graphdelta_canonicalizes():
    d = GraphDelta.inserts([1, 1, 2], [2, 2, 1]).canonical(10)
    got = set(zip(d.insert_src.tolist(), d.insert_dst.tolist()))
    # duplicates collapse, both directions present
    assert got == {(1, 2), (2, 1)}
    assert d.n_delete == 0
    with pytest.raises(ValueError):
        GraphDelta.inserts([0], [10]).canonical(10)


def test_graphdelta_rejects_malformed_at_construction():
    """Self-loops, negative ids, NaN payloads, and length mismatches used
    to sail through construction and blow up (or not) deep inside layout
    patching — now they fail fast with a clear error."""
    with pytest.raises(ValueError, match="self-loop"):
        GraphDelta.inserts([3], [3])
    with pytest.raises(ValueError, match="negative"):
        GraphDelta.inserts([-1], [2])
    with pytest.raises(ValueError, match="non-finite"):
        GraphDelta.inserts([np.nan], [2.0])
    with pytest.raises(ValueError, match="non-integral"):
        GraphDelta.inserts([1.5], [2.0])
    with pytest.raises(ValueError, match="mismatch"):
        GraphDelta.inserts([1, 2], [3])
    # integral floats are accepted and normalized to int32
    d = GraphDelta.inserts([1.0], [2.0])
    assert d.insert_src.dtype == np.int32


def test_graphdelta_directed_keeps_orientation():
    d = GraphDelta.inserts([1, 1], [2, 2]).canonical(10, symmetric=False)
    assert set(zip(d.insert_src.tolist(), d.insert_dst.tolist())) == {(1, 2)}


def test_apply_delta_set_semantics(net):
    n, src, dst = net
    keys = edge_keys(src, dst, n)
    # inserting an existing edge and deleting a missing one are no-ops
    existing = (int(src[0]), int(dst[0]))
    missing = next((u, v) for u in range(n) for v in range(n)
                   if u != v and u * n + v not in set(keys.tolist()))
    s2, d2 = apply_delta(src, dst, GraphDelta.inserts(*existing), n)
    np.testing.assert_array_equal(edge_keys(s2, d2, n), keys)
    s2, d2 = apply_delta(src, dst, GraphDelta.deletes(*missing), n)
    np.testing.assert_array_equal(edge_keys(s2, d2, n), keys)
    # an edge named on both sides survives (deletes apply first)
    both = GraphDelta(np.array([existing[0]]), np.array([existing[1]]),
                      np.array([existing[0]]), np.array([existing[1]]))
    s2, d2 = apply_delta(src, dst, both, n)
    np.testing.assert_array_equal(edge_keys(s2, d2, n), keys)


def test_compose_matches_sequential_application():
    """compose(ds) must equal applying the deltas in order — including
    conflicts (delete-of-queued-insert, re-insert-of-queued-delete)."""
    n = 40
    src, dst = gen.erdos_renyi(n, avg_degree=4.0, seed=11)
    ds = [
        GraphDelta.inserts([1, 2], [30, 31], timestamp=1.0),
        GraphDelta.deletes([1, int(src[0])], [30, int(dst[0])],
                           timestamp=2.0),      # kills a queued insert
        GraphDelta.inserts([1], [30], timestamp=3.0),   # ...re-added
    ]
    seq = (src, dst)
    for d in ds:
        seq = apply_delta(seq[0], seq[1], d, n)
    merged = compose(ds, n)
    assert merged.timestamp == 3.0
    got = apply_delta(src, dst, merged, n)
    np.testing.assert_array_equal(edge_keys(*got, n), edge_keys(*seq, n))


def test_edge_stream_evolves_consistently():
    n = 100
    stream = EdgeStream(n, m_edges=3, seed=1, insert_per_step=5,
                        delete_per_step=3, dt=0.5)
    cur = stream.base()
    last_t = 0.0
    for _, delta in zip(range(4), stream):
        assert delta.timestamp > last_t
        last_t = delta.timestamp
        assert np.all(delta.insert_src != delta.insert_dst)
        # canonical: every directed insert has its reverse
        ik = set(edge_keys(delta.insert_src, delta.insert_dst, n).tolist())
        assert all((k % n) * n + k // n in ik for k in ik)
        cur = apply_delta(cur[0], cur[1], delta, n)
    # stream's internal live-edge count tracks the applied edge list
    assert len(edge_keys(cur[0], cur[1], n)) == 2 * stream.n_live_edges


# --------------------------------------------------------------------------- #
# x0 threading through run_tol — all six backends                             #
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("backend", ALL_LOCAL)
def test_run_tol_x0_warm_start(net, backend):
    n, src, dst = net
    eng = PageRankEngine(src, dst, n, backend=backend)
    pr, cold, res = eng.run_tol(tol=1e-7, max_iters=500)
    pr2, warm, res2 = eng.run_tol(tol=1e-7, max_iters=500, x0=pr)
    assert int(cold) > 2
    assert int(warm) <= 2            # restarting at the fixed point
    assert float(res2) <= 1e-7
    assert _l1(pr, pr2) < 1e-5


@pytest.mark.parametrize("backend", ["dense_sharded", "ell_sharded"])
def test_run_tol_x0_warm_start_sharded(net, backend, multi_device):
    n, src, dst = net
    eng = PageRankEngine(src, dst, n, backend=backend)
    pr, cold, _ = eng.run_tol(tol=1e-7, max_iters=500)
    pr2, warm, res2 = eng.run_tol(tol=1e-7, max_iters=500, x0=pr)
    assert int(warm) <= 2 < int(cold)
    assert float(res2) <= 1e-7


# --------------------------------------------------------------------------- #
# DynamicPageRankEngine: strategies, parity, invariants                       #
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("backend", DYN_BACKENDS)
@pytest.mark.parametrize("strategy", ["auto", "push", "warm", "rebuild"])
def test_update_matches_from_scratch(net, backend, strategy):
    n, src, dst = net
    dyn = DynamicPageRankEngine(src, dst, n, backend=backend)
    dyn.run_tol(1e-7, max_iters=500)
    iu, iv = _absent_pairs(src, dst, n, 3, seed=1)
    delta = GraphDelta(iu, iv, np.asarray(src[:2]), np.asarray(dst[:2]))
    pr, info = dyn.update(delta, strategy=strategy)
    assert info.strategy == (strategy if strategy != "auto" else "push")
    pr = np.asarray(pr)
    assert (pr >= 0).all()
    assert pr.sum() == pytest.approx(1.0, abs=1e-4)
    assert _l1(pr, _scratch_ranks(src, dst, n, delta)) <= 1e-5


@pytest.mark.parametrize("backend", DYN_BACKENDS)
@settings(max_examples=3, deadline=None)
@given(seed=st.integers(0, 10_000), dseed=st.integers(0, 10_000))
def test_update_properties_random_deltas(backend, seed, dseed):
    """Property: for random graphs and random mixed deltas, the refreshed
    ranks stay a distribution and match the from-scratch oracle."""
    n = 32
    src, dst = gen.erdos_renyi(n, avg_degree=4.0, seed=seed)
    if len(src) < 8:
        return
    rng = np.random.default_rng(dseed)
    iu = rng.integers(0, n, size=3)
    iv = (iu + rng.integers(1, n, size=3)) % n        # guaranteed u != v
    k = rng.integers(0, len(src), size=2)
    delta = GraphDelta(iu, iv, src[k], dst[k])
    dyn = DynamicPageRankEngine(src, dst, n, backend=backend)
    dyn.run_tol(1e-7, max_iters=500)
    pr, info = dyn.update(delta)
    pr = np.asarray(pr)
    assert (pr >= 0).all()
    assert pr.sum() == pytest.approx(1.0, abs=1e-4)
    assert _l1(pr, _scratch_ranks(src, dst, n, delta)) <= 1e-5


@pytest.mark.parametrize("strategy", ["push", "warm", "rebuild"])
def test_update_tol_bounds_the_rank_error(net, strategy):
    """``update``'s tol bounds the refreshed ranks' L1 distance from the
    fixed point: every strategy stops at a residual of (1 - d)·tol, not at
    tol, from which a warm start can exit with a delta half applied."""
    n, src, dst = net
    tol = 1e-4
    dyn = DynamicPageRankEngine(src, dst, n, backend="ell")
    dyn.run_tol(tol, max_iters=500)
    iu, iv = _absent_pairs(src, dst, n, 3, seed=6)
    delta = GraphDelta(iu, iv, np.asarray(src[:2]), np.asarray(dst[:2]))
    pr, info = dyn.update(delta, tol=tol, strategy=strategy)
    assert info.strategy == strategy
    assert info.residual <= (1 - dyn.d) * tol
    assert _l1(pr, _scratch_ranks(src, dst, n, delta)) <= tol


@pytest.mark.parametrize("backend", DYN_BACKENDS)
def test_insert_then_delete_is_noop(net, backend):
    """Applying a delta and its inverse restores the prepared layout
    arrays exactly and the ranks to within the refresh tolerance."""
    import jax
    n, src, dst = net
    dyn = DynamicPageRankEngine(src, dst, n, backend=backend)
    pr0 = dyn.run_tol(1e-7, max_iters=500)[0]
    before = [np.asarray(o) for o in jax.tree_util.tree_leaves(dyn.operands)]
    dang_before = np.asarray(dyn.prepared.dang)
    edges = _absent_pairs(src, dst, n, 3, seed=2)
    dyn.update(GraphDelta.inserts(*edges))
    pr2, _ = dyn.update(GraphDelta.deletes(*edges))
    for a, b in zip(before, jax.tree_util.tree_leaves(dyn.operands)):
        np.testing.assert_array_equal(a, np.asarray(b))
    np.testing.assert_array_equal(dang_before, np.asarray(dyn.prepared.dang))
    assert _l1(pr0, pr2) <= 1e-5


def test_auto_policy_picks_by_delta_size(net):
    n, src, dst = net
    dyn = DynamicPageRankEngine(src, dst, n, backend="ell")
    (u1, u2), (v1, v2) = _absent_pairs(src, dst, n, 2, seed=3)
    # without previous ranks a patchable delta warm-starts from cold
    _, info = dyn.update(GraphDelta.inserts([u1], [v1]))
    assert info.strategy == "warm"
    dyn.run_tol(1e-7, max_iters=500)
    # tiny delta with ranks available: push
    _, info = dyn.update(GraphDelta.inserts([u2], [v2]))
    assert info.strategy == "push"
    # delta above rebuild_frac of the edge set: rebuild
    rng = np.random.default_rng(0)
    bu = rng.integers(0, n, size=dyn.n_edges // 4)
    bv = (bu + rng.integers(1, n, size=bu.size)) % n  # guaranteed u != v
    _, info = dyn.update(GraphDelta.inserts(bu, bv))
    assert info.strategy == "rebuild"
    # noop delta
    pr, info = dyn.update(GraphDelta.inserts(bu[:1], bv[:1]))
    assert info.strategy == "noop" and pr is dyn.ranks


def test_ell_row_overflow_escalates_to_rebuild(net):
    n, src, dst = net
    dyn = DynamicPageRankEngine(src, dst, n, backend="ell", slack=2)
    dyn.run_tol(1e-7, max_iters=500)
    # bury one low-degree node in new neighbors: its SELL row outgrows
    # the capacity slack, so the patch path must refuse and rebuild
    deg = np.bincount(src, minlength=n)
    w = int(np.argmin(np.where(deg > 0, deg, n)))
    nbrs = [v for v in range(n) if v != w][:dyn._sell.widths[0] + 2]
    delta = GraphDelta.inserts([w] * len(nbrs), nbrs)
    pr, info = dyn.update(delta)
    assert info.overflow and info.strategy == "rebuild"
    assert _l1(pr, _scratch_ranks(src, dst, n, delta)) <= 1e-5


def test_forced_strategy_validation(net):
    n, src, dst = net
    (u1, u2), (v1, v2) = _absent_pairs(src, dst, n, 2, seed=4)
    dyn = DynamicPageRankEngine(src, dst, n, backend="ell")
    with pytest.raises(ValueError, match="strategy"):
        dyn.update(GraphDelta.inserts([u1], [v1]), strategy="bogus")
    with pytest.raises(ValueError, match="push"):
        dyn.update(GraphDelta.inserts([u1], [v1]), strategy="push")
    # a rejected update must leave NO trace: the same delta applied with a
    # valid strategy afterwards is fully effective (not a bogus noop) and
    # still matches the from-scratch oracle
    delta = GraphDelta.inserts([u1], [v1])
    edges_before = dyn.n_edges
    pr, info = dyn.update(delta, strategy="warm")
    assert info.strategy == "warm" and info.n_inserted == 2
    assert dyn.n_edges == edges_before + 2
    assert _l1(pr, _scratch_ranks(src, dst, n, delta)) <= 1e-5
    # BSR patches values inside existing blocks, so a forced push on an
    # in-block delta (n=64 < one 128-block) now works instead of raising
    dyn_bsr = DynamicPageRankEngine(src, dst, n, backend="bsr")
    dyn_bsr.run_tol(1e-7, max_iters=500)
    d2 = GraphDelta.inserts([u2], [v2])
    pr, info = dyn_bsr.update(d2, strategy="push")
    assert info.strategy == "push" and info.coerced_from is None
    assert _l1(pr, _scratch_ranks(src, dst, n, d2)) <= 1e-5


def test_bsr_structure_change_forces_rebuild(net):
    """An insert landing in a block the BSR layout never materialized
    cannot be patched in place: the auto policy escalates to a rebuild
    and records the coercion (a genuine block-structure change, unlike
    the in-block patches DYN_BACKENDS covers)."""
    n, src, dst = net
    dyn = DynamicPageRankEngine(src, dst, n, backend="bsr",
                                bsr_block_size=8, rebuild_frac=1.0)
    dyn.run_tol(1e-7, max_iters=500)
    bs, nbc = 8, dyn._bsr_nbc
    present = set(dyn._bsr_pairs.tolist())
    u, v = next((u, v) for u in range(n) for v in range(u + 1, n)
                if (v // bs) * nbc + u // bs not in present
                and (u // bs) * nbc + v // bs not in present)
    delta = GraphDelta.inserts([u], [v])
    with pytest.raises(ValueError, match="patchable"):
        dyn.update(delta, strategy="push")       # forced patch must refuse
    pr, info = dyn.update(delta)
    assert info.overflow and info.strategy == "rebuild"
    assert info.coerced_from == "push"
    assert _l1(pr, _scratch_ranks(src, dst, n, delta)) <= 1e-5


def test_overflow_coercion_is_recorded(net):
    """Satellite: when the auto policy wants a push but the layout cannot
    take the patch, the coercion surfaces in ``UpdateInfo.coerced_from``,
    the ``update.coerced`` counter, and an ``update_coerced`` event."""
    from repro.obs.registry import MetricsRegistry
    n, src, dst = net
    metrics = MetricsRegistry()
    dyn = DynamicPageRankEngine(src, dst, n, backend="ell", slack=2,
                                rebuild_frac=1.0, metrics=metrics)
    dyn.run_tol(1e-7, max_iters=500)
    deg = np.bincount(src, minlength=n)
    w = int(np.argmin(np.where(deg > 0, deg, n)))
    nbrs = [v for v in range(n) if v != w][:dyn._sell.widths[0] + 2]
    pr, info = dyn.update(GraphDelta.inserts([w] * len(nbrs), nbrs))
    assert info.overflow and info.strategy == "rebuild"
    assert info.coerced_from == "push"
    assert metrics.counter("update.coerced").value == 1
    evs = [e for e in metrics.events if e["kind"] == "update_coerced"]
    assert len(evs) == 1
    assert evs[0]["requested"] == "push" and evs[0]["ran"] == "rebuild"


# --------------------------------------------------------------------------- #
# sharded tiers: in-place patches + shard-local push on the mesh              #
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("backend", SHARDED)
@pytest.mark.parametrize("strategy", ["auto", "push", "warm", "rebuild"])
def test_sharded_update_matches_from_scratch(net, backend, strategy,
                                             multi_device):
    n, src, dst = net
    dyn = DynamicPageRankEngine(src, dst, n, backend=backend)
    dyn.run_tol(1e-7, max_iters=500)
    iu, iv = _absent_pairs(src, dst, n, 3, seed=1)
    delta = GraphDelta(iu, iv, np.asarray(src[:2]), np.asarray(dst[:2]))
    pr, info = dyn.update(delta, strategy=strategy)
    assert info.strategy == (strategy if strategy != "auto" else "push")
    assert info.coerced_from is None
    pr = np.asarray(pr)
    assert (pr >= 0).all()
    assert pr.sum() == pytest.approx(1.0, abs=1e-4)
    assert _l1(pr, _scratch_ranks(src, dst, n, delta)) <= 1e-5


@pytest.mark.parametrize("backend", SHARDED)
def test_sharded_insert_then_delete_is_noop(net, backend, multi_device):
    """A delta and its inverse restore the shard-local operand arrays
    bit-exactly — the patch path writes the same values the builder
    produced, on the same devices."""
    import jax
    n, src, dst = net
    dyn = DynamicPageRankEngine(src, dst, n, backend=backend)
    pr0 = dyn.run_tol(1e-7, max_iters=500)[0]
    before = [np.asarray(o) for o in jax.tree_util.tree_leaves(dyn.operands)]
    dang_before = np.asarray(dyn.prepared.dang)
    edges = _absent_pairs(src, dst, n, 3, seed=2)
    dyn.update(GraphDelta.inserts(*edges))
    pr2, _ = dyn.update(GraphDelta.deletes(*edges))
    for a, b in zip(before, jax.tree_util.tree_leaves(dyn.operands)):
        np.testing.assert_array_equal(a, np.asarray(b))
    np.testing.assert_array_equal(dang_before, np.asarray(dyn.prepared.dang))
    assert _l1(pr0, pr2) <= 1e-5


@pytest.mark.parametrize("backend", SHARDED)
def test_sharded_patch_preserves_shardings(net, backend, multi_device):
    """Patching must not silently replicate: the operands keep the exact
    ``NamedSharding``s the layout was built with."""
    n, src, dst = net
    dyn = DynamicPageRankEngine(src, dst, n, backend=backend)
    dyn.run_tol(1e-7, max_iters=500)
    import jax
    specs_before = [o.sharding.spec
                    for o in jax.tree_util.tree_leaves(dyn.operands)]
    delta = GraphDelta.inserts(*_absent_pairs(src, dst, n, 2, seed=6))
    _, info = dyn.update(delta)
    assert info.strategy == "push"
    specs_after = [o.sharding.spec
                   for o in jax.tree_util.tree_leaves(dyn.operands)]
    assert specs_before == specs_after


def test_sharded_capacity_overflow_escalates(net, multi_device):
    """Burying a node past the ell_sharded row capacity (its SELL tier's
    width) escalates to a rebuild with the coercion recorded — and the
    rebuilt layout regrows its capacity."""
    n, src, dst = net
    dyn = DynamicPageRankEngine(src, dst, n, backend="ell_sharded",
                                slack=2, rebuild_frac=1.0)
    dyn.run_tol(1e-7, max_iters=500)
    indeg = np.bincount(dst, minlength=n)
    caps = np.asarray(dyn._sell.widths)[dyn._sell.tier[:n]]
    w = int(np.argmin(caps - indeg))     # the row closest to its capacity
    cap = int(caps[w])
    have = set(dst[src == w].tolist()) | {w}
    nbrs = [v for v in range(n) if v not in have][:cap - indeg[w] + 2]
    pr, info = dyn.update(GraphDelta.inserts([w] * len(nbrs), nbrs))
    assert info.overflow and info.strategy == "rebuild"
    assert info.coerced_from == "push"
    assert dyn._sell.widths[dyn._sell.tier[w]] > cap
    delta = GraphDelta.inserts([w] * len(nbrs), nbrs)
    assert _l1(pr, _scratch_ranks(src, dst, n, delta)) <= 1e-5


@pytest.mark.parametrize("backend", SHARDED)
def test_sharded_auto_policy_matches_single_device(net, backend,
                                                   multi_device):
    """The auto policy must pick the same strategy sharded as it does on
    the equivalent single-device layout — sharding changes where the work
    runs, never whether a delta is patchable."""
    n, src, dst = net
    local = "dense" if backend == "dense_sharded" else "ell"
    a = DynamicPageRankEngine(src, dst, n, backend=local)
    b = DynamicPageRankEngine(src, dst, n, backend=backend)
    (u1, u2), (v1, v2) = _absent_pairs(src, dst, n, 2, seed=8)
    # no previous ranks: both warm-start
    _, ia = a.update(GraphDelta.inserts([u1], [v1]))
    _, ib = b.update(GraphDelta.inserts([u1], [v1]))
    assert ia.strategy == ib.strategy == "warm"
    a.run_tol(1e-7, max_iters=500)
    b.run_tol(1e-7, max_iters=500)
    # tiny delta with ranks: both push, neither coerced
    _, ia = a.update(GraphDelta.inserts([u2], [v2]))
    _, ib = b.update(GraphDelta.inserts([u2], [v2]))
    assert ia.strategy == ib.strategy == "push"
    assert ia.coerced_from is None and ib.coerced_from is None
    # delta above rebuild_frac: both rebuild
    rng = np.random.default_rng(9)
    bu = rng.integers(0, n, size=a.n_edges // 4)
    bv = (bu + rng.integers(1, n, size=bu.size)) % n
    _, ia = a.update(GraphDelta.inserts(bu, bv))
    _, ib = b.update(GraphDelta.inserts(bu, bv))
    assert ia.strategy == ib.strategy == "rebuild"


def test_sharded_stream_of_updates_tracks_scratch(net, multi_device):
    """A stream of mixed deltas on the sharded tier: incremental ranks
    never drift from the from-scratch oracle."""
    n, src, dst = net
    stream = EdgeStream(n, m_edges=3, seed=4, insert_per_step=4,
                        delete_per_step=3)
    s0, d0 = stream.base()
    dyn = DynamicPageRankEngine(s0, d0, n, backend="ell_sharded")
    dyn.run_tol(1e-7, max_iters=500)
    cur = (s0, d0)
    for _, delta in zip(range(4), stream):
        pr, _ = dyn.update(delta)
        cur = apply_delta(cur[0], cur[1], delta, n)
    assert _l1(pr, _scratch_ranks(cur[0], cur[1], n)) <= 1e-5


def test_dynamic_ell_ppr_matches_static(net):
    """The dynamic SELL layout (slack 8) serves the same batched PPR as the
    static ``ell`` tier's (slack 0; the serve path flushes through
    engine.ppr)."""
    n, src, dst = net
    seed_sets = [np.array([1, 2]), np.array([7])]
    got = DynamicPageRankEngine(src, dst, n, backend="ell").ppr(
        seed_sets, n_iters=40)
    want = PageRankEngine(src, dst, n, backend="ell").ppr(
        seed_sets, n_iters=40)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-7)


def test_stream_of_updates_tracks_scratch(net):
    """A whole stream of mixed deltas: incremental ranks never drift from
    the from-scratch oracle (the error stays bounded by the per-update
    residual — no compounding)."""
    n, src, dst = net
    stream = EdgeStream(n, m_edges=3, seed=2, insert_per_step=4,
                        delete_per_step=3)
    s0, d0 = stream.base()
    dyn = DynamicPageRankEngine(s0, d0, n, backend="ell")
    dyn.run_tol(1e-7, max_iters=500)
    cur = (s0, d0)
    for _, delta in zip(range(5), stream):
        pr, _ = dyn.update(delta)
        cur = apply_delta(cur[0], cur[1], delta, n)
    assert _l1(pr, _scratch_ranks(cur[0], cur[1], n)) <= 1e-5


# --------------------------------------------------------------------------- #
# host key-set planning                                                       #
# --------------------------------------------------------------------------- #
def _edit_case(case):
    """(sorted unique keys, keys to drop, keys to add) for one edit shape;
    drop and add come unordered, as ``_plan`` hands over reverse keys."""
    rng = np.random.default_rng(4)
    keys = np.unique(rng.integers(0, 10**6, size=3000))
    pool = np.setdiff1d(np.arange(10**6, dtype=np.int64), keys)
    drop = rng.choice(keys[1:-1], 12, replace=False)
    add = rng.choice(pool[(pool > keys[0]) & (pool < keys[-1])], 20,
                     replace=False)
    if case == "no_drops":
        drop = drop[:0]
    elif case == "no_adds":
        add = add[:0]
    elif case == "ends":
        drop = np.concatenate([drop, keys[[0, -1]]])
        add = np.concatenate([add, [keys[0] - 1, keys[-1] + 1]])
    elif case == "drop_then_add_same_key":
        add = np.concatenate([add, drop[:3]])
    elif case == "rebuild_sized":
        drop = rng.choice(keys, len(keys) // 10, replace=False)
        add = rng.choice(pool, len(keys) // 10, replace=False)
    return keys, rng.permutation(drop), rng.permutation(add)


@pytest.mark.parametrize("case", ["no_drops", "no_adds", "ends",
                                  "drop_then_add_same_key", "rebuild_sized"])
def test_edit_sorted_matches_set_ops(case):
    from repro.pagerank.dynamic import _edit_sorted
    keys, drop, add = _edit_case(case)
    before = keys.copy()
    got = _edit_sorted(keys, drop, add)
    want = np.union1d(np.setdiff1d(keys, drop, assume_unique=True), add)
    assert got.dtype == np.int64
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(keys, before)     # input left alone


@pytest.mark.parametrize("backend", DYN_BACKENDS)
def test_plan_leaves_keys_and_inverse_delta_restores_them(net, backend):
    """``_plan`` builds the post-delta key sets without touching the
    engine's (``update`` rolls back by restoring them); a mixed delta and
    its inverse, as the delta-stream benchmark cycles them, bring both key
    arrays back to the originals with ranks on the from-scratch oracle."""
    n, src, dst = net
    dyn = DynamicPageRankEngine(src, dst, n, backend=backend)
    dyn.run_tol(1e-7, max_iters=500)
    keys0, rkeys0 = dyn._keys, dyn._rkeys
    keys_copy, rkeys_copy = keys0.copy(), rkeys0.copy()
    iu, iv = _absent_pairs(src, dst, n, 3, seed=5)
    delta = GraphDelta(iu, iv, np.asarray(src[:2]), np.asarray(dst[:2]))
    inverse = GraphDelta(delta.delete_src, delta.delete_dst,
                         delta.insert_src, delta.insert_dst)

    plan = dyn._plan(delta)
    assert dyn._keys is keys0 and dyn._rkeys is rkeys0
    np.testing.assert_array_equal(keys0, keys_copy)
    np.testing.assert_array_equal(rkeys0, rkeys_copy)
    s1, d1 = apply_delta(src, dst, delta, n)
    np.testing.assert_array_equal(plan["keys"], edge_keys(s1, d1, n))
    np.testing.assert_array_equal(plan["rkeys"], edge_keys(d1, s1, n))

    pr, _ = dyn.update(delta)
    assert _l1(pr, _scratch_ranks(s1, d1, n)) <= 1e-5
    pr, _ = dyn.update(inverse)
    np.testing.assert_array_equal(dyn._keys, keys_copy)
    np.testing.assert_array_equal(dyn._rkeys, rkeys_copy)
    assert _l1(pr, _scratch_ranks(src, dst, n)) <= 1e-5


# --------------------------------------------------------------------------- #
# serve-layer refresh path                                                    #
# --------------------------------------------------------------------------- #
def test_serve_refresh_before_flush(net):
    from repro.serve import PageRankQueryEngine
    n, src, dst = net
    dyn = DynamicPageRankEngine(src, dst, n, backend="ell")
    dyn.run_tol(1e-7, max_iters=500)
    qe = PageRankQueryEngine(dyn, n_iters=50, max_batch=8)
    rng = np.random.default_rng(3)
    seeds = [rng.choice(n, size=2, replace=False) for _ in range(3)]
    queries = [qe.submit(uid, s, top_k=4) for uid, s in enumerate(seeds)]
    iu, iv = _absent_pairs(src, dst, n, 3, seed=7)
    # two deltas arrive while queries are queued: one refresh coalesces
    # them into a single engine update
    qe.push_update(GraphDelta.inserts(iu[:2], iv[:2]))
    qe.push_update(GraphDelta.inserts(iu[2:], iv[2:]))
    qe.flush()
    assert qe.n_refreshes == 1
    assert qe.last_update_info.strategy == "push"
    assert qe.last_update_info.n_inserted == 6      # all 3 pairs, 1 solve
    # in-flight queries were served against the POST-delta graph
    s2, d2 = apply_delta(src, dst, GraphDelta.inserts(iu, iv), n)
    fresh = PageRankQueryEngine(
        PageRankEngine(s2, d2, n, backend="ell"), n_iters=50)
    want = fresh.query_batch(seeds, top_k=4)
    for q, (widx, wscores) in zip(queries, want):
        np.testing.assert_array_equal(q.result[0], widx)
        np.testing.assert_allclose(q.result[1], wscores, rtol=1e-4,
                                   atol=1e-7)


def test_serve_push_update_requires_dynamic_engine(net):
    from repro.serve import PageRankQueryEngine
    n, src, dst = net
    qe = PageRankQueryEngine(PageRankEngine(src, dst, n, backend="ell"))
    with pytest.raises(TypeError, match="DynamicPageRankEngine"):
        qe.push_update(GraphDelta.inserts([1], [2]))
