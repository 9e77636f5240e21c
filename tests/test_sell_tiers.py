"""The SELL layout's degree tiers on a power-law graph with hub rows.

Tier bounds double from a width of 4 up to the maximum degree, so every
row is padded to under twice its degree (plus 4 and the slack): the static
``ell`` tier (slack 0), the dynamic engine (slack 8) and the row-sharded
layout all follow the graph's own degree histogram.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.graph import generators as gen
from repro.graph import transition as tr
from repro.graph.delta import GraphDelta, apply_delta, edge_keys
from repro.obs.registry import MetricsRegistry
from repro.pagerank import DynamicPageRankEngine, PageRankEngine, sell
from repro.pagerank.engine import SellLayout, global_push


def _l1(a, b) -> float:
    return float(np.abs(np.asarray(a, np.float64)
                        - np.asarray(b, np.float64)).sum())


@pytest.fixture(scope="module")
def hubs():
    """A Barabasi-Albert graph on 400 vertices (degrees 3 to 69, p90 11)
    plus two isolated vertices."""
    src, dst = gen.barabasi_albert(400, 3, seed=1)
    n = 402
    deg = np.bincount(dst, minlength=n)
    assert deg.max() > 4 * np.percentile(deg, 90)
    return src, dst, n


@pytest.mark.parametrize("slack,shards", [(0, 1), (8, 1), (0, 4), (8, 4)])
@pytest.mark.parametrize("graph", ["hubs", "small"])
def test_tier_capacity_bounds_each_rows_padding(hubs, graph, slack, shards):
    # "small" (maximum degree 24) is where the widest tier's round-up to
    # 32 would outgrow 2·deg + 4 + slack, so its clamp decides the width
    src, dst, n = (hubs if graph == "hubs"
                   else (*gen.barabasi_albert(60, 3, seed=1), 60))
    n_pad = -(-n // shards) * shards
    csr = tr.build_transition_csr(src, dst, n)
    (inv, tiers), index = sell.build(csr, n_pad, shards=shards, slack=slack)
    deg = np.zeros(n_pad, np.int64)
    deg[:n] = np.diff(np.asarray(csr.indptr))
    cap = np.asarray(index.widths)[index.tier]
    assert len(index.widths) > 2
    assert (cap >= deg + slack).all()             # every row fits, headroom
    assert (cap[deg > 0] < 2 * deg[deg > 0] + 4 + slack).all()
    assert (cap[deg == 0] == 4 + slack).all()
    assert index.slots == sum(int(t[0].size) for t in tiers)
    assert index.slots == sum(int(t[1].size) for t in tiers)
    # every row of a shard has its own slot in the shard's tiers
    for block in np.asarray(inv).reshape(shards, -1):
        assert len(set(block.tolist())) == len(block)


@pytest.mark.parametrize("dynamic", [False, True])
def test_layout_slots_gauge_counts_the_tier_arrays(hubs, dynamic):
    src, dst, n = hubs
    reg = MetricsRegistry()
    cls = DynamicPageRankEngine if dynamic else PageRankEngine
    eng = cls(src, dst, n, backend="ell", metrics=reg)
    slots = sum(int(data.size) for data, _ in eng.operands[1])
    assert eng._sell.slots == slots
    assert reg.gauge("layout.slots").value == slots
    assert eng.layout == eng._sell.describe(8 if dynamic else 0)
    # the padding the tiers keep: under twice the edges, plus 4 per row
    assert eng.n_edges < slots < 2 * eng.n_edges + (4 + eng._slack) * n


@pytest.mark.parametrize("op", ["run", "run_tol", "ppr"])
def test_static_ell_tier_matches_dense(hubs, op):
    src, dst, n = hubs
    ell = PageRankEngine(src, dst, n, backend="ell")
    dense = PageRankEngine(src, dst, n, backend="dense")
    assert isinstance(ell.prepared, SellLayout) and ell._sell is not None
    if op == "run":
        assert _l1(ell.run(60), dense.run(60)) <= 1e-6
    elif op == "run_tol":
        a, b = (e.run_tol(1e-7, max_iters=500) for e in (ell, dense))
        assert _l1(a[0], b[0]) <= 1e-6 and int(a[1]) == int(b[1])
    else:
        hub = int(np.argmax(np.bincount(dst, minlength=n)))
        seeds = [np.array([hub]), np.array([5, 17]), np.array([n - 1])]
        a, b = (np.asarray(e.ppr(seeds, n_iters=60)) for e in (ell, dense))
        assert a.shape == (n, 3)
        assert max(_l1(a[:, q], b[:, q]) for q in range(3)) <= 1e-6


def test_dynamic_delta_patches_the_new_tiers_in_place(hubs):
    """A delta on a hub row and a width-4 row rewrites both in their
    tiers: no capacity overflow, no coerced rebuild, no shape change."""
    src, dst, n = hubs
    dyn = DynamicPageRankEngine(src, dst, n, backend="ell")
    dyn.run_tol(1e-7, max_iters=500)
    before = [a.shape for a in jax.tree.leaves(dyn.operands)]
    widths = dyn._sell.widths
    deg = np.bincount(dst, minlength=n)
    hub = int(np.argmax(deg))
    low = int(np.flatnonzero((deg > 0) & (deg <= 4))[0])
    have = set(edge_keys(src, dst, n).tolist())
    pairs = [(u, next(v for v in range(n - 2) if v != u
                      and u * n + v not in have)) for u in (hub, low)]
    delta = GraphDelta.inserts([u for u, _ in pairs], [v for _, v in pairs])
    assert dyn._sell.tier[low] == 0
    assert dyn._sell.tier[hub] == len(widths) - 1
    pr, info = dyn.update(delta)
    assert info.strategy == "push" and info.coerced_from is None
    assert not info.overflow and info.rows_patched >= 4
    assert [a.shape for a in jax.tree.leaves(dyn.operands)] == before
    assert dyn._sell.widths == widths
    s2, d2 = apply_delta(src, dst, delta, n)
    want = PageRankEngine(s2, d2, n, backend="dense").run_tol(
        1e-8, max_iters=1000)[0]
    assert _l1(pr, want) <= 1e-5


def _hub_delta(src, dst, n, kind):
    """A delta at the hub column: four new edges of the hub, or four of
    its edges removed; returns it, the hub and the four partners."""
    hub = int(np.argmax(np.bincount(src, minlength=n)))
    nbrs = np.sort(dst[src == hub])
    if kind == "insert":
        ends = np.setdiff1d(np.arange(n - 2), np.append(nbrs, hub))[:4]
        return GraphDelta.inserts(np.full(4, hub), ends), hub, ends
    ends = nbrs[:4]
    return (GraphDelta(np.empty(0, np.int64), np.empty(0, np.int64),
                       np.full(4, hub), ends), hub, ends)


@pytest.mark.parametrize("backend", ["ell", "ell_sharded"])
@pytest.mark.parametrize("kind", ["insert", "delete"])
def test_hub_delta_rewrites_only_the_changed_edges_rows(hubs, kind, backend,
                                                        request):
    """The dynamic SELL keeps ``1/outdeg`` in its column scale, so a delta
    that changes a hub's out-degree rewrites the rows its edges end in
    (the partners, and the hub for the reverse edges), not every row that
    holds the hub."""
    if backend == "ell_sharded":
        request.getfixturevalue("multi_device")
    src, dst, n = hubs
    reg = MetricsRegistry()
    dyn = DynamicPageRankEngine(src, dst, n, backend=backend, metrics=reg)
    dyn.run_tol(1e-7, max_iters=500)
    delta, hub, ends = _hub_delta(src, dst, n, kind)
    rows = len(np.unique(np.append(ends, hub)))
    assert rows == 5 and int(np.sum(src == hub)) > 4 * rows
    pr, info = dyn.update(delta)
    assert info.strategy == "push" and not info.overflow
    assert info.rows_patched == rows
    assert reg.counter("update.rows_patched").value == rows
    s2, d2 = apply_delta(src, dst, delta, n)
    fresh = PageRankEngine(s2, d2, n, backend="dense").run_tol(
        1e-8, max_iters=1000)[0]
    assert _l1(pr, fresh) <= 1e-5


def _valued_layout(src, dst, n, precision):
    """A SELL of the dynamic engine's widths (slack 8) whose rows hold
    their values ``1/outdeg``: what the scaled layout must act as."""
    operands, _ = sell.build(tr.build_transition_csr(src, dst, n), n,
                             slack=8, precision=precision)
    dang = tr.dangling_mask(src, n).astype(np.float32)
    return SellLayout(operands=operands, dang=jnp.asarray(dang), n=n)


@pytest.mark.parametrize("precision", ["f32", "bf16"])
@pytest.mark.parametrize("q", [None, 5])
def test_scaled_mv_matches_a_valued_sell(hubs, precision, q):
    """The dynamic layout's structure rows times its column scale give the
    valued SELL's matvec, for a vector and a batch, before and after a
    delta at the hub."""
    src, dst, n = hubs
    dyn = DynamicPageRankEngine(src, dst, n, backend="ell",
                                precision=precision)
    dyn.run_tol(1e-6, max_iters=500)
    assert dyn.prepared.col_scale.dtype == dyn.storage_dtype
    assert all(bool((np.asarray(data, np.float32) <= 1.0).all())
               for data, _ in dyn.operands[1])
    X = jax.random.uniform(jax.random.key(0), (n,) if q is None else (n, q))
    want = _valued_layout(src, dst, n, precision)
    assert [d.shape for d, _ in want.operands[1]] == \
        [d.shape for d, _ in dyn.operands[1]]
    np.testing.assert_allclose(dyn.prepared.mv(X), want.mv(X), rtol=1e-6,
                               atol=0)
    delta, _, _ = _hub_delta(src, dst, n, "insert")
    dyn.update(delta)
    s2, d2 = apply_delta(src, dst, delta, n)
    np.testing.assert_allclose(dyn.prepared.mv(X),
                               _valued_layout(s2, d2, n, precision).mv(X),
                               rtol=1e-6, atol=0)


@pytest.mark.parametrize("precision", ["f32", "bf16"])
def test_scaled_push_takes_the_valued_sweeps(hubs, precision):
    src, dst, n = hubs
    dyn = DynamicPageRankEngine(src, dst, n, backend="ell",
                                precision=precision)
    x0 = jnp.full((n,), 1.0 / n, jnp.float32)
    got, want = (global_push(lay, dyn.d, jnp.float32(1e-7), x0,
                             max_pushes=500)
                 for lay in (dyn.prepared,
                             _valued_layout(src, dst, n, precision)))
    assert int(got[1]) == int(want[1]) > 10
    assert _l1(got[0], want[0]) <= 1e-6


@pytest.mark.parametrize("engine,precision,scaled", [
    ("static", "f32", False), ("static", "bf16", False),
    ("dynamic", "f32", True), ("dynamic", "bf16", True),
    ("dynamic", "int8", False)])
def test_only_the_patching_engine_scales_columns(hubs, engine, precision,
                                                 scaled):
    """The static engine's SELL holds its values and no scale (its leaves
    are the operands and the dangling mask, as ever); the dynamic engine's
    holds the structure and ``col_scale``, but for int8, which never
    patches and keeps its valued, quantized rows."""
    src, dst, n = hubs
    cls = DynamicPageRankEngine if engine == "dynamic" else PageRankEngine
    eng = cls(src, dst, n, backend="ell", precision=precision)
    lay = eng.prepared
    extra = [] if lay.col_scale is None else [lay.col_scale]
    assert (lay.col_scale is not None) == scaled
    assert jax.tree.leaves(lay) == (jax.tree.leaves(lay.operands)
                                    + [lay.dang] + extra)
    data = np.concatenate([np.asarray(t[0], np.float32).ravel()
                           for t in lay.operands[1]])
    if scaled:
        assert set(np.unique(data)) == {0.0, 1.0}
        outdeg = np.bincount(src, minlength=n)
        want = np.where(outdeg > 0, np.float32(1.0)
                        / np.maximum(outdeg, 1).astype(np.float32), 0.0)
        np.testing.assert_array_equal(
            np.asarray(lay.col_scale),
            np.asarray(want.astype(np.float32)).astype(eng.storage_dtype))
    else:
        assert len(np.unique(data)) > 2                # one value per degree
