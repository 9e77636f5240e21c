"""The SELL layout's degree tiers on a power-law graph with hub rows.

Tier bounds double from a width of 4 up to the maximum degree, so every
row is padded to under twice its degree (plus 4 and the slack): the static
``ell`` tier (slack 0), the dynamic engine (slack 8) and the row-sharded
layout all follow the graph's own degree histogram.
"""
import jax
import numpy as np
import pytest

from repro.graph import generators as gen
from repro.graph import transition as tr
from repro.graph.delta import GraphDelta, apply_delta, edge_keys
from repro.obs.registry import MetricsRegistry
from repro.pagerank import DynamicPageRankEngine, PageRankEngine, sell
from repro.pagerank.engine import SellLayout


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
