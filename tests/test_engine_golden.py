"""Golden regression: pin engine-vs-reference drift per backend.

The benchmark graphs (``BENCH_pagerank_engine.json``: N-node protein
networks at fixed seeds, 100-iteration schedule) are re-derived here at a
CI-friendly size and every backend's max-abs-diff against the
``pagerank_dense_fixed`` float32 reference is asserted against a pinned
bound.  A future kernel or schedule edit that silently degrades accuracy
(reordered reductions, dropped leak terms, bad padding) fails here even if
the relative-tolerance parity tests still scrape by.

The committed JSON artifact's own recorded diffs are also re-checked, so
the numbers the docs cite stay consistent with the claims.
"""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.graph import generators as gen
from repro.graph import transition as tr
from repro.pagerank import PageRankEngine, pagerank_dense_fixed

# fixed-seed golden graphs: (n, generator seed, schedule length)
GOLDEN_GRAPHS = [(256, 0, 100), (200, 7, 100)]

# pinned per-backend drift bounds vs the float32 dense reference.  dense is
# bitwise (it dispatches the very same jitted program); the XLA sparse and
# sharded tiers differ only by reduction order; the Pallas tier pays one
# extra rounding in the fused epilogue.
DRIFT_BOUNDS = {
    "dense": 0.0,
    "ell": 1e-6,
    "bsr": 1e-6,
    "dense_sharded": 1e-6,
    "ell_sharded": 1e-6,
    "pallas_dense": 1e-5,
}

BENCH_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_pagerank_engine.json")


@pytest.mark.parametrize("n,seed,iters", GOLDEN_GRAPHS)
@pytest.mark.parametrize("backend", sorted(DRIFT_BOUNDS))
def test_backend_drift_within_golden_bound(backend, n, seed, iters):
    src, dst = gen.protein_network(n, seed=seed)
    H = tr.build_transition_dense(src, dst, n)
    if backend == "pallas_dense":
        iters = 15                    # interpret mode on CPU: keep short
    # d passed explicitly to match the engine's call convention: an
    # unfilled default is baked as a compile-time constant and XLA emits a
    # (bitwise-different) program, which would break the dense 0.0 bound
    ref = pagerank_dense_fixed(H, n_iters=iters, d=0.85)
    eng = PageRankEngine(src, dst, n, backend=backend)
    pr = eng.run(n_iters=iters)
    drift = float(jnp.max(jnp.abs(pr - ref)))
    assert drift <= DRIFT_BOUNDS[backend], (
        f"{backend} drifted to {drift:.2e} on golden graph "
        f"(n={n}, seed={seed}); bound {DRIFT_BOUNDS[backend]:.0e}")


def test_ppr_drift_within_golden_bound():
    """Batched PPR across backends pinned against the ELL tier on a fixed
    graph/seed-set combination."""
    n, seed = 200, 7
    src, dst = gen.protein_network(n, seed=seed)
    rng = np.random.default_rng(42)
    seed_sets = [rng.choice(n, size=3, replace=False) for _ in range(4)]
    want = PageRankEngine(src, dst, n, backend="ell").ppr(seed_sets,
                                                         n_iters=60)
    for backend in ("dense", "dense_sharded", "ell_sharded"):
        got = PageRankEngine(src, dst, n, backend=backend).ppr(seed_sets,
                                                              n_iters=60)
        drift = float(jnp.max(jnp.abs(got - want)))
        assert drift <= 1e-5, f"{backend} PPR drifted to {drift:.2e}"


def test_committed_bench_artifact_claims_hold():
    """The JSON artifact the docs cite must keep its accuracy claims: the
    dense engine bitwise-identical, every recorded engine diff <= 1e-5."""
    with open(BENCH_PATH) as f:
        report = json.load(f)
    diffs = dict(report["max_abs_diff"])
    diffs.update(report.get("sharded", {}).get("max_abs_diff", {}))
    assert diffs["engine_dense_vs_reference"] == 0.0
    engine_diffs = {k: v for k, v in diffs.items() if k.startswith("engine")}
    assert len(engine_diffs) >= 2
    for name, v in engine_diffs.items():
        assert v <= 1e-5, f"{name}={v:.2e} breaks the <=1e-5 claim"
    assert report["claim"]["diff_le_1e-5"] is True


def test_committed_bench_artifact_dynamic_claims_hold():
    """The ``dynamic`` block (benchmarks/dynamic_bench.py) must keep the
    acceptance claims: a 10-edge delta refresh ≥5x faster than full
    rebuild+rerun and within 1e-5 L1 of the from-scratch oracle."""
    with open(BENCH_PATH) as f:
        dyn = json.load(f)["dynamic"]
    assert dyn["delta_edges"] == 10 and dyn["n"] == 5000
    assert dyn["claim"]["meets_5x"] is True
    assert dyn["claim"]["l1_le_1e-5"] is True
    assert dyn["l1_update_vs_scratch"] <= 1e-5
    assert dyn["rebuild_rerun_ms"] / dyn["update_ms"] >= 5.0
    # the crossover sweep must exercise every strategy of the auto policy
    assert {r["strategy"] for r in dyn["delta_size_sweep"]} == {
        "push", "warm", "rebuild"}


def test_committed_bench_artifact_dynamic_sharded_claims_hold():
    """The ``dynamic_sharded`` block (benchmarks/dynamic_bench.py
    run_sharded) must keep the acceptance claims: on 8 virtual devices at
    N=5000, a ≤64-edge delta on both sharded backends refreshes via
    in-place patch + shard-local push ≥5x faster than the rebuild +
    cold-solve fallback it replaces, within 1e-5 L1 of the from-scratch
    oracle."""
    with open(BENCH_PATH) as f:
        dyn = json.load(f)["dynamic_sharded"]
    assert dyn["n"] == 5000 and dyn["devices"] >= 8
    assert dyn["delta_edges_directed"] <= 64
    assert set(dyn["backends"]) == {"ell_sharded", "dense_sharded"}
    assert dyn["claim"]["meets_5x"] is True
    assert dyn["claim"]["l1_le_1e-5"] is True
    assert dyn["claim"]["strategy_push"] is True
    for name, b in dyn["backends"].items():
        assert b["strategy"] == "push", name
        assert b["speedup_update_vs_rebuild"] >= 5.0, name
        assert b["l1_update_vs_scratch"] <= 1e-5, name
        assert b["rebuild_cold_ms"] / b["update_ms"] >= 5.0, name


def test_committed_bench_artifact_precision_claims_hold():
    """The ``precision`` block (benchmarks/precision_bench.py) must keep
    the acceptance claims: all four tiers recorded, the f32 tier
    bit-identical to the pre-precision engine, bf16 operand value bytes
    <= 0.55x f32 per layout, bf16/f16 top-100 overlap >= 0.99 and
    Kendall-tau >= 0.95 vs the f32 fixed point at tol=1e-6, and the
    <=64-edge bf16 SELL delta refreshing via push within 1e-5 of a
    same-precision cold solve.  Wall-clock speedup may only be claimed
    where the storage dtype executes natively."""
    with open(BENCH_PATH) as f:
        prec = json.load(f)["precision"]
    assert prec["n"] == 2048 and prec["tol"] == 1e-6
    tiers = prec["tiers"]
    for layout in ("dense", "ell", "bsr"):
        for p in ("f32", "bf16", "f16", "int8"):
            assert f"{layout}/{p}" in tiers, f"missing tier {layout}/{p}"
        ratio = (tiers[f"{layout}/bf16"]["value_bytes"]
                 / tiers[f"{layout}/f32"]["value_bytes"])
        assert ratio <= 0.55, f"{layout} bf16 bytes ratio {ratio:.3f}"
        for p in ("bf16", "f16"):
            t = tiers[f"{layout}/{p}"]
            assert t["top100_overlap"] >= 0.99, (layout, p)
            assert t["kendall_tau_top100"] >= 0.95, (layout, p)
    claim = prec["claim"]
    assert claim["f32_bit_identical"] is True
    assert claim["bf16_bytes_le_0.55x"] is True
    assert claim["overlap_ge_0.99"] is True
    assert claim["tau_ge_0.95"] is True
    dyn = prec["dynamic_bf16_sell"]
    assert dyn["n_changed_directed"] <= 64
    assert dyn["no_rebuild"] is True and dyn["strategy"] == "push"
    assert dyn["parity_l1_vs_cold_same_precision"] <= 1e-5
    if prec["device"] != "tpu":
        assert prec["speed_claimed"] is False, (
            "speedup must not be claimed on emulated dtypes")


def test_committed_bench_artifact_serve_claims_hold():
    """The ``serve`` block (benchmarks/serve_bench.py) must keep the
    acceptance claims: the Zipf(1.1) workload over N=5000 has >= 0.8
    achievable hit rate, cached hits answer >= 10x faster at p50 than
    the pre-PR cold solve, hub-combination answers hold top-100 overlap
    and Kendall-tau >= 0.99 vs the exact oracle, and every cache entry
    surviving the delta stream matches a post-delta cold solve within
    1e-5 L1."""
    with open(BENCH_PATH) as f:
        serve = json.load(f)["serve"]
    assert serve["n"] == 5000 and serve["zipf_s"] == 1.1
    claim = serve["claim"]
    assert claim["achievable_ge_0.8"] is True
    assert claim["achievable_hit_rate"] >= 0.8
    assert claim["hit_p50_ge_10x_faster"] is True
    assert claim["hit_p50_speedup_vs_cold"] >= 10.0
    assert claim["overlap_ge_0.99"] is True
    assert claim["min_top100_overlap"] >= 0.99
    assert claim["tau_ge_0.99"] is True
    assert claim["min_kendall_tau_top100"] >= 0.99
    assert claim["parity_le_1e-5"] is True
    assert claim["post_delta_parity_l1"] <= 1e-5
    # the measured run must have actually exercised both cache outcomes
    # and the delta-aware invalidation
    assert serve["cache"]["hits"] > 0 and serve["cache"]["misses"] > 0
    assert serve["cache"]["invalidations"] > 0
    assert serve["graph_version"] > 0
