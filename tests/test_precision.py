"""Reduced-precision layout tiers (bf16/f16/int8) with f32 accumulation.

The contract under test:

* the ``f32`` tier is **bit-identical** to the pre-precision engine — the
  upcasts are trace-time no-ops and the dense fast paths stay gated on
  f32, so the very same XLA programs dispatch;
* the low tiers halve (bf16/f16) or quarter (int8 values) the operand
  bytes while every kernel accumulates in f32, keeping rank *ordering*
  essentially intact (top-100 overlap / Kendall-tau gates on the N=2048
  Barabasi-Albert graph);
* structural invariants (non-negativity exactly, sum-to-1 within a
  storage-dtype-sized slack) hold on every backend x precision;
* the dynamic engine patches bf16/f16 layouts in place without widening
  them (insert-then-delete restores the arrays bit-exactly; a <=64-edge
  delta refreshes a bf16 SELL layout via push, within 1e-5 of a fresh
  same-precision cold solve), and int8 deltas coerce to rebuild;
* user solve inputs are coerced at exactly one warned point
  (``solve_dtype``), never silently.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.graph import generators as gen
from repro.graph.delta import GraphDelta
from repro.kernels.common import upcast_f32
from repro.pagerank import PageRankEngine
from repro.pagerank.dynamic import DynamicPageRankEngine
from repro.pagerank.fidelity import kendall_tau, l1, topk_overlap
from repro.pagerank.precision import (PRECISIONS, layout_nbytes,
                                      resolve_precision, solve_dtype)
from repro.obs.registry import MetricsRegistry

BACKENDS = ["dense", "ell", "bsr", "pallas_dense",
            "dense_sharded", "ell_sharded"]

# sum-to-1 slack per tier: the quantized transition columns sum to
# 1 +- O(storage eps), and the fixed point inherits that scale of drift
# (int8's 1/127 quantization grid is the coarsest)
SUM_TOL = {"f32": 1e-5, "bf16": 0.06, "f16": 0.01, "int8": 0.2}


@pytest.fixture(scope="module")
def net():
    n = 200
    src, dst = gen.protein_network(n, seed=3)
    return src, dst, n


# --------------------------- f32 bit-identity --------------------------- #
@pytest.mark.parametrize("backend", BACKENDS)
def test_f32_tier_bit_identical_to_default(backend, net):
    """precision='f32' (and the 'auto' default) must dispatch the exact
    program the engine dispatched before precision existed."""
    src, dst, n = net
    base = PageRankEngine(src, dst, n, backend=backend)
    f32 = PageRankEngine(src, dst, n, backend=backend, precision="f32")
    assert base.precision == "f32"                  # auto resolves to f32
    iters = 15 if backend == "pallas_dense" else 60
    assert np.array_equal(np.asarray(base.run(iters)),
                          np.asarray(f32.run(iters)))
    a = base.run_tol(tol=1e-8)
    b = f32.run_tol(tol=1e-8)
    assert np.array_equal(np.asarray(a[0]), np.asarray(b[0]))
    assert int(a[1]) == int(b[1])


# ----------------------- structural property gates ---------------------- #
@pytest.mark.parametrize("precision", PRECISIONS)
@pytest.mark.parametrize("backend", BACKENDS)
def test_rank_invariants_all_backends_precisions(backend, precision, net):
    src, dst, n = net
    eng = PageRankEngine(src, dst, n, backend=backend, precision=precision)
    if precision != "f32":
        assert f"[{precision}]" in eng.layout
    pr, _, res = eng.run_tol(tol=1e-6, max_iters=500)
    pr = np.asarray(pr, np.float64)
    assert np.isfinite(pr).all()
    # every term of the iteration is non-negative: exact, not approximate
    assert pr.min() >= 0.0
    assert abs(pr.sum() - 1.0) <= SUM_TOL[precision], (
        f"sum={pr.sum():.6f} outside the {precision} slack")


@pytest.mark.parametrize("precision", ["bf16", "f16", "int8"])
def test_low_tiers_halve_value_bytes(precision, net):
    src, dst, n = net
    for backend in ("dense", "ell", "bsr"):
        f32 = PageRankEngine(src, dst, n, backend=backend)
        low = PageRankEngine(src, dst, n, backend=backend,
                             precision=precision)
        ratio = (low.layout_bytes["value_bytes"]
                 / f32.layout_bytes["value_bytes"])
        # bf16/f16 are exactly half; int8 is a quarter plus f32 scales
        assert ratio <= 0.55, (backend, precision, ratio)
        # index payload is unchanged by the value dtype
        assert (low.layout_bytes["index_bytes"]
                == f32.layout_bytes["index_bytes"])


def test_layout_bytes_gauge_and_accounting(net):
    src, dst, n = net
    m = MetricsRegistry()
    eng = PageRankEngine(src, dst, n, backend="ell", precision="bf16",
                         metrics=m)
    lb = eng.layout_bytes
    assert lb["total_bytes"] == lb["value_bytes"] + lb["index_bytes"]
    assert m.gauge("layout.bytes").value == lb["total_bytes"]
    # layout_nbytes over the operands agrees with the engine's record
    assert layout_nbytes(tuple(eng.operands)) == lb


# ------------------------- rank-fidelity gates -------------------------- #
def test_bf16_f16_top100_fidelity_n2048():
    """ISSUE acceptance: on the N=2048 BA graph at tol=1e-6, bf16 and f16
    keep top-100 overlap >= 0.99 and Kendall-tau >= 0.95 vs the f32 fixed
    point."""
    n = 2048
    src, dst = gen.barabasi_albert(n, 8, seed=0)
    ref = np.asarray(PageRankEngine(src, dst, n, backend="ell")
                     .run_tol(tol=1e-8, max_iters=3000)[0])
    for precision in ("bf16", "f16"):
        eng = PageRankEngine(src, dst, n, backend="ell",
                             precision=precision)
        pr = np.asarray(eng.run_tol(tol=1e-6, max_iters=2000)[0])
        assert topk_overlap(pr, ref, k=100) >= 0.99, precision
        assert kendall_tau(pr, ref, k=100) >= 0.95, precision


def test_fidelity_helpers_are_exact_on_identical_input():
    x = np.random.default_rng(0).random(500)
    assert topk_overlap(x, x, k=50) == 1.0
    assert kendall_tau(x, x, k=50) == 1.0
    assert l1(x, x) == 0.0


@pytest.mark.parametrize("precision", ["bf16", "int8"])
def test_sell_tiers_low_precision_on_hub_graph(precision):
    """The ``ell`` tier's SELL stores its reduced values per degree tier
    (int8 with per-row scales in every tier); on a graph with hub rows far
    above the 90th degree percentile it keeps the tier's bounds."""
    n = 400
    src, dst = gen.barabasi_albert(n, 3, seed=1)
    ref = PageRankEngine(src, dst, n, backend="ell")
    eng = PageRankEngine(src, dst, n, backend="ell", precision=precision)
    assert len(eng._sell.widths) > 3 and f"[{precision}]" in eng.layout
    assert all(t[0].dtype == eng.storage_dtype for t in eng.operands[1])
    assert all(len(t) == (3 if precision == "int8" else 2)
               for t in eng.operands[1])
    pr = np.asarray(eng.run_tol(tol=1e-6, max_iters=500)[0], np.float64)
    assert np.isfinite(pr).all() and pr.min() >= 0.0
    assert abs(pr.sum() - 1.0) <= SUM_TOL[precision]
    want = np.asarray(ref.run_tol(tol=1e-8, max_iters=500)[0])
    assert topk_overlap(pr, want, k=100) >= 0.99


# ----------------------------- dynamic tiers ---------------------------- #
@pytest.mark.parametrize("backend", ["dense", "ell", "bsr", "pallas_dense"])
def test_dynamic_insert_then_delete_restores_bf16_bitexact(backend, net):
    """In-place patches write deltas in the layout's storage dtype: an
    insert-then-delete round trip must restore the reduced-precision
    arrays bit-exactly (no widening, no drift)."""
    src, dst, n = net
    eng = DynamicPageRankEngine(src, dst, n, backend=backend,
                                precision="bf16")
    eng.run_tol(tol=1e-6)

    def arrays():
        ops = (eng.operands if backend != "bsr"
               else (eng.operands[0].blocks, eng.operands[0].block_cols))
        return [np.asarray(o) for o in jax.tree.leaves(ops)]

    before = arrays()
    assert any(a.dtype == jnp.bfloat16 for a in before)
    # pick a guaranteed non-edge so the insert is never a noop
    u = 11
    existing = set((eng._keys[(eng._keys // n) == u] % n).tolist())
    v = next(w for w in range(n) if w != u and w not in existing
             and u not in set((eng._keys[(eng._keys // n) == w]
                               % n).tolist()))
    ins = GraphDelta(insert_src=np.array([u]), insert_dst=np.array([v]),
                     delete_src=np.empty(0, np.int64),
                     delete_dst=np.empty(0, np.int64))
    rem = GraphDelta(insert_src=np.empty(0, np.int64),
                     insert_dst=np.empty(0, np.int64),
                     delete_src=np.array([u]), delete_dst=np.array([v]))
    _, i1 = eng.update(ins, tol=1e-7)
    _, i2 = eng.update(rem, tol=1e-7)
    assert i1.strategy in ("push", "warm") and i1.coerced_from is None
    assert i2.strategy in ("push", "warm") and i2.coerced_from is None
    after = arrays()
    assert all(b.dtype == a.dtype for b, a in zip(before, after))
    assert all(np.array_equal(b, a) for b, a in zip(before, after))


def test_dynamic_bf16_sell_push_parity_64_edges():
    """ISSUE acceptance: a <=64-edge delta on a bf16 SELL layout refreshes
    via push (no rebuild) and lands within 1e-5 L1 of a fresh
    same-precision engine cold-solving the post-delta graph."""
    n = 512
    src, dst = gen.barabasi_albert(n, 6, seed=2)
    eng = DynamicPageRankEngine(src, dst, n, backend="ell",
                                precision="bf16")
    eng.run_tol(tol=1e-7)
    rng = np.random.default_rng(9)
    k = 32                                    # 64 directed under symmetric
    ins_s = rng.integers(0, n, k)
    ins_d = (ins_s + rng.integers(1, n, k)) % n
    delta = GraphDelta(insert_src=ins_s, insert_dst=ins_d,
                       delete_src=np.empty(0, np.int64),
                       delete_dst=np.empty(0, np.int64))
    pr, info = eng.update(delta, tol=1e-7)
    assert info.strategy == "push" and info.coerced_from is None
    assert info.n_inserted + info.n_deleted <= 64
    # storage stayed bf16 through the patch: every SELL tier's data
    assert all(data.dtype == jnp.bfloat16 for data, _ in eng.operands[1])

    keys = eng._keys
    oracle = DynamicPageRankEngine((keys // n).astype(np.int32),
                                   (keys % n).astype(np.int32), n,
                                   backend="ell", precision="bf16")
    pr_ref, *_ = oracle.run_tol(tol=1e-7)
    assert l1(np.asarray(pr), np.asarray(pr_ref)) <= 1e-5


def test_dynamic_int8_delta_coerces_to_rebuild(net):
    """int8 rows can't be value-patched (the per-row scale would go
    stale), so the auto policy records a coerced rebuild."""
    src, dst, n = net
    eng = DynamicPageRankEngine(src, dst, n, backend="ell",
                                precision="int8")
    eng.run_tol(tol=1e-6)
    delta = GraphDelta(insert_src=np.array([3]), insert_dst=np.array([90]),
                       delete_src=np.empty(0, np.int64),
                       delete_dst=np.empty(0, np.int64))
    _, info = eng.update(delta, tol=1e-6)
    assert info.strategy == "rebuild"
    assert info.coerced_from in ("push", "warm")
    # forcing a patch strategy on the (non-patchable) int8 layout raises;
    # the delete delta is non-empty, so it can't short-circuit as a noop
    undo = GraphDelta(insert_src=np.empty(0, np.int64),
                      insert_dst=np.empty(0, np.int64),
                      delete_src=np.array([3]), delete_dst=np.array([90]))
    with pytest.raises(ValueError, match="patchable"):
        eng.update(undo, strategy="push")


# ------------------------- solve-input coercion ------------------------- #
def test_solve_dtype_single_warned_f64_downcast(net):
    src, dst, n = net
    eng = PageRankEngine(src, dst, n, backend="ell")
    x0 = np.full(n, 1.0 / n, np.float64)
    with pytest.warns(UserWarning, match="float64"):
        pr, *_ = eng.run_tol(tol=1e-6, x0=x0)
    assert pr.dtype == jnp.float32

    # f32 input passes through untouched; python floats never warn
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        x = jnp.full((n,), 1.0 / n, jnp.float32)
        assert solve_dtype(x) is x
        assert solve_dtype(None) is None
        t = solve_dtype(1e-6, name="tol")
        assert t.dtype == jnp.float32
        eng.run_tol(tol=1e-6, x0=np.full(n, 1.0 / n, np.float32))


def test_resolve_precision_and_upcast_helpers():
    assert resolve_precision("auto") == "f32"
    for p in PRECISIONS:
        assert resolve_precision(p) == p
    with pytest.raises(ValueError, match="precision"):
        resolve_precision("f8")
    with pytest.raises(ValueError, match="precision"):
        PageRankEngine(np.array([0]), np.array([1]), 2, precision="f64")
    x = jnp.ones(4, jnp.float32)
    assert upcast_f32(x) is x                   # trace-time no-op on f32
    assert upcast_f32(x.astype(jnp.bfloat16)).dtype == jnp.float32


# ------------------------------ events ---------------------------------- #
def test_solve_event_carries_precision_tier(net):
    src, dst, n = net
    m = MetricsRegistry()
    eng = PageRankEngine(src, dst, n, backend="ell", precision="f16",
                         metrics=m)
    eng.run_tol(tol=1e-6)
    solves = [e for e in m.events if e["kind"] == "solve"]
    assert solves and solves[-1]["precision"] == "f16"
