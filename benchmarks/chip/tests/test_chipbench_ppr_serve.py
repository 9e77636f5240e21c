"""The PPR serving cell on the CPU at a tiny scale, past the look for a
chip: a sound run comes out correct; the lower-precision control, a push
that returns its warm start unchanged and a served score altered where it
is produced come out not correct.  Its per-layer metric readers on canned
records, its work function, and its seed-set draw."""
from __future__ import annotations

import jax
import numpy as np
import pytest

from benchmarks.chip import cells
from benchmarks.chip.work_ppr import batched_sweep_bytes

CELL = "graph500-20.ppr-serve"
METRICS = ["serve.sweeps", "serve.fallbacks", "serve.hbm_share",
           "device_idle.serve", "serve.host_idle_ms"]


@pytest.fixture(autouse=True)
def _fresh_programs():
    # a planted fault must be traced anew, not served from an earlier trace
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_sound_run_is_correct(run_tiny):
    res, err = run_tiny(CELL)
    assert res["correct"] is True, err
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "solve_ms"}
    checks = res["checks"]
    assert set(checks) == {"l1_vs_f64", "top10_score_err", "max_residual",
                           "answers_compared", "failed_calls"}
    assert 0 < checks["max_residual"]["value"] <= 1e-7
    assert checks["max_residual"]["limit"] == 1e-7
    assert checks["answers_compared"]["value"] >= 2
    assert "compiles_in_window=0" in err and "warm hub_build_s=" in err


def test_lower_precision_control_is_not_correct(run_tiny):
    res, _ = run_tiny(CELL, precision="bf16")
    assert res["correct"] is False
    for name in ("l1_vs_f64", "top10_score_err"):
        assert res["checks"][name]["value"] > res["checks"][name]["limit"]


def test_push_returning_its_warm_start_is_not_correct(run_tiny, monkeypatch):
    import jax.numpy as jnp

    import repro.pagerank.landmarks as lmk

    def push(operands, dang, scales, V, X0, tol, **kw):
        return X0, jnp.zeros((X0.shape[1],), jnp.float32), jnp.int32(0)
    monkeypatch.setattr(lmk, "_hub_push", push)
    res, _ = run_tiny(CELL)
    assert res["correct"] is False
    assert res["checks"]["l1_vs_f64"]["value"] > 1e-3


def test_score_altered_where_produced_is_not_correct(run_tiny, monkeypatch):
    import repro.serve.engine as se
    real = se._rank_batch

    def rank(PPR, atol, *, k):
        idx, scores, ok = real(PPR, atol, k=k)
        return idx, scores.at[:, 0].add(1e-3), ok
    monkeypatch.setattr(se, "_rank_batch", rank)
    res, _ = run_tiny(CELL)
    assert res["correct"] is False
    assert res["checks"]["top10_score_err"]["value"] >= 9e-4
    assert res["checks"]["l1_vs_f64"]["value"] < 1e-4


def test_program_without_the_residual_report_fails_before_building(
        monkeypatch):
    """A program that does not report the push's residual (the parent of
    this cell) fails in the op's constructor, before any engine exists."""
    import repro.pagerank.engine as eng
    import repro.pagerank.landmarks as lmk
    monkeypatch.delattr(lmk.LandmarkIndex, "compile_fallback")
    built = []
    monkeypatch.setattr(eng.PageRankEngine, "__init__",
                        lambda self, *a, **k: built.append(1))
    from benchmarks.chip.ops.ppr_serve import Op
    cell = cells.load_cell(cells.BENCH_DIR.parents[1], CELL)
    with pytest.raises(RuntimeError, match="compile_fallback"):
        Op(cell.config, cell.traffic, (None, None, 10), 1, "f32", None,
           cell.limits)
    assert not built


PEAKS = {"hbm_bytes_per_s": 819e9}
TRACE = {"busy_s": 8.0, "window_s": 10.0,
         "idle_gaps": [["serve", 0.3], ["landmarks.estimate", 0.2],
                       ["serve.topk", 0.04], ["landmarks.answer", 0.01],
                       ["bench.call", 0.5], ["landmarks.push", 0.9]]}


def _rec(op, counters, trace=TRACE, items=4, n=1000, nnz=20000):
    return {"cell": "c", "op": op, "end_to_end": "solve_ms",
            "precision": "f32", "n": n, "nnz": nnz,
            "items": [{"ok": True}] * items, "window_s": 10.0,
            "counters": counters, "trace": trace, "peaks": PEAKS}


SERVE = _rec("ppr_serve", {"ppr.sweeps": 84, "ppr.column_sweeps": 1344,
                           "landmarks.fallbacks": 2})
SERVE_NO_FALLBACK = _rec("ppr_serve", {"ppr.sweeps": 80,
                                       "ppr.column_sweeps": 1280})
SOLVE = _rec("solve_tol", {"ppr.sweeps": 84, "ppr.column_sweeps": 1344})

EXPECT = {
    ("serve.sweeps", "SERVE"): 21.0,
    ("serve.sweeps", "SERVE_NO_FALLBACK"): 20.0,
    ("serve.fallbacks", "SERVE"): 0.5,
    ("serve.fallbacks", "SERVE_NO_FALLBACK"): 0.0,
    ("serve.hbm_share", "SERVE"):
        100 * (8 * 20000 * 84 + (4 * 20000 + 16 * 1000) * 1344)
        / 819e9 / 8.0,
    ("device_idle.serve", "SERVE"): 20.0,
    ("serve.host_idle_ms", "SERVE"): 1e3 * (0.3 + 0.2 + 0.04 + 0.01) / 4,
}


@pytest.mark.parametrize("name,rec", sorted(EXPECT), ids=str)
def test_metric_reader_on_canned_record(name, rec):
    got = cells.metric_reader(name)(globals()[rec])
    assert got == pytest.approx(EXPECT[(name, rec)], rel=1e-12)


@pytest.mark.parametrize("name", METRICS)
def test_readers_read_nothing_of_another_op_or_without_a_trace(name):
    read = cells.metric_reader(name)
    assert read(SOLVE) is None
    if name in ("serve.hbm_share", "device_idle.serve",
                "serve.host_idle_ms"):
        assert read(dict(SERVE, trace=None)) is None


def test_batched_sweep_bytes_counts_shared_and_per_column_bytes():
    # one sweep of one column reads what a single-vector sweep reads
    from benchmarks.chip.work import sweep_bytes
    assert batched_sweep_bytes(10, 100, 1, 1) == sweep_bytes(10, 100)
    # value and index are read once per sweep, whatever the batch width
    assert batched_sweep_bytes(10, 100, 2, 32) == 8 * 100 * 2 + (
        4 * 100 + 16 * 10) * 32


def test_seed_sets_are_fresh_and_the_same_sets_in_every_runs_labels():
    from benchmarks.chip import graphs
    from benchmarks.chip.ops.ppr_serve import SeedSets
    cell = cells.load_cell(cells.BENCH_DIR.parents[1], CELL)
    n = 50
    runs = {}
    for seed in (2**31 + 1, 7):
        sets = SeedSets(cell.traffic, (None, None, n), seed, stream=5)
        drawn = sets.draw(40)
        assert len({s.tobytes() for s in drawn}) == 40
        assert all(1 <= len(s) <= 4 for s in drawn)
        inv = np.argsort(graphs.permutation(seed, n))
        runs[seed] = [np.sort(inv[s]).tolist() for s in drawn]
    assert runs[2**31 + 1] == runs[7]
