"""Whole runs of each cell on the CPU at a tiny scale, past the look for a
chip: sound runs come out correct, and a run with the timed path broken
underneath, or at the lower-precision control, comes out not correct.

The faults planted are those of ``faults.py``, which reads the same
faults on the chip at each cell's own size.
"""
from __future__ import annotations

import jax
import pytest

from benchmarks.chip.tests import faults

CELLS = ["graph500-20.pr-tol", "protein-5k.pr-fixed100",
         "graph500-20.delta-stream"]


@pytest.fixture(autouse=True)
def _fresh_programs():
    # a planted fault must be traced anew, not served from an earlier trace
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(run_tiny, name):
    res, err = run_tiny(name)
    assert res["correct"] is True, err
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) >= {"setup_s"} and len(res["metrics"]) == 2
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert err.rstrip().splitlines()[-1].startswith("check ")


@pytest.mark.parametrize("name", CELLS)
def test_lower_precision_control_is_not_correct(run_tiny, name):
    res, _ = run_tiny(name, precision="bf16")
    assert res["correct"] is False
    assert res["checks"]["l1_vs_f64"]["value"] > \
        res["checks"]["l1_vs_f64"]["limit"]


@pytest.mark.parametrize("name", CELLS)
def test_state_left_unchanged_is_not_correct(run_tiny, monkeypatch, name):
    faults.unchanged(monkeypatch, name)
    res, _ = run_tiny(name)
    assert res["correct"] is False
    if name.endswith("delta-stream"):
        rel = res["checks"]["l1_over_change"]
        assert rel["value"] > rel["limit"]


@pytest.mark.parametrize("name", CELLS)
def test_answer_altered_where_produced_is_not_correct(run_tiny, monkeypatch,
                                                      name):
    faults.altered(monkeypatch, name)
    res, _ = run_tiny(name)
    assert res["correct"] is False


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result(capsys):
    from benchmarks.chip import run
    rc = run.main(["--workload", CELLS[1], "--seed", str(2**31 + 9),
                   "--seconds", "1"])
    out, err = capsys.readouterr()
    assert rc != 0 and out == "" and "no TPU" in err


def test_fault_script_plants_and_needs_a_chip(capsys):
    import repro.pagerank.engine as eng
    step = eng.sparse_step
    rc = faults.main(["unchanged", "--workload", CELLS[0], "--seconds", "1",
                      "--seeds", "3"])
    assert rc != 0 and "no TPU" in capsys.readouterr().err
    assert eng.sparse_step is step


class _Dev:
    platform, device_kind = "tpu", "TPU v5 lite"


@pytest.mark.parametrize("seen,chips", [(1, 1), (4, 4), (4, 1), (1, 4)])
def test_require_tpu_wants_exactly_the_cells_chips(monkeypatch, seen, chips):
    from benchmarks.chip import run
    monkeypatch.setattr(jax, "devices", lambda: [_Dev()] * seen)
    if seen != chips:
        with pytest.raises(run.NoChip):
            run.require_tpu(chips)
    else:
        assert run.require_tpu(chips) == {
            "platform": "tpu", "kind": "TPU v5 lite", "count": seen}
