"""Device time by named scope (``scopes.py``): on synthetic traces, on the
trace ``test_chipbench_trace.py`` pins, and pinned on a small trace of the
scoped program recorded on a TPU v5e (``testdata_scopes/``, made by
``record_scoped_trace.py``); and the readers of the per-layer metrics
that read the program's compile counter and its refresh spans."""
from __future__ import annotations

import subprocess
import sys
from pathlib import Path

import pytest

from benchmarks.chip import cells, scopes, trace

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
SCOPED = BENCH / "testdata_scopes"


@pytest.mark.parametrize("tf_op,want", [
    ("jit(_run_tol)/while/body/pagerank.vector/pagerank.ell_gather/gather:",
     "pagerank.ell_gather"),
    ("jit(_run_tol)/while/body/pagerank.vector/add:", "pagerank.vector"),
    ("jit(_push_tol)/while/body/pagerank.push/pagerank.vector/"
     "pagerank.sell_order/gather:", "pagerank.sell_order"),
    ("jit(_run_fixed)/while/body/closed_call/gather:", scopes.UNSCOPED),
    ("", scopes.UNSCOPED),
])
def test_scope_of_is_the_innermost_pagerank_scope(tf_op, want):
    assert scopes.scope_of(tf_op) == want


def _space(device_ops, host_events):
    """An ``XSpace`` with one TPU plane whose ``XLA Ops`` line holds
    ``device_ops`` (``(start_ns, dur_ns, tf_op or None)``) and one host
    plane holding ``host_events`` (``(name, start_ns, dur_ns)``)."""
    pb = scopes.xplane_schema()
    space = pb.XSpace()
    dev = space.planes.add(name="/device:TPU:0")
    dev.stat_metadata[7].name = "tf_op"
    ops = dev.lines.add(name="XLA Ops", timestamp_ns=1000)
    for i, (start, dur, tf_op) in enumerate(device_ops, 1):
        md = dev.event_metadata[i]
        md.id, md.name = i, f"%op.{i}"
        if tf_op is not None:
            md.stats.add(metadata_id=7, str_value=tf_op)
        ops.events.add(metadata_id=i, offset_ps=start * 1000,
                       duration_ps=dur * 1000)
    host = space.planes.add(name="/host:CPU")
    line = host.lines.add(name="main", timestamp_ns=1000)
    for i, (name, start, dur) in enumerate(host_events, 1):
        host.event_metadata[i].id, host.event_metadata[i].name = i, name
        line.events.add(metadata_id=i, offset_ps=start * 1000,
                        duration_ps=dur * 1000)
    return space


def test_nested_ops_go_to_their_innermost_scope():
    ops = [(100, 400, "jit(f)/while:"),
           (150, 100, "jit(f)/while/body/pagerank.vector/"
                      "pagerank.ell_gather/gather:"),
           (300, 100, "jit(f)/while/body/pagerank.vector/add:"),
           (700, 100, None),
           (900, 200, "jit(g)/pagerank.row_patch/scatter:")]
    got = scopes.scope_times(_space(ops, [("bench.window", 0, 1000)]))
    # the while's self time (400 - 200) and the op with no tf_op are
    # unscoped; the last op is clipped to the window's end
    assert got == pytest.approx({scopes.UNSCOPED: 300e-9,
                                 "pagerank.ell_gather": 100e-9,
                                 "pagerank.vector": 100e-9,
                                 "pagerank.row_patch": 100e-9})


def test_without_a_window_span_the_ops_bound_the_window():
    got = scopes.scope_times(_space([(10, 30, "a/pagerank.push/mul:")],
                                    [("other", 0, 5)]))
    assert got == pytest.approx({"pagerank.push": 30e-9})


def test_no_device_operation_is_an_error():
    with pytest.raises(ValueError):
        scopes.scope_times(_space([], [("bench.window", 0, 10)]))


def test_missing_schema_reads_none(monkeypatch):
    monkeypatch.setattr(scopes, "xplane_schema", lambda: None)
    assert scopes.reduce_dir(BENCH / "testdata") is None


def test_unscoped_trace_sums_to_its_busy_time():
    # the trace pinned by test_chipbench_trace.py predates the scopes:
    # every op is unscoped, and self times add up to the busy time
    got = scopes.reduce_dir(BENCH / "testdata")
    busy = trace.reduce_dir(BENCH / "testdata")["busy_s"]
    assert list(got) == [scopes.UNSCOPED]
    assert got[scopes.UNSCOPED] == pytest.approx(busy, abs=1e-8)


def test_reading_a_trace_imports_no_tensorflow():
    code = ("import sys; from benchmarks.chip import scopes; "
            f"assert scopes.reduce_dir({str(SCOPED)!r}); "
            "assert 'tensorflow' not in sys.modules")
    subprocess.run([sys.executable, "-c", code], cwd=ROOT, check=True,
                   timeout=120)


def test_pinned_on_a_scoped_trace_recorded_on_the_chip():
    # three 100-iteration solves of the 5,000-node protein network, then
    # a 4-edge insert and its inverse on the dynamic engine, on one TPU
    # v5 lite; numbers read once from this file and pinned
    got = scopes.reduce_dir(SCOPED)
    r = trace.reduce_dir(SCOPED)
    # the trace's clock is in picoseconds, trace.py's events in whole ns
    assert sum(got.values()) == pytest.approx(r["busy_s"], rel=1e-5)
    assert set(got) == PINNED.keys()
    assert got == pytest.approx(PINNED, abs=1e-9)
    assert got[scopes.UNSCOPED] < 0.1 * r["busy_s"]


PINNED = {scopes.UNSCOPED: 0.001090899184,
          "pagerank.coo_tail": 0.028070312310,
          "pagerank.ell_gather": 0.160168221448,
          "pagerank.push": 0.000015674532,
          "pagerank.row_patch": 0.000046662658,
          "pagerank.sell_order": 0.000832708202,
          "pagerank.vector": 0.000525958278}


def test_window_spans_and_per_call_readings():
    before = {"span.update.plan": {"count": 1, "sum": 10.0},
              "serve.batch_ms": {"count": 3, "sum": 1.0}}
    after = {"span.update.plan": {"count": 3, "sum": 50.0},
             "span.update.patch": {"count": 2, "sum": 8.0},
             "span.idle": {"count": 0, "window": 8},
             "serve.batch_ms": {"count": 9, "sum": 2.0}}
    spans = scopes.window_spans(before, after)
    assert spans == {"update.plan": {"count": 2, "ms": 40.0},
                     "update.patch": {"count": 2, "ms": 8.0}}
    got = scopes.per_call({"pagerank.ell_gather": 0.5,
                           "pagerank.row_patch": 0.002}, spans, 2)
    assert got == pytest.approx({"gather_ms": 250.0, "tail_ms": None,
                                 "scatter_ms": 1.0, "plan_ms": 20.0,
                                 "patch_ms": 4.0})
    assert scopes.per_call(None, {}, 2) == dict.fromkeys(got)


def _rec(e2e, op, items, counters, idle_gaps):
    trace_ = {"busy_s": 8.0, "window_s": 10.0, "idle_gaps": idle_gaps}
    return {"cell": "c", "op": op, "end_to_end": e2e, "precision": "f32",
            "n": 1000, "nnz": 20000, "items": items, "window_s": 10.0,
            "counters": counters, "trace": trace_,
            "peaks": {"hbm_bytes_per_s": 819e9}}


DELTA = _rec("update_ms", "delta", [{"iters": 2}] * 4, {"compiles": 0},
             [["update.plan.keys", 12.0], ["update.patch.rows", 6.0],
              ["update.plan.rows", 4.0], ["update.patch", 2.0],
              ["update", 0.5]])
# what the program before the refresh spans and compile watch gives
OLD_DELTA = _rec("update_ms", "delta", [{"iters": 2}] * 4, {},
                 [["update", 18.0], ["update.patch", 9.0]])
SOLVE = _rec("solve_ms", "solve_tol", [{"iters": 17}] * 3, {"compiles": 0},
             [["bench.call", 0.01]])

EXPECT = {
    ("update.compiles", "DELTA"): 0,
    ("update.compiles", "OLD_DELTA"): None,
    ("update.compiles", "SOLVE"): None,
    ("update.plan_idle_ms", "DELTA"): 4000.0,
    ("update.plan_idle_ms", "OLD_DELTA"): None,
    ("update.plan_idle_ms", "SOLVE"): None,
    ("update.patch_idle_ms", "DELTA"): 2000.0,
    ("update.patch_idle_ms", "OLD_DELTA"): 2250.0,
    ("update.patch_idle_ms", "SOLVE"): None,
}


@pytest.mark.parametrize("name,rec", sorted(EXPECT), ids=str)
def test_refresh_reader_on_canned_record(name, rec):
    got = cells.metric_reader(name)(globals()[rec])
    want = EXPECT[(name, rec)]
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("name", ["update.plan_idle_ms",
                                  "update.patch_idle_ms"])
def test_idle_readers_read_nothing_without_a_trace(name):
    assert cells.metric_reader(name)(dict(DELTA, trace=None)) is None
