"""The trace reduction: on synthetic events, and pinned on a small trace
recorded on a TPU v5e (``testdata/``, made by ``record_trace.py``)."""
from __future__ import annotations

from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from benchmarks.chip import trace


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _profile(device_lines, host_events):
    dev = [NS(name=f"/device:TPU:{i}",
              lines=[NS(name="XLA Modules", events=[]),
                     NS(name="XLA Ops", events=evs)])
           for i, evs in enumerate(device_lines)]
    host = NS(name="/host:CPU",
              lines=[NS(name="main", events=host_events)])
    return NS(planes=[host] + dev)


def test_busy_window_ops_and_idle_gaps():
    ops = [_ev("while", 100, 400), _ev("gather", 150, 100),
           _ev("scatter", 300, 100), _ev("fusion", 700, 100)]
    host = [_ev("bench.window", 0, 1000), _ev("bench.call", 60, 490),
            _ev("solve", 70, 460), _ev("bench.call", 600, 300)]
    r = trace.reduce_profile(_profile([ops], host))
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(500e-9)
    assert r["devices"] == 1
    assert dict(r["device_ops"]) == pytest.approx(
        {"while": 200e-9, "gather": 100e-9, "scatter": 100e-9,
         "fusion": 100e-9})
    # gaps by midpoint: [0,100) at 50 in bench.window alone, [500,700) at
    # 600 in the second bench.call, [800,1000) at 900 after that call ended
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"bench.window": 300e-9, "bench.call": 200e-9})


def test_busy_is_averaged_over_devices_and_clipped_to_the_window():
    a = [_ev("x", 0, 500)]
    b = [_ev("x", 400, 1000)]
    host = [_ev("bench.window", 100, 800)]
    r = trace.reduce_profile(_profile([a, b], host))
    assert r["window_s"] == pytest.approx(800e-9)
    assert r["busy_s"] == pytest.approx((400e-9 + 500e-9) / 2)


def test_no_device_operation_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce_profile(_profile([[]], [_ev("bench.window", 0, 10)]))


def test_op_name_keeps_name_shape_and_opcode():
    assert trace.op_name(
        "%fusion.8 = f32[34909110]{0:T(1024)} fusion(f32[646465]{0:T(1024)"
        "S(1)} %custom-call.8), kind=kCustom") == "%fusion.8 = f32[34909110] fusion"
    assert trace.op_name(
        "%while.3 = (s32[]{:T(128)}, f32[5000]{0:T(1024)}) while((s32[]"
        "{:T(128)}, f32[5000]{0:T(1024)}) %tuple.24)") == \
        "%while.3 = (s32[], f32[5000]) while"


def test_pinned_on_a_trace_recorded_on_the_chip():
    # three 100-iteration solves of the 5,000-node protein network on one
    # TPU v5 lite; numbers read once from this file and pinned
    r = trace.reduce_dir(Path(__file__).resolve().parents[1] / "testdata")
    assert r["devices"] == 1
    assert r["busy_s"] == pytest.approx(0.170865986, abs=1e-9)
    assert r["window_s"] == pytest.approx(0.176291302, abs=1e-9)
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.030774, abs=1e-5)
    names = [n for n, _ in r["device_ops"]]
    assert names[:3] == ["%fusion.11 = f32[70000] fusion",
                         "%fusion.14 = f32[5000] fusion",
                         "%fusion.13 = f32[5993] fusion"]
    assert r["device_ops"][0][1] == pytest.approx(0.138887093, abs=1e-9)
    assert len(r["device_ops"]) == trace.TOP
    assert sum(t for _, t in r["device_ops"]) <= r["busy_s"] + 1e-9
    assert r["idle_gaps"] == [["bench.call", pytest.approx(0.005425316,
                                                           abs=1e-9)]]
