"""Rehearsal of ``chip_smoke.py`` on the CPU at a tiny size.

The script refuses to run without a TPU.  These tests call its phase
functions directly, with the device expectations relaxed here: one
device for the single-chip phases (the suite runs on eight virtual CPU
devices), and the Pallas tiers interpreted.  Every check the phases make
on the chip is made here too, against the same float64 references.
"""
import importlib.util
from pathlib import Path

import jax
import pytest

from repro.graph import generators as gen

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def one_cpu_chip(smoke, monkeypatch):
    """The single-chip phases as on one chip: auto sees one device, and
    the Pallas tiers interpret on the CPU."""
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    monkeypatch.setattr(smoke, "EXPECT_INTERPRET", True)
    return smoke


def test_device_check_refuses_cpu(smoke):
    with pytest.raises(smoke.NoChip, match="no TPU"):
        smoke.require_tpu(1)


def test_main_without_chip_prints_no_result(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "no TPU" in out.err


def test_paper_phase_tiny(one_cpu_chip):
    chk = one_cpu_chip.Checks()
    one_cpu_chip.phase_paper(chk, n=256, n_iters=20, d=0.85, seed=0)
    assert not chk.failed


def test_global_and_serve_phases_tiny(one_cpu_chip):
    n = 4096
    src, dst = gen.protein_network(n, seed=0)
    chk = one_cpu_chip.Checks()
    one_cpu_chip.phase_global(chk, src, dst, n, 0.85)
    one_cpu_chip.phase_serve(chk, src, dst, n, 0.85, seed=0)
    assert not chk.failed


def test_multichip_phase_tiny(smoke, multi_device):
    chk = smoke.Checks()
    smoke.phase_multichip(chk, n=2048, n_paper=256, n_iters=30, d=0.85,
                          seed=0)
    assert not chk.failed


def test_phase_failure_is_reported(smoke, capsys):
    chk = smoke.Checks()

    def boom():
        raise ValueError("broken phase")

    smoke.run_phases(chk, [("boom", boom), ("next", lambda: None)])
    assert len(chk.failed) == 1 and "phase=boom" in chk.failed[0]
    assert "phase=next" in capsys.readouterr().out
