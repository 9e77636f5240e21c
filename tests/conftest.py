"""Test-suite bootstrap.

Both settings below must be in the environment before anything imports
``jax``; conftest is imported before every test module, and child
processes that a test starts inherit them.

1. **CPU only.**  ``JAX_PLATFORMS`` defaults to ``cpu``, so neither pytest
   nor any child it starts takes an accelerator: a chip belongs to one
   process at a time, and the chip is the job of ``chip_smoke.py``.  A
   caller that sets ``JAX_PLATFORMS`` itself keeps its value.

2. **Virtual multi-device CPU.**  The sharded engine tiers
   (``dense_sharded`` / ``ell_sharded``) need a real device mesh; on the
   CPU we get one by injecting ``--xla_force_host_platform_device_count=8``
   into ``XLA_FLAGS``.  Single-device code paths are unaffected —
   unsharded arrays live on device 0.  Opt out with
   ``REPRO_SINGLE_DEVICE=1``; tests that genuinely need the mesh take the
   ``multi_device`` fixture, which skips (rather than fails) if the
   injection could not take effect (e.g. jax was already initialized by a
   plugin).
"""
from __future__ import annotations

import os

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

if (os.environ.get("REPRO_SINGLE_DEVICE") != "1"
        and "xla_force_host_platform_device_count"
        not in os.environ.get("XLA_FLAGS", "")):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()


@pytest.fixture(scope="session")
def multi_device():
    """Device count when >1 virtual device is actually live; skips the
    test otherwise (env injection can only work if jax initialized after
    conftest import)."""
    import jax
    n = jax.device_count()
    if n < 2:
        pytest.skip("sharded tiers need >1 device; XLA_FLAGS injection "
                    "did not take effect")
    return n
