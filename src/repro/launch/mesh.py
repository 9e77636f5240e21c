"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — smoke tests see 1 device; only
``dryrun.py`` forces 512 host-platform devices.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _axis_kwargs(n_axes: int) -> dict:
    return {"axis_types": (AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16 x 16 = 256 chips per pod; 2 x 16 x 16 = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_kwargs(len(axes)))


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    """Arbitrary mesh (tests, examples, elastic re-mesh)."""
    return jax.make_mesh(shape, axes, **_axis_kwargs(len(axes)))


def make_host_mesh() -> Mesh:
    """Whatever devices exist right now, as a (data, model) mesh with
    model=1 — the CPU/test fallback."""
    n = len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))
