"""Distributed fabric-MV (shard_map) tests.

In-process tests run on a trivial 1x1 mesh; the multi-device semantics
(real collectives, block permutation) run in a subprocess on a 2x2 mesh of
four virtual CPU devices, the shape of a four-chip host.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import fabric_matvec as fm


def _mesh11():
    from repro.launch.mesh import make_mesh
    return make_mesh((1, 1), ("data", "model"))


def test_matvec_single_device():
    mesh = _mesh11()
    A = jax.random.normal(jax.random.PRNGKey(0), (8, 8))
    x = jax.random.normal(jax.random.PRNGKey(1), (8,))
    y = fm.matvec(A, x, mesh)
    np.testing.assert_allclose(np.asarray(y), np.asarray(A) @ np.asarray(x),
                               rtol=1e-5)


def test_matvec_scatter_single_device():
    mesh = _mesh11()
    A = jax.random.normal(jax.random.PRNGKey(0), (8, 8))
    x = jax.random.normal(jax.random.PRNGKey(1), (8,))
    y = fm.matvec_scatter(A, x, mesh)
    np.testing.assert_allclose(np.asarray(y), np.asarray(A) @ np.asarray(x),
                               rtol=1e-5)


def test_gemv_batched_single_device():
    mesh = _mesh11()
    W = jax.random.normal(jax.random.PRNGKey(2), (16, 8))
    X = jax.random.normal(jax.random.PRNGKey(3), (4, 8))
    Y = fm.fabric_gemv_batched(W, X, mesh)
    np.testing.assert_allclose(np.asarray(Y),
                               np.asarray(X) @ np.asarray(W).T,
                               rtol=1e-4, atol=1e-5)


_SUBPROCESS_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys; sys.path.insert(0, {src!r})
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import fabric_matvec as fm

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 2), ("data", "model"))
    N = 32
    A = jax.random.normal(jax.random.PRNGKey(0), (N, N))
    x = jax.random.normal(jax.random.PRNGKey(1), (N,))
    Ad = jax.device_put(A, NamedSharding(mesh, P("data", "model")))
    xd = jax.device_put(x, NamedSharding(mesh, P("model")))

    y = fm.matvec(Ad, xd, mesh)
    np.testing.assert_allclose(np.asarray(y), np.asarray(A) @ np.asarray(x),
                               rtol=1e-4, atol=1e-5)
    x2 = fm.matvec_iterated_reshard(y, mesh)
    np.testing.assert_allclose(np.asarray(x2), np.asarray(y), rtol=1e-6)
    assert x2.sharding.spec == P("model",), x2.sharding

    # iterated distributed pagerank loop vs dense reference
    H = jax.random.uniform(jax.random.PRNGKey(4), (N, N))
    H = H / H.sum(0, keepdims=True)
    Hd = jax.device_put(H, NamedSharding(mesh, P("data", "model")))
    pr_ref = np.full((N,), 1.0 / N, np.float32)
    prd = jax.device_put(jnp.full((N,), 1.0 / N),
                         NamedSharding(mesh, P("model")))
    for _ in range(8):
        yd = 0.85 * fm.matvec(Hd, prd, mesh) + 0.15 / N
        prd = fm.matvec_iterated_reshard(yd, mesh)
        pr_ref = 0.85 * (np.asarray(H) @ pr_ref) + 0.15 / N
    np.testing.assert_allclose(np.asarray(prd), pr_ref, rtol=1e-4)

    # the horizontal bus must actually lower to collectives
    txt = jax.jit(lambda A, x: fm.matvec_scatter(A, x, mesh)).lower(
        Ad, xd).compile().as_text()
    assert "reduce-scatter" in txt or "all-reduce" in txt, "no collective!"
    print("SUBPROCESS_OK")
""")


@pytest.mark.slow
def test_multidevice_semantics_subprocess():
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = _SUBPROCESS_SCRIPT.format(src=os.path.abspath(src))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SUBPROCESS_OK" in out.stdout
