"""The main path's Pallas kernels compile for a TPU v5e.

Interpret mode on the CPU runs a kernel's body but not Mosaic, the TPU
compiler, which refuses what interpret mode accepts: a scalar stored into
VMEM, a block that is not aligned to the (8, 128) tile.  These tests
compile each kernel for one chip of a described ``v5e:2x2`` topology, with
no chip attached, at the paper deployment's width (5,000 nodes padded to
5,120), and check that the kernel is in the compiled program.

The topology is described inside a fixture: only the worker that runs
these tests loads the TPU compiler, and where it cannot be loaded the
tests skip.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bsr_spmv import bsr_spmv
from repro.kernels.pagerank_step import pagerank_step_fused
from repro.kernels.streaming_matvec import streaming_matvec
from repro.pagerank.engine import PallasLayout, fixed_loop

N, NP, BLOCK = 5000, 5120, 256


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda s, dt=jnp.float32: jax.ShapeDtypeStruct(s, dt,
                                                          sharding=one_chip)


def _compiled_kernel(fn, *args) -> str:
    txt = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in txt
    return txt


@pytest.mark.parametrize("storage", ["f32", "bf16", "int8"])
def test_pagerank_step_fused_compiles(shape, storage):
    dtype = {"f32": jnp.float32, "bf16": jnp.bfloat16,
             "int8": jnp.int8}[storage]
    args = [shape((NP, NP), dtype), shape((1, NP)), shape((1, NP)),
            shape(())]
    if storage == "int8":
        args.append(shape((1, NP)))
    _compiled_kernel(
        lambda *a: pagerank_step_fused(*a, block_n=BLOCK, block_m=BLOCK,
                                       interpret=False), *args)


@pytest.mark.parametrize("q", [1, 64])
def test_streaming_matvec_compiles(shape, q):
    _compiled_kernel(
        lambda w, x: streaming_matvec(w, x, block_n=BLOCK, block_m=BLOCK,
                                      interpret=False),
        shape((NP, NP)), shape((q, NP)))


def test_bsr_spmv_compiles(shape):
    nb, mb, bs = NP // 128, 8, 128
    _compiled_kernel(lambda b, c, x: bsr_spmv(b, c, x, interpret=False),
                     shape((nb, mb, bs, bs)), shape((nb, mb), jnp.int32),
                     shape((NP,)))


def test_run_fixed_pallas_compiles(shape):
    lay = PallasLayout(operands=(shape((NP, NP)),), dang=shape((NP,)), n=N,
                       d=0.85, block=(BLOCK, BLOCK), interpret=False)
    _compiled_kernel(lambda lay: fixed_loop(lay, 0.85, n_iters=100), lay)


def test_ppr_serve_programs_compile(shape):
    """The teleport matrix, the landmark estimate, the push and the serve
    path's ranking step compile for a TPU v5e at a batch of 16 on a SELL
    layout."""
    import numpy as np

    from repro.graph import generators as gen
    from repro.pagerank.engine import PageRankEngine
    from repro.pagerank.landmarks import (_hub_estimate, _hub_push,
                                          _teleport)
    from repro.serve.engine import _rank_batch

    src, dst = gen.protein_network(2000, seed=0)
    eng = PageRankEngine(src, dst, 2000, backend="ell")
    lay = jax.tree.map(lambda a: shape(a.shape, a.dtype), eng.prepared)
    n, q, h = 2000, 16, 64
    V = shape((n, q))
    _teleport.lower(shape((q, 4), jnp.int32), shape((q, 4)), n=n).compile()
    _hub_estimate.lower(lay, 0.85, V, shape((n, h)),
                        shape((h,), jnp.int32)).compile()
    _hub_push.lower(lay, 0.85, 256, V, V, np.float32(1e-7)).compile()
    _rank_batch.lower(V, 1e-3, k=10).compile()


def test_scaled_sell_push_gathers_only_the_iterate(shape):
    """The dynamic SELL's refresh push applies its column scale once per
    matvec: compiled for a v5e, every fused gather reads one (N,) float
    vector, the scaled iterate, and the program has as many fusions as the
    same push over rows that hold their values (no scale)."""
    import dataclasses
    import re

    import numpy as np

    from repro.graph import generators as gen
    from repro.obs.registry import NullRegistry
    from repro.pagerank import DynamicPageRankEngine
    from repro.pagerank.engine import global_push

    src, dst = gen.barabasi_albert(N, 8, seed=0)
    dyn = DynamicPageRankEngine(src, dst, N, backend="ell",
                                metrics=NullRegistry())
    scaled = jax.tree.map(lambda a: shape(a.shape, a.dtype), dyn.prepared)
    assert scaled.col_scale is not None

    def fusions(lay):
        txt = global_push.lower(lay, 0.85, np.float32(1e-6), shape((N,)),
                                max_pushes=100).compile().as_text()
        gathers = [re.findall(r"= (f32\[[\d,]*\])\S* parameter\(", c)
                   for c in txt.split("\n\n") if " gather(" in c]
        return txt.count(" fusion("), gathers

    n_scaled, gathers = fusions(scaled)
    n_valued, valued = fusions(dataclasses.replace(scaled, col_scale=None))
    assert n_scaled == n_valued
    assert len(gathers) == len(valued) >= len(dyn._sell.widths)
    assert all(g == [f"f32[{N}]"] for g in gathers)
