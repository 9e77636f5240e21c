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
from repro.pagerank.engine import _run_fixed_pallas

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
    _compiled_kernel(
        lambda h, dg: _run_fixed_pallas(h, dg, None, n=N, n_iters=100,
                                        d=0.85, block_n=BLOCK,
                                        block_m=BLOCK, interpret=False),
        shape((NP, NP)), shape((1, NP)))
