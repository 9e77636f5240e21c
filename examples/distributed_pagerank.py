"""Distributed PageRank through the one engine front door — the paper's
fabric schedule as real collectives over every device the process sees,
arranged as a near-square 2-D mesh (2x2 on a four-chip host).

The vertical bus is the ``P('model')`` layout of the rank vector, the
horizontal bus is the ``psum`` over the mesh row, and the adder-column
re-injection is the diagonal broadcast (DESIGN.md §2).  Since PR 3 the
whole thing is a :class:`~repro.pagerank.engine.PageRankEngine` backend:
``dense_sharded`` builds the blocked ``NamedSharding`` layout once and
compiles the 100-iteration schedule into a single dispatch; the same
engine serves query-sharded batched PPR to ``PageRankQueryEngine``.

Run on a multi-chip host:  PYTHONPATH=src python examples/distributed_pagerank.py
On the CPU, give it virtual devices first:
    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        PYTHONPATH=src python examples/distributed_pagerank.py
"""
import time

import jax
import numpy as np

from repro.graph import generators as gen
from repro.pagerank import PageRankEngine
from repro.serve import PageRankQueryEngine


def main() -> None:
    n, iters = 1024, 100
    src, dst = gen.protein_network(n, seed=3)
    # no mesh given: the engine lays every visible device out as a
    # near-square (row, col) mesh
    eng = PageRankEngine(src, dst, n, backend="dense_sharded")
    print(f"mesh: {dict(eng.mesh.shape)} over {eng.mesh.size} devices")
    H_sharded = eng.operands[0]
    print(f"H: {H_sharded.shape} sharded P('row','col') -> "
          f"{H_sharded.sharding.shard_shape(H_sharded.shape)} per device "
          f"[{eng.layout}]")

    eng.run(n_iters=iters).block_until_ready()          # compile
    t0 = time.time()
    pr = eng.run(n_iters=iters).block_until_ready()
    dt = time.time() - t0

    ref = PageRankEngine(src, dst, n, backend="dense").run(n_iters=iters)
    np.testing.assert_allclose(np.asarray(pr), np.asarray(ref), rtol=2e-4,
                               atol=1e-8)
    txt = eng.lower_run(n_iters=iters).compile().as_text()
    n_ar = txt.count(" all-reduce(") + txt.count(" all-reduce-start(")
    print(f"{iters} fabric-schedule iterations: {dt * 1e3:.1f} ms "
          f"({jax.device_count()} {jax.devices()[0].platform} devices)")
    print(f"collectives in compiled HLO: all-reduce x{n_ar} "
          f"(horizontal bus + diagonal re-injection)")
    print(f"distributed == single-device reference: OK")

    # the same prepared engine serves multi-user personalized PageRank with
    # the (N, Q) batch sharded over the mesh's query axis
    qe = PageRankQueryEngine(eng, n_iters=40, max_batch=8)
    rng = np.random.default_rng(0)
    t0 = time.time()
    results = qe.query_batch(
        [rng.choice(n, size=3, replace=False) for _ in range(8)], top_k=5)
    dt = time.time() - t0
    print(f"8-user PPR batch, query-sharded over the mesh: "
          f"{dt * 1e3:.1f} ms -> top-1 proteins "
          f"{[int(idx[0]) for idx, _ in results]}")


if __name__ == "__main__":
    main()
