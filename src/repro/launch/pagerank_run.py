"""End-to-end PageRank driver — the paper's own application, all tiers,
one front door.

Every execution tier goes through :class:`~repro.pagerank.engine.
PageRankEngine` (layout prepared once, whole power iteration in one
compiled dispatch): the dense reference tier, the sliced-ELL tier, the
fused-Pallas tier, and — when the process sees more than one JAX device —
the sharded mesh tiers (``dense_sharded`` fabric schedule and the
row-sharded ``ell_sharded``).  The analytical fabric timing model (the
paper's 213.6 ms headline) prints alongside for comparison.

Usage:
    python -m repro.launch.pagerank_run --nodes 5000 --iters 100
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python -m repro.launch.pagerank_run --nodes 2048
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.pagerank_5k import full as pagerank_cfg
from repro.core import timing
from repro.graph import generators as gen
from repro.graph import transition as tr
from repro.launch.compile_cache import use_compile_cache
from repro.pagerank import PageRankEngine
from repro.serve.engine import top_k_proteins


def _time_engine(eng: PageRankEngine, iters: int) -> tuple[float, jax.Array]:
    """Warm (compile) then time one whole-loop dispatch."""
    eng.run(n_iters=iters).block_until_ready()
    t0 = time.time()
    pr = eng.run(n_iters=iters).block_until_ready()
    return time.time() - t0, pr


def run(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=pagerank_cfg().n_nodes)
    ap.add_argument("--iters", type=int, default=pagerank_cfg().n_iters)
    ap.add_argument("--damping", type=float, default=0.85)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--top-k", type=int, default=10)
    ap.add_argument("--skip-bsr", action="store_true",
                    help="skip the Pallas tier (interpret mode is slow "
                    "on CPU)")
    args = ap.parse_args(argv)

    n, iters, d = args.nodes, args.iters, args.damping
    print(f"compile cache: {use_compile_cache()}")
    n_dev = jax.device_count()
    print(f"protein network: {n} nodes (BA scale-free + noise), "
          f"{iters} iterations, d={d}, {n_dev} device(s)")
    src, dst = gen.protein_network(n, seed=args.seed)
    print(f"  edges (directed): {len(src):,}   "
          f"dangling: {int(tr.dangling_mask(src, n).sum())}")

    results = {}

    # dense reference tier (the engine dispatches the reference program)
    eng_dense = PageRankEngine(src, dst, n, d=d, backend="dense")
    results["engine_dense"], pr_dense = _time_engine(eng_dense, iters)

    # sliced-ELL tier
    eng_ell = PageRankEngine(src, dst, n, d=d, backend="ell")
    results["engine_ell"], pr_ell = _time_engine(eng_ell, iters)
    err = float(jnp.max(jnp.abs(pr_ell - pr_dense)))
    print(f"  engine[{eng_ell.layout}] vs dense: max|diff|={err:.2e}")

    # sharded mesh tiers: the same front door, any device topology
    pr_shard = {}
    if n_dev > 1:
        for backend in ("dense_sharded", "ell_sharded"):
            eng_s = PageRankEngine(src, dst, n, d=d, backend=backend)
            results[f"engine_{backend}"], pr_s = _time_engine(eng_s, iters)
            pr_shard[backend] = pr_s
            err = float(jnp.max(jnp.abs(pr_s - pr_dense)))
            print(f"  engine[{eng_s.layout}] vs dense: max|diff|={err:.2e}")
    else:
        print("  (single device: sharded tiers skipped — set XLA_FLAGS="
              "--xla_force_host_platform_device_count=8 to exercise them)")

    # fused-Pallas tier: whole loop inside one lax.scan around the fused
    # kernel with the in-kernel dangling reduction
    if not args.skip_bsr:
        engp = PageRankEngine(src, dst, n, d=d, backend="pallas_dense")
        k_iters = min(iters, 5) if engp.interpret else iters
        t, pr_k = _time_engine(engp, k_iters)
        tag = "x%d" % k_iters if engp.interpret else ""
        results[f"engine_pallas_fused{tag}"] = t
        ref_k = (pr_dense if k_iters == iters
                 else eng_dense.run(n_iters=k_iters))
        err = float(jnp.max(jnp.abs(pr_k - ref_k)))
        print(f"  engine[pallas_dense] vs dense ({k_iters} iters): "
              f"max|diff|={err:.2e}")

    # paper's fabric model
    model_s = timing.pagerank_latency_s(n, iters)
    results["paper_fabric_model"] = model_s

    np.testing.assert_allclose(np.asarray(pr_dense), np.asarray(pr_ell),
                               rtol=1e-3, atol=1e-7)
    for backend, pr_s in pr_shard.items():
        np.testing.assert_allclose(np.asarray(pr_dense), np.asarray(pr_s),
                                   rtol=1e-3, atol=1e-7)
    idx, scores = top_k_proteins(pr_dense, k=args.top_k)
    print(f"\ntop-{args.top_k} proteins: "
          f"{[(int(i), round(float(s), 5)) for i, s in zip(idx, scores)]}")
    print("\ntimings:")
    for k, v in results.items():
        print(f"  {k:>24}: {v * 1e3:9.2f} ms")
    print(f"  (paper reports 213.6 ms for N=5000, 100 iters @200MHz, "
          f"4096 sites)")
    return results


if __name__ == "__main__":
    run()
