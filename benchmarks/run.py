"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  The dry-run/roofline artifacts
(64 production-mesh compiles) are produced separately by
``python -m repro.launch.dryrun`` (they take ~an hour); ``roofline`` here
summarizes whatever artifacts exist.

Modes:
  --quick   smaller Fig. 6B sweep (2 sizes, 20 iters)
  --smoke   CI mode: tiny N, 3 iterations, every tier — catches engine
            perf-path regressions in seconds (no JSON artifact written;
            speed claims only make sense at full size)
"""
from __future__ import annotations

import sys


def main() -> None:
    from benchmarks import (dynamic_bench, fig5_routing,
                            fig6a_matvec_latency, fig6b_pagerank_throughput,
                            kernel_bench, observability_bench,
                            pagerank_engine_bench, precision_bench,
                            resilience_bench, roofline, serve_bench,
                            table1_design)
    from repro.launch.compile_cache import use_compile_cache

    print(f"# compile cache: {use_compile_cache()}")

    smoke = "--smoke" in sys.argv
    quick = "--quick" in sys.argv or smoke
    if smoke:
        sizes, iters = [256], 3
        engine_kw = dict(n=256, iters=3, reps=1, out_path=None)
        dynamic_kw = dict(n=256, reps=1, out_path=None)
        dynamic_sharded_kw = dict(n=256, reps=1, out_path=None)
        resilience_kw = dict(n=256, iters=10, reps=3, out_path=None)
        obs_kw = dict(n=256, iters=10, reps=3, out_path=None)
        precision_kw = dict(n=256, iters=3, reps=1, out_path=None)
        serve_kw = dict(n=256, pool=8, picks=40, delta_every=10,
                        n_hubs=8, out_path=None)
    elif quick:
        sizes, iters = [1000, 2000], 20
        # out_path=None: never overwrite the full-size JSON artifact with
        # reduced-size numbers
        engine_kw = dict(n=1024, iters=20, out_path=None)
        dynamic_kw = dict(n=1024, reps=3, out_path=None)
        dynamic_sharded_kw = dict(n=1024, reps=1, out_path=None)
        resilience_kw = dict(n=1024, iters=50, reps=3, out_path=None)
        obs_kw = dict(n=1024, iters=50, reps=3, out_path=None)
        precision_kw = dict(n=1024, iters=20, reps=3, out_path=None)
        serve_kw = dict(n=1024, pool=16, picks=120, delta_every=30,
                        n_hubs=16, out_path=None)
    else:
        sizes, iters = None, 100
        engine_kw = dict()
        dynamic_kw = dict()
        dynamic_sharded_kw = dict()
        resilience_kw = dict()
        obs_kw = dict()
        precision_kw = dict()
        serve_kw = dict()

    benches = [
        fig5_routing.run,
        fig6a_matvec_latency.run,
        (lambda: fig6b_pagerank_throughput.run(sizes=sizes, iters=iters)),
        table1_design.run,
        kernel_bench.run,
        (lambda: pagerank_engine_bench.run(**engine_kw)),
        (lambda: dynamic_bench.run(**dynamic_kw)),
        # self-skips (with a note) on a single device
        (lambda: dynamic_bench.run_sharded(**dynamic_sharded_kw)),
        (lambda: resilience_bench.run(**resilience_kw)),
        (lambda: observability_bench.run(**obs_kw)),
        (lambda: precision_bench.run(**precision_kw)),
        (lambda: serve_bench.run(**serve_kw)),
        roofline.run,
    ]
    print("name,us_per_call,derived")
    for bench in benches:
        try:
            r = bench()
            print(f"{r['name']},{r['us_per_call']:.2f},{r['derived']}")
        except Exception as e:          # keep the harness running
            name = getattr(bench, "__module__", str(bench))
            print(f"{name},ERROR,{type(e).__name__}:{e}")
            raise


if __name__ == "__main__":
    main()
