"""Records the small chip trace that ``test_chipbench_scopes.py`` pins.

    python3 benchmarks/chip/tests/record_scoped_trace.py [<out_dir>]

Runs on a TPU, inside the harness's ``bench.window`` and ``bench.call``
spans with the program's registry spans in the trace: three 100-iteration
solves of the 5,000-node protein network on the static engine (ELL gather,
COO tail), then two edge deltas on the dynamic engine (row patch, push
over the SELL tiers), a 4-edge insert and its inverse, each warmed up
before the trace.  Copies the ``.xplane.pb`` to
``<out_dir>/protein5k_scoped.xplane.pb`` (``<out_dir>`` defaults to
``benchmarks/chip/testdata_scopes``).
"""
from __future__ import annotations

import json
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
OUT = ROOT / "benchmarks/chip/testdata_scopes"
NAME = "protein5k_scoped.xplane.pb"


def main(out_dir: str | Path = OUT) -> int:
    import jax
    import numpy as np
    from jax.profiler import TraceAnnotation

    from benchmarks.chip import graphs
    from repro.graph.delta import GraphDelta
    from repro.obs.registry import MetricsRegistry
    from repro.pagerank.dynamic import DynamicPageRankEngine
    from repro.pagerank.engine import PageRankEngine

    if jax.devices()[0].platform != "tpu":
        print("record_scoped_trace: no TPU", file=sys.stderr)
        return 3
    cfg = json.loads((ROOT / "benchmarks/chip/configs/protein-5k.json")
                     .read_text())
    src, dst, n = graphs.load(cfg, 0, None)
    reg = MetricsRegistry(profiler_annotations=True)
    eng = PageRankEngine(src, dst, n, metrics=reg)
    dyn = DynamicPageRankEngine(src, dst, n, metrics=reg)
    have = set(zip(src.tolist(), dst.tolist()))
    pairs = [(u, v) for u, v in zip(range(0, n, 97), range(50, n, 89))
             if u != v and (u, v) not in have][:4]
    u, v = (np.array(a, np.int32) for a in zip(*pairs))
    empty = np.zeros(0, np.int32)
    cycle = [GraphDelta(u, v, empty, empty), GraphDelta(empty, empty, u, v)]
    eng.run(100).block_until_ready()
    dyn.run_tol(1e-6)[0].block_until_ready()
    for d in cycle:
        dyn.update(d)[0].block_until_ready()
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with TraceAnnotation("bench.window"):
        for _ in range(3):
            with TraceAnnotation("bench.call"):
                eng.run(100).block_until_ready()
        for d in cycle:
            with TraceAnnotation("bench.call"):
                dyn.update(d)[0].block_until_ready()
    jax.profiler.stop_trace()
    pb = sorted(Path(tmp).rglob("*.xplane.pb"))[-1]
    Path(out_dir).mkdir(parents=True, exist_ok=True)
    shutil.copy(pb, Path(out_dir) / NAME)
    shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps({"layout": eng.layout, "dynamic_layout": dyn.layout,
                      "pairs": pairs, "compiles": reg.counter(
                          "compiles").value}))
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
