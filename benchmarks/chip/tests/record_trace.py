"""Records the small chip trace that ``test_chipbench_trace.py`` pins.

    python3 benchmarks/chip/tests/record_trace.py <out_dir>

Runs on a TPU: three 100-iteration solves of the 5,000-node protein
network inside the harness's ``bench.window`` and ``bench.call`` spans,
with the program's registry spans in the trace, and copies the
``.xplane.pb`` to ``<out_dir>/protein5k_fixed100.xplane.pb``.
"""
from __future__ import annotations

import json
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(out_dir: str) -> int:
    import jax
    from jax.profiler import TraceAnnotation

    from benchmarks.chip import graphs
    from repro.obs.registry import MetricsRegistry
    from repro.pagerank.engine import PageRankEngine

    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 3
    cfg = json.loads((ROOT / "benchmarks/chip/configs/protein-5k.json")
                     .read_text())
    src, dst, n = graphs.load(cfg, 0, None)
    eng = PageRankEngine(src, dst, n, metrics=MetricsRegistry(
        profiler_annotations=True))
    eng.run(100).block_until_ready()
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with TraceAnnotation("bench.window"):
        for _ in range(3):
            with TraceAnnotation("bench.call"):
                eng.run(100).block_until_ready()
    jax.profiler.stop_trace()
    pb = sorted(Path(tmp).rglob("*.xplane.pb"))[-1]
    Path(out_dir).mkdir(parents=True, exist_ok=True)
    shutil.copy(pb, Path(out_dir) / "protein5k_fixed100.xplane.pb")
    shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
