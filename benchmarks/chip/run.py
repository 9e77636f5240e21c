#!/usr/bin/env python3
"""Runs one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1> [--precision <tier>]

The cell, its configuration, traffic mix, limits and per-layer metrics are
found by name from ``BENCHMARK.json`` (``cells.py``).  The run:

1. fails (exit 3, no result) unless JAX sees exactly the TPU chips the
   cell asks for, and (exit 4) if the chip's kind has no entry in
   ``peaks.json``;
2. sets up: compile cache, graph (generated or read from the graph cache,
   relabelled by ``--seed``), and the traffic's kind of operation
   (``ops/<op>.py``), which builds the system under test and warms up
   every shape the window uses; all of it is ``setup_s``;
3. runs the operation's window for ``--seconds`` (a closed loop finishes
   the operation in flight); with ``--trace 1`` the
   window (at most the traffic's ``trace_seconds``) is traced, the
   program's registry spans are put into the trace, and the per-layer
   metrics are reported instead of the end-to-end ones;
4. reads the chip's peak memory, frees the program's state, and holds the
   window's answers to the float64 reference.

The last line of standard output is the JSON result; the last lines of
standard error are the numbers compared, each beside its limit.
``--precision`` runs the program at another storage tier than the
configuration states: the lower-precision control, never used by a timed
run.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
# run as a script, this directory would shadow the standard library's
# ``trace``; the benchmark is imported as a package from the checkout root
if sys.path and Path(sys.path[0]).resolve() == BENCH:
    sys.path[0] = str(ROOT)
else:
    sys.path.insert(0, str(ROOT))
sys.path.insert(1, str(ROOT / "src"))     # the system under test

GRAPH_CACHE = BENCH / ".graph_cache"


class NoChip(RuntimeError):
    """JAX found no TPU, or not the number of chips the cell asks for."""


def require_tpu(chips: int) -> dict:
    """The device block of the result line; raises :class:`NoChip` unless
    JAX's devices are exactly ``chips`` TPUs (with more, the program's
    ``auto`` backend would pick a sharded layout the cell does not ask
    for)."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU chip: JAX's first device is on platform "
                     f"{devs[0].platform!r}")
    if len(devs) != chips:
        raise NoChip(f"the cell asks for {chips} TPU chip(s), JAX sees "
                     f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peaks_for(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} has no entry in peaks.json")
    return table[kind]


def memory_peak_bytes(chips: int) -> int | None:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts XLA compilations: JAX's backend-compile events, which it
    reports for a program loaded from the persistent cache as well, less
    the cache's hits."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.programs = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.programs += 1

    def _on_event(self, event: str, **kw) -> None:
        if event == self.HIT:
            self.hits += 1

    @property
    def count(self) -> int:
        return self.programs - self.hits


def per_layer_record(cell, op, graph, items, window_s, registry_counters,
                     traced, peaks, precision) -> dict:
    """What the per-layer metric readers read: one dict per traced run."""
    src, dst, n = graph
    return {"cell": cell.name, "op": cell.traffic["op"],
            "end_to_end": op.e2e, "precision": precision,
            "n": n, "nnz": int(len(src)), "items": items,
            "window_s": window_s, "counters": registry_counters,
            "trace": traced, "peaks": peaks}


def _fmt(v) -> str:
    return repr(v) if isinstance(v, float) else str(v)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--precision", default=None)
    args = ap.parse_args(argv)

    from benchmarks.chip import cells
    cell = cells.load_cell(ROOT, args.workload)
    try:
        device = require_tpu(cell.chips)
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 3
    try:
        peaks = peaks_for(device["kind"])
    except KeyError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 4
    return run_cell(cell, args, device, peaks)


def run_cell(cell, args, device, peaks, graph_cache=GRAPH_CACHE) -> int:
    """Everything after the look for a chip: set-up, window, checks and
    the result lines."""
    import jax
    from benchmarks.chip import cells, graphs, trace as trace_mod
    from repro.launch.compile_cache import use_compile_cache
    from repro.obs.registry import MetricsRegistry

    cfg, traffic = cell.config, cell.traffic
    traced = bool(args.trace)
    precision = args.precision or cfg["precision"]
    print(f"compile_cache dir={use_compile_cache()}", file=sys.stderr)
    # every program goes into the cache, not only those that took a second
    # to compile, so that a run after the first compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = CompileCounter()
    graph = graphs.load(cfg, args.seed, graph_cache)
    src, dst, n = graph
    registry = MetricsRegistry(profiler_annotations=traced)
    op = cells.load_op(traffic["op"])(cfg, traffic, graph, args.seed,
                                      precision, registry, cell.limits)
    op.warm()
    setup_s = time.perf_counter() - T_START
    print(f"setup nodes={n} directed_edges={len(src)} "
          f"layout={op.layout} setup_s={setup_s!r} "
          f"compiles={compiles.count} cache_hits={compiles.hits}",
          file=sys.stderr, flush=True)

    seconds = args.seconds
    if traced and traffic.get("trace_seconds"):
        seconds = min(seconds, float(traffic["trace_seconds"]))
    before = dict(registry.as_dict()["counters"])
    compiles_before = compiles.count
    trace_dir = None
    if traced:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        items, window_s = op.window(seconds)
    finally:
        if traced:
            jax.profiler.stop_trace()
    in_window = compiles.count - compiles_before
    after = registry.as_dict()["counters"]
    counters = {k: v - before.get(k, 0) for k, v in after.items()}
    by_kind: dict = {}
    for it in items:
        key = it.get("strategy")
        if key is not None:
            if it.get("coerced_from"):
                key = f"{key}(coerced_from={it['coerced_from']})"
            by_kind[key] = by_kind.get(key, 0) + 1
    not_ok = sum(1 for it in items if not it.get("ok", True))
    print(f"window calls={len(items)} window_s={window_s!r} "
          f"not_ok={not_ok} compiles_in_window={in_window}"
          + (f" strategies={json.dumps(by_kind, sort_keys=True)}"
             if by_kind else ""), file=sys.stderr, flush=True)
    device = dict(device)
    device["memory_peak_bytes"] = memory_peak_bytes(cell.chips)

    reduced = None
    if traced:
        try:
            reduced = trace_mod.reduce_dir(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]

    checks = op.checks()
    checks["failed_calls"] = (not_ok, 0, not_ok == 0)
    correct = all(ok for _, _, ok in checks.values())

    if traced:
        rec = per_layer_record(cell, op, graph, items, window_s, counters,
                               reduced, peaks, precision)
        metrics = {}
        for m in cell.per_layer:
            v = cells.metric_reader(m["name"])(rec)
            if v is not None and math.isfinite(v):
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        e2e = op.end_to_end(window_s, items)
        e2e["setup_s"] = setup_s
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in e2e}
    result = {"correct": bool(correct), "attempted": len(items),
              "failed": not_ok, "metrics": metrics, "device": device}
    if traced:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim, _) in checks.items()}
    for k, (v, lim, ok) in checks.items():
        print(f"check {k}={_fmt(v)} limit={_fmt(lim)} ok={ok}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
