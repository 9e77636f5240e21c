#!/usr/bin/env python3
"""Device time by the program's named scopes, and a traced breakdown of one
cell that reports it beside the program's spans.

    python3 benchmarks/chip/scopes.py --workload <cell> --seed <n> \
        --seconds <s> [--keep <dir>]

The program runs each kernel of its hot path under a fixed
``jax.named_scope`` (``pagerank.ell_gather``, ``pagerank.coo_tail``,
``pagerank.sell_order``, ``pagerank.vector``, ``pagerank.push``,
``pagerank.row_patch``).  The profiler writes an op's scope path into the
``tf_op`` stat of the op's event metadata.  :func:`reduce_dir` sums the
self time of the device ops (an op's duration less that of the ops nested
in it, as ``trace.py`` computes it) inside the harness's ``bench.window``
by the innermost ``pagerank.*`` scope of each op; ops with none go under
``(unscoped)``.  A fusion carries the scope of its root op.

``jax.profiler.ProfileData`` does not expose event metadata, so the file is
read with the profiler's generated schema (``xplane_pb2.py``, which needs
only ``google.protobuf``), loaded by path: TensorFlow, the package it is
installed in, is never imported.  Without the schema the scopes read None.

Run as a script on a TPU, it sets the cell up as ``run.py`` does, traces
one window (at most the traffic's ``trace_seconds``) with the program's
spans in the trace, and prints one JSON line: the trace reduction of
``trace.py``, the scopes, the window's registry spans (count and summed
ms) and counters, and per call of the window the ELL gather, COO tail and
row patch device time and the ``update.plan`` and ``update.patch`` span
time.  It checks no answer; ``run.py`` is the benchmark.  ``--keep`` copies
the trace file into a directory.
"""
from __future__ import annotations

import functools
import importlib.util
import re
import sys
from pathlib import Path

if __name__ == "__main__":
    _HERE = Path(__file__).resolve().parent
    # run as a script, this directory would shadow the standard library's
    # ``trace``; the benchmark is imported as a package from the checkout
    if sys.path and Path(sys.path[0]).resolve() == _HERE:
        sys.path[0] = str(_HERE.parents[1])
    sys.path.insert(1, str(_HERE.parents[1] / "src"))

from benchmarks.chip.trace import (DEVICE_PLANE, OPS_LINE,  # noqa: E402
                                   WINDOW_SPAN, _self_times)

SCOPE = re.compile(r"pagerank\.\w+")
UNSCOPED = "(unscoped)"
XPLANE_SCHEMA = Path("tensorflow", "tsl", "profiler", "protobuf",
                     "xplane_pb2.py")


def scope_of(tf_op: str) -> str:
    """The innermost ``pagerank.*`` scope of an op's ``tf_op`` path, or
    ``(unscoped)``."""
    found = SCOPE.findall(tf_op)
    return found[-1] if found else UNSCOPED


@functools.cache
def xplane_schema():
    """The profiler's generated ``xplane_pb2`` module, loaded from its file
    on ``sys.path`` without importing the package that holds it; None when
    no such file is installed."""
    for entry in sys.path:
        path = Path(entry or ".") / XPLANE_SCHEMA
        if path.is_file():
            spec = importlib.util.spec_from_file_location(
                "_bench_xplane_pb2", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
    return None


def _line_events(line, name) -> list[tuple[float, float, str]]:
    """``(start_ns, end_ns, name(event))`` of an ``XLine``, sorted."""
    return sorted((line.timestamp_ns + e.offset_ps * 1e-3,
                   line.timestamp_ns + (e.offset_ps + e.duration_ps) * 1e-3,
                   name(e)) for e in line.events)


def _window(space) -> tuple[float, float] | None:
    for plane in space.planes:
        if not plane.name.startswith("/host:"):
            continue
        ids = {k for k, m in plane.event_metadata.items()
               if m.name == WINDOW_SPAN}
        for line in plane.lines:
            for e in line.events:
                if e.metadata_id in ids:
                    s = line.timestamp_ns + e.offset_ps * 1e-3
                    return s, s + e.duration_ps * 1e-3
    return None


def scope_times(space) -> dict[str, float]:
    """Self seconds per scope of the device ops of an ``XSpace`` inside
    its ``bench.window`` (else from its first to its last device op),
    summed over the devices and divided by their number."""
    devices = []
    for plane in space.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        tf_op = {k for k, m in plane.stat_metadata.items()
                 if m.name == "tf_op"}
        scope = {k: scope_of(next((s.str_value for s in md.stats
                                   if s.metadata_id in tf_op), ""))
                 for k, md in plane.event_metadata.items()}
        for line in plane.lines:
            if line.name == OPS_LINE:
                devices.append(_line_events(
                    line, lambda e: scope[e.metadata_id]))
                break
    if not any(devices):
        raise ValueError("the trace holds no operation on a TPU device")
    w0, w1 = _window(space) or (
        min(d[0][0] for d in devices if d),
        max(max(t for _, t, _ in d) for d in devices if d))
    out: dict[str, float] = {}
    for evs in devices:
        # clipped after sorting, as trace.py does, so an op that encloses
        # another stays before it
        clipped = [(max(s, w0), min(t, w1), n) for s, t, n in evs
                   if t > w0 and s < w1]
        for name, t in _self_times(clipped).items():
            out[name] = out.get(name, 0.0) + t
    return {k: v / len(devices) * 1e-9 for k, v in sorted(out.items())}


def reduce_dir(log_dir: str | Path) -> dict[str, float] | None:
    """:func:`scope_times` of the newest ``.xplane.pb`` under ``log_dir``,
    or None without the schema."""
    files = sorted(Path(log_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    schema = xplane_schema()
    if schema is None:
        return None
    space = schema.XSpace()
    space.ParseFromString(files[-1].read_bytes())
    return scope_times(space)


def window_spans(before: dict, after: dict) -> dict:
    """``{name: {"count", "ms"}}`` of each registry span between two
    ``as_dict()["histograms"]`` exports, from its ``span.<name>``
    histogram's count and sum (kept over the whole stream, so their
    differences are exact)."""
    out = {}
    for key, h in after.items():
        if not key.startswith("span."):
            continue
        b = before.get(key, {})
        count = h["count"] - b.get("count", 0)
        if count:
            out[key[len("span."):]] = {
                "count": count, "ms": h.get("sum", 0.0) - b.get("sum", 0.0)}
    return out


def per_call(scopes: dict | None, spans: dict, calls: int) -> dict:
    """Per-call readings: device ms under a scope, host ms under a span;
    None where the run has no such scope or span."""
    out = {}
    for key, scope in (("gather_ms", "pagerank.ell_gather"),
                       ("tail_ms", "pagerank.coo_tail"),
                       ("scatter_ms", "pagerank.row_patch")):
        t = (scopes or {}).get(scope)
        out[key] = None if t is None or not calls else 1e3 * t / calls
    for key, span in (("plan_ms", "update.plan"),
                      ("patch_ms", "update.patch")):
        s = spans.get(span)
        out[key] = None if s is None or not calls else s["ms"] / calls
    return out


def main(argv=None) -> int:
    import argparse
    import json
    import shutil
    import tempfile

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)

    from benchmarks.chip import cells, graphs, run, trace
    cell = cells.load_cell(run.ROOT, args.workload)
    try:
        run.require_tpu(cell.chips)
    except run.NoChip as e:
        print(f"scopes.py: {e}", file=sys.stderr)
        return 3
    import jax
    from repro.launch.compile_cache import use_compile_cache
    from repro.obs.registry import MetricsRegistry

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cfg, traffic = cell.config, cell.traffic
    graph = graphs.load(cfg, args.seed, run.GRAPH_CACHE)
    registry = MetricsRegistry(profiler_annotations=True)
    op = cells.load_op(traffic["op"])(cfg, traffic, graph, args.seed,
                                      cfg["precision"], registry,
                                      cell.limits)
    op.warm()
    seconds = min(args.seconds,
                  float(traffic.get("trace_seconds") or args.seconds))
    before = registry.as_dict()
    n_events = len(registry.events)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        items, window_s = op.window(seconds)
    finally:
        jax.profiler.stop_trace()
    after = registry.as_dict()
    try:
        reduced = trace.reduce_dir(trace_dir)
        scopes = reduce_dir(trace_dir)
        if args.keep:
            Path(args.keep).mkdir(parents=True, exist_ok=True)
            pb = sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]
            shutil.copy(pb, Path(args.keep) / f"{cell.name}.xplane.pb")
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    spans = window_spans(before["histograms"], after["histograms"])
    counters = {k: v - before["counters"].get(k, 0)
                for k, v in after["counters"].items()}
    print(json.dumps({
        "cell": cell.name, "calls": len(items), "window_s": window_s,
        "busy_s": reduced["busy_s"], "trace_window_s": reduced["window_s"],
        "device_ops": reduced["device_ops"],
        "idle_gaps": reduced["idle_gaps"], "scopes": scopes,
        "spans": spans, "counters": counters,
        "compile_events": [e for e in registry.events[n_events:]
                           if e["kind"] == "compile"],
        "per_call": per_call(scopes, spans, len(items))}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
