#!/usr/bin/env python
"""Text dashboard over an observability JSONL event log.

Reads the event stream a :class:`repro.obs.registry.MetricsRegistry`
wrote (``jsonl_path=`` live appends or ``dump_jsonl``) and derives the
serving story back out of it: query counts by freshness status, the
refresh-ladder outcomes, dead-letter quarantines, solve verdicts, and the
serve-latency distribution, the spans (latency, and self time: a span's
duration less its child spans'), and the compiles by the span they ran in.

The latency quantiles are recomputed by feeding the ``serve`` events'
``ms`` values through the *same* :class:`repro.obs.registry.Histogram`
the live registry used (nearest-rank over the last-``window``
observations, floats JSON-round-tripped exactly), so ``--metrics
metrics.json`` can cross-check the report against the registry's own
``as_dict`` dump — any mismatch exits nonzero.  That is the acceptance
bar: the log alone reproduces fresh/stale/degraded counts and p50/p95
serve latency **exactly**.

Usage:
    python scripts/obs_report.py events.jsonl [--metrics metrics.json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from collections import Counter

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

from repro.obs.registry import (DEFAULT_WINDOW,  # noqa: E402
                                EVENT_SCHEMA_VERSION, Histogram)

# slack on the containment test of a child span in its parent: start_ms
# and t_ms are rounded to the microsecond
_SPAN_SLACK_MS = 2e-3


def load_events(path: str) -> list[dict]:
    events = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                events.append(json.loads(line))
    return events


def derive(events: list[dict], window: int = DEFAULT_WINDOW) -> dict:
    """Re-derive the registry's serve-side instruments from the log."""
    queries = Counter()
    refreshes = Counter()
    solves = Counter()
    dead_letters = 0
    dead_reasons = Counter()
    batch_ms = Histogram(window)
    last_lag = None
    spans = {}
    span_self_ms = Counter()
    # spans whose parent has not ended yet, by the parent's name:
    # (start_ms, end_ms, ms); a span ends before the span around it does
    open_children: dict[str, list[tuple[float, float, float]]] = {}
    compiles = Counter()
    # result-cache story (serve events carry the per-flush cache fields
    # only when a cache is attached; cache_invalidate events ride every
    # cache-aware refresh) — "active" flips when either appears
    cache = {"active": False, "hits": 0, "misses": 0, "evictions": 0,
             "invalidations": 0, "kept": None}
    cache_hit_ms = Histogram(window)
    cache_miss_ms = Histogram(window)
    for ev in events:
        kind = ev.get("kind")
        if kind == "serve":
            queries[ev["status"]] += ev["batch"]
            batch_ms.observe(ev["ms"])
            last_lag = ev.get("freshness_lag_s", last_lag)
            if "cache_hits" in ev:
                cache["active"] = True
                cache["hits"] += ev["cache_hits"]
                cache["misses"] += ev["cache_misses"]
                cache["evictions"] += ev["cache_evictions"]
                if ev.get("hit_ms") is not None:
                    cache_hit_ms.observe(ev["hit_ms"])
                if ev.get("miss_ms") is not None:
                    cache_miss_ms.observe(ev["miss_ms"])
        elif kind == "cache_invalidate":
            cache["active"] = True
            cache["invalidations"] += ev["dropped"]
            cache["kept"] = ev["kept"]
        elif kind == "refresh":
            refreshes[ev["status"]] += 1
        elif kind == "solve":
            solves[ev["status"]] += 1
        elif kind == "dead_letter":
            dead_letters += ev["n_edges"]
            for r in ev.get("reasons", []):
                dead_reasons[r] += 1
        elif kind == "span":
            spans.setdefault(ev["name"], Histogram(window)).observe(
                ev["ms"])
            span_self_ms[ev["name"]] += _self_ms(ev, open_children)
        elif kind == "compile":
            compiles[ev.get("span") or "(no span)"] += 1
    return {"queries": dict(queries), "refreshes": dict(refreshes),
            "solves": dict(solves), "dead_letters": dead_letters,
            "dead_reasons": dict(dead_reasons),
            "batch_ms": batch_ms, "freshness_lag_s": last_lag,
            "spans": spans, "span_self_ms": dict(span_self_ms),
            "compiles": dict(compiles), "cache": cache,
            "cache_hit_ms": cache_hit_ms, "cache_miss_ms": cache_miss_ms}


def _self_ms(ev: dict, open_children: dict) -> float:
    """``ev``'s duration less that of the spans that ran inside it; a
    schema-1 span, with no start or parent recorded, is all self time."""
    ms = ev["ms"]
    if "start_ms" not in ev:
        return ms
    start, end = ev["start_ms"], ev["start_ms"] + ms
    inside, other = [], []
    for c in open_children.pop(ev["name"], []):
        (inside if c[0] >= start - _SPAN_SLACK_MS
         and c[1] <= end + _SPAN_SLACK_MS else other).append(c)
    if other:               # a same-named span open on another thread
        open_children[ev["name"]] = other
    if ev["parent"] is not None:
        open_children.setdefault(ev["parent"], []).append((start, end, ms))
    return ms - sum(c[2] for c in inside)


def _fmt_hist(h: Histogram) -> str:
    s = h.summary()
    if s["count"] == 0:
        return "no samples"
    return (f"n={s['count']}  p50={s['p50']:.3f}ms  p95={s['p95']:.3f}ms  "
            f"p99={s['p99']:.3f}ms  max={s['max']:.3f}ms")


def render(d: dict) -> str:
    lines = ["== observability report =="]
    lines.append("-- serve --")
    total = sum(d["queries"].values())
    lines.append(f"queries served: {total}")
    for status in sorted(d["queries"]):
        lines.append(f"  {status:<10} {d['queries'][status]}")
    lines.append(f"batch latency: {_fmt_hist(d['batch_ms'])}")
    if d["freshness_lag_s"] is not None:
        lines.append(f"freshness lag (last serve): "
                     f"{d['freshness_lag_s']:.3f}s")
    if d["cache"]["active"]:
        c = d["cache"]
        lines.append("-- result cache --")
        lookups = c["hits"] + c["misses"]
        rate = c["hits"] / lookups if lookups else 0.0
        lines.append(f"lookups: {lookups}  hits: {c['hits']}  "
                     f"misses: {c['misses']}  (hit rate {rate:.2f})")
        lines.append(f"evictions: {c['evictions']}  "
                     f"invalidated: {c['invalidations']}"
                     + (f"  kept after last delta: {c['kept']}"
                        if c["kept"] is not None else ""))
        lines.append(f"hit latency:  {_fmt_hist(d['cache_hit_ms'])}")
        lines.append(f"miss latency: {_fmt_hist(d['cache_miss_ms'])}")
    lines.append("-- refresh ladder --")
    for status in sorted(d["refreshes"]):
        lines.append(f"  {status:<10} {d['refreshes'][status]}")
    if not d["refreshes"]:
        lines.append("  (no refreshes)")
    lines.append("-- solves --")
    for status in sorted(d["solves"]):
        lines.append(f"  {status:<10} {d['solves'][status]}")
    if not d["solves"]:
        lines.append("  (no solves)")
    lines.append("-- quarantine --")
    lines.append(f"dead-letter edges: {d['dead_letters']}")
    for reason in sorted(d["dead_reasons"]):
        lines.append(f"  {reason}: {d['dead_reasons'][reason]} event(s)")
    if d["spans"]:
        lines.append("-- spans --")
        for name in sorted(d["spans"]):
            lines.append(f"  {name:<18} {_fmt_hist(d['spans'][name])}  "
                         f"self_sum={d['span_self_ms'][name]:.3f}ms")
    if d["compiles"]:
        lines.append("-- compiles, by the span open --")
        for name in sorted(d["compiles"]):
            lines.append(f"  {name:<18} {d['compiles'][name]}")
    return "\n".join(lines)


def cross_check(d: dict, metrics: dict) -> list[str]:
    """Compare the log-derived numbers against a registry ``as_dict`` dump;
    returns human-readable mismatch descriptions (empty == exact)."""
    errs = []
    counters = metrics.get("counters", {})
    for status, n in d["queries"].items():
        if status == "legacy":
            continue
        want = counters.get(f"serve.queries.{status}", 0)
        if want != n:
            errs.append(f"serve.queries.{status}: log={n} registry={want}")
    total = sum(d["queries"].values())
    if counters.get("serve.queries", 0) != total:
        errs.append(f"serve.queries: log={total} "
                    f"registry={counters.get('serve.queries', 0)}")
    for status, n in d["refreshes"].items():
        want = counters.get(f"serve.refresh.{status}", 0)
        if want != n:
            errs.append(f"serve.refresh.{status}: log={n} registry={want}")
    if counters.get("serve.dead_letters", 0) != d["dead_letters"]:
        errs.append(f"serve.dead_letters: log={d['dead_letters']} "
                    f"registry={counters.get('serve.dead_letters', 0)}")
    hist = metrics.get("histograms", {}).get("serve.batch_ms")
    if hist is not None and hist.get("count", 0) > 0:
        got = d["batch_ms"].summary()
        for q in ("count", "p50", "p95", "p99", "min", "max"):
            if got.get(q) != hist.get(q):
                errs.append(f"serve.batch_ms {q}: log={got.get(q)} "
                            f"registry={hist.get(q)}")
    if d["cache"]["active"]:
        for name in ("hits", "misses", "evictions", "invalidations"):
            want = counters.get(f"serve.cache.{name}", 0)
            if want != d["cache"][name]:
                errs.append(f"serve.cache.{name}: log={d['cache'][name]} "
                            f"registry={want}")
        for name, h in (("serve.cache.hit_ms", d["cache_hit_ms"]),
                        ("serve.cache.miss_ms", d["cache_miss_ms"])):
            hist = metrics.get("histograms", {}).get(name)
            if hist is None or hist.get("count", 0) == 0:
                continue
            got = h.summary()
            for q in ("count", "p50", "p95", "p99", "min", "max"):
                if got.get(q) != hist.get(q):
                    errs.append(f"{name} {q}: log={got.get(q)} "
                                f"registry={hist.get(q)}")
    return errs


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("jsonl", help="event log written by MetricsRegistry")
    ap.add_argument("--metrics", default=None,
                    help="registry as_dict JSON dump to cross-check "
                         "against (exit 1 on any mismatch)")
    ap.add_argument("--window", type=int, default=DEFAULT_WINDOW,
                    help="histogram window the registry used")
    args = ap.parse_args(argv)
    events = load_events(args.jsonl)
    bad = [e for e in events
           if e.get("v") not in range(1, EVENT_SCHEMA_VERSION + 1)
           or "t_ms" not in e or "kind" not in e
           or (e["kind"] == "span" and e["v"] >= 2
               and not {"start_ms", "parent"} <= e.keys())]
    if bad:
        print(f"error: {len(bad)} malformed event(s), e.g. {bad[0]}",
              file=sys.stderr)
        return 2
    d = derive(events, window=args.window)
    print(f"{len(events)} events")
    print(render(d))
    if args.metrics:
        with open(args.metrics) as f:
            metrics = json.load(f)
        errs = cross_check(d, metrics)
        if errs:
            print("\nCROSS-CHECK FAILED:", file=sys.stderr)
            for e in errs:
                print(f"  {e}", file=sys.stderr)
            return 1
        print("\ncross-check vs registry dump: exact match")
    return 0


if __name__ == "__main__":
    sys.exit(main())
