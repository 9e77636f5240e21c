"""Observability layer: registry export stability, deterministic
quantiles, JSONL event schema, on-device solve traces on every backend,
SolveInfo iteration parity, and the serve -> JSONL -> report exact
round-trip."""
import json

import numpy as np
import pytest

from repro.graph import generators as gen
from repro.graph.delta import GraphDelta
from repro.obs.registry import (DEFAULT_WINDOW, EVENT_SCHEMA_VERSION,
                                Histogram, MetricsRegistry, NullRegistry)
from repro.obs.trace import TRACE_LEN, SolveTrace
from repro.pagerank.dynamic import DynamicPageRankEngine
from repro.pagerank.engine import BACKENDS, PageRankEngine
from repro.serve.engine import PageRankQueryEngine, ServeResilience


def _graph(n=48, seed=0):
    return gen.protein_network(n, seed=seed)


# --------------------------------------------------------------------------- #
# registry                                                                    #
# --------------------------------------------------------------------------- #
def test_registry_export_roundtrips_json():
    reg = MetricsRegistry()
    reg.counter("a.b").inc(3)
    reg.gauge("lag").set(1.5)
    reg.histogram("ms").observe(2.0)
    reg.histogram("ms").observe(4.0)
    with reg.span("work", tag="x"):
        pass
    d = reg.as_dict()
    again = json.loads(json.dumps(d))
    assert again == d
    assert again["counters"]["a.b"] == 3
    assert again["gauges"]["lag"] == 1.5
    assert again["histograms"]["ms"]["count"] == 2
    assert "span.work" in again["histograms"]
    # stable key order: sorted names
    assert list(again["counters"]) == sorted(again["counters"])
    assert list(again["histograms"]) == sorted(again["histograms"])


def test_histogram_quantiles_deterministic_under_seeded_workload():
    rng = np.random.default_rng(42)
    vals = rng.exponential(10.0, size=5000)
    h1, h2 = Histogram(DEFAULT_WINDOW), Histogram(DEFAULT_WINDOW)
    for v in vals:
        h1.observe(v)
        h2.observe(float(v))
    assert h1.summary() == h2.summary()
    # nearest-rank over the last-`window` observations, by definition
    tail = sorted(float(v) for v in vals[-DEFAULT_WINDOW:])
    import math
    for q in (0.5, 0.95, 0.99):
        want = tail[min(max(1, math.ceil(q * len(tail))), len(tail)) - 1]
        assert h1.quantile(q) == want
    # full-stream stats are over everything, not just the window
    assert h1.count == len(vals)
    assert h1.min == float(vals.min()) and h1.max == float(vals.max())


def test_histogram_single_value_and_window_eviction():
    h = Histogram(window=4)
    h.observe(7.0)
    assert h.quantile(0.5) == 7.0 and h.quantile(0.99) == 7.0
    for v in (1.0, 2.0, 3.0, 4.0, 5.0):
        h.observe(v)
    assert h.quantile(0.5) == 3.0          # window holds [2, 3, 4, 5]
    assert h.count == 6 and h.max == 7.0   # stream stats keep everything


def test_jsonl_event_schema_golden(tmp_path):
    path = str(tmp_path / "events.jsonl")
    reg = MetricsRegistry(jsonl_path=path)
    reg.event("serve", ms=1.25, batch=4, status="fresh")
    reg.event("refresh", status="ok", applied=True)
    with reg.span("work", tag="x"):
        pass
    reg.close()
    lines = [json.loads(line) for line in open(path)]
    assert len(lines) == 3
    assert EVENT_SCHEMA_VERSION == 2
    for ev in lines:
        # golden schema: version, monotonic relative timestamp, kind, then
        # the caller's fields in sorted key order
        keys = list(ev)
        assert keys[:3] == ["v", "t_ms", "kind"]
        assert keys[3:] == sorted(keys[3:])
        assert ev["v"] == EVENT_SCHEMA_VERSION
        assert isinstance(ev["t_ms"], (int, float)) and ev["t_ms"] >= 0
    assert lines[0]["kind"] == "serve" and lines[0]["batch"] == 4
    assert lines[1]["t_ms"] >= lines[0]["t_ms"]      # monotonic
    # schema 2: a span records its start and the span open around it
    assert list(lines[2]) == ["v", "t_ms", "kind", "ms", "name", "parent",
                              "start_ms", "tag"]
    assert lines[2]["parent"] is None
    # the in-memory log and the file agree
    assert reg.events == lines


def test_event_retention_bounded():
    reg = MetricsRegistry(max_events=8)
    for i in range(20):
        reg.event("tick", i=i)
    assert len(reg.events) == 8
    assert reg.events_dropped == 12
    assert reg.as_dict()["n_events"] == 8
    assert reg.events[0]["i"] == 12                  # oldest retained


def test_null_registry_is_inert():
    reg = NullRegistry()
    reg.counter("c").inc(5)
    reg.gauge("g").set(1.0)
    reg.histogram("h").observe(3.0)
    reg.event("anything", x=1)
    with reg.span("s"):
        pass
    d = reg.as_dict()
    assert d["counters"] == {} and d["histograms"] == {}
    assert d["n_events"] == 0
    assert reg.histogram("h").quantile(0.5) is None


# --------------------------------------------------------------------------- #
# on-device solve traces, every backend                                       #
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("backend", BACKENDS)
def test_solve_trace_every_backend(backend):
    n = 48
    src, dst = _graph(n)
    eng = PageRankEngine(src, dst, n, backend=backend,
                         metrics=NullRegistry())
    res = eng.run_tol(1e-7, max_iters=300)
    tr = res.info.trace
    assert isinstance(tr, SolveTrace)
    assert tr.n_iters == res.info.iterations == int(res.iters)
    r = tr.residuals
    assert len(r) == min(tr.n_iters, TRACE_LEN)
    assert np.isfinite(r).all() and (r > 0).all()
    # last recorded residual IS the solve's exit residual
    assert r[-1] == pytest.approx(float(res.residual), rel=1e-6)
    # healthy damped power iteration: strictly contracting tail
    assert (tr.ratios < 1.0).all()
    # trace=False compiles the ring out
    assert eng.run_tol(1e-7, trace=False).info.trace is None


def test_trace_ring_wraparound_keeps_tail():
    n = 48
    src, dst = _graph(n)
    eng = PageRankEngine(src, dst, n, backend="ell",
                         metrics=NullRegistry())
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        # tol=-1 pins the iteration count (the float32 residual hits an
        # exact 0.0 fixed point well before 74 iterations on a graph this
        # small, so tol=0.0 would exit early); watchdog off because the
        # noise-floor jitter would (correctly) trip the growth abort
        short = eng.run_tol(tol=-1.0, max_iters=TRACE_LEN,
                            watchdog=False)
        res = eng.run_tol(tol=-1.0, max_iters=TRACE_LEN + 10,
                          watchdog=False)
    tr = res.info.trace
    assert tr.n_iters == TRACE_LEN + 10
    assert len(tr.residuals) == TRACE_LEN
    # the ring holds the LAST TRACE_LEN residuals, chronological: the
    # final entry is the exit residual...
    assert tr.residuals[-1] == pytest.approx(float(res.residual),
                                             rel=1e-6)
    # ...and the reconstruction is the deterministic solve's tail: the
    # wrapped trace shifted by 10 matches the unwrapped trace exactly
    np.testing.assert_array_equal(tr.residuals[:TRACE_LEN - 10],
                                  short.info.trace.residuals[10:])


def test_trace_ratios_pair_adjacent_samples_across_wraparound():
    """Regression: for a solve longer than the ring (65+ iterations),
    ``SolveTrace.ratios`` must pair only chronologically adjacent retained
    residuals — never the artificial ring-buffer seam ``ring[-1]/ring[0]``
    of the raw (unrotated) storage order."""
    iters = TRACE_LEN + 6
    res = 1.0 / (2.0 + np.arange(iters, dtype=np.float32))
    ring = np.zeros(TRACE_LEN, np.float32)
    for i in range(iters):                 # replay the device ring writes
        ring[i % TRACE_LEN] = res[i]
    import jax.numpy as jnp
    tr = SolveTrace(jnp.asarray(ring), iters)
    # retained = the last TRACE_LEN residuals, oldest first
    np.testing.assert_array_equal(tr.residuals, res[iters - TRACE_LEN:])
    got = tr.ratios
    assert len(got) == TRACE_LEN - 1
    want = res[iters - TRACE_LEN + 1:] / res[iters - TRACE_LEN:-1]
    np.testing.assert_array_equal(got, want)
    # every ratio reflects the decaying trajectory: no seam ratio > 1
    assert (got < 1.0).all() and np.isfinite(got).all()


@pytest.mark.parametrize("backend", ("dense", "ell", "pallas_dense"))
def test_solve_info_iteration_parity_incl_push(backend):
    """Every refresh strategy reports its real iteration/sweep count and
    final residual through the same SolveInfo surface."""
    n = 48
    src, dst = _graph(n)
    eng = DynamicPageRankEngine(src, dst, n, backend=backend,
                                metrics=NullRegistry())
    res = eng.run_tol(1e-7)
    assert eng.last_solve_info.iterations == int(res.iters) > 0
    assert eng.last_solve_info.residual == pytest.approx(
        float(res.residual))
    # pick edges guaranteed absent, so the delta is not a no-op
    have = set(zip(src.tolist(), dst.tolist()))
    new = [(u, v) for u in range(n) for v in range(n)
           if u != v and (u, v) not in have][:2]
    _, info = eng.update(GraphDelta.inserts([u for u, _ in new],
                                            [v for _, v in new]),
                         strategy="push")
    assert eng.last_solve_info.iterations == info.iters > 0
    assert eng.last_solve_info.residual == pytest.approx(info.residual)
    assert eng.last_solve_info.converged
    # the push solve records its residual trajectory too
    tr = eng.last_solve_info.trace
    assert tr is not None and tr.n_iters == info.iters
    assert tr.residuals[-1] == pytest.approx(info.residual, rel=1e-6)


def test_solve_trace_iteration_parity_across_backends():
    """All six backends agree on the iteration count and the (near-)
    identical residual trajectory for the same graph + tolerance."""
    n = 48
    src, dst = _graph(n)
    runs = {}
    for backend in BACKENDS:
        eng = PageRankEngine(src, dst, n, backend=backend,
                             metrics=NullRegistry())
        res = eng.run_tol(1e-7, max_iters=300)
        runs[backend] = (res.info.iterations, res.info.trace.residuals)
    iters = sorted(it for it, _ in runs.values())
    # float32 accumulation order can move the exit across the tolerance
    # boundary by one iteration, never more
    assert iters[-1] - iters[0] <= 1, f"iteration counts disagree: {runs}"
    ref = runs["dense"][1]
    for backend, (_, r) in runs.items():
        k = min(len(r), len(ref))
        # atol sits just above the float32 noise floor at tol=1e-7:
        # once residuals reach ~1e-7 the accumulation-order jitter is
        # the same magnitude as the values themselves
        np.testing.assert_allclose(r[:k], ref[:k], rtol=5e-4, atol=2e-7,
                                   err_msg=backend)


# --------------------------------------------------------------------------- #
# engine + serve instrumentation                                              #
# --------------------------------------------------------------------------- #
def test_engine_metrics_counters_and_events():
    n = 48
    src, dst = _graph(n)
    reg = MetricsRegistry()
    eng = DynamicPageRankEngine(src, dst, n, backend="ell", metrics=reg)
    eng.run_tol(1e-6)
    # insert edges guaranteed absent, else the delta is a no-op and the
    # incremental push solve never runs
    have = set(zip(src.tolist(), dst.tolist()))
    new = [(u, v) for u in range(n) for v in range(n)
           if u != v and (u, v) not in have][:2]
    eng.update(GraphDelta.inserts([u for u, _ in new],
                                  [v for _, v in new]))
    eng.ppr([np.array([0]), np.array([1])], n_iters=5)
    d = reg.as_dict()
    assert d["counters"]["engine.solves"] == 2
    assert d["counters"]["engine.solve.converged"] == 2
    assert d["counters"]["update.push"] == 1
    assert d["counters"]["engine.ppr_queries"] == 2
    for span in ("span.prepare", "span.solve", "span.update",
                 "span.update.patch", "span.ppr.dispatch"):
        assert d["histograms"][span]["count"] >= 1, span
    kinds = [e["kind"] for e in reg.events]
    assert "solve" in kinds and "update" in kinds
    ev = next(e for e in reg.events if e["kind"] == "update")
    assert ev["strategy"] == "push" and ev["healthy"] is True


def test_serve_report_roundtrip_exact(tmp_path, monkeypatch):
    """The acceptance bar: a seeded streaming-serve run's JSONL alone
    reproduces the fresh/stale/degraded counts, refresh outcomes, and
    p50/p95 serve latency exactly (obs_report cross-check passes)."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    import obs_report

    n = 48
    src, dst = _graph(n)
    jsonl = str(tmp_path / "events.jsonl")
    reg = MetricsRegistry(jsonl_path=jsonl)
    eng = DynamicPageRankEngine(src, dst, n, backend="ell", metrics=reg)
    eng.run_tol(1e-6)
    server = PageRankQueryEngine(eng, n_iters=20, max_batch=10_000,
                                 resilience=ServeResilience(), metrics=reg)
    rng = np.random.default_rng(3)
    # fresh
    server.push_update(GraphDelta.inserts(rng.integers(0, n, 3),
                                          rng.integers(0, n, 3)))
    for uid in range(3):
        server.submit(uid, rng.integers(0, n, 2))
    server.flush()
    # out-of-range ids -> dead letters
    server.push_update(GraphDelta.inserts([0, n + 1], [n + 2, 1]))
    # degraded: the batched PPR dispatch raises; recovery is monkeypatched
    # out so the fallback answers from last-known-good global ranks
    monkeypatch.setattr(eng, "ppr",
                        lambda *a, **k: (_ for _ in ()).throw(
                            RuntimeError("injected")))
    monkeypatch.setattr(server.refresher, "recover",
                        lambda *a, **k: None)
    for uid in range(2):
        server.submit(uid, rng.integers(0, n, 2))
    out = server.flush()
    assert [q.status for q in out] == ["degraded", "degraded"]
    reg.dump_json(str(tmp_path / "metrics.json"))
    reg.close()

    derived = obs_report.derive(obs_report.load_events(jsonl))
    assert derived["queries"] == {"fresh": 3, "degraded": 2}
    assert derived["refreshes"].get("ok", 0) >= 1
    assert derived["dead_letters"] == 2
    errs = obs_report.cross_check(
        derived, json.load(open(tmp_path / "metrics.json")))
    assert errs == []
    # and through main(): exit 0 == exact
    assert obs_report.main([jsonl, "--metrics",
                            str(tmp_path / "metrics.json")]) == 0


def test_serve_latency_histogram_and_freshness_gauge():
    n = 48
    src, dst = _graph(n)
    reg = MetricsRegistry()
    eng = DynamicPageRankEngine(src, dst, n, backend="ell", metrics=reg)
    eng.run_tol(1e-6)
    server = PageRankQueryEngine(eng, n_iters=10, max_batch=10_000,
                                 metrics=reg)    # legacy mode
    server.query_batch([[0], [1], [2]])
    server.query_batch([[3]])
    d = reg.as_dict()
    h = d["histograms"]["serve.batch_ms"]
    assert h["count"] == 2 and h["p50"] > 0
    assert d["counters"]["serve.batches"] == 2
    assert d["counters"]["serve.queries"] == 4
    assert d["gauges"]["serve.freshness_lag_s"] >= 0
    ev = [e for e in reg.events if e["kind"] == "serve"]
    assert len(ev) == 2 and ev[0]["status"] == "legacy"


def test_engine_default_registry_shared_with_serve():
    """Engines built without metrics= land in the process default
    registry, and the serving layer inherits the engine's registry."""
    n = 32
    src, dst = _graph(n)
    reg = MetricsRegistry()
    eng = PageRankEngine(src, dst, n, backend="dense", metrics=reg)
    server = PageRankQueryEngine(eng, n_iters=5)
    assert server.metrics is reg


# --------------------------------------------------------------------------- #
# span records, refresh spans, compile watch, named scopes                    #
# --------------------------------------------------------------------------- #
def test_span_event_records_start_and_parent():
    reg = MetricsRegistry()
    with reg.span("outer"):
        with reg.span("inner"):
            pass
        with reg.span("inner"):
            pass
    with reg.span("after"):
        pass
    spans = [e for e in reg.events if e["kind"] == "span"]
    assert [(e["name"], e["parent"]) for e in spans] == [
        ("inner", "outer"), ("inner", "outer"), ("outer", None),
        ("after", None)]
    inner, inner2, outer, after = spans
    # start_ms is on the event clock: a span starts inside its parent and
    # ends (t_ms) no earlier than it started plus its duration, rounded
    assert outer["start_ms"] <= inner["start_ms"] <= inner2["start_ms"]
    assert inner2["start_ms"] + inner2["ms"] <= outer["t_ms"] + 1e-3
    assert after["start_ms"] >= outer["t_ms"] - 1e-3
    for e in spans:
        assert e["t_ms"] >= e["start_ms"]


def test_span_stack_survives_a_raise():
    reg = MetricsRegistry()
    with pytest.raises(RuntimeError):
        with reg.span("fails"):
            raise RuntimeError("x")
    with reg.span("next"):
        pass
    assert [(e["name"], e["parent"]) for e in reg.events] == [
        ("fails", None), ("next", None)]


def test_update_emits_refresh_spans_with_parents():
    n = 48
    src, dst = _graph(n)
    reg = MetricsRegistry()
    eng = DynamicPageRankEngine(src, dst, n, backend="ell", metrics=reg)
    eng.run_tol(1e-6)
    have = set(zip(src.tolist(), dst.tolist()))
    new = [(u, v) for u in range(n) for v in range(n)
           if u != v and (u, v) not in have][:2]
    start = len(reg.events)
    _, info = eng.update(GraphDelta.inserts([u for u, _ in new],
                                            [v for _, v in new]))
    assert info.strategy == "push"
    parents = {}
    for e in reg.events[start:]:
        if e["kind"] == "span":
            parents.setdefault(e["name"], set()).add(e["parent"])
    assert parents["update"] == {None}
    assert parents["update.plan"] == {"update"}
    assert parents["update.plan.keys"] == {"update.plan"}
    assert parents["update.plan.rows"] == {"update.plan"}
    assert parents["update.commit"] == {"update"}
    assert parents["update.patch"] == {"update"}
    # one host row rebuild per SELL tier the delta touches
    assert parents["update.patch.rows"] == {"update.patch"}
    assert parents["solve"] == {"update"}


def test_watch_compiles_counts_real_compiles_and_names_the_span(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc

    reg = MetricsRegistry()
    reg.watch_compiles()
    reg.watch_compiles()                        # idempotent
    assert reg.as_dict()["counters"]["compiles"] == 0
    null = NullRegistry()
    null.watch_compiles()

    def fresh():
        # a new function object: never in JAX's in-memory caches
        return jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)

    x = (jnp.arange(8.0) + 1).block_until_ready()
    count = lambda: reg.as_dict()["counters"]["compiles"]
    c0, e0 = count(), len(reg.events)
    with reg.span("outer"):
        f = fresh()
        f(x).block_until_ready()
    assert count() == c0 + 1
    ev = [e for e in reg.events[e0:] if e["kind"] == "compile"]
    assert len(ev) == 1 and ev[0]["span"] == "outer"
    assert ev[0]["s"] >= 0
    f(x).block_until_ready()                    # in-memory hit
    assert count() == c0 + 1
    # a program loaded from the persistent cache is not a compile
    keys = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        cc.reset_cache()
        fresh()(x).block_until_ready()          # compiled, written
        assert count() == c0 + 2
        jax.clear_caches()
        fresh()(x).block_until_ready()          # read back
        assert count() == c0 + 2
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()
    assert len([e for e in reg.events[e0:] if e["kind"] == "compile"]) == 2
    assert null.as_dict()["counters"] == {} and null.events == []


# the ``ell`` tier's programs with the named-scope set each has carried
# since the scopes were introduced
_ELL_PROGRAMS = {
    "tol": {"pagerank.ell_gather", "pagerank.sell_order", "pagerank.vector"},
    "fixed": {"pagerank.ell_gather", "pagerank.sell_order",
              "pagerank.vector"},
    "ppr": {"pagerank.ell_gather", "pagerank.sell_order", "pagerank.vector"},
    "push": {"pagerank.ell_gather", "pagerank.sell_order", "pagerank.vector",
             "pagerank.push"},
}


@pytest.mark.parametrize("program", sorted(_ELL_PROGRAMS))
def test_named_scopes_change_only_metadata(monkeypatch, program):
    """The ``pagerank.*`` scopes are op metadata: the compiled program of
    each of the ``ell`` tier's loop drivers (tolerance solve, fixed run,
    batched PPR, refresh push) is the unscoped one, instruction for
    instruction."""
    import contextlib
    import re

    import jax
    import jax.numpy as jnp

    from repro.pagerank.engine import (fixed_loop, global_push, ppr_loop,
                                       tol_loop)

    n = 96
    src, dst = gen.barabasi_albert(n, 3, seed=0)   # hub rows: several tiers
    eng = PageRankEngine(src, dst, n, backend="ell",
                         metrics=NullRegistry())
    assert len(eng._sell.widths) > 2
    lay, d, x0 = eng.prepared, eng.d, jnp.full((n,), 1.0 / n, jnp.float32)
    lower = {
        "tol": lambda: tol_loop.lower(lay, d, jnp.float32(1e-6), None,
                                      max_iters=100),
        "fixed": lambda: fixed_loop.lower(lay, d, n_iters=100),
        "ppr": lambda: ppr_loop.lower(
            lay, jax.ShapeDtypeStruct((n, 16), jnp.float32), d, n_iters=100),
        "push": lambda: global_push.lower(lay, d, jnp.float32(1e-6), x0,
                                          max_pushes=100),
    }[program]

    def compiled() -> str:
        jax.clear_caches()
        return lower().compile().as_text()

    def instructions(text: str) -> list[str]:
        text = re.sub(r",? metadata=\{[^}]*\}", "", text)
        # the stack-frame tables the metadata points into
        return [line for line in text.splitlines() if not re.match(
            r"^(\d+ |FileNames|FunctionNames|FileLocations|StackFrames)",
            line)]

    scoped = compiled()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = compiled()
    monkeypatch.undo()
    jax.clear_caches()
    assert set(re.findall(r"pagerank\.\w+", scoped)) == _ELL_PROGRAMS[program]
    assert "pagerank." not in plain
    assert instructions(scoped) == instructions(plain)


def test_ppr_span_times_the_dispatch():
    """``engine.ppr`` returns before the device finishes, so its span is
    named for what it times: ``ppr.dispatch``."""
    n = 32
    src, dst = _graph(n)
    reg = MetricsRegistry()
    eng = PageRankEngine(src, dst, n, backend="ell", metrics=reg)
    eng.ppr([np.array([0])], n_iters=3)
    hists = reg.as_dict()["histograms"]
    assert hists["span.ppr.dispatch"]["count"] == 1
    assert "span.ppr" not in hists


def test_obs_report_span_self_time_and_compiles(tmp_path):
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    import obs_report

    def span(name, start, ms, parent):
        return {"v": 2, "t_ms": start + ms, "kind": "span", "ms": ms,
                "name": name, "parent": parent, "start_ms": start}

    events = [span("update.plan.keys", 1.0, 3.0, "update.plan"),
              span("update.plan.rows", 4.0, 1.0, "update.plan"),
              span("update.plan", 0.5, 5.0, "update"),
              {"v": 2, "t_ms": 6.0, "kind": "compile", "fun": "f",
               "s": 0.1, "span": "update.patch"},
              span("update.patch", 6.0, 2.0, "update"),
              span("update", 0.0, 10.0, None),
              {"v": 1, "t_ms": 11.0, "kind": "span", "ms": 4.0,
               "name": "legacy"}]
    d = obs_report.derive(events)
    assert d["span_self_ms"] == pytest.approx(
        {"update.plan.keys": 3.0, "update.plan.rows": 1.0,
         "update.plan": 1.0, "update.patch": 2.0, "update": 3.0,
         "legacy": 4.0})
    assert d["compiles"] == {"update.patch": 1}
    text = obs_report.render(d)
    assert "self_sum=1.000ms" in text and "update.patch" in text
    path = tmp_path / "events.jsonl"
    path.write_text("".join(json.dumps(e) + "\n" for e in events))
    assert obs_report.main([str(path)]) == 0
    # a schema-2 span without its start and parent is malformed
    bad = dict(events[0])
    del bad["parent"]
    path.write_text(json.dumps(bad) + "\n")
    assert obs_report.main([str(path)]) == 2
