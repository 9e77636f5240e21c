#!/usr/bin/env python3
"""Bring-up check of the PageRank system on a TPU.

Drives the main path once through the entry points a user calls
(``PageRankEngine``, ``DynamicPageRankEngine``, ``PageRankQueryEngine``)
and holds every result to a float64 numpy reference computed here from the
same seeded edges.  Phases on one chip (the default):

1. device   -- JAX's first device is a TPU and the device count is the
               number of chips asked for; there is no CPU fallback.
2. paper    -- the paper's deployment: the 5,000-node protein network,
               100 iterations, d = 0.85, through ``auto`` and each
               single-chip tier by name (dense, ell, bsr, pallas_dense,
               pallas_dense with bf16 storage).  Warm times are printed
               labelled as an overheads-only size.
3. global   -- a 2^20-node protein network (about 8.6M directed edges)
               solved to tol 1e-6 through ``auto``.
4. serve    -- personalized PageRank served on the same graph: a result
               cache and landmark hubs in front of the dynamic engine, 64
               Zipf-skewed queries, one edge delta, 64 more queries.

``--chips 4`` runs only the multi-chip path: ``ell_sharded`` on a 2^19-node
network (solve and a batched PPR of 16 queries), ``dense_sharded`` on the
5,000-node network, and one shard-local push delta through the dynamic
engine on ``ell_sharded``, each with its reference, and the bytes every
device holds of each sharded layout.

Every check prints one line.  The last line of a run whose checks all
passed is one JSON object, ``{"ok": true, "device": {...}}``; any failure
exits non-zero without it.

Usage:  python chip_smoke.py [--chips 1|4] [--seed 0]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402
import scipy.sparse as sp  # noqa: E402

from repro.configs import pagerank_5k  # noqa: E402
from repro.graph import generators as gen  # noqa: E402
from repro.graph.delta import GraphDelta  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.pagerank.dynamic import DynamicPageRankEngine  # noqa: E402
from repro.pagerank.engine import PageRankEngine  # noqa: E402
from repro.pagerank.fidelity import topk_overlap  # noqa: E402
from repro.pagerank.landmarks import LandmarkIndex  # noqa: E402
from repro.serve.cache import ResultCache  # noqa: E402
from repro.serve.engine import (PageRankQueryEngine,  # noqa: E402
                                ServeResilience)

# engines on the chip run their Pallas kernels compiled, never interpreted
EXPECT_INTERPRET = False
PAPER_TIERS = (("auto", "f32"), ("dense", "f32"), ("ell", "f32"),
               ("bsr", "f32"), ("pallas_dense", "f32"),
               ("pallas_dense", "bf16"))


class NoChip(RuntimeError):
    """JAX found no TPU, or not the number of chips asked for."""


def require_tpu(chips: int) -> dict:
    """The device block of the result line; raises :class:`NoChip` unless
    JAX's devices are ``chips`` TPUs."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU chip: JAX's first device is on platform "
                     f"{devs[0].platform!r}")
    if len(devs) != chips:
        raise NoChip(f"asked for {chips} TPU chip(s), JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class Checks:
    """Prints one line per check and remembers the failures."""

    def __init__(self):
        self.failed: list[str] = []

    def __call__(self, phase: str, name: str, value, limit, ok: bool,
                 **info) -> bool:
        fields = " ".join(f"{k}={_fmt(v)}" for k, v in info.items())
        line = (f"check phase={phase} {name}={_fmt(value)} "
                f"limit={_fmt(limit)} ok={bool(ok)} {fields}").rstrip()
        print(line, flush=True)
        if not ok:
            self.failed.append(line)
        return bool(ok)

    def note(self, phase: str, **info) -> None:
        fields = " ".join(f"{k}={_fmt(v)}" for k, v in info.items())
        print(f"note phase={phase} {fields}", flush=True)


def _fmt(v) -> str:
    if isinstance(v, float):
        return repr(v)
    return str(v).replace(" ", "")


# --------------------------------------------------------------------------- #
# float64 references, independent of the engine                               #
# --------------------------------------------------------------------------- #
class RefGraph:
    """Deduplicated directed edges with the engine's semantics: column-
    stochastic transitions, and the dangling mass sent to the teleport
    distribution.  Iterates in float64 with a scipy CSR matrix, on one
    vector (n,) or a batch of columns (n, Q)."""

    def __init__(self, src: np.ndarray, dst: np.ndarray, n: int):
        keys = np.unique(np.asarray(src, np.int64) * n
                         + np.asarray(dst, np.int64))
        self.n = n
        src, dst = keys // n, keys % n
        outdeg = np.bincount(src, minlength=n).astype(np.float64)
        self.H = sp.csr_matrix((1.0 / outdeg[src], (dst, src)),
                               shape=(n, n))
        self.dang = outdeg == 0

    def step(self, x: np.ndarray, v: np.ndarray, d: float) -> np.ndarray:
        return d * (self.H @ x + x[self.dang].sum(axis=0) * v) \
            + (1.0 - d) * v

    def solve(self, v: np.ndarray | None = None, d: float = 0.85,
              n_iters: int | None = None, tol: float = 1e-10,
              max_iters: int = 10_000) -> np.ndarray:
        """From ``x0 = v`` (uniform when ``v`` is None): ``n_iters``
        iterations, or until every column's L1 step is at most ``tol``."""
        v = np.full(self.n, 1.0 / self.n) if v is None else v
        x = v.copy()
        for it in range(n_iters if n_iters is not None else max_iters):
            new = self.step(x, v, d)
            done = (n_iters is None
                    and np.abs(new - x).sum(axis=0).max() <= tol)
            x = new
            if done:
                break
        return x


def seed_vector(n: int, seeds) -> np.ndarray:
    v = np.zeros(n)
    np.add.at(v, np.asarray(seeds, np.int64), 1.0 / len(seeds))
    return v


def seed_columns(n: int, seed_sets) -> np.ndarray:
    return np.stack([seed_vector(n, s) for s in seed_sets], axis=1)


# --------------------------------------------------------------------------- #
# helpers                                                                     #
# --------------------------------------------------------------------------- #
def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def device_bytes(arrays) -> dict[int, int]:
    """Bytes of the arrays of the pytree ``arrays`` held on each device."""
    out: dict[int, int] = {}
    for a in jax.tree.leaves(arrays):
        for sh in a.addressable_shards:
            out[sh.device.id] = out.get(sh.device.id, 0) + sh.data.nbytes
    return dict(sorted(out.items()))


def check_spread(chk: Checks, phase: str, what: str, arrays,
                 n_devices: int) -> None:
    """A sharded layout holds an equal share on every device."""
    per = device_bytes(arrays)
    total = sum(per.values())
    chk(phase, f"{what}_devices_holding", sum(v > 0 for v in per.values()),
        n_devices, len(per) == n_devices and min(per.values()) > 0,
        bytes_per_device=per)
    chk(phase, f"{what}_max_device_share", max(per.values()) / total,
        1.0 / n_devices, max(per.values()) <= total / n_devices * 1.001)


def zipf_picks(rng, pool: int, size: int, s: float = 1.1) -> np.ndarray:
    w = 1.0 / np.arange(1, pool + 1, dtype=np.float64) ** s
    return rng.choice(pool, size=size, p=w / w.sum())


def undirected_delta(rng, src, dst, n: int, n_ins: int,
                     n_del: int) -> GraphDelta:
    """``n_ins`` new undirected edges and ``n_del`` existing ones removed
    (the engine symmetrizes both sides)."""
    keys = np.unique(np.asarray(src, np.int64) * n + dst)
    ins: set[tuple[int, int]] = set()
    while len(ins) < n_ins:
        u, v = (int(a) for a in rng.integers(0, n, size=2))
        if u != v and not np.isin(u * n + v, keys) and (v, u) not in ins:
            ins.add((u, v))
    fwd = np.flatnonzero(np.asarray(src) < np.asarray(dst))
    pick = rng.choice(fwd, size=n_del, replace=False)
    i = np.array(sorted(ins), np.int64)
    return GraphDelta(i[:, 0], i[:, 1], np.asarray(src)[pick],
                      np.asarray(dst)[pick])


def post_delta_edges(src, dst, n: int, delta: GraphDelta):
    """The undirected graph after ``delta``, from the edge lists alone."""
    def both(a, b):
        a, b = np.asarray(a, np.int64), np.asarray(b, np.int64)
        return np.concatenate([a * n + b, b * n + a])
    keys = np.union1d(
        np.setdiff1d(np.asarray(src, np.int64) * n + dst,
                     both(delta.delete_src, delta.delete_dst)),
        both(delta.insert_src, delta.insert_dst))
    return keys // n, keys % n


# --------------------------------------------------------------------------- #
# phases                                                                      #
# --------------------------------------------------------------------------- #
def phase_paper(chk: Checks, *, n: int, n_iters: int, d: float,
                seed: int, tiers=PAPER_TIERS) -> None:
    src, dst = gen.protein_network(n, seed=seed)
    ref = RefGraph(src, dst, n).solve(d=d, n_iters=n_iters)
    chk.note("paper", nodes=n, directed_edges=len(src), iters=n_iters, d=d)
    f32_pallas = None
    for backend, precision in tiers:
        eng, t_prep = _timed(lambda: PageRankEngine(
            src, dst, n, d=d, backend=backend, precision=precision))
        tag = dict(requested=backend, backend=eng.backend,
                   layout=eng.layout, precision=precision)
        chk("paper", "interpret", eng.interpret, EXPECT_INTERPRET,
            eng.interpret is EXPECT_INTERPRET, **tag)
        pr, t_first = _timed(lambda: eng.run(n_iters=n_iters))
        _, t_warm = _timed(lambda: eng.run(n_iters=n_iters))
        pr = np.asarray(pr, np.float64)
        chk.note("paper", **tag, prepare_s=t_prep, first_run_s=t_first,
                 warm_run_s=t_warm,
                 size="overheads-only")
        if precision == "f32":
            err = float(np.abs(pr - ref).max())
            chk("paper", "max_abs_diff", err, 1e-5, err <= 1e-5, **tag)
            if backend == "pallas_dense":
                f32_pallas = pr
        else:
            base = ref if f32_pallas is None else f32_pallas
            ov = topk_overlap(pr, base, k=100)
            chk("paper", "top100_overlap_vs_f32", ov, 0.99, ov >= 0.99,
                **tag)
        res, t_tol = _timed(lambda: eng.run_tol(tol=1e-6))
        chk("paper", "run_tol_converged", res.info.converged, True,
            res.info.converged, iters=res.info.iters,
            residual=res.info.residual, s=t_tol, **tag)


def phase_global(chk: Checks, src, dst, n: int, d: float) -> None:
    eng, t_prep = _timed(lambda: PageRankEngine(src, dst, n, d=d))
    chk("global", "backend", eng.backend, "ell", eng.backend == "ell",
        layout=eng.layout, prepare_s=t_prep,
        layout_bytes=eng.layout_bytes["total_bytes"])
    res, t_first = _timed(lambda: eng.run_tol(tol=1e-6))
    res, t_warm = _timed(lambda: eng.run_tol(tol=1e-6))
    chk("global", "run_tol_converged", res.info.converged, True,
        res.info.converged, iters=res.info.iters,
        residual=res.info.residual, first_s=t_first, warm_s=t_warm)
    t0 = time.perf_counter()
    ref = RefGraph(src, dst, n).solve(d=d, tol=1e-10)
    pr = np.asarray(res[0], np.float64)
    l1 = float(np.abs(pr - ref).sum())
    chk("global", "l1_vs_f64", l1, 1e-4, l1 <= 1e-4,
        reference_s=time.perf_counter() - t0)
    ov = topk_overlap(pr, ref, k=100)
    chk("global", "top100_overlap", ov, 0.99, ov >= 0.99)


def phase_serve(chk: Checks, src, dst, n: int, d: float, seed: int, *,
                pool: int = 48, per_round: int = 64, max_batch: int = 16,
                n_ins: int = 64, n_del: int = 32, n_ref: int = 4) -> None:
    rng = np.random.default_rng(seed + 1)
    eng, t_prep = _timed(lambda: DynamicPageRankEngine(src, dst, n, d=d))
    res, t_solve = _timed(lambda: eng.run_tol(tol=1e-6))
    chk("serve", "initial_solve_converged", res.info.converged, True,
        res.info.converged, backend=eng.backend, layout=eng.layout,
        prepare_s=t_prep, solve_s=t_solve, iters=res.info.iters)
    lm = LandmarkIndex(eng)
    qe = PageRankQueryEngine(eng, max_batch=max_batch,
                             resilience=ServeResilience(),
                             cache=ResultCache(), landmarks=lm)
    _, t_build = _timed(lambda: lm.build(qe.graph_version))
    chk.note("serve", hubs=lm.n_hubs, hub_build_s=t_build)
    seed_sets = [np.sort(rng.choice(n, size=int(rng.integers(1, 5)),
                                    replace=False)) for _ in range(pool)]

    def serve_round(uid0: int) -> list:
        picks = zipf_picks(rng, pool, per_round)
        t0 = time.perf_counter()
        qs = [qe.submit(uid0 + i, seed_sets[j]) for i, j in enumerate(picks)]
        qe.flush()
        chk.note("serve", queries=len(qs), round_s=time.perf_counter() - t0,
                 cache_hits=qe.cache.hits, cache_misses=qe.cache.misses)
        return qs

    served = serve_round(0)
    delta = undirected_delta(rng, src, dst, n, n_ins, n_del)
    vr = qe.push_update(delta)
    chk("serve", "delta_dead_letters", len(vr.dead_letters), 0,
        not vr.dead_letters, inserted=n_ins, deleted=n_del)
    after = serve_round(per_round)
    served += after
    outcome = qe.last_refresh_outcome
    info = qe.last_update_info
    chk("serve", "refresh_outcome", getattr(outcome, "status", None), "ok",
        outcome is not None and outcome.status == "ok",
        strategy=getattr(info, "strategy", None),
        iters=getattr(info, "iters", None))
    fresh = sum(q.status == "fresh" for q in served)
    chk("serve", "fresh_queries", fresh, len(served), fresh == len(served),
        statuses=sorted({q.status for q in served}))
    chk("serve", "dead_letters", len(qe.dead_letters), 0,
        len(qe.dead_letters) == 0)
    chk("serve", "cache_hits", qe.cache.hits, ">=1", qe.cache.hits >= 1,
        misses=qe.cache.misses,
        landmark_fallbacks=qe.metrics.counter("landmarks.fallbacks").value)
    graph = RefGraph(*post_delta_edges(src, dst, n, delta), n)
    ref = graph.solve(seed_columns(n, [q.seeds for q in after[:n_ref]]),
                      d=d, tol=1e-10)
    worst = 0.0
    for q, x in zip(after[:n_ref], ref.T):
        idx, scores = q.result
        top = np.sort(x)[::-1][:len(idx)]
        worst = max(worst, float(np.abs(scores - x[idx]).max()),
                    float(np.abs(scores - top).max()))
    chk("serve", "top10_score_max_abs_diff", worst, 1e-5, worst <= 1e-5,
        queries_compared=n_ref)


def phase_multichip(chk: Checks, *, n: int, n_paper: int, n_iters: int,
                    d: float, seed: int, n_queries: int = 16) -> None:
    n_dev = jax.device_count()
    rng = np.random.default_rng(seed + 2)
    src, dst = gen.protein_network(n, seed=seed)
    graph = RefGraph(src, dst, n)
    chk.note("multichip", nodes=n, directed_edges=len(src))

    eng, t_prep = _timed(lambda: PageRankEngine(src, dst, n, d=d))
    chk("multichip", "backend", eng.backend, "ell_sharded",
        eng.backend == "ell_sharded", layout=eng.layout, prepare_s=t_prep)
    check_spread(chk, "multichip", "ell_sharded_layout", eng.operands, n_dev)
    res, t_tol = _timed(lambda: eng.run_tol(tol=1e-6))
    ref = graph.solve(d=d, tol=1e-10)
    pr = np.asarray(res[0], np.float64)
    l1 = float(np.abs(pr - ref).sum())
    chk("multichip", "ell_sharded_run_tol_l1", l1, 1e-4,
        res.info.converged and l1 <= 1e-4, converged=res.info.converged,
        iters=res.info.iters, s=t_tol)
    ov = topk_overlap(pr, ref, k=100)
    chk("multichip", "ell_sharded_top100_overlap", ov, 0.99, ov >= 0.99)
    seed_sets = [np.sort(rng.choice(n, size=int(rng.integers(1, 5)),
                                    replace=False))
                 for _ in range(n_queries)]
    X, t_ppr = _timed(lambda: eng.ppr(seed_sets, n_iters=n_iters))
    ref = graph.solve(seed_columns(n, seed_sets), d=d, n_iters=n_iters)
    err = float(np.abs(np.asarray(X, np.float64) - ref).max())
    chk("multichip", "ell_sharded_ppr_max_abs_diff", err, 1e-5,
        err <= 1e-5, queries=n_queries, s=t_ppr)
    del eng

    psrc, pdst = gen.protein_network(n_paper, seed=seed)
    deng = PageRankEngine(psrc, pdst, n_paper, d=d, backend="dense_sharded")
    check_spread(chk, "multichip", "dense_sharded_layout", deng.operands,
                 n_dev)
    pr, t_run = _timed(lambda: deng.run(n_iters=n_iters))
    pref = RefGraph(psrc, pdst, n_paper).solve(d=d, n_iters=n_iters)
    err = float(np.abs(np.asarray(pr, np.float64) - pref).max())
    chk("multichip", "dense_sharded_max_abs_diff", err, 1e-5, err <= 1e-5,
        layout=deng.layout, iters=n_iters, s=t_run)
    del deng

    dyn, t_prep = _timed(lambda: DynamicPageRankEngine(src, dst, n, d=d))
    chk("multichip", "dynamic_backend", dyn.backend, "ell_sharded",
        dyn.backend == "ell_sharded", layout=dyn.layout, prepare_s=t_prep)
    check_spread(chk, "multichip", "dynamic_layout", dyn.operands, n_dev)
    dyn.run_tol(tol=1e-6)
    delta = undirected_delta(rng, src, dst, n, 16, 8)
    (pr, info), t_upd = _timed(lambda: dyn.update(delta, tol=1e-6))
    chk("multichip", "delta_strategy", info.strategy, "push",
        info.strategy == "push" and info.healthy, sweeps=info.iters,
        residual=info.residual, s=t_upd)
    ref = RefGraph(*post_delta_edges(src, dst, n, delta), n).solve(
        d=d, tol=1e-10)
    l1 = float(np.abs(np.asarray(pr, np.float64) - ref).sum())
    chk("multichip", "delta_l1_vs_f64", l1, 1e-4, l1 <= 1e-4)
    check_spread(chk, "multichip", "patched_layout", dyn.operands, n_dev)


def run_phases(chk: Checks, phases) -> None:
    """Run each phase; a phase that raises counts as a failed check and the
    next phase still runs."""
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn()
        except Exception as e:          # noqa: BLE001 — reported, then fail
            traceback.print_exc()
            chk(name, "raised", type(e).__name__, "none", False)
        chk.note(name, phase_s=time.perf_counter() - t0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        device = require_tpu(args.chips)
    except NoChip as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 2
    print(f"device kind={device['kind']!r} count={device['count']} "
          f"jax={jax.__version__}", flush=True)
    print(f"compile_cache dir={use_compile_cache()}", flush=True)
    cfg = pagerank_5k.full()
    chk = Checks()
    if args.chips == 1:
        big = {}

        def load_big():
            (big["src"], big["dst"]), t = _timed(
                lambda: gen.protein_network(2 ** 20, seed=args.seed))
            chk.note("global", nodes=2 ** 20, directed_edges=len(big["src"]),
                     generate_s=t)
            phase_global(chk, big["src"], big["dst"], 2 ** 20, cfg.damping)

        phases = [
            ("paper", lambda: phase_paper(
                chk, n=cfg.n_nodes, n_iters=cfg.n_iters, d=cfg.damping,
                seed=args.seed)),
            ("global", load_big),
            ("serve", lambda: phase_serve(
                chk, big["src"], big["dst"], 2 ** 20, cfg.damping,
                args.seed)),
        ]
    else:
        phases = [("multichip", lambda: phase_multichip(
            chk, n=2 ** 19, n_paper=cfg.n_nodes, n_iters=cfg.n_iters,
            d=cfg.damping, seed=args.seed))]
    run_phases(chk, phases)
    if chk.failed:
        print(f"chip_smoke: {len(chk.failed)} check(s) failed:",
              file=sys.stderr)
        for line in chk.failed:
            print("  " + line, file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
