"""Batched serving engine: prefill + decode with slot-based continuous
batching (host-side scheduler over a fixed device batch).

The decode step is the paper's workload shape: every matmul against
stationary weights with a single activation vector per sequence — the
fabric-MV schedule (DESIGN.md §2).  The engine keeps a fixed-size device
batch of ``n_slots`` sequences; finished sequences free their slot and the
scheduler immediately prefills a queued request into it (continuous
batching a la vLLM/Orca, collapsed to the synchronous JAX step model).
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.graph.validate import (DeadLetterQueue, ValidationPolicy,
                                  validate_delta)
from repro.models import model as M
from repro.obs.registry import default_registry
from repro.pagerank.engine import PageRankEngine
from repro.pagerank.resilience import (RankStore, ResilientRefresher,
                                       RetryPolicy, ppr_health)
from repro.serve.cache import ResultCache


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray            # (S,) int32
    max_new_tokens: int = 32
    temperature: float = 0.0      # 0 => greedy
    output: list[int] = dataclasses.field(default_factory=list)
    done: bool = False


class ServeEngine:
    """Single-host engine (the slot scheduler is pure host logic; the device
    functions are jit'd once per shape)."""

    def __init__(self, cfg: ModelConfig, params, max_len: int = 512,
                 eos_id: int | None = None, seed: int = 0):
        if not cfg.embed_input:
            raise ValueError("token serving requires an embedding frontend")
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.eos_id = eos_id
        self._key = jax.random.PRNGKey(seed)
        self._decode = jax.jit(
            lambda p, b, c: M.decode_step(p, b, c, cfg))
        self._prefill = jax.jit(
            lambda p, b: M.prefill(p, b, cfg, max_len))

    # ---------------- single-sequence paths ---------------- #
    def generate(self, prompt: np.ndarray, max_new_tokens: int = 32,
                 temperature: float = 0.0) -> list[int]:
        logits, cache = self._prefill(
            self.params, {"tokens": jnp.asarray(prompt)[None, :]})
        out = []
        tok = self._sample(logits, temperature)
        for _ in range(max_new_tokens):
            t = int(tok[0])
            out.append(t)
            if self.eos_id is not None and t == self.eos_id:
                break
            logits, cache = self._decode(
                self.params, {"tokens": tok[:, None]}, cache)
            tok = self._sample(logits, temperature)
        return out

    # ---------------- batched continuous serving ---------------- #
    def serve(self, requests: list[Request], n_slots: int = 4,
              max_steps: int = 10_000) -> list[Request]:
        """Run all requests to completion with ``n_slots`` device slots.
        Sequences are prefixed independently (per-slot prefill) and decoded
        as one batched step; finished slots are refilled from the queue."""
        queue = deque(requests)     # popleft is O(1); list.pop(0) was O(n)
        slots: list[Request | None] = [None] * n_slots
        # exposed as self._caches so tests (and memory accounting) can
        # verify drained slots release their KV cache
        self._caches = caches = [None] * n_slots
        last_tok = np.zeros((n_slots,), np.int32)

        def fill_slot(i: int) -> None:
            if not queue:
                # drain: drop the finished sequence's KV cache too, so it
                # stops pinning device memory for the rest of the serve
                slots[i] = None
                caches[i] = None
                return
            req = queue.popleft()
            logits, cache = self._prefill(
                self.params, {"tokens": jnp.asarray(req.prompt)[None, :]})
            tok = self._sample(logits, req.temperature)
            req.output.append(int(tok[0]))
            slots[i] = req
            caches[i] = cache
            last_tok[i] = int(tok[0])

        for i in range(n_slots):
            fill_slot(i)

        for _ in range(max_steps):
            active = [i for i, r in enumerate(slots) if r is not None]
            if not active:
                break
            for i in active:
                req = slots[i]
                done = (len(req.output) >= req.max_new_tokens or
                        (self.eos_id is not None
                         and req.output[-1] == self.eos_id))
                if done:
                    req.done = True
                    fill_slot(i)
            active = [i for i, r in enumerate(slots) if r is not None]
            if not active:
                break
            # one decode step per active slot (batch=1 caches); a production
            # deployment shares one batched cache — see launch/serve.py for
            # the fixed-batch variant the dry-run lowers.
            for i in active:
                req = slots[i]
                logits, caches[i] = self._decode(
                    self.params,
                    {"tokens": jnp.asarray([[last_tok[i]]], jnp.int32)},
                    caches[i])
                tok = self._sample(logits, req.temperature)
                req.output.append(int(tok[0]))
                last_tok[i] = int(tok[0])
        return requests

    def _sample(self, logits: jax.Array, temperature: float) -> jax.Array:
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        self._key, sub = jax.random.split(self._key)
        return jax.random.categorical(
            sub, logits / temperature, axis=-1).astype(jnp.int32)


def batched_decode_fn(cfg: ModelConfig) -> Callable:
    """The fixed-batch decode step the dry-run lowers for decode cells."""
    def step(params, batch, cache):
        return M.decode_step(params, batch, cache, cfg)
    return step


@dataclasses.dataclass(frozen=True)
class ServeResilience:
    """Resilience knobs for :class:`PageRankQueryEngine` — pass an instance
    (or just ``ServeResilience()``) to turn the serving path from
    raise-on-anything into validate / quarantine / degrade-gracefully.

    ``validation`` screens every pushed delta
    (:func:`repro.graph.validate.validate_delta`); ``retry`` bounds the
    exponential-backoff update retries; ``snapshots`` is the last-known-
    good ring size; ``healthy_atol`` the sum-to-1 tolerance of the serve
    health checks; ``dead_letter_maxlen`` the quarantine audit window."""

    validation: ValidationPolicy = ValidationPolicy()
    retry: RetryPolicy = RetryPolicy()
    snapshots: int = 4
    healthy_atol: float = 1e-3
    dead_letter_maxlen: int = 256


def top_k_proteins(pr, k: int = 10):
    """Ranked (index, score) of the k highest-ranked vertices of a rank
    vector (N,), or of every column of an (N, Q) batch as (Q, k) arrays."""
    scores, idx = jax.lax.top_k(jnp.asarray(pr).T, k)
    return idx, scores


@partial(jax.jit, static_argnames=("k",))
def _rank_batch(PPR, atol, *, k: int):
    """One device step over a served (N, Q) batch: every column's top-``k``
    ids and scores, and its health flag (:func:`ppr_health`)."""
    idx, scores = top_k_proteins(PPR, k)
    return idx, scores, ppr_health(PPR, atol)


@dataclasses.dataclass
class PPRQuery:
    uid: int
    seeds: np.ndarray             # int indices of the user's seed proteins
    top_k: int = 10
    result: tuple | None = None   # (indices, scores) once served
    # resilience tags, stamped at serve time (resilient mode only):
    # "fresh"    — ranks include every accepted delta
    # "stale"    — last refresh failed; ranks predate the pending deltas
    # "degraded" — personalized serve unhealthy; global last-known-good
    #              ranks substituted
    status: str = "unserved"
    graph_version: int = -1       # RankStore version the result was built on
    # cache-enabled engines stamp how the answer was produced:
    # "hit" (served from cache) / "miss" (solved this flush); None when
    # the engine runs without a cache
    cache_outcome: str | None = None


class PageRankQueryEngine:
    """Multi-user personalized-PageRank serving over one prepared graph.

    The graph-analytics analogue of the token engine above: per-user seed
    sets queue up and are flushed as **one** batched (N, Q) propagation
    through :class:`~repro.pagerank.engine.PageRankEngine` — Q queries
    share each sweep over H instead of paying Q independent power
    iterations (the MELOPPR batching).  Host logic is only the queue; the
    device work is a single whole-loop-compiled dispatch per flush.

    **Live refresh** — when the engine is a
    :class:`~repro.pagerank.dynamic.DynamicPageRankEngine`, streamed edge
    deltas queue up via :meth:`push_update` and are folded into the
    prepared layouts (``engine.update``) by :meth:`refresh`.  ``flush``
    always refreshes first, so every served batch — including queries that
    were already in flight when the delta arrived — sees ranks no staler
    than one refresh interval.

    **Resilient mode** — pass ``resilience=ServeResilience()`` and the
    live path stops trusting its inputs and its own solves: pushed deltas
    are screened by :func:`repro.graph.validate.validate_delta` (bad edges
    quarantined into :attr:`dead_letters` instead of raising), refreshes
    run through the :class:`~repro.pagerank.resilience.ResilientRefresher`
    escalation ladder (retry → rebuild → restore last-known-good snapshot)
    and never raise, and every served batch is health-checked — an
    unhealthy PPR triggers one recovery + re-serve, then falls back to the
    last good *global* ranks.  Every query is stamped with ``status``
    (``"fresh"`` / ``"stale"`` / ``"degraded"``) and the graph version it
    was answered from, so callers can tell exactly what they got.  With
    ``resilience=None`` (default) behavior is the legacy raise-on-error
    path, unchanged.

    **Serve acceleration** (both optional, independent) — ``cache`` (a
    :class:`~repro.serve.cache.ResultCache`) answers repeated seed sets
    host-side; refreshes invalidate only the entries whose ranks the
    delta's Gauss–Southwell frontier actually perturbed (see
    ``_after_refresh``), never wholesale.  ``landmarks`` (a
    :class:`~repro.pagerank.landmarks.LandmarkIndex` over the same
    engine) replaces cold batched power iterations with hub-combination
    warm starts plus a short bounded residual push.  Every query is
    stamped ``cache_outcome`` (``"hit"``/``"miss"``) and flushes record
    per-outcome counters and latency histograms.
    """

    def __init__(self, engine: PageRankEngine, n_iters: int = 100,
                 max_batch: int = 8, refresh_tol: float = 1e-6,
                 resilience: ServeResilience | None = None,
                 metrics=None, cache: ResultCache | None = None,
                 landmarks=None):
        self.engine = engine
        self.n_iters = n_iters
        self.max_batch = max_batch
        self.refresh_tol = refresh_tol
        self._queue: list[PPRQuery] = []
        self._pending_deltas: list = []
        self.n_refreshes = 0
        self.last_update_info = None
        self.resilience = resilience
        self.last_refresh_outcome = None
        self._stale = False
        # serve-acceleration layer (both optional, independent):
        # ``cache`` answers repeat seed sets without touching the device
        # (delta-aware invalidation — see repro.serve.cache); ``landmarks``
        # (a repro.pagerank.landmarks.LandmarkIndex over this engine)
        # replaces cold batched solves with hub-combination + short push
        self.cache = cache
        self.landmarks = landmarks
        # cache-consistency clock: bumped on every applied refresh (and on
        # any recovery that may have moved the engine past the cached
        # entries' graph), independent of the resilience RankStore version
        self.graph_version = 0
        self._last_flush_stats: dict | None = None
        # metrics sink: share the engine's registry by default so solves,
        # updates, and serves land in one event log
        self.metrics = (metrics if metrics is not None
                        else getattr(engine, "metrics", None)
                        or default_registry())
        # freshness clock: when the served ranks last matched the stream
        # (start of life counts as fresh — nothing has been pushed yet)
        self._last_refresh_t = time.monotonic()
        if resilience is not None:
            self.dead_letters = DeadLetterQueue(
                maxlen=resilience.dead_letter_maxlen)
            self.refresher = ResilientRefresher(
                store=RankStore(maxlen=resilience.snapshots),
                retry=resilience.retry,
                healthy_atol=resilience.healthy_atol)
            self._ensure_baseline()

    # ----------------------- resilience plumbing ----------------------- #
    def _recoverable(self) -> bool:
        return hasattr(self.engine, "rebuild_and_solve")

    def _ensure_baseline(self) -> None:
        """Record the engine's current state as the first restore target
        (no-op until the engine has healthy solved ranks)."""
        if (self._recoverable() and len(self.refresher.store) == 0):
            self.refresher.baseline(self.engine)

    def submit(self, uid: int, seeds, top_k: int = 10) -> PPRQuery:
        """Queue one user's query; flushed automatically at ``max_batch``.
        Rejects bad seed sets here, before they can poison a batch."""
        seeds = np.unique(np.asarray(seeds, np.int64).ravel())
        if seeds.size == 0:
            raise ValueError(f"uid {uid}: empty seed set")
        if seeds.min() < 0 or seeds.max() >= self.engine.n:
            raise ValueError(f"uid {uid}: seed index out of range "
                             f"[0, {self.engine.n})")
        q = PPRQuery(uid, seeds, top_k)
        self._queue.append(q)
        if len(self._queue) >= self.max_batch:
            self.flush()
        return q

    def push_update(self, delta):
        """Queue a streamed :class:`~repro.graph.delta.GraphDelta`; it is
        folded into the graph at the next :meth:`refresh`/:meth:`flush`,
        before any queued query is served.  Like ``submit`` for seed sets,
        a malformed delta (out-of-range node ids) is handled HERE, before
        it can poison the pending batch: the legacy path raises; in
        resilient mode the delta runs through
        :func:`~repro.graph.validate.validate_delta` — invalid edges land
        in :attr:`dead_letters` with structured reasons, the clean
        remainder is queued, and the
        :class:`~repro.graph.validate.ValidationResult` is returned (a
        ``"reject"`` validation policy still raises
        :class:`~repro.graph.validate.DeltaRejected`)."""
        if not hasattr(self.engine, "update"):
            raise TypeError(
                "push_update needs a DynamicPageRankEngine; "
                f"got a static {type(self.engine).__name__}")
        if self.resilience is None:
            self._pending_deltas.append(delta.canonical(
                self.engine.n, symmetric=self.engine.symmetric))
            return None
        result = validate_delta(delta, self.engine.n,
                                self.resilience.validation)
        self.dead_letters.extend(result.dead_letters)
        if result.dead_letters:
            n_edges = sum(dl.n_edges for dl in result.dead_letters)
            self.metrics.counter("serve.dead_letters").inc(n_edges)
            self.metrics.event(
                "dead_letter", n_edges=n_edges,
                reasons=sorted({dl.reason for dl in result.dead_letters}))
        if result.delta is not None:
            self._pending_deltas.append(result.delta.canonical(
                self.engine.n, symmetric=self.engine.symmetric))
        return result

    def refresh(self) -> list:
        """Apply every pending delta to the live engine now — coalesced
        into ONE update (``graph.delta.compose`` keeps the in-order
        semantics), so a backlog of k stream ticks costs one solve, not k.

        Legacy mode returns the
        :class:`~repro.pagerank.dynamic.UpdateInfo` records (one entry
        when anything was pending) and re-queues the deltas on an
        exception, which propagates.  Resilient mode never raises: the
        update runs through the
        :class:`~repro.pagerank.resilience.ResilientRefresher` escalation
        ladder and the
        :class:`~repro.pagerank.resilience.RefreshOutcome` is returned
        (and kept as :attr:`last_refresh_outcome`); if the delta could not
        be applied it is re-queued and subsequent serves are tagged
        ``"stale"`` until a refresh succeeds."""
        from repro.graph.delta import compose
        deltas, self._pending_deltas = self._pending_deltas, []
        if not deltas:
            return []
        merged = deltas[0] if len(deltas) == 1 else compose(
            deltas, self.engine.n, symmetric=self.engine.symmetric)
        # pre-update out-degrees anchor the per-column perturbation
        # weights of the delta-aware cache invalidation
        old_outdeg = (np.asarray(self.engine._outdeg).copy()
                      if self.cache is not None else None)
        if self.resilience is None:
            try:
                _, info = self.engine.update(merged, tol=self.refresh_tol)
            except Exception:
                self._pending_deltas = deltas + self._pending_deltas
                raise
            self.n_refreshes += 1
            self.last_update_info = info
            self._last_refresh_t = time.monotonic()
            self.metrics.counter("serve.refresh.ok").inc()
            self.metrics.event("refresh", applied=True, attempts=1,
                               status="ok", strategy=info.strategy)
            self._after_refresh(merged, old_outdeg)
            return [info]
        self._ensure_baseline()
        outcome = self.refresher.refresh(self.engine, merged,
                                         tol=self.refresh_tol)
        self.last_refresh_outcome = outcome
        self._stale = not outcome.delta_applied
        info = outcome.update_info
        self.metrics.counter(f"serve.refresh.{outcome.status}").inc()
        self.metrics.event("refresh", applied=outcome.delta_applied,
                           attempts=outcome.attempts,
                           status=outcome.status,
                           strategy=getattr(info, "strategy", None))
        if info is not None and not info.healthy:
            self.metrics.event("watchdog", source="refresh",
                               strategy=info.strategy,
                               diverged=info.diverged,
                               nonfinite=info.nonfinite)
        if outcome.delta_applied:
            self.n_refreshes += 1
            self.last_update_info = outcome.update_info
            self._last_refresh_t = time.monotonic()
            if outcome.status == "ok":
                self._after_refresh(merged, old_outdeg)
            else:
                # "recovered": the engine was rebuilt from host bookkeeping
                # after a poisoned solve — the per-column story no longer
                # describes how far the graph moved, so flush wholesale
                self._invalidate_all()
        else:
            # the graph never took the delta (every retry raised, or the
            # engine was rolled back to the snapshot) — re-queue it ahead
            # of anything pushed meanwhile, so order is preserved
            self._pending_deltas = deltas + self._pending_deltas
            if outcome.status == "restored":
                # rollback may have moved the graph BEHIND the cached
                # entries (the snapshot can predate served answers)
                self._invalidate_all()
        return [outcome]

    # ------------------------ cache invalidation ----------------------- #
    def _after_refresh(self, merged, old_outdeg) -> None:
        """Bump the cache-consistency clock after an applied delta and run
        the delta-aware invalidation: the transition columns that changed
        are exactly the delta's source endpoints, and a column's L1
        perturbation is bounded by ``2·(#changed edges at u)/deg(u)`` (an
        edge at a high-degree hub barely moves its column; at a leaf it
        rewrites it).  Entries holding enough rank mass on perturbed
        columns to matter are dropped; the rest are re-stamped — see
        :meth:`ResultCache.invalidate`."""
        self.graph_version += 1
        if self.cache is None:
            return
        cols = np.concatenate([
            np.asarray(merged.insert_src, np.int64),
            np.asarray(merged.delete_src, np.int64)])
        uniq, counts = np.unique(cols, return_counts=True)
        new_deg = np.asarray(self.engine._outdeg)[uniq].astype(np.float64)
        old_deg = old_outdeg[uniq].astype(np.float64)
        w = np.minimum(2.0, 2.0 * counts
                       / np.maximum(np.maximum(old_deg, new_deg), 1.0))
        dropped, kept = self.cache.invalidate(uniq, w, self.graph_version)
        self.metrics.counter("serve.cache.invalidations").inc(dropped)
        self.metrics.event("cache_invalidate", cols=int(uniq.size),
                           dropped=dropped, kept=kept,
                           version=self.graph_version)

    def _invalidate_all(self) -> None:
        """Escape hatch for recovery paths with no per-column story."""
        self.graph_version += 1
        if self.cache is None:
            return
        dropped, kept = self.cache.invalidate(None, None,
                                              self.graph_version)
        self.metrics.counter("serve.cache.invalidations").inc(dropped)
        self.metrics.event("cache_invalidate", cols=None, dropped=dropped,
                           kept=kept, version=self.graph_version)

    def flush(self) -> list[PPRQuery]:
        """Serve every queued query with one batched device dispatch —
        after folding in any pending graph deltas, so in-flight queries
        never see ranks staler than one refresh interval.

        Resilient mode additionally health-checks the batched PPR matrix
        (finite, non-negative, every column sum-to-1).  An unhealthy or
        raising serve triggers ONE engine recovery (rebuild from host
        bookkeeping, else restore the last-known-good snapshot) and a
        re-serve; if that also fails, queries are answered from the last
        good *global* rank vector — finite, sum-to-1, tagged
        ``"degraded"`` — and the call never raises.

        Every non-empty flush records one ``serve`` event and a
        ``serve.batch_ms`` latency sample (refresh included — the number a
        waiting query actually experiences), bumps the batch/query
        counters (per-status in resilient mode), and sets the
        ``serve.freshness_lag_s`` gauge to the served ranks' age."""
        t0 = time.perf_counter()
        batch = self._flush()
        if not batch:
            return batch
        ms = (time.perf_counter() - t0) * 1e3
        lag = time.monotonic() - self._last_refresh_t
        status = "legacy" if self.resilience is None else batch[0].status
        m = self.metrics
        m.histogram("serve.batch_ms").observe(ms)
        m.gauge("serve.freshness_lag_s").set(lag)
        m.counter("serve.batches").inc()
        m.counter("serve.queries").inc(len(batch))
        if self.resilience is not None:
            m.counter(f"serve.queries.{status}").inc(len(batch))
        extra = {}
        if self.cache is not None:
            st = self._last_flush_stats or {}
            m.counter("serve.cache.hits").inc(st.get("hits", 0))
            m.counter("serve.cache.misses").inc(st.get("misses", 0))
            m.counter("serve.cache.evictions").inc(st.get("evictions", 0))
            if st.get("hit_ms") is not None:
                m.histogram("serve.cache.hit_ms").observe(st["hit_ms"])
            if st.get("miss_ms") is not None:
                m.histogram("serve.cache.miss_ms").observe(st["miss_ms"])
            # additive optional fields: cache-less serve events keep the
            # key set they always had
            extra = dict(cache_hits=st.get("hits", 0),
                         cache_misses=st.get("misses", 0),
                         cache_evictions=st.get("evictions", 0),
                         hit_ms=st.get("hit_ms"), miss_ms=st.get("miss_ms"))
        m.event("serve", batch=len(batch), freshness_lag_s=lag,
                graph_version=batch[0].graph_version, ms=ms,
                status=status,
                precision=getattr(self.engine, "precision", "f32"),
                **extra)
        return batch

    def _flush(self) -> list[PPRQuery]:
        if self._pending_deltas:
            self.refresh()
        batch, self._queue = self._queue, []
        if not batch:
            return []
        with self.metrics.span("serve", q=len(batch)):
            if self.cache is None:
                self._serve_queries(batch)
            else:
                self._serve_cached(batch)
        return batch

    def _serve_cached(self, batch) -> None:
        """Answer repeats from the cache (no device work), solve only the
        misses, and cache what the misses produced."""
        precision = str(getattr(self.engine, "precision", "f32"))
        t0 = time.perf_counter()
        hits: list[tuple[PPRQuery, np.ndarray]] = []
        misses: list[tuple[PPRQuery, tuple]] = []
        for q in batch:
            key = ResultCache.key(q.seeds, precision)
            ranks = self.cache.get(key, self.graph_version)
            if ranks is not None:
                hits.append((q, ranks))
            else:
                misses.append((q, key))
        st = {"hits": len(hits), "misses": len(misses), "evictions": 0,
              "hit_ms": None, "miss_ms": None}
        if hits:
            status = "stale" if self._stale else "fresh"
            version = (self.refresher.store.version
                       if self.resilience is not None else -1)
            for q, ranks in hits:
                idx, scores = top_k_proteins(ranks, k=q.top_k)
                q.result = (np.asarray(idx), np.asarray(scores))
                q.cache_outcome = "hit"
                if self.resilience is not None:
                    q.status = status
                    q.graph_version = version
            st["hit_ms"] = (time.perf_counter() - t0) * 1e3
        if misses:
            t1 = time.perf_counter()
            PPR = self._serve_queries([q for q, _ in misses])
            # the (N, Q) matrix comes to the host once, for the cache alone
            host = None if PPR is None else np.asarray(PPR)
            for j, (q, key) in enumerate(misses):
                q.cache_outcome = "miss"
                if host is not None and q.status != "degraded":
                    st["evictions"] += self.cache.put(
                        key, np.ascontiguousarray(host[:, j]),
                        self.graph_version)
            st["miss_ms"] = (time.perf_counter() - t1) * 1e3
        self._last_flush_stats = st

    def _serve_queries(self, batch) -> jax.Array | None:
        """Answer ``batch`` in place (results + resilience tags) with one
        batched solve and one device step that ranks every query; returns
        the solved (N, Q) device matrix so the cache path can keep the full
        rank vectors (``None`` when the resilient path degraded to global
        ranks — never cached)."""
        if self.resilience is None:
            PPR = self._solve_batch([q.seeds for q in batch])  # (N, Q)
            idx, scores, _ = self._rank(PPR, batch)
            for j, q in enumerate(batch):
                q.result = (idx[j, :q.top_k], scores[j, :q.top_k])
            return PPR
        served = self._serve_ppr(batch)
        if served is None and self._recoverable():
            # one recovery attempt, then one re-serve — bounded work per
            # flush, no retry storm.  Recovery rebuilds/rolls back the
            # engine, so any cached answer may now describe a different
            # graph: flush wholesale (no per-column story exists)
            self.refresher.recover(self.engine, tol=self.refresh_tol)
            self._invalidate_all()
            served = self._serve_ppr(batch)
        version = self.refresher.store.version
        if served is not None:
            PPR, idx, scores = served
            status = "stale" if self._stale else "fresh"
            for j, q in enumerate(batch):
                q.result = (idx[j, :q.top_k], scores[j, :q.top_k])
                q.status = status
                q.graph_version = version
            return PPR
        # degraded: answer from the last-known-good global ranks (or the
        # uniform distribution if no snapshot exists yet) — finite and
        # sum-to-1 by construction, explicitly tagged
        snap = self.refresher.store.latest()
        if snap is not None and snap.ranks is not None:
            ranks = np.asarray(snap.ranks, np.float32)
        else:
            ranks = np.full(self.engine.n, 1.0 / self.engine.n, np.float32)
        idx, scores = top_k_proteins(ranks, k=max(q.top_k for q in batch))
        idx, scores = np.asarray(idx), np.asarray(scores)
        for q in batch:
            q.result = (idx[:q.top_k], scores[:q.top_k])
            q.status = "degraded"
            q.graph_version = version
        return None

    def _rank(self, PPR, batch) -> tuple[np.ndarray, ...]:
        """Every query's top-k ids and scores, (Q, k), and the column
        health flags, (Q,), of the served (N, Q) batch: one device step,
        whose small results alone come to the host."""
        atol = (self.resilience or ServeResilience()).healthy_atol
        with self.metrics.span("serve.topk", q=len(batch)):
            out = _rank_batch(PPR, atol, k=max(q.top_k for q in batch))
            return tuple(np.asarray(a) for a in out)

    def _solve_batch(self, seed_sets) -> jax.Array:
        """The cold-solve choke point: hub-combination + bounded residual
        push when a landmark index is attached (exact-solve fallback per
        column lives inside ``answer``), else the classic batched power
        iteration.  The (N, Q) matrix stays on the device."""
        if self.landmarks is not None:
            self.landmarks.ensure(self.graph_version)
            X, _ = self.landmarks.answer(seed_sets)
            return X
        return self.engine.ppr(seed_sets, n_iters=self.n_iters)

    def _serve_ppr(self, batch) -> tuple | None:
        """One batched PPR dispatch, ranked and health-checked in one
        device step: ``(PPR, ids, scores)``, or ``None`` if the dispatch
        raised or produced a poisoned batch."""
        try:
            PPR = self._solve_batch([q.seeds for q in batch])
            idx, scores, ok = self._rank(PPR, batch)
        except Exception:       # noqa: BLE001 — degradation contract
            return None
        return (PPR, idx, scores) if ok.all() else None

    def query_batch(self, seed_sets, top_k: int = 10) -> list[tuple]:
        """One-shot convenience: serve ``seed_sets`` now, return per-user
        ``(indices, scores)`` ranked top-k."""
        queries = [self.submit(uid, s, top_k=top_k)
                   for uid, s in enumerate(seed_sets)]
        self.flush()
        return [q.result for q in queries]
