"""Serving launcher: batched request serving on the smoke configs (CPU) or
full configs (pod).  The decode step is the paper's fabric-MV workload.

Usage:
    python -m repro.launch.serve --arch llama3-8b --smoke --requests 6
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.models import model as M
from repro.serve import Request, ServeEngine


def run(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args(argv)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    engine = ServeEngine(cfg, params, max_len=args.max_len)

    rng = np.random.default_rng(0)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size, size=5 + i % 4,
                                        dtype=np.int32),
                    max_new_tokens=args.max_new,
                    temperature=args.temperature)
            for i in range(args.requests)]
    # perf_counter, not time.time(): wall-clock adjustments (NTP slew)
    # corrupt an interval measurement; perf_counter is monotonic
    t0 = time.perf_counter()
    engine.serve(reqs, n_slots=args.slots)
    dt = time.perf_counter() - t0
    total_tokens = sum(len(r.output) for r in reqs)
    print(f"served {len(reqs)} requests, {total_tokens} tokens "
          f"in {dt:.2f}s ({total_tokens / dt:.1f} tok/s)")
    for r in reqs[:3]:
        print(f"  req {r.uid}: prompt={r.prompt.tolist()} -> {r.output}")
    return reqs


if __name__ == "__main__":
    run()
