"""The kinds of operation a traffic mix drives, one module each.

A traffic file (``traffic/<mix>.json``) names its ``op`` and gives its
parameters.  The op is the class ``Op`` of ``ops/<op>.py``, found by name
(``cells.load_op``), so a new kind of operation is a new module and
nothing else changes.  An op is built as

    Op(cfg, traffic, graph, seed, precision, metrics, limits)

from the configuration, the traffic's parameters, the run's graph
``(src, dst, n)`` and seed, the storage precision, the program's metrics
registry and the cell's limits.  It builds the system under test itself,
through the program's own entry points, and gives:

- ``e2e``: the name of the end-to-end metric it reports;
- ``layout``: the layout the system under test runs on, for the set-up line;
- ``warm()``: set-up, counted in ``setup_s``; it runs every shape the
  window will use;
- ``window(seconds)``: the timed window, returning one item per operation
  (``{"ok": False}`` for one that failed) and the window's length;
  :class:`ClosedLoop` runs ``call()`` back to back;
- ``end_to_end(window_s, items)``: its end-to-end metrics;
- ``checks()``: ``{name: (value, limit, ok)}`` against the float64
  reference (``reference.py``), run after the window with the program's
  device state freed first.
"""
from __future__ import annotations

import sys
import time

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per use of the run's seed."""
    return np.random.default_rng([int(seed), stream])


class ClosedLoop:
    """An op whose window calls ``call()`` back to back until ``seconds``
    have passed, finishing the call in flight."""

    def window(self, seconds: float) -> tuple[list, float]:
        from jax.profiler import TraceAnnotation
        items = []
        with TraceAnnotation("bench.window"):
            t0 = time.perf_counter()
            while True:
                try:
                    with TraceAnnotation("bench.call"):
                        items.append(self.call())
                except Exception as e:  # noqa: BLE001 — counted as failed
                    print(f"call failed: {type(e).__name__}: {e}",
                          file=sys.stderr, flush=True)
                    items.append({"ok": False})
                if time.perf_counter() - t0 >= seconds:
                    break
            window_s = time.perf_counter() - t0
        return items, window_s


class Reservoir:
    """A uniform sample of at most ``k`` of the items offered, drawn with
    ``rng``; the last item offered is always kept as well."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k, self.rng, self.seen = k, rng, 0
        self._items: list = []
        self._last = None

    def offer(self, x) -> None:
        self.seen += 1
        self._last = x
        if len(self._items) < self.k:
            self._items.append(x)
        else:
            j = int(self.rng.integers(self.seen))
            if j < self.k:
                self._items[j] = x

    @property
    def items(self) -> list:
        if self._last is None or any(x is self._last for x in self._items):
            return list(self._items)
        return self._items + [self._last]
