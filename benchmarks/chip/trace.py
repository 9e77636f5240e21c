"""Reduction of a JAX profiler trace (``.xplane.pb``) to device numbers.

- Busy time: the union of the intervals of the operations on each device's
  ``XLA Ops`` line, clipped to the window, averaged over the devices.
- The window: the harness's host span ``bench.window`` when the trace has
  it, else the first to the last device operation.
- Device ops: self time per operation (an event's duration less that of
  the events nested in it on the same line, as a ``while`` holds its
  body's ops), by ``op_name``, summed over devices and divided by their
  number.
- Idle gaps: each stretch of the window in which no operation ran on the
  first device, named by the innermost host span open on the window's
  thread at the gap's midpoint, summed by name.
"""
from __future__ import annotations

import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
# an XLA op's event is named by its HLO text: "%name = <shape> opcode(...",
# the shape a tuple in parentheses or one array type
HLO_OP = re.compile(r"^(%[\w.\-]+) = (\((?:[^()]|\([^()]*\))*\)|\S+) "
                    r"([\w\-]+)\(")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
TOP = 10


def op_name(hlo: str) -> str:
    """``%fusion.8 = f32[34909110] fusion`` from an op's HLO text: its
    name, result shape without layout, and opcode."""
    m = HLO_OP.match(hlo)
    if not m:
        return hlo[:120]
    return f"{m[1]} = {re.sub(r'{[^}]*}', '', m[2])} {m[3]}"


def _events(line, name=lambda s: s) -> list[tuple[float, float, str]]:
    return sorted((float(e.start_ns), float(e.start_ns + e.duration_ns),
                   name(e.name)) for e in line.events)


def _union(iv: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[tuple[float, float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _self_times(evs: list[tuple[float, float, str]]) -> dict[str, float]:
    """Self time per name of properly nested events sorted by start."""
    out: dict[str, float] = {}
    stack: list[list] = []          # [end, name, child_total, duration]

    def close(item):
        end, name, child, dur = item
        out[name] = out.get(name, 0.0) + dur - child
        if stack:
            stack[-1][2] += dur

    for s, e, name in evs:
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        stack.append([e, name, 0.0, e - s])
    while stack:
        close(stack.pop())
    return out


def _innermost(evs: list[tuple[float, float, str]], times: list[float]
               ) -> list[str]:
    """For each of the ascending ``times``, the name of the innermost of the
    nested events ``evs`` (sorted by start) open at it, or ``"(none)"``."""
    names, stack, i = [], [], 0
    for t in times:
        while i < len(evs) and evs[i][0] <= t:
            while stack and stack[-1][1] <= evs[i][0]:
                stack.pop()
            stack.append(evs[i])
            i += 1
        while stack and stack[-1][1] <= t:
            stack.pop()
        names.append(stack[-1][2] if stack else "(none)")
    return names


def reduce_profile(pd) -> dict:
    """``busy_s``, ``window_s``, ``devices``, ``device_ops`` and
    ``idle_gaps`` of a ``jax.profiler.ProfileData``."""
    devices = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.append(_events(line, op_name))
                    break
    if not devices or not any(devices):
        raise ValueError("the trace holds no operation on a TPU device")
    window_line, window = None, None
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        window = (float(e.start_ns),
                                  float(e.start_ns + e.duration_ns))
                        window_line = line
                        break
                if window:
                    break
        if window:
            break
    if window is None:
        window = (min(d[0][0] for d in devices if d),
                  max(max(e for _, e, _ in d) for d in devices if d))
    w0, w1 = window
    busy, ops = [], {}
    first_union = None
    for evs in devices:
        clipped = [(max(s, w0), min(e, w1), n) for s, e, n in evs
                   if e > w0 and s < w1]
        u = _union([(s, e) for s, e, _ in clipped])
        if first_union is None:
            first_union = u
        busy.append(sum(e - s for s, e in u))
        for name, t in _self_times(clipped).items():
            ops[name] = ops.get(name, 0.0) + t
    nd = len(devices)
    gaps, prev = [], w0
    for s, e in first_union:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if w1 > prev:
        gaps.append((prev, w1))
    host = _events(window_line) if window_line is not None else []
    labels = _innermost(host, [(s + e) / 2 for s, e in gaps])
    idle: dict[str, float] = {}
    for (s, e), name in zip(gaps, labels):
        idle[name] = idle.get(name, 0.0) + (e - s) * 1e-9
    top = lambda d: sorted(([k, v] for k, v in d.items()),
                           key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": sum(busy) / nd * 1e-9,
        "window_s": (w1 - w0) * 1e-9,
        "devices": nd,
        "device_ops": top({k: v / nd * 1e-9 for k, v in ops.items()}),
        "idle_gaps": top(idle),
    }


def reduce_dir(log_dir: str | Path) -> dict:
    """Reduce the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    files = sorted(Path(log_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return reduce_profile(ProfileData.from_file(str(files[-1])))
