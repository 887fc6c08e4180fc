"""From a profiler trace (``.xplane.pb``) to device busy time, the top
device operations, and idle gaps labelled by what the host was doing.

- The window is the host span named ``bench.window`` that the harness
  writes around the measured loop.
- Busy time on a chip is the union of the intervals of the events on its
  plane's ``XLA Ops`` line (planes named ``/device:TPU:<n>``), clipped to
  the window, averaged over the chips that ran anything.
- A device op's time is its duration inside the window.  Ops that hold
  other ops (``while``, ``conditional``, ``call``) are not listed: the
  ops of their bodies are, on the same line.  Ops are named by their HLO
  name, kind and result type.
- An idle gap is a stretch of the window in which no operation runs on
  the first chip.  It is labelled by the innermost ``bench.*`` host span
  (other than the window) that covers its midpoint, or ``host:other``.
"""
from __future__ import annotations

import collections
import glob
import os
import re
from typing import Any, Dict, List, Optional, Tuple

WINDOW = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
CONTAINERS = {"while", "conditional", "call"}

Interval = Tuple[float, float]


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def merge(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(iv: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def _parse(hlo: str) -> Tuple[str, str, str]:
    """``%fusion.5 = f32[4,8]{1,0:T(8,128)} fusion(...), kind=...`` ->
    ``("%fusion.5", "fusion", "f32[4,8]")``: the op, its kind and its
    result type (kind and type empty where the text has no such form)."""
    lhs, _, rhs = hlo.partition(" = ")
    rhs = re.sub(r"\{[^{}]*\}", "", rhs)
    m = re.match(r"(.*?)\s([a-z][\w-]*)\(", rhs)
    return (lhs, m.group(2), m.group(1)) if m else (lhs, "", "")


def short_name(hlo: str) -> str:
    """The op, its kind and its result type: ``%fusion.5 fusion f32[4,8]``."""
    lhs, kind, result = _parse(hlo)
    return (f"{lhs} {kind} {result}" if kind else lhs)[:160]


def op_times(ops: List[Tuple[str, float, float]], lo: float, hi: float
             ) -> Dict[str, float]:
    """Seconds per op inside [lo, hi], by short name, leaving out the ops
    that hold other ops."""
    out: Dict[str, float] = collections.defaultdict(float)
    for name, s, e in ops:
        if e > lo and s < hi and _parse(name)[1] not in CONTAINERS:
            out[short_name(name)] += min(e, hi) - max(s, lo)
    return out


def reduce(path: str, top: int = 10) -> Dict[str, Any]:
    """Reduce one ``.xplane.pb``.  Returns ``busy_s`` and ``window_s`` (None
    where the trace holds no device or no window), ``device_ops`` and
    ``idle_gaps`` as ``[[name, seconds], ...]``, and ``n_devices``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host: List[Tuple[str, float, float]] = []
    devices: List[List[Tuple[str, float, float]]] = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append((ev.name, ev.start_ns * 1e-9,
                                     (ev.start_ns + ev.duration_ns) * 1e-9))
        elif DEVICE_PLANE.match(plane.name):
            ops = [(ev.name, ev.start_ns * 1e-9,
                    (ev.start_ns + ev.duration_ns) * 1e-9)
                   for line in plane.lines if line.name == OPS_LINE
                   for ev in line.events]
            if ops:
                devices.append(ops)
    windows = [(s, e) for n, s, e in host if n == WINDOW]
    out: Dict[str, Any] = {"busy_s": None, "window_s": None, "device_ops": [],
                           "idle_gaps": [], "n_devices": len(devices)}
    if not windows or not devices:
        return out
    lo, hi = windows[0]
    out["window_s"] = hi - lo
    busy = [merge(_clip([(s, e) for _, s, e in ops], lo, hi)) for ops in devices]
    out["busy_s"] = sum(sum(e - s for s, e in b) for b in busy) / len(busy)

    per_op = op_times(devices[0], lo, hi)
    out["device_ops"] = [[n, t] for n, t in
                         sorted(per_op.items(), key=lambda kv: -kv[1])[:top]]

    spans = [(n, s, e) for n, s, e in host if n != WINDOW]
    per_label: Dict[str, float] = collections.defaultdict(float)
    edges = [lo] + [x for iv in busy[0] for x in iv] + [hi]
    for gs, ge in zip(edges[0::2], edges[1::2]):
        if ge <= gs:
            continue
        mid = 0.5 * (gs + ge)
        covering = [(e - s, n) for n, s, e in spans if s <= mid <= e]
        per_label[min(covering)[1] if covering else "host:other"] += ge - gs
    out["idle_gaps"] = [[n, t] for n, t in
                        sorted(per_label.items(), key=lambda kv: -kv[1])[:top]]
    return out
