"""From a profiler trace (``.xplane.pb``) to device busy time, every
device operation's time, the host's spans, and idle gaps split by what
the host was doing; one pass over the file.

- The window is the host span named ``bench.window`` that the harness
  writes around the measured loop.
- Host spans are the host events named ``<area>.<phase>``: lower-case
  words joined by dots (``bench.*`` from the harness, ``serve.*`` and
  ``data.*`` from the program's ``repro.obs.spans.span``, and any that
  a later change adds).  The runtime's own events (``PjitFunction(...)``,
  ``tpu::System::...``) have no such name.
- Busy time on a chip is the union of the intervals of the events on its
  plane's ``XLA Ops`` line (planes named ``/device:TPU:<n>``), clipped to
  the window, averaged over the chips that ran anything.
- A device op's time is its own time inside the window: an op that holds
  other ops (``while``, ``conditional``, ``call``), whose body's ops lie
  on the same line inside it, keeps only what they leave uncovered.  Ops
  are named by their HLO name, kind and result type.
- An idle gap is a stretch of the window in which no operation runs on
  the first chip.  Each instant of it goes to the innermost host span
  (the shortest, the window aside) that covers that instant, or to
  ``host:other``, so a gap that crosses spans is split among them.

``reduce`` returns ``window_s`` and ``busy_s``, ``n_devices``, and
``host_spans`` (``{name: [count, seconds]}`` of the spans that start in
the window, each clipped to its end), ``op_seconds`` (``{short name:
seconds}`` of every op on the first chip), and for the breakdown the
longest of those, ``device_ops``, and ``idle_gaps``, each as ``[[name,
seconds], ...]``.
"""
from __future__ import annotations

import collections
import glob
import os
import re
from typing import Any, Dict, List, Optional, Tuple

WINDOW = "bench.window"
SPAN_NAME = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z][a-z0-9_]*)+$")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
OTHER = "host:other"

Interval = Tuple[float, float]
Span = Tuple[str, float, float]


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


def op_times(ops: List[Span], lo: float, hi: float) -> Dict[str, float]:
    """Seconds per op inside [lo, hi], by short name, each op's own: every
    instant goes to the latest-started op that covers it, so an op that
    holds others (``while``, ``conditional``, ``call``) keeps only what its
    body's ops leave uncovered, and the seconds of all ops add up to the
    union of their intervals."""
    out: Dict[str, float] = collections.defaultdict(float)
    names: Dict[str, str] = {}
    stack: List[List[Any]] = []   # [end, short name, own seconds], by start
    clipped = sorted(((max(s, lo), -min(e, hi), n) for n, s, e in ops
                      if e > lo and s < hi))
    for s, neg_e, name in clipped:
        e = -neg_e
        while stack and s >= stack[-1][0]:
            _, n, t = stack.pop()
            out[n] += t
        # [s, e] leaves the ops that held it: each instant was the topmost
        # op's that had not yet ended
        t = s
        for entry in reversed(stack):
            if t >= e:
                break
            if entry[0] > t:
                taken = min(e, entry[0]) - t
                entry[2] -= taken
                t += taken
        if name not in names:
            names[name] = short_name(name)
        stack.append([e, names[name], e - s])
    for _, n, t in stack:
        out[n] += t
    return dict(out)


def spans_in(spans: List[Span], lo: float, hi: float
             ) -> Dict[str, List[float]]:
    """``{name: [count, seconds]}`` over the spans that start in [lo, hi),
    each clipped to ``hi``."""
    out: Dict[str, List[float]] = {}
    for n, s, e in spans:
        if lo <= s < hi:
            c = out.setdefault(n, [0, 0.0])
            c[0] += 1
            c[1] += min(e, hi) - s
    return out


def split_gaps(spans: List[Span], gaps: List[Interval]) -> Dict[str, float]:
    """Seconds of the gaps (in time order, not overlapping) by the innermost
    span that covers each instant, or ``host:other``."""
    by_start = sorted(spans, key=lambda sp: sp[1])
    out: Dict[str, float] = collections.defaultdict(float)
    i, live = 0, []
    for gs, ge in gaps:
        while i < len(by_start) and by_start[i][1] < ge:
            live.append(by_start[i])
            i += 1
        live = [sp for sp in live if sp[2] > gs]
        cuts = sorted({gs, ge} | {x for _, s, e in live for x in (s, e)
                                  if gs < x < ge})
        for a, b in zip(cuts, cuts[1:]):
            covering = [(e - s, n) for n, s, e in live if s <= a and b <= e]
            out[min(covering)[1] if covering else OTHER] += b - a
    return out


def _top(d: Dict[str, float], top: int) -> List[List[Any]]:
    return [[n, t] for n, t in sorted(d.items(), key=lambda kv: -kv[1])[:top]]


def reduce(path: str, top: int = 10) -> Dict[str, Any]:
    """Reduce one ``.xplane.pb`` (see the module's docstring).  ``busy_s``
    and ``window_s`` are None, and the rest empty, where the trace holds no
    device or no window."""
    from jax.profiler import ProfileData

    host: List[Span] = []
    devices: List[List[Span]] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            host += [(ev.name, ev.start_ns * 1e-9,
                      (ev.start_ns + ev.duration_ns) * 1e-9)
                     for line in plane.lines for ev in line.events
                     if SPAN_NAME.match(ev.name)]
        elif DEVICE_PLANE.match(plane.name):
            ops = [(ev.name, ev.start_ns * 1e-9,
                    (ev.start_ns + ev.duration_ns) * 1e-9)
                   for line in plane.lines if line.name == OPS_LINE
                   for ev in line.events]
            if ops:
                devices.append(ops)
    out: Dict[str, Any] = {"busy_s": None, "window_s": None,
                           "n_devices": len(devices), "host_spans": {},
                           "op_seconds": {}, "device_ops": [], "idle_gaps": []}
    windows = [(s, e) for n, s, e in host if n == WINDOW]
    if not windows or not devices:
        return out
    lo, hi = windows[0]
    busy = [merge(_clip([(s, e) for _, s, e in ops], lo, hi)) for ops in devices]
    spans = [sp for sp in host if sp[0] != WINDOW]
    edges = [lo] + [x for iv in busy[0] for x in iv] + [hi]
    gaps = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
    op_seconds = op_times(devices[0], lo, hi)
    out.update(window_s=hi - lo,
               busy_s=sum(sum(e - s for s, e in b) for b in busy) / len(busy),
               host_spans=spans_in(spans, lo, hi), op_seconds=op_seconds,
               device_ops=_top(op_seconds, top),
               idle_gaps=_top(split_gaps(spans, gaps), top))
    return out
