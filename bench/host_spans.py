"""The program's own spans in a profiler trace (``.xplane.pb``), read beside
the harness's.

``trace_reduce.reduce`` labels the window's idle gaps by the ``bench.*``
spans the harness writes and reads no other host event.  The program
writes spans of its own (``repro.obs.spans.span``): ``serve.*`` inside
``ContinuousBatcher.step`` and ``data.get`` in the prefetcher.  ``reduce``
here keeps every host span whose name starts with one of ``PREFIXES``:

- ``host_spans``: for each such name (the window aside), how many spans
  start inside the window and their seconds, clipped to the window, as
  ``{name: [count, seconds]}``.
- ``idle_gaps``: the stretches of the window in which no operation runs on
  the first chip, each labelled by the innermost such span that covers
  its midpoint, or ``host:other``, as ``[[label, seconds], ...]``.  Under
  ``bench.batcher_step`` a gap so takes the name of the batcher's phase.

The window, the device planes and their busy intervals are found as in
``trace_reduce``, whose ``busy_s``, ``window_s`` and ``device_ops`` this
leaves to that module.
"""
from __future__ import annotations

import collections
from typing import Any, Dict, List, Tuple

from .trace_reduce import DEVICE_PLANE, OPS_LINE, WINDOW, _clip, merge

PREFIXES = ("bench.", "serve.", "data.")

Span = Tuple[str, float, float]


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


def label_gaps(spans: List[Span], gaps: List[Tuple[float, float]]
               ) -> Dict[str, float]:
    """Seconds of the gaps (in time order) by the innermost span that covers
    each gap's midpoint, or ``host:other``."""
    by_start = sorted(spans, key=lambda sp: sp[1])
    out: Dict[str, float] = collections.defaultdict(float)
    i, open_spans = 0, []
    for gs, ge in gaps:
        mid = 0.5 * (gs + ge)
        while i < len(by_start) and by_start[i][1] <= mid:
            open_spans.append(by_start[i])
            i += 1
        open_spans = [sp for sp in open_spans if sp[2] >= mid]
        covering = [(e - s, n) for n, s, e in open_spans]
        out[min(covering)[1] if covering else "host:other"] += ge - gs
    return out


def reduce(path: str, top: int = 10) -> Dict[str, Any]:
    """Read one ``.xplane.pb``: ``window_s``, ``host_spans`` and ``idle_gaps``
    (``window_s`` None, the others empty, where the trace holds no window
    or no device)."""
    from jax.profiler import ProfileData

    host: List[Span] = []
    ops: List[Tuple[float, float]] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            host += [(ev.name, ev.start_ns * 1e-9,
                      (ev.start_ns + ev.duration_ns) * 1e-9)
                     for line in plane.lines for ev in line.events
                     if ev.name.startswith(PREFIXES)]
        elif DEVICE_PLANE.match(plane.name) and not ops:
            ops = [(ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                   for line in plane.lines if line.name == OPS_LINE
                   for ev in line.events]
    out: Dict[str, Any] = {"window_s": None, "host_spans": {}, "idle_gaps": []}
    windows = [(s, e) for n, s, e in host if n == WINDOW]
    if not windows or not ops:
        return out
    lo, hi = windows[0]
    spans = [sp for sp in host if sp[0] != WINDOW]
    edges = [lo] + [x for iv in merge(_clip(ops, lo, hi)) for x in iv] + [hi]
    gaps = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
    per_label = label_gaps(spans, gaps)
    out.update(window_s=hi - lo, host_spans=spans_in(spans, lo, hi),
               idle_gaps=[[n, t] for n, t in sorted(
                   per_label.items(), key=lambda kv: -kv[1])[:top]])
    return out
