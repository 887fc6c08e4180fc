"""Span recording: the distributed EEG's buffers and the profiler's spans
(DESIGN.md §16).

Two kinds of span, for two readers:

- :class:`SpanRecorder` events are the EEG's, from the graph engine, the
  wire layer and the RPC client, merged across processes into one
  Chrome trace (``obs/export.py``).
- :func:`span` is for the jitted paths (the continuous batcher, the
  input prefetcher) run under the JAX profiler: a
  ``jax.profiler.TraceAnnotation``, written by the profiler on the clock
  of its device planes, so that a gap on the device can be read against
  what the host was doing.  The profiler being on is its only switch;
  off, entering and leaving one costs about a microsecond.

A :class:`SpanRecorder` is a thread-safe append-only buffer of start/end
events.  Executors, the wire layer and the RPC client each record into
one when tracing is enabled; when it is not, every instrumentation site
reduces to a single ``is None`` check — the off path allocates nothing
and takes no locks (asserted by benchmark b15).

Its timestamps are ``time.time()`` (epoch seconds) rather than a process
monotonic clock: merging streams from several processes then reduces to
subtracting one estimated clock offset per stream (§16.3), instead of
reconstructing per-process epochs.  Durations stay meaningful because a
span's start and end are read in the same process.

Span categories (the ``cat`` field):

========== ==============================================================
``op``         one runtime op executed by an executor
``region``     one FusedRegion dispatch — a single span for the whole
               jitted super-node (never demoted to per-member events)
``wait``       time blocked on the rendezvous (Recv not ready, or a
               deferral ``wait_any``) — rendered on its own lane
``rpc``        client side of a wire RPC (``Channel._call_once``)
``rpc-server`` server side of a wire RPC (worker serve loop)
``step``       one whole training step (launch layer)
========== ==============================================================
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

CAT_OP = "op"
CAT_REGION = "region"
CAT_WAIT = "wait"
CAT_RPC = "rpc"
CAT_RPC_SERVER = "rpc-server"
CAT_STEP = "step"


class SpanRecorder:
    """Thread-safe buffer of span events for one process (or one run).

    An event is a plain dict — ``{"name", "cat", "device", "ts", "dur"}``
    plus an optional ``"args"`` — with ``ts``/``dur`` in epoch seconds
    (converted to microseconds only at export time).  Events are picklable
    as-is so worker buffers ship over the wire unchanged.
    """

    def __init__(self, process: str = "local") -> None:
        self.process = process
        self._events: List[Dict[str, Any]] = []
        self._lock = threading.Lock()

    @staticmethod
    def now() -> float:
        return time.time()

    def record(self, name: str, cat: str, device: str,
               t_start: float, t_end: float,
               args: Optional[Dict[str, Any]] = None) -> None:
        e: Dict[str, Any] = {
            "name": name,
            "cat": cat,
            "device": device,
            "ts": t_start,
            "dur": max(t_end - t_start, 1e-8),
        }
        if args:
            e["args"] = args
        with self._lock:
            self._events.append(e)

    def drain(self) -> List[Dict[str, Any]]:
        """Return all buffered events and clear the buffer."""
        with self._lock:
            out, self._events = self._events, []
        return out

    def snapshot(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    def extend(self, events: List[Dict[str, Any]]) -> None:
        with self._lock:
            self._events.extend(events)

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)


# ---------------------------------------------------------------------------
# Process-global recorder.  The RPC client (distrib/protocol.py) cannot be
# handed a recorder per call, so it consults this slot; ``get()`` is the
# whole cost of the disabled path.

_GLOBAL: Optional[SpanRecorder] = None


def get() -> Optional[SpanRecorder]:
    return _GLOBAL


def enabled() -> bool:
    return _GLOBAL is not None


def install(recorder: Optional[SpanRecorder]) -> Optional[SpanRecorder]:
    """Install (or clear, with None) the process-global recorder."""
    global _GLOBAL
    _GLOBAL = recorder
    return recorder


# ---------------------------------------------------------------------------
# Profiler spans.  Names are ``<layer>.<phase>``: ``serve.*`` in the
# continuous batcher, ``data.*`` in the prefetcher.


def span(name: str) -> TraceAnnotation:
    """A host span named ``name`` in the JAX profiler's trace, when one is
    being recorded; use as ``with span("serve.step"): ...``."""
    return TraceAnnotation(name)
