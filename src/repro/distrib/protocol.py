"""Wire protocol of the multi-process distributed runtime (DESIGN.md §11).

Framing is deliberately minimal: every message is a 4-byte big-endian
length prefix followed by that many payload bytes.  A payload is a
pickled ``dict`` with a ``"kind"`` field naming the RPC
(``register_graph`` / ``run_graph`` / ``recv_tensor`` / ``heartbeat`` /
``get_variables`` / ``set_variables`` / ``cleanup`` / ``shutdown`` /
``collect_trace`` / ``metrics_snapshot``).

Tensors anywhere inside a message are hoisted through an explicit binary
codec (:func:`encode_tensor` / :func:`decode_tensor`) instead of relying
on ndarray pickling internals: the wire layout is ``flags | dtype name |
shape | C-order bytes``, which is deterministic and bit-faithful for
every dtype the graph engine produces (including ``bfloat16`` via
ml_dtypes and the §5.5 ``uint16`` compress16 wire format).  §4.4 dead
tensors are a first-class wire concept — ``DEAD_TENSOR`` crosses a
process boundary as a dedicated flag, never as data — so deadness
propagates through untaken cond branches and terminating loop iterations
exactly as it does between threads.

Graphs ship as pickled :class:`~repro.core.graph.Graph` slices; any
``Call`` node closure is rejected with a clear :class:`ProtocolError`.
Distributed graphs are built from registered primitive ops, module-level
callables, or wire-shippable Call *factories* — attrs carrying an
importable ``module:qualname`` plus static args, rebuilt worker-side at
registration (``GraphBuilder.call_factory``, DESIGN.md §15).
"""
from __future__ import annotations

import io
import pickle
import select
import socket
import struct
import threading
import time
from collections import deque
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import metrics as obs_metrics
from ..obs import spans as obs_spans
from ..runtime.rendezvous import DEAD_TENSOR, _DeadTensor
from . import faults

MAX_FRAME = 1 << 30  # 1 GiB sanity bound per message

_FLAG_DEAD = 0x01
_FLAG_JAX = 0x02  # value was a jax.Array at the producer


class ProtocolError(Exception):
    """Malformed frame, oversized message, or non-wire-serializable object."""


class WorkerError(Exception):
    """The peer processed the request and replied with an application error
    (the worker itself is alive — distinct from a dead-connection OSError)."""


# ---------------------------------------------------------------------------
# tensor codec


def _np_dtype(name: str) -> np.dtype:
    try:
        return np.dtype(name)
    except TypeError:
        # bfloat16 / float8 etc. live in ml_dtypes, not numpy proper
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def encode_tensor(x: Any) -> bytes:
    """Array (numpy / jax / scalar) or DEAD_TENSOR -> deterministic bytes.

    The producer's array *kind* travels with the bytes: a jax array
    rehydrates as a jax array, a numpy array as numpy.  Execution is
    kind-sensitive (``a @ b`` dispatches to XLA vs numpy with different
    accumulation orders), so preserving it is part of the bit-parity
    contract between wire and in-process runs.
    """
    if isinstance(x, _DeadTensor):
        return struct.pack(">B", _FLAG_DEAD)
    flags = _FLAG_JAX if isinstance(x, jax.Array) else 0
    arr = np.asarray(x)
    if not arr.flags.c_contiguous:
        # 0-d arrays are always contiguous, so this can never flatten a
        # scalar (ascontiguousarray promotes 0-d to 1-d — a shape change)
        arr = np.ascontiguousarray(arr)
    dt = arr.dtype.name.encode("ascii")
    head = struct.pack(">BB", flags, len(dt)) + dt + struct.pack(">B", arr.ndim)
    head += b"".join(struct.pack(">Q", d) for d in arr.shape)
    return head + arr.tobytes()


def decode_tensor(data: bytes) -> Any:
    """Inverse of :func:`encode_tensor` — bit-identical, a buffer copy,
    never a cast.  Numpy-origin arrays stay numpy (jnp.asarray would
    silently downcast 64-bit dtypes with x64 disabled); jax-origin arrays
    come back as jax arrays so kernels see the kind the producer had."""
    (flags,) = struct.unpack_from(">B", data, 0)
    if flags & _FLAG_DEAD:
        return DEAD_TENSOR
    (dtlen,) = struct.unpack_from(">B", data, 1)
    off = 2
    dtype = _np_dtype(data[off:off + dtlen].decode("ascii"))
    off += dtlen
    (ndim,) = struct.unpack_from(">B", data, off)
    off += 1
    shape = struct.unpack_from(f">{ndim}Q", data, off) if ndim else ()
    off += 8 * ndim
    # .copy(): writable, and decoupled from the (much larger) frame buffer
    arr = np.frombuffer(data, dtype=dtype, offset=off).reshape(shape).copy()
    if flags & _FLAG_JAX:
        return jnp.asarray(arr)
    return arr


class _WirePickler(pickle.Pickler):
    """Pickler that routes every tensor through the explicit codec."""

    def reducer_override(self, obj):  # noqa: D102 — pickle hook
        if isinstance(obj, _DeadTensor):
            return (_load_dead, ())
        if isinstance(obj, (np.ndarray, np.generic)):
            return (decode_tensor, (encode_tensor(obj),))
        if isinstance(obj, jax.Array):
            return (decode_tensor, (encode_tensor(obj),))
        return NotImplemented


def _load_dead() -> _DeadTensor:
    return DEAD_TENSOR


def pack_msg(msg: Dict[str, Any]) -> bytes:
    buf = io.BytesIO()
    try:
        _WirePickler(buf, protocol=pickle.HIGHEST_PROTOCOL).dump(msg)
    except Exception as e:  # noqa: BLE001 — rewrap with actionable context
        raise ProtocolError(
            f"message {msg.get('kind')!r} contains a non-wire-serializable "
            f"object ({e}); distributed graphs must be built from registered "
            f"primitive ops, importable callables, or Call factories "
            f"(GraphBuilder.call_factory — closures cannot ship; "
            f"DESIGN.md §15)"
        ) from e
    return buf.getvalue()


def unpack_msg(data: bytes) -> Dict[str, Any]:
    return pickle.loads(data)


# ---------------------------------------------------------------------------
# framing


def write_frame(sock: socket.socket, data: bytes) -> None:
    if len(data) > MAX_FRAME:
        raise ProtocolError(f"frame of {len(data)} bytes exceeds MAX_FRAME")
    sock.sendall(struct.pack(">I", len(data)) + data)


def _read_exact(sock: socket.socket, n: int, *, eof_ok: bool) -> Optional[bytes]:
    chunks = []
    got = 0
    while got < n:
        chunk = sock.recv(min(n - got, 1 << 20))
        if not chunk:
            if eof_ok and got == 0:
                return None
            raise ProtocolError(f"connection closed mid-frame ({got}/{n} bytes)")
        chunks.append(chunk)
        got += len(chunk)
    return b"".join(chunks)


def read_frame(sock: socket.socket) -> Optional[bytes]:
    """One frame, or None on a clean EOF at a frame boundary."""
    head = _read_exact(sock, 4, eof_ok=True)
    if head is None:
        return None
    (n,) = struct.unpack(">I", head)
    if n > MAX_FRAME:
        raise ProtocolError(f"peer announced {n}-byte frame (> MAX_FRAME)")
    return _read_exact(sock, n, eof_ok=False)


def send_msg(sock: socket.socket, msg: Dict[str, Any]) -> None:
    write_frame(sock, pack_msg(msg))


def recv_msg(sock: socket.socket) -> Optional[Dict[str, Any]]:
    data = read_frame(sock)
    return None if data is None else unpack_msg(data)


# ---------------------------------------------------------------------------
# client channel

# §13 idempotency contract (DESIGN.md): RPCs whose effect is identical if
# re-executed, so a transport failure mid-call may be retried without
# risking a double effect.  heartbeat/get_variables/debug_state are pure
# reads; set_variables/update_cluster force-write the values they carry;
# register_graph SEEDs only (re-registering an already-registered handle
# replaces it with identical content); cleanup/purge_execution purge an
# already-purged namespace to the same empty state; recv_tensor is
# at-most-once — a retry after the peer popped the mailbox entry but
# before the reply landed cannot return the wrong tensor, it blocks and
# surfaces an execution failure that §3.3 recovery handles anyway.
# run_graph and shutdown are deliberately absent: run_graph mutates
# Variables per execution (a blind re-run could double-apply a training
# step) and keeps its fail-fast contract.  metrics_snapshot is a pure
# read; collect_trace drains the worker's span buffer, so a retry whose
# first attempt reached the peer can lose those events — acceptable for
# best-effort diagnostics, and retrying keeps trace collection alive
# across transient transport hiccups.
IDEMPOTENT_RPCS = frozenset({
    "heartbeat", "recv_tensor", "get_variables", "set_variables",
    "register_graph", "cleanup", "purge_execution", "update_cluster",
    "debug_state", "collect_trace", "metrics_snapshot",
})

RETRY_ATTEMPTS = 4          # total tries for an idempotent RPC
RETRY_BASE_S = 0.05         # first backoff; doubles per retry
RETRY_JITTER = 0.25         # +/- fraction of the backoff
CONNECT_ATTEMPTS = 4        # refused-connection retries while dialing


def _backoff(attempt: int, deadline: float) -> bool:
    """Sleep the exponential-backoff-with-jitter delay for ``attempt``
    (0-based), bounded by ``deadline``.  False if the deadline would pass
    before the retry could start (caller should give up instead)."""
    delay = RETRY_BASE_S * (2 ** attempt)
    delay *= 1.0 + RETRY_JITTER * (2.0 * faults.jitter_rng().random() - 1.0)
    if time.monotonic() + delay >= deadline:
        return False
    time.sleep(delay)
    return True


class Channel:
    """Pooled request/reply client to one worker endpoint.

    Each in-flight RPC owns a whole TCP connection (no multiplexing):
    concurrent calls draw distinct connections from the idle pool or dial
    new ones.  This is what makes concurrent ``recv_tensor`` fetches
    deadlock-free — a blocked fetch for a late tensor can never head-of-
    line-block the fetch whose arrival would unblock the producer.

    Failure handling (§13): dialing retries refused connections with
    exponential backoff (a standby worker still binding its port must not
    fail a whole rebind), and idempotent RPCs (:data:`IDEMPOTENT_RPCS`)
    additionally retry transport failures mid-call — bounded attempts,
    jittered backoff, all under the ``_timeout`` deadline.  Non-idempotent
    RPCs (``run_graph``) stay fail-fast once the request may have reached
    the peer.
    """

    def __init__(self, host: str, port: int, *, connect_timeout: float = 5.0,
                 connect_attempts: int = CONNECT_ATTEMPTS) -> None:
        self.host, self.port = host, port
        self.connect_timeout = connect_timeout
        self.connect_attempts = max(1, connect_attempts)
        self._idle: deque = deque()
        self._lock = threading.Lock()
        self._closed = False

    def _connect(self, deadline: float) -> socket.socket:
        """Dial with bounded retry on refused/unreachable connections.
        Always safe regardless of the RPC's idempotency: a connection
        that never opened never delivered a request."""
        last: Optional[Exception] = None
        for attempt in range(self.connect_attempts):
            budget = min(self.connect_timeout, deadline - time.monotonic())
            if budget <= 0:
                break
            try:
                faults.on_connect(self.host, self.port)
                sock = socket.create_connection((self.host, self.port),
                                                timeout=budget)
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                return sock
            except OSError as e:
                last = e
                if attempt + 1 >= self.connect_attempts:
                    break
                if not _backoff(attempt, deadline):
                    break
        raise last if last is not None else OSError(
            f"connect deadline passed for {self.host}:{self.port}")

    def _acquire(self, deadline: float) -> socket.socket:
        while True:
            with self._lock:
                if self._closed:
                    raise OSError(f"channel to {self.host}:{self.port} is closed")
                sock = self._idle.popleft() if self._idle else None
            if sock is None:
                break
            # liveness probe: a socket closed while parked (peer restarted
            # on the same endpoint) is readable with EOF — reusing it
            # would surface a transport error and falsely condemn the
            # healthy restarted worker.  select(timeout=0) is cheap and,
            # unlike retry-on-failure, can never double-execute an RPC.
            readable, _, _ = select.select([sock], [], [], 0)
            if not readable:
                return sock
            sock.close()
        return self._connect(deadline)

    def _release(self, sock: socket.socket) -> None:
        with self._lock:
            if not self._closed and len(self._idle) < 8:
                self._idle.append(sock)
                return
        sock.close()

    def _call_once(self, kind: str, fields: Dict[str, Any],
                   deadline: float) -> Dict[str, Any]:
        # §16 client-side RPC span: one process-global recorder check —
        # the whole cost of the path when tracing is off
        rec = obs_spans.get()
        t_rpc = time.time() if rec is not None else None
        sock = self._acquire(deadline)
        try:
            faults.on_call(kind, fields, self.host, self.port)
            sock.settimeout(max(0.05, deadline - time.monotonic()))
            send_msg(sock, {"kind": kind, **fields})
            reply = recv_msg(sock)
        except Exception:
            sock.close()  # transport/encode failure: connection state unknown
            raise
        if reply is None:
            sock.close()
            raise ProtocolError(
                f"{self.host}:{self.port} closed the connection mid-call ({kind})")
        self._release(sock)
        if not reply.get("ok", False):
            raise WorkerError(reply.get("error", f"unknown {kind} failure"))
        if rec is not None:
            rec.record(kind, obs_spans.CAT_RPC, f"{self.host}:{self.port}",
                       t_rpc, time.time(), args={"kind": kind})
        return reply

    def call(self, kind: str, *, _timeout: float = 60.0,
             _attempts: Optional[int] = None, **fields: Any) -> Dict[str, Any]:
        """One RPC.  Raises :class:`WorkerError` on application errors
        (peer alive) and ``OSError``/:class:`ProtocolError` on transport
        failures (peer presumed lost).

        ``_timeout`` is the total deadline across every attempt.
        ``_attempts`` overrides the retry budget — idempotent RPCs
        (:data:`IDEMPOTENT_RPCS`) default to :data:`RETRY_ATTEMPTS`,
        everything else to 1 (the heartbeat monitor also passes 1: its
        own loop is the retry, and it must see raw per-probe failures to
        count misses honestly).
        """
        attempts = (_attempts if _attempts is not None
                    else (RETRY_ATTEMPTS if kind in IDEMPOTENT_RPCS else 1))
        deadline = time.monotonic() + _timeout
        for attempt in range(max(1, attempts)):
            try:
                return self._call_once(kind, fields, deadline)
            except WorkerError:
                raise  # application error: the peer is alive, never retry
            except (OSError, ProtocolError):
                if attempt + 1 >= attempts or not _backoff(attempt, deadline):
                    raise
                obs_metrics.counter("distrib.rpc_retries").inc()
        raise AssertionError("unreachable")  # pragma: no cover

    def close(self) -> None:
        with self._lock:
            self._closed = True
            while self._idle:
                self._idle.popleft().close()
