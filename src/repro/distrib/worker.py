"""§3.3 worker process: serves per-device subgraphs over the wire protocol.

A Worker owns the runtime state of its slice of the cluster — a
process-wide rendezvous mailbox, a VariableStore, queues and checkpoint
IO — and serves the DESIGN.md §11 RPCs:

* ``register_graph`` — receive a partitioned per-task subgraph from the
  master, seed Variable state, optionally run §7 region fusion on each
  local device subgraph (strict fusion is bit-identical, so wire runs
  keep the compiled-super-node speedups), and build one reusable
  :class:`~repro.core.executor.Executor` per local device.
* ``run_graph`` — execute one registered graph under an execution id:
  one thread per local device, all coordinating through a
  :class:`~repro.distrib.wire.WireRendezvous` view of the mailbox.
* ``recv_tensor`` — the pull half of a cross-process Send/Recv pair:
  block until the local mailbox holds the (execution-namespaced) key,
  pop it and reply.  DEAD_TENSOR replies carry §4.4 deadness across the
  process boundary.
* ``heartbeat`` / ``get_variables`` / ``set_variables`` / ``cleanup`` /
  ``shutdown`` — liveness, checkpoint sync and lifecycle.

CLI (one process per task)::

    python -m repro.distrib.worker --host 127.0.0.1 --port 7077 --task 0

``--port 0`` picks a free port; the worker announces
``WORKER_READY host:port task=N pid=P`` on stdout either way, which is
what :func:`start_worker_processes` parses.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import select
import socket
import subprocess
import sys
import threading
import time
import traceback
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..core.executor import ExecutionContext, Executor
from ..core.graph import Graph, TensorRef
from ..core import fusion as fusion_mod
from ..core import kernel_registry
from ..core import ops as ops_mod
from ..obs import metrics as obs_metrics
from ..obs import spans as obs_spans
from ..runtime.containers import ContainerManager, VariableStore
from ..runtime.rendezvous import Rendezvous
from . import faults
from .protocol import Channel, recv_msg, send_msg
from .wire import ClusterSpec, WireRendezvous

# RPCs excluded from server-side span recording even when tracing: the
# heartbeat fires continuously and the trace/metrics scrapes would trace
# themselves.
_UNTRACED_RPCS = frozenset({"heartbeat", "collect_trace", "metrics_snapshot"})


@dataclasses.dataclass
class _Registered:
    """One graph the master registered with this worker."""

    graph: Graph
    executors: Dict[str, Executor]                 # local device -> Executor
    fetch_specs: Dict[str, List[Tuple[int, TensorRef]]]  # dev -> (global idx, ref)
    fetch_remap: Dict[TensorRef, TensorRef]
    cluster: ClusterSpec
    task: int
    namespace: str  # owning session's store namespace (§4.7)


class Worker:
    """One OS process serving one cluster task's devices (DESIGN.md §11)."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0, task: int = 0, *,
                 rendezvous_timeout: float = 30.0,
                 checkpoint_root: Optional[str] = None) -> None:
        self.host, self.port, self.task = host, port, task
        self.mailbox = Rendezvous(timeout=rendezvous_timeout)
        # one VariableStore per *session* namespace, mirroring the
        # in-process default of one ContainerManager per Session (§4.7):
        # sessions sharing this pool never alias each other's Variables
        # (VariableStore.write resolves names across its containers)
        self._stores: Dict[str, VariableStore] = {}
        self._var_containers: Dict[str, Dict[str, str]] = {}
        self.queues: Dict[str, Any] = {}
        if checkpoint_root:
            from ..checkpoint import FileCheckpointIO

            self.checkpoint_io: Any = FileCheckpointIO(checkpoint_root)
        else:
            from ..core.session import _DictCheckpointIO

            self.checkpoint_io = _DictCheckpointIO()
        # keyed by (handle, cluster task): §13 partial re-placement may
        # land a dead task's subgraph on a SURVIVOR, which then serves two
        # tasks of the same plan — one registry slot each, never an
        # overwrite
        self._graphs: "OrderedDict[Tuple[str, int], _Registered]" = OrderedDict()
        self.max_graphs = 32  # LRU bound on registered graphs
        # eid -> rendezvous views; a dual-task survivor runs two per eid
        self._active: Dict[str, List[WireRendezvous]] = {}
        # keyed by ENDPOINT, not task id: after a partial pool restart
        # (dead task re-spawned on a new port) the re-registered cluster
        # spec must dial the new endpoint, never a stale cached channel
        self._peers: Dict[Tuple[str, int], Channel] = {}
        self._peers_lock = threading.Lock()
        self._stop = threading.Event()
        self._sock: Optional[socket.socket] = None
        self._started = time.monotonic()
        # §16 distributed EEG: the process-level span buffer (server-side
        # RPC spans + any events not yet shipped on a run_graph reply),
        # drained by the collect_trace RPC.  Recording stays off until the
        # first traced run_graph arrives — the flag makes every
        # instrumentation site a single bool check when the master never
        # asked for tracing.
        self.spans = obs_spans.SpanRecorder(process=f"worker-task{task}")
        self._trace = False

    # ------------------------------------------------------------------
    def start(self) -> Tuple[str, int]:
        sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        sock.bind((self.host, self.port))
        sock.listen(64)
        self.port = sock.getsockname()[1]
        self._sock = sock
        threading.Thread(target=self._accept_loop, daemon=True,
                         name=f"worker{self.task}-accept").start()
        return self.host, self.port

    def stop(self) -> None:
        self._stop.set()
        self.mailbox.abort(RuntimeError(
            f"worker task:{self.task} (pid {os.getpid()}) shut down"))
        for views in list(self._active.values()):
            for rdv in views:
                rdv.abort(RuntimeError(f"worker task:{self.task} shutting down"))
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
        with self._peers_lock:
            for ch in self._peers.values():
                ch.close()
            self._peers.clear()

    @property
    def address(self) -> str:
        return f"{self.host}:{self.port}"

    # ------------------------------------------------------------------
    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return  # listener closed
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            threading.Thread(target=self._serve_conn, args=(conn,),
                             daemon=True,
                             name=f"worker{self.task}-conn").start()

    def _serve_conn(self, conn: socket.socket) -> None:
        try:
            while not self._stop.is_set():
                msg = recv_msg(conn)
                if msg is None:
                    return
                kind = msg.pop("kind", "?")
                try:
                    # §13 fault injection: a stall_hb rule drops this
                    # connection without replying — the master's monitor
                    # counts a miss against a perfectly healthy process
                    faults.on_serve(kind, self.task)
                except faults._DropConnection:
                    return
                handler = getattr(self, f"_rpc_{kind}", None)
                if handler is None:
                    reply: Dict[str, Any] = {"ok": False,
                                             "error": f"unknown RPC {kind!r}"}
                else:
                    t_rpc = (time.time()
                             if self._trace and kind not in _UNTRACED_RPCS
                             else None)
                    try:
                        reply = handler(msg)
                        reply.setdefault("ok", True)
                    except Exception as e:  # noqa: BLE001 — report, don't die
                        reply = {"ok": False,
                                 "error": f"worker task:{self.task} "
                                          f"(pid {os.getpid()}) {kind} failed: "
                                          f"{type(e).__name__}: {e}\n"
                                          f"{traceback.format_exc(limit=8)}"}
                    if t_rpc is not None:
                        # §16 server-side RPC span, paired with the client
                        # span the caller's Channel recorded
                        self.spans.record(kind, obs_spans.CAT_RPC_SERVER,
                                          f"task:{self.task}", t_rpc,
                                          time.time(),
                                          args={"kind": kind,
                                                "ok": bool(reply.get("ok"))})
                send_msg(conn, reply)
                if kind == "shutdown":
                    self.stop()
                    return
        except Exception:  # noqa: BLE001 — connection-level failure
            pass
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def store(self, namespace: str) -> VariableStore:
        st = self._stores.get(namespace)
        if st is None:
            st = self._stores[namespace] = VariableStore(ContainerManager())
            self._var_containers[namespace] = {}
        return st

    def _peer_channel(self, cluster: ClusterSpec, task: int) -> Channel:
        endpoint = cluster.host_port(task)
        with self._peers_lock:
            ch = self._peers.get(endpoint)
            if ch is None:
                ch = Channel(*endpoint)
                self._peers[endpoint] = ch
            return ch

    # ------------------------------------------------------------------
    # RPC handlers
    def _rpc_register_graph(self, p: Dict[str, Any]) -> Dict[str, Any]:
        cluster = ClusterSpec.from_wire(p["cluster"])
        g: Graph = p["graph"]
        device_nodes = {d: set(ns) for d, ns in p["device_nodes"].items()}
        names = set().union(*device_nodes.values()) if device_nodes else set()
        placement = dict(p["placement"])
        feed_keys = frozenset(TensorRef(n, pt) for n, pt in p["feed_keys"])
        fetch_specs = {d: [(i, TensorRef(n, pt)) for i, n, pt in lst]
                       for d, lst in p["fetches"].items()}
        ns = p.get("namespace", "s")
        store = self.store(ns)
        for vname, (container, value) in p["variables"].items():
            cont = store.manager.get(container)
            if not cont.has(vname):
                # SEED-only: registration must never clobber live state —
                # a second Executable on the same session registers here
                # mid-training, when this store (not the master's) holds
                # the trained weights.  Recovery pushes explicitly via
                # set_variables (Session.rebind_cluster).
                cont.write(vname, value)
            self._var_containers[ns][vname] = container

        # §15 factory-form Calls rebuild *at registration*, not first run:
        # an unimportable factory (missing module, bad qualname) surfaces
        # as a register_graph error naming the node, and the built kernel
        # is memoised per (factory, args) so N replicas of one step share
        # a single model build in this process
        for name in sorted(names):
            node = g.nodes[name]
            if node.op == "Call" and "call_factory" in node.attrs:
                try:
                    ops_mod.resolve_call_fn(node)
                except Exception as e:  # noqa: BLE001 — rewrap with the node
                    raise RuntimeError(
                        f"Call node {name!r}: factory "
                        f"{node.attrs['call_factory']!r} failed to build on "
                        f"worker task:{self.task}: {e}") from e

        fetch_remap: Dict[TensorRef, TensorRef] = {}
        if p.get("fuse", True) and names:
            # §7 region fusion on the local slice: placement keeps regions
            # per-device, Send/Recv nodes are runtime ops and never join a
            # region, so the fused graph is safe to interleave with wire
            # transfers.  Strict numerics stays bit-identical (§9); the
            # master's kernel-backend choice rides the payload (§12/§15)
            # so wire runs dispatch e.g. Pallas kernels too.
            all_fetch_refs = [r for lst in fetch_specs.values() for _, r in lst]
            fus = fusion_mod.try_fuse(
                g, set(names), placement=placement, feeds=feed_keys,
                fetch_refs=all_fetch_refs,
                written_vars=fusion_mod.written_variables(g, names),
                numerics=p.get("numerics", "strict"),
                backend=p.get("backend", "generic"))
            if fus is not None and (fus.regions or fus.changed):
                g = fus.graph
                fetch_remap = fus.fetch_map
                device_nodes = {}
                for n in fus.names:
                    device_nodes.setdefault(fus.placement[n], set()).add(n)
        executors = {dev: Executor(g, node_filter=ns, device_label=dev)
                     for dev, ns in device_nodes.items()}
        key = (p["handle"], p["task"])
        self._graphs[key] = _Registered(
            graph=g, executors=executors, fetch_specs=fetch_specs,
            fetch_remap=fetch_remap, cluster=cluster, task=p["task"],
            namespace=ns)
        self._graphs.move_to_end(key)
        while len(self._graphs) > self.max_graphs:
            # bounded registry: masters whose signature churn outlives
            # this cap get a "not registered" reply and transparently
            # re-register (master.WirePlan.run)
            self._graphs.popitem(last=False)
        return {"devices": sorted(executors), "n_nodes": len(g.nodes)}

    def _find_registered(self, handle: str,
                         task: Optional[int]) -> Tuple[Any, _Registered]:
        if task is not None:
            key = (handle, task)
            reg = self._graphs.get(key)
        else:  # legacy master without task routing: any slot for the handle
            key = next((k for k in self._graphs if k[0] == handle), None)
            reg = self._graphs.get(key) if key is not None else None
        if reg is None:
            raise KeyError(f"graph {handle!r} (task {task}) is not registered "
                           f"here (worker restarted or registry evicted? "
                           f"re-register before running)")
        return key, reg

    def _rpc_run_graph(self, p: Dict[str, Any]) -> Dict[str, Any]:
        # §13 fault injection FIRST: a kill rule must fire on *receipt* of
        # the N-th run_graph, before any execution state exists — the
        # deterministic twin of `kill -9` mid-step
        faults.on_run_graph(self.task)
        key, reg = self._find_registered(p["handle"], p.get("task"))
        self._graphs.move_to_end(key)
        eid: str = p["execution_id"]
        timeout: float = float(p.get("timeout", 60.0))
        feeds: Dict[TensorRef, Any] = p.get("feeds") or {}
        # §16: the master flags traced executions; one recorder per
        # execution keeps concurrent run_graphs from draining each other,
        # and the flag arms server-side RPC spans for the process
        run_spans: Optional[obs_spans.SpanRecorder] = None
        if p.get("trace"):
            self._trace = True
            run_spans = obs_spans.SpanRecorder(
                process=f"worker-task{self.task}")
        wire = WireRendezvous(
            self.mailbox, reg.cluster, reg.task, eid, timeout=timeout,
            channel_of=lambda t: self._peer_channel(reg.cluster, t))
        self._active.setdefault(eid, []).append(wire)
        results: Dict[int, Any] = {}
        errors: List[BaseException] = []
        lock = threading.Lock()

        store = self.store(reg.namespace)

        timings: Dict[str, Dict[str, float]] = {}

        def run_device(dev: str, ex: Executor) -> None:
            # §16.4 last-progress gauge: hang reports below read this to
            # say how long each stuck device has been silent
            progress = obs_metrics.gauge(f"worker.device.{dev}.last_progress_ts")
            progress.set(time.time())
            ctx = ExecutionContext(
                variables=store, rendezvous=wire, queues=self.queues,
                checkpoint_io=self.checkpoint_io,
                device_kind=dev.split("device:")[-1].split(":")[0])
            specs = reg.fetch_specs.get(dev, [])
            local = [reg.fetch_remap.get(r, r) for _, r in specs]
            t_wall, t_cpu = time.monotonic(), time.thread_time()
            try:
                vals = ex.run(local, feeds, ctx=ctx, spans=run_spans)
                with lock:
                    for (i, _), v in zip(specs, vals):
                        results[i] = v
            except BaseException as e:  # noqa: BLE001 — §3.3 surface any failure
                with lock:
                    errors.append(e)
            finally:
                # wall vs thread-CPU split: the gap is time this device
                # spent blocked (Recv waits, scheduler) — §3.3 diagnostics
                # surfaced through run_graph replies into last_run_stats
                # AND the §16.4 metrics registry (worker.device_*)
                wall = time.monotonic() - t_wall
                cpu = time.thread_time() - t_cpu
                obs_metrics.histogram("worker.device_wall_s").observe(wall)
                obs_metrics.histogram("worker.device_cpu_s").observe(cpu)
                progress.set(time.time())
                with lock:
                    timings[dev] = {"wall_s": wall, "cpu_s": cpu}

        threads = {dev: threading.Thread(target=run_device, args=(dev, ex),
                                         daemon=True,
                                         name=f"worker{reg.task}:{dev}")
                   for dev, ex in reg.executors.items()}
        try:
            for t in threads.values():
                t.start()
            deadline = time.monotonic() + timeout
            for t in threads.values():
                t.join(timeout=max(0.0, deadline - time.monotonic()))
            if errors:
                raise errors[0]
            stuck = sorted(dev for dev, t in threads.items() if t.is_alive())
            if stuck:
                wire.abort(RuntimeError(f"execution {eid} timed out"))
                now = time.time()

                def _age(dev: str) -> str:
                    ts = obs_metrics.gauge(
                        f"worker.device.{dev}.last_progress_ts").value
                    return f"{now - ts:.1f}s ago" if ts else "never"

                raise TimeoutError(
                    f"worker task:{reg.task} (pid {os.getpid()}): device(s) "
                    + ", ".join(f"{d} (last progress {_age(d)})"
                                for d in stuck)
                    + f" never finished within {timeout:.1f}s (stuck "
                    f"Send/Recv or hung kernel; §3.3 failure reporting)")
            out = {"results": results,
                   "sends": wire.sends, "bytes_sent": wire.bytes_sent,
                   "remote_fetches": wire.remote_fetches,
                   "timings": timings}
            if run_spans is not None:
                # ship this execution's spans on the reply; the clock
                # sample lets the master sanity-check its offset estimate
                out["spans"] = run_spans.drain()
                out["clock"] = time.time()
            return out
        finally:
            # stop straggler fetcher threads (blocked in recv_tensor RPCs
            # for up to their timeout) from depositing into the mailbox
            # after the master's cleanup purge has run — a late deposit
            # would leak for the worker's lifetime
            wire.close()
            views = self._active.get(eid)
            if views is not None:
                try:
                    views.remove(wire)
                except ValueError:
                    pass
                if not views:
                    self._active.pop(eid, None)

    def _rpc_recv_tensor(self, p: Dict[str, Any]) -> Dict[str, Any]:
        wait = float(p.get("wait", self.mailbox.timeout))
        try:
            value = self.mailbox.recv(p["key"], timeout=wait)
        except TimeoutError:
            if p.get("poll"):
                # chunked fetcher (wire.WireRendezvous._fetch): a clean
                # not-yet marker, so the client re-polls between its
                # closed/abort checks instead of burning one long blocking
                # RPC it cannot interrupt
                return {"timeout": True}
            raise
        return {"value": value}

    def _rpc_heartbeat(self, p: Dict[str, Any]) -> Dict[str, Any]:
        # "clock" piggybacks NTP-style offset estimation on the liveness
        # probe (§16.3): the master brackets the call with its own send /
        # receive times and assumes this sample was taken at the midpoint
        return {"task": self.task, "pid": os.getpid(),
                "active": len(self._active),
                "uptime_s": time.monotonic() - self._started,
                "registered": len(self._graphs),
                "clock": time.time()}

    def _rpc_collect_trace(self, p: Dict[str, Any]) -> Dict[str, Any]:
        """§16.2 drain the process-level span buffer (server-side RPC
        spans; run_graph spans ship on their own replies).  Draining is
        destructive, so a retried call can lose the events the first
        attempt drained — acceptable for diagnostics, and why this RPC
        is marked idempotent rather than given dedup bookkeeping."""
        return {"events": self.spans.drain(), "clock": time.time(),
                "task": self.task}

    def _rpc_metrics_snapshot(self, p: Dict[str, Any]) -> Dict[str, Any]:
        """§16.4 read-only dump of this process's metrics registry."""
        return {"metrics": obs_metrics.snapshot(), "task": self.task,
                "pid": os.getpid()}

    def _rpc_get_variables(self, p: Dict[str, Any]) -> Dict[str, Any]:
        ns = p.get("namespace", "s")
        store = self.store(ns)
        names = p.get("names")
        out: Dict[str, Any] = {}
        for vname, container in self._var_containers.get(ns, {}).items():
            if names is not None and vname not in names:
                continue
            cont = store.manager.get(container)
            if cont.has(vname):
                out[vname] = cont.read(vname)
        return {"values": out}

    def _rpc_set_variables(self, p: Dict[str, Any]) -> Dict[str, Any]:
        ns = p.get("namespace", "s")
        store = self.store(ns)
        for vname, (container, value) in p["values"].items():
            store.manager.get(container).write(vname, value)
            self._var_containers[ns].setdefault(vname, container)
        return {"n": len(p["values"])}

    def _rpc_cleanup(self, p: Dict[str, Any]) -> Dict[str, Any]:
        purged = self.mailbox.purge_prefix(f"{p['execution_id']}|")
        return {"purged": purged}

    def _rpc_purge_execution(self, p: Dict[str, Any]) -> Dict[str, Any]:
        """§13 abort path: poison an in-flight execution and scrub its
        rendezvous state.  The master calls this on every SURVIVOR when a
        peer dies mid-run, so executors blocked on tensors the dead task
        will never produce unwind promptly (instead of burning their full
        recv timeout) and nothing leaks into the process-wide mailbox."""
        eid = p["execution_id"]
        reason = p.get("reason", f"execution {eid} aborted by master (§3.3)")
        views = self._active.get(eid, [])
        for wire in list(views):
            wire.abort(RuntimeError(reason))
            wire.close()  # straggler fetcher deposits drop, not leak
        purged = self.mailbox.purge_prefix(f"{eid}|")
        return {"aborted": len(views), "purged": purged}

    def _rpc_update_cluster(self, p: Dict[str, Any]) -> Dict[str, Any]:
        """§13 partial re-placement: patch registered graphs' cluster spec
        in place — survivors keep their graphs, executors and Variable
        state, but future peer fetches must dial the replacement endpoint,
        never the dead one.  Idempotent: re-applying the same spec is a
        no-op.  ``handles`` limits the patch to specific plans."""
        new = ClusterSpec.from_wire(p["cluster"])
        handles = p.get("handles")
        updated = 0
        for key, reg in self._graphs.items():
            if handles is not None and key[0] not in handles:
                continue
            if len(reg.cluster.workers) == len(new.workers):
                reg.cluster = new
                updated += 1
        # drop pooled channels to endpoints no longer in any updated spec:
        # a parked connection to the dead endpoint would only resurface as
        # a spurious transport error on the next fetch
        keep = {reg.cluster.host_port(t)
                for reg in self._graphs.values()
                for t in range(len(reg.cluster.workers))}
        with self._peers_lock:
            for ep in list(self._peers):
                if ep not in keep:
                    self._peers.pop(ep).close()
        return {"updated": updated}

    def _rpc_debug_state(self, p: Dict[str, Any]) -> Dict[str, Any]:
        """Hygiene probe (§13 tests / operator debugging): what is still
        live in this process — pending mailbox keys, active executions,
        straggler fetcher threads, registered (handle, task) slots."""
        return {
            "task": self.task, "pid": os.getpid(),
            "pending_keys": self.mailbox.pending_keys(),
            "active_executions": sorted(self._active),
            "fetch_threads": sum(
                1 for t in threading.enumerate()
                if t.is_alive() and t.name.startswith("wire-fetch:")),
            "registered": sorted(f"{h}@task:{t}" for h, t in self._graphs),
            # §12/§15: per-backend kernel dispatch counts in THIS process —
            # the proof that a wire run routed fused idioms through the
            # registry (trace-time counts, once per compiled signature)
            "kernel_dispatch": {f"{b}:{k}": v for (b, k), v
                                in sorted(kernel_registry.DISPATCH.items())},
        }

    def _rpc_shutdown(self, p: Dict[str, Any]) -> Dict[str, Any]:
        return {"task": self.task}  # _serve_conn stops after replying


# ---------------------------------------------------------------------------
# process helpers (tests, examples, CI smoke)


def start_worker_processes(
    n: int, *, host: str = "127.0.0.1", timeout: float = 120.0,
    rendezvous_timeout: float = 30.0, first_task: int = 0,
    extra_env: Optional[Dict[str, str]] = None,
) -> Tuple[List[subprocess.Popen], ClusterSpec]:
    """Spawn ``n`` worker processes on free ports; returns (procs, spec).

    Blocks until every worker announced ``WORKER_READY`` (imports of
    jax dominate startup).  Callers own the processes — pair with
    :func:`stop_worker_processes`.

    ``first_task`` numbers the spawned tasks from an offset — a §13
    standby is a worker spawned with the next free task id, registered
    into the pool only when recovery re-places a dead task onto it.
    ``extra_env`` overlays the inherited environment (e.g. a seeded
    ``REPRO_FAULTS`` plan shipped to every process of the pool).  Workers
    run on the CPU (``JAX_PLATFORMS=cpu``), so a parent that holds a chip
    can spawn them.
    """
    src_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ)
    env["PYTHONPATH"] = src_root + os.pathsep + env.get("PYTHONPATH", "")
    # the pool is a CPU path: a worker must never contend for the chip
    # its parent may hold, whatever JAX_PLATFORMS the parent runs with
    env["JAX_PLATFORMS"] = "cpu"
    if extra_env:
        env.update(extra_env)
    procs: List[subprocess.Popen] = []
    addrs: List[str] = []
    try:
        for t in range(first_task, first_task + n):
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "repro.distrib.worker",
                 "--host", host, "--port", "0", "--task", str(t),
                 "--rendezvous-timeout", str(rendezvous_timeout)],
                stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                text=True, env=env))
        deadline = time.monotonic() + timeout
        for t, proc in enumerate(procs):
            while True:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError(f"worker task:{t} never became ready")
                # select before readline: a worker that hangs silently
                # (wedged import, deadlock) must trip the deadline, not
                # block this call forever on an empty pipe
                rl, _, _ = select.select([proc.stdout], [], [],
                                         min(remaining, 1.0))
                if not rl:
                    continue
                line = proc.stdout.readline()
                if not line:
                    raise RuntimeError(
                        f"worker task:{t} exited (rc={proc.poll()}) before ready")
                if line.startswith("WORKER_READY "):
                    addrs.append(line.split()[1])
                    break
            # keep draining stdout so the pipe can never fill and block
            threading.Thread(target=lambda s=proc.stdout: s.read(),
                             daemon=True).start()
    except BaseException:
        stop_worker_processes(procs)
        raise
    return procs, ClusterSpec(tuple(addrs))


def stop_worker_processes(procs: Sequence[subprocess.Popen],
                          spec: Optional[ClusterSpec] = None) -> None:
    """Best-effort graceful shutdown, then terminate/kill."""
    if spec is not None:
        for t in range(len(spec.workers)):
            try:
                # connect_attempts=1: a pool being torn down is usually
                # already gone — retrying refused dials only slows tests
                ch = Channel(*spec.host_port(t), connect_timeout=1.0,
                             connect_attempts=1)
                ch.call("shutdown", _timeout=2.0)
                ch.close()
            except Exception:  # noqa: BLE001 — already gone is fine
                pass
    for p in procs:
        if p.poll() is None:
            p.terminate()
    for p in procs:
        try:
            p.wait(timeout=5.0)
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait(timeout=5.0)


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0,
                    help="0 picks a free port (announced on stdout)")
    ap.add_argument("--task", type=int, default=0)
    ap.add_argument("--rendezvous-timeout", type=float, default=30.0)
    ap.add_argument("--ckpt-root", default=None,
                    help="directory for worker-local Save/Restore nodes")
    args = ap.parse_args(argv)
    # §13: declare this process's task so task-scoped fault rules (kill,
    # stall_hb) shipped via REPRO_FAULTS fire only in the right process
    faults.set_context(args.task)
    w = Worker(args.host, args.port, args.task,
               rendezvous_timeout=args.rendezvous_timeout,
               checkpoint_root=args.ckpt_root)
    host, port = w.start()
    print(f"WORKER_READY {host}:{port} task={args.task} pid={os.getpid()}",
          flush=True)
    try:
        while not w._stop.wait(0.5):
            pass
    except KeyboardInterrupt:
        w.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
