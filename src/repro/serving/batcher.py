"""Continuous-batching serving layer on the paper's substrate.

Requests arrive through a §4.6 FIFO queue; a fixed pool of batch *slots*
shares one jitted serve step (cache batch dim = n_slots).  Each decode
step every live slot advances one token; finished slots are immediately
refilled from the queue (continuous batching, the standard production
serving discipline).  Per-slot positions are tracked host-side and the
whole-batch step uses per-slot position masking, so slots at different
depths coexist in one cache.

This requires per-slot decode positions, which the single-``pos`` serve
step doesn't expose — so the batcher drives the model with a vmapped
single-sequence step over the slot axis.  Sampling: greedy or
temperature.  The step picks every slot's greedy token on the device
(argmax over the vocabulary), and only those int32 tokens reach the
host; the logits stay on the device.  A request with ``temperature > 0``
samples from its own slot's row there and reads back one token.
Counters ``serving.tokens_greedy_device`` and
``serving.tokens_sampled_row`` count the tokens each path takes.

A slot holds whatever decode state the model's layers keep: a KV cache
for attention layers, and for Mamba-2 layers (``ssm``) the recurrent
state and the conv windows.  Admission resets all of it in place
(``serve.reset_slot``, counter ``serving.slot_resets``); gauges
``serving.slot_kv_bytes`` and ``serving.slot_ssm_bytes`` give one slot's
bytes of each.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.api import Model
from ..models.params import init_params
from ..obs import metrics as obs_metrics
from ..obs.spans import span
from ..runtime.queues import FIFOQueue, QueueClosed


def _slot_step_for(model: Model):
    """Prepared-step reuse (the serving-side analogue of the Session's
    Executable cache, DESIGN.md §5): restarting or multiplying batchers
    over one model reuses the traced/jitted vmapped slot step instead of
    re-tracing it.  The step is cached on the model instance itself so
    its lifetime tracks the model — nothing is pinned process-wide.

    The cache argument is donated: the step writes each slot's new k/v
    into it in place, so the caller must use the returned cache.  The
    layer loop is unrolled: in a loop the cache and the weights are
    loop-invariant operands of default-precision matmuls, and the TPU
    compiler converts each whole one to bfloat16 ahead of the loop on
    every step; unrolled, each layer's slice is converted inside its
    matmul.

    Inputs ``(params, cache, tokens (n, 1) int32, positions (n,) int32)``;
    returns ``(next_tokens, logits, new_cache)``: each slot's greedy token
    as int32 ``(n,)`` (``jnp.argmax``, the first of equal maxima, as
    ``np.argmax``) and its logits row ``(n, V)``."""
    step = getattr(model, "_batcher_slot_step", None)
    if step is not None:
        return step

    def one_slot_step(params, cache, token, pos):
        logits, new_cache = model.serve_step(params, cache, token[None, :],
                                             pos, scan_unroll=True)
        return logits[0, 0], new_cache

    def slot_step(params, cache, tokens, positions):
        logits, new_cache = jax.vmap(one_slot_step, in_axes=(None, 0, 0, 0))(
            params, cache, tokens, positions)
        next_tokens = jnp.argmax(logits[:, :model.cfg.vocab_size],
                                 axis=-1).astype(jnp.int32)
        return next_tokens, logits, new_cache

    step = jax.jit(slot_step, donate_argnums=(1,))
    model._batcher_slot_step = step
    return step


@functools.partial(jax.jit, static_argnums=(4,))
def _sample_row(key, logits, s, temperature, vocab):
    """A token drawn from slot ``s``'s row of ``logits`` (its first
    ``vocab`` entries) at ``temperature``, on the device: only the token
    leaves it."""
    return jax.random.categorical(key, logits[s, :vocab] / temperature)


@functools.partial(jax.jit, donate_argnums=(0,))
def _reset_slot(cache, empty, s):
    """Slot ``s`` of the slot-stacked ``cache`` set to ``empty``, in place:
    one executable for every slot, touching that slot's entries alone (its
    KV cache, SSM state and conv windows)."""
    return jax.tree.map(
        lambda full, e: jax.lax.dynamic_update_index_in_dim(full, e, s, 0),
        cache, empty)


def slot_bytes(cache) -> Tuple[int, int]:
    """(KV bytes, SSM state and conv window bytes) of one slot's cache."""
    kv = ssm = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        n = leaf.size * leaf.dtype.itemsize
        if any(getattr(k, "key", None) == "ssm" for k in path):
            ssm += n
        else:
            kv += n
    return kv, ssm


@dataclasses.dataclass
class Request:
    rid: int
    prompt: Sequence[int]
    max_new_tokens: int = 16
    temperature: float = 0.0
    eos_id: Optional[int] = None


@dataclasses.dataclass
class RequestResult:
    rid: int
    tokens: List[int]
    prompt_len: int
    steps: int
    latency_s: float  # submit() to the last token, queueing included


class ContinuousBatcher:
    def __init__(self, model: Model, params, *, n_slots: int = 4,
                 max_seq: int = 256, seed: int = 0) -> None:
        self.model = model
        self.params = params
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.queue: FIFOQueue = FIFOQueue(capacity=64, name="requests")
        self.results: Dict[int, RequestResult] = {}
        self._key = jax.random.PRNGKey(seed)

        cdesc = model.init_cache_desc(batch=1, max_seq=max_seq)
        self._empty_cache = init_params(cdesc, jax.random.PRNGKey(1))
        # slot-stacked cache: add a leading slot axis via vmap-compatible stack
        self.cache = jax.tree.map(
            lambda x: jnp.stack([x] * n_slots), self._empty_cache)
        kv, ssm = slot_bytes(self._empty_cache)
        obs_metrics.gauge("serving.slot_kv_bytes").set(kv)
        obs_metrics.gauge("serving.slot_ssm_bytes").set(ssm)

        # params is an explicit argument (vmap in_axes=None), so the jitted
        # step is shared across batcher instances serving the same model
        self._step = _slot_step_for(model)

        # host-side slot state
        self.slot_req: List[Optional[Request]] = [None] * n_slots
        self.slot_pos = np.zeros(n_slots, dtype=np.int64)
        self.slot_pending: List[List[int]] = [[] for _ in range(n_slots)]
        self.slot_out: List[List[int]] = [[] for _ in range(n_slots)]
        # time.perf_counter() at submit(): a request's latency counts its
        # wait in the queue
        self.slot_submitted = np.zeros(n_slots)
        self.slot_steps = np.zeros(n_slots, dtype=np.int64)
        # the last step's greedy token of each slot, copied to the host
        self.next_tokens = np.zeros(n_slots, dtype=np.int32)
        self.stats = {"steps": 0, "slot_tokens": 0, "idle_slot_tokens": 0}

    # ------------------------------------------------------------------
    def submit(self, req: Request) -> None:
        self.queue.enqueue((time.perf_counter(), req))

    def _reset_slot_cache(self, s: int) -> None:
        with span("serve.reset_slot"):
            self.cache = _reset_slot(self.cache, self._empty_cache, np.int32(s))
        obs_metrics.counter("serving.slot_resets").inc()

    def _try_fill_slots(self) -> None:
        for s in range(self.n_slots):
            if self.slot_req[s] is not None:
                continue
            if self.queue.size() == 0:
                continue
            try:
                submitted, req = self.queue.dequeue()
            except (TimeoutError, QueueClosed):
                return
            self.slot_req[s] = req
            self.slot_pos[s] = 0
            self.slot_pending[s] = list(req.prompt)
            self.slot_out[s] = []
            self.slot_submitted[s] = submitted
            self.slot_steps[s] = 0
            self._reset_slot_cache(s)

    def _live(self) -> List[int]:
        return [s for s in range(self.n_slots) if self.slot_req[s] is not None]

    # ------------------------------------------------------------------
    def step(self) -> int:
        """Advance every live slot one token; returns #completed requests.

        Each phase is a profiler span (``obs.spans.span``) inside
        ``serve.step``: ``serve.admit``, ``serve.dispatch``,
        ``serve.device_wait`` (the host waits for the step),
        ``serve.logits_to_host`` (the step's result reaching the host: the
        greedy tokens the device chose, ``n_slots`` int32; the logits stay
        on the device) and ``serve.sample`` (each slot's token taken, and
        the bookkeeping)."""
        with span("serve.step"):
            with span("serve.admit"):
                self._try_fill_slots()
            live = self._live()
            if not live:
                return 0

            with span("serve.dispatch"):
                tokens = np.zeros((self.n_slots, 1), dtype=np.int32)
                for s in live:
                    if self.slot_pending[s]:
                        tokens[s, 0] = self.slot_pending[s][0]
                    elif self.slot_out[s]:
                        tokens[s, 0] = self.slot_out[s][-1]
                    else:
                        tokens[s, 0] = 0
                positions = jnp.asarray(self.slot_pos.astype(np.int32))
                next_tokens, logits, self.cache = self._step(
                    self.params, self.cache, jnp.asarray(tokens), positions)
                self.stats["steps"] += 1
                self.stats["slot_tokens"] += len(live)
                self.stats["idle_slot_tokens"] += self.n_slots - len(live)
            with span("serve.device_wait"):
                jax.block_until_ready(next_tokens)
            with span("serve.logits_to_host"):
                self.next_tokens = np.asarray(next_tokens)
            with span("serve.sample"):
                return self._sample(live, logits)

    def _sample(self, live: List[int], logits: jax.Array) -> int:
        """Take each live slot's next token, and finish the requests that
        are done; returns how many finished.  A greedy slot takes the token
        the device chose (``self.next_tokens``); a slot with
        ``temperature > 0`` samples from its row of the step's ``logits``
        on the device."""
        done = greedy = sampled = 0
        for s in live:
            req = self.slot_req[s]
            self.slot_pos[s] += 1
            self.slot_steps[s] += 1
            if self.slot_pending[s]:
                self.slot_pending[s].pop(0)
                if self.slot_pending[s]:
                    continue  # still prefilling
            if req.temperature > 0:
                self._key, sub = jax.random.split(self._key)
                tok = int(_sample_row(sub, logits, s, req.temperature,
                                      self.model.cfg.vocab_size))
                sampled += 1
            else:
                tok = int(self.next_tokens[s])
                greedy += 1
            self.slot_out[s].append(tok)
            finished = (len(self.slot_out[s]) >= req.max_new_tokens
                        or (req.eos_id is not None and tok == req.eos_id)
                        or self.slot_pos[s] >= self.max_seq - 1)
            if finished:
                latency = time.perf_counter() - self.slot_submitted[s]
                self.results[req.rid] = RequestResult(
                    rid=req.rid, tokens=list(self.slot_out[s]),
                    prompt_len=len(req.prompt),
                    steps=int(self.slot_steps[s]),
                    latency_s=latency)
                # §16.4: request latency lands in the process registry so
                # serve.py (and the metrics_snapshot RPC) can report
                # p50/p99 without reaching into batcher internals
                obs_metrics.histogram("serving.request_latency_s").observe(
                    latency)
                obs_metrics.counter("serving.requests_completed").inc()
                self.slot_req[s] = None
                done += 1
        obs_metrics.counter("serving.tokens_greedy_device").inc(greedy)
        obs_metrics.counter("serving.tokens_sampled_row").inc(sampled)
        return done

    def run_until_drained(self, max_steps: int = 10_000) -> Dict[int, RequestResult]:
        for _ in range(max_steps):
            self.step()
            if self.queue.size() == 0 and not self._live():
                break
        return self.results

    def occupancy(self) -> float:
        tot = self.stats["slot_tokens"] + self.stats["idle_slot_tokens"]
        return self.stats["slot_tokens"] / tot if tot else 0.0
