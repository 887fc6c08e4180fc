"""§4.5/§4.6 input pipeline: input ops + prefetch queues.

The paper's pattern: special input operation nodes configured with
filenames yield example tensors straight into the worker process, and
queues decouple the IO cadence from the compute cadence (prefetching the
next batch while the current one trains).  We implement:

  * ``SyntheticLMDataset`` — deterministic synthetic LM token stream (the
    substrate for training runs in this repo; vocab-bounded, seeded).
  * ``FileRecordReader``  — a real file-backed record reader (length-
    prefixed binary records), the §4.5 "read directly from storage" path.
  * ``Prefetcher``        — a background thread feeding a FIFO/shuffling
    queue; the training loop dequeues (§4.6).
"""
from __future__ import annotations

import os
import struct
import threading
import time
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from ..obs.spans import span
from ..runtime.queues import FIFOQueue, QueueClosed, ShufflingQueue


class SyntheticLMDataset:
    """Deterministic pseudo-text: Zipfian tokens with local correlations.

    A tiny fixed bigram structure makes the next-token task learnable, so
    "loss decreases" integration tests are meaningful rather than noise.
    """

    def __init__(self, vocab_size: int, seq_len: int, seed: int = 0) -> None:
        self.vocab_size = vocab_size
        self.seq_len = seq_len
        self.seed = seed
        rng = np.random.RandomState(seed)
        # each token deterministically prefers a successor: easy structure
        self._succ = rng.randint(0, vocab_size, size=(vocab_size,), dtype=np.int64)
        ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
        p = 1.0 / ranks ** 1.1
        self._p = p / p.sum()

    def batch(self, batch_size: int, step: int) -> Dict[str, np.ndarray]:
        rng = np.random.RandomState((self.seed * 1_000_003 + step) % (2 ** 31))
        toks = np.empty((batch_size, self.seq_len + 1), dtype=np.int32)
        toks[:, 0] = rng.choice(self.vocab_size, size=batch_size, p=self._p)
        coin = rng.random_sample((batch_size, self.seq_len))
        rand = rng.choice(self.vocab_size, size=(batch_size, self.seq_len), p=self._p)
        for t in range(self.seq_len):
            follow = self._succ[toks[:, t]]
            toks[:, t + 1] = np.where(coin[:, t] < 0.75, follow, rand[:, t])
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        step = 0
        while True:
            yield self.batch(1, step)
            step += 1


class FileRecordReader:
    """Length-prefixed binary record files (§4.5 input operations).

    Format: repeated [uint32 length][payload bytes].  ``write_records``
    is provided for tests and example-data generation.
    """

    def __init__(self, filenames: Sequence[str],
                 parse: Optional[Callable[[bytes], Any]] = None) -> None:
        self.filenames = list(filenames)
        self.parse = parse or (lambda b: b)

    @staticmethod
    def write_records(path: str, records: Sequence[bytes]) -> None:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "wb") as f:
            for r in records:
                f.write(struct.pack("<I", len(r)))
                f.write(r)

    def __iter__(self) -> Iterator[Any]:
        for fname in self.filenames:
            with open(fname, "rb") as f:
                while True:
                    hdr = f.read(4)
                    if len(hdr) < 4:
                        break
                    (n,) = struct.unpack("<I", hdr)
                    payload = f.read(n)
                    if len(payload) < n:
                        raise IOError(f"truncated record in {fname}")
                    yield self.parse(payload)


class Prefetcher:
    """Background thread: source iterator -> (shuffling) queue (§4.6)."""

    def __init__(self, source: Iterator[Any], capacity: int = 8,
                 shuffle: bool = False, min_after_dequeue: Optional[int] = None,
                 seed: Optional[int] = None) -> None:
        if shuffle:
            # Pre-fill contract: without a floor, a consumer that drains
            # as fast as the producer fills holds the shuffle window at
            # ~1 item and the "shuffled" stream can come out in order
            # (the old test_prefetcher_shuffling flake).  Defaulting the
            # floor to half the capacity keeps a real window resident
            # until the source closes; pass min_after_dequeue=0 to opt
            # out (e.g. latency-critical consumers).
            if min_after_dequeue is None:
                # clamped to capacity-1: a capacity-1 queue can never
                # hold the min_after_dequeue+1 items dequeue waits for
                min_after_dequeue = min(capacity - 1, max(1, capacity // 2))
            self.queue: FIFOQueue = ShufflingQueue(
                capacity=capacity, min_after_dequeue=min_after_dequeue, seed=seed)
        else:
            self.queue = FIFOQueue(capacity=capacity)
        self._source = source
        self._thread = threading.Thread(target=self._fill, daemon=True)
        self._started = False

    def _fill(self) -> None:
        try:
            for item in self._source:
                while True:
                    try:
                        self.queue.enqueue(item)
                        break
                    except TimeoutError:
                        # a full queue is back-pressure from a slow
                        # consumer (a step that compiles for minutes),
                        # not a fault: keep waiting
                        continue
                # Yield the GIL right after publishing: a consumer blocked
                # in dequeue() was just notified, but without an explicit
                # yield the producer keeps the GIL for up to the switch
                # interval (5ms default) while it generates the *next*
                # item, serialising the very overlap the queue exists to
                # provide (the b5 convoy effect).
                time.sleep(0)
        except QueueClosed:
            return
        finally:
            self.queue.close()

    def start(self) -> "Prefetcher":
        if not self._started:
            self._thread.start()
            self._started = True
        return self

    def get(self) -> Any:
        with span("data.get"):
            return self.queue.dequeue()

    def __iter__(self) -> Iterator[Any]:
        self.start()
        while True:
            try:
                yield self.queue.dequeue()
            except QueueClosed:
                return

    def stop(self) -> None:
        self.queue.close()


def batch_iterator(dataset: SyntheticLMDataset, batch_size: int,
                   start_step: int = 0) -> Iterator[Dict[str, np.ndarray]]:
    step = start_step
    while True:
        yield dataset.batch(batch_size, step)
        step += 1


def input_pipeline(vocab_size: int, seq_len: int, batch_size: int,
                   *, prefetch: int = 4, seed: int = 0,
                   start_step: int = 0) -> Prefetcher:
    """The standard train-input pipeline: synthetic LM -> prefetch queue."""
    ds = SyntheticLMDataset(vocab_size, seq_len, seed=seed)
    return Prefetcher(batch_iterator(ds, batch_size, start_step),
                      capacity=prefetch).start()
