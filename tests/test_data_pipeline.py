"""§4.5/§4.6 input pipeline: readers, prefetch queues, determinism."""
import glob
import os
import time

import jax
import numpy as np

from repro.data import (SyntheticLMDataset, FileRecordReader, Prefetcher,
                        input_pipeline)


def test_synthetic_dataset_deterministic_and_bounded():
    ds = SyntheticLMDataset(vocab_size=128, seq_len=16, seed=3)
    b1 = ds.batch(4, step=7)
    b2 = ds.batch(4, step=7)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert b1["tokens"].max() < 128 and b1["tokens"].min() >= 0
    assert b1["labels"].shape == (4, 16)
    # labels are next-token shifted
    full = np.concatenate([b1["tokens"], b1["labels"][:, -1:]], axis=1)
    np.testing.assert_array_equal(full[:, 1:], b1["labels"])
    b3 = ds.batch(4, step=8)
    assert not np.array_equal(b1["tokens"], b3["tokens"])


def test_synthetic_dataset_is_learnable_structure():
    """75% of successors follow the bigram table (so loss CAN decrease)."""
    ds = SyntheticLMDataset(vocab_size=64, seq_len=128, seed=0)
    b = ds.batch(16, step=0)
    follows = ds._succ[b["tokens"]] == b["labels"]
    assert 0.6 < follows.mean() < 0.9


def test_file_record_reader_roundtrip(tmp_path):
    records = [bytes([i]) * (i + 1) for i in range(10)]
    path = os.path.join(str(tmp_path), "data.rec")
    FileRecordReader.write_records(path, records)
    got = list(FileRecordReader([path]))
    assert got == records


def test_prefetcher_preserves_order_and_closes():
    src = iter(range(20))
    pf = Prefetcher(src, capacity=4).start()
    assert list(pf) == list(range(20))


def test_prefetcher_outlasts_a_consumer_slower_than_the_queue_timeout():
    pf = Prefetcher(iter(range(6)), capacity=2)
    pf.queue.timeout = 0.05  # the producer's enqueue times out repeatedly
    pf.start()
    time.sleep(0.3)
    assert list(pf) == list(range(6))


def test_prefetcher_shuffling():
    # Deflaked: Prefetcher(shuffle=True) now pre-fills the window
    # (min_after_dequeue defaults to capacity//2), so the shuffle buffer
    # can never collapse to ~1 item when the consumer keeps pace with
    # the producer — the stream is guaranteed to shuffle across a >=32
    # item window rather than "usually, if the producer wins the race".
    pf = Prefetcher(iter(range(64)), capacity=64, shuffle=True, seed=0).start()
    out = list(pf)
    assert sorted(out) == list(range(64))
    assert out != list(range(64))
    displaced = sum(1 for i, v in enumerate(out) if v != i)
    assert displaced >= 16  # a real window, not a lucky swap


def test_input_pipeline_end_to_end():
    pipe = input_pipeline(vocab_size=100, seq_len=8, batch_size=4, prefetch=2)
    b = pipe.get()
    assert b["tokens"].shape == (4, 8)
    b2 = pipe.get()
    assert not np.array_equal(b["tokens"], b2["tokens"])
    pipe.stop()


def test_prefetcher_get_is_a_profiler_span(tmp_path):
    from jax.profiler import ProfileData

    pf = Prefetcher(iter(range(5)), capacity=2).start()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        got = [pf.get() for _ in range(3)]
    finally:
        jax.profiler.stop_trace()
    assert got == [0, 1, 2]
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    names = [ev.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events]
    assert names.count("data.get") == 3

