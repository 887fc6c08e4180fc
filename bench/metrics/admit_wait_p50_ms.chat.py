"""Median time from a request's due time to its admission into a batcher
slot, over the window's requests (host clock; admission is seen after
each ContinuousBatcher.step)."""
import statistics


def read(run):
    waits = run.get("admit_wait_s")
    return 1e3 * statistics.median(waits) if waits else None
