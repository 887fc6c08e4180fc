"""Mean host time per window step spent taking the next batch from the
prefetcher and putting it on the device (host clock)."""


def read(run):
    waits = run.get("input_wait_s")
    return 1e3 * sum(waits) / len(waits) if waits else None
