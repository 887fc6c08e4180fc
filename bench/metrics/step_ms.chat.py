"""Mean host time of one ContinuousBatcher.step over all the window's
steps (host clock, summed over the window)."""


def read(run):
    steps = run.get("step_s")
    return 1e3 * sum(steps) / len(steps) if steps else None
