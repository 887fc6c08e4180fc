"""Host time a ContinuousBatcher step spends in ``serve.admit`` (it dequeues
requests into free slots and resets their caches): the span's seconds in
the traced window over the number of ``serve.step`` spans there, in ms
(the program's profiler spans, read by bench/trace_reduce.py)."""


def read(run):
    spans = (run.get("trace") or {}).get("host_spans") or {}
    if "serve.admit" not in spans or not spans.get("serve.step"):
        return None
    return 1e3 * spans["serve.admit"][1] / spans["serve.step"][0]
