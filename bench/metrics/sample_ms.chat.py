"""Host time a ContinuousBatcher step spends in ``serve.sample`` (it picks
each live slot's token and keeps the books): the span's seconds in the
traced window over the number of ``serve.step`` spans there, in ms (the
program's profiler spans, read by bench/trace_reduce.py)."""


def read(run):
    spans = (run.get("trace") or {}).get("host_spans") or {}
    if "serve.sample" not in spans or not spans.get("serve.step"):
        return None
    return 1e3 * spans["serve.sample"][1] / spans["serve.step"][0]
