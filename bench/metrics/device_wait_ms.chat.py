"""Host time a ContinuousBatcher step spends in ``serve.device_wait`` (it
waits for the slot step's logits on the device): the span's seconds in the
traced window over the number of ``serve.step`` spans there, in ms (the
program's profiler spans, read by bench/trace_reduce.py)."""


def read(run):
    spans = (run.get("trace") or {}).get("host_spans") or {}
    if "serve.device_wait" not in spans or not spans.get("serve.step"):
        return None
    return 1e3 * spans["serve.device_wait"][1] / spans["serve.step"][0]
