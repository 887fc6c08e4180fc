"""The decode step's share of its roofline: the least time the window's
steps need (bench/flops.py: FLOPs over the peak or bytes over the HBM
bandwidth, whichever is larger, for the live slots) over the device's busy
time in the traced window."""


def read(run):
    tr = run.get("trace")
    if not tr or not tr.get("busy_s") or not run.get("decode_least_s"):
        return None
    return 100.0 * run["decode_least_s"] / tr["busy_s"]
