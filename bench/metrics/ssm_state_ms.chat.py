"""Device time a ContinuousBatcher step spends on the slots' Mamba-2 state
and conv windows: the seconds of the ops that read or write them in the
traced window, over the number of ``serve.step`` spans there, in ms.

The trace names an op by its HLO name, kind and result type
(bench/trace_reduce.py), and carries no op metadata, so the ops are found
by their result's shape, as the compiled slot step shows them on a v5e
(H heads of P values, state size N, conv width K, I = H x P, GN = groups x
N, S slots, from the run's configuration):

- ``[..., H, P, N]``: each layer's state update, which reads its slice of
  the slot-stacked state and writes the new one in place (its result is
  the whole group's state), and the slot resets' writes;
- ``[S, H, P]``: each layer's readout C.h from the state (with a few
  elementwise ops of that shape, microseconds each);
- ``[..., K - 1, I]`` and ``[..., K - 1, GN]``: the conv windows' reads,
  writes and copies.

None where no op matches (a renamed or reshaped op reads as missing, not
as 0) or the trace holds no step.
"""
import re

TYPE = re.compile(r"\w+\[([\d,]*)\]$")


def _dims(op):
    m = TYPE.search(op)
    return tuple(int(d) for d in m.group(1).split(",") if d) if m else ()


def read(run):
    tr = run.get("trace") or {}
    ops, spans = tr.get("op_seconds") or {}, tr.get("host_spans") or {}
    c = run.get("config") or {}
    if not spans.get("serve.step") or "mamba_d_state" not in c:
        return None
    H, P, N = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"]
    K, GN = c["mamba_d_conv"], c["mamba_n_groups"] * N
    S = c["deployment"]["n_slots"]
    tails = {(H, P, N), (K - 1, H * P), (K - 1, GN)}

    def matches(d):
        return d == (S, H, P) or d[-3:] in tails or d[-2:] in tails

    hits = [t for op, t in ops.items() if matches(_dims(op))]
    if not hits:
        return None
    return 1e3 * sum(hits) / spans["serve.step"][0]
