"""Roofline-term derivation from a compiled dry-run artifact.

Per (arch × shape × mesh) we derive three per-step time lower bounds from
the SPMD-partitioned per-device HLO module:

    compute_s    = HLO_FLOPs_per_device / peak FLOP/s
    memory_s     = HLO_bytes_per_device / peak HBM bytes/s
    collective_s = collective_bytes_per_device / peak bytes/s per link

with the peaks of the target chip taken from :data:`PEAKS` by its
``device_kind``.

FLOPs, HBM traffic and collective wire bytes come from the trip-count-
aware HLO analyzer (hlo_analysis.py) over the SPMD-partitioned module —
``compiled.cost_analysis()`` visits ``while`` bodies once and therefore
under-reports scanned-layer models by ~n_layers; we record its raw
numbers alongside for reference.  MODEL_FLOPS = 6·N·D (train) or 2·N·D
(inference), N = active parameters, D = tokens — the MODEL/HLO ratio
exposes remat, padding and dispatch waste.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

#: Per-chip peaks keyed by ``jax.Device.device_kind``.  TPU v5e (kind
#: "TPU v5 lite"): Google Cloud documentation, "TPU v5e" — 197 TFLOP/s
#: bf16, 819 GB/s of HBM bandwidth, and 1,600 Gbit/s of interchip
#: interconnect over four links (50 GB/s per link).
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak table of one chip kind; a kind not in :data:`PEAKS` is an
    error, never another chip's numbers."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peak table for device kind {device_kind!r}; "
                       f"known kinds: {sorted(PEAKS)}")
    return PEAKS[device_kind]

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"\b([a-z]+[0-9]+(?:e[0-9a-z]+)?)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    b = _DTYPE_BYTES.get(dtype)
    if b is None:
        return 0
    if not dims:
        return b
    n = 1
    for d in dims.split(","):
        n *= int(d)
    return n * b


def parse_collectives(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Sum result bytes per collective kind from per-device HLO."""
    out: Dict[str, Dict[str, float]] = {
        k: {"count": 0, "bytes": 0} for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.+)$", stripped)
        if m is None:
            continue
        rhs = m.group(1)
        kind = None
        for k in _COLLECTIVES:
            if re.search(rf"\b{k}(-start|-done)?\(", rhs):
                kind = k
                break
        if kind is None or f"{kind}-done(" in rhs:
            continue  # count start, not done
        # result shape(s) precede the op name
        head = rhs.split(f"{kind}", 1)[0]
        nbytes = sum(_shape_bytes(d, dims) for d, dims in _SHAPE_RE.findall(head))
        out[kind]["count"] += 1
        out[kind]["bytes"] += nbytes
    return out


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    n_devices: int
    device_kind: str
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float
    collectives: Dict[str, Dict[str, float]]
    memory: Dict[str, float]
    model_flops_global: float
    cost_analysis_raw: Dict[str, float] = dataclasses.field(default_factory=dict)
    loops: Any = None
    compute_s: float = 0.0
    memory_s: float = 0.0
    collective_s: float = 0.0

    def __post_init__(self):
        peak = peaks(self.device_kind)
        self.compute_s = self.flops_per_device / peak["flops"]
        self.memory_s = self.bytes_per_device / peak["hbm_bw"]
        self.collective_s = self.collective_bytes_per_device / peak["link_bw"]

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_ratio(self) -> float:
        hlo_global = self.flops_per_device * self.n_devices
        return self.model_flops_global / hlo_global if hlo_global else 0.0

    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["dominant"] = self.dominant
        d["useful_ratio"] = self.useful_ratio
        return d


def model_flops(cfg, n_params_active: int, shape) -> float:
    """6·N·D train, 2·N·D inference (D = tokens this step)."""
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_params_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_params_active * tokens
    tokens = shape.global_batch  # one token per sequence
    return 2.0 * n_params_active * tokens


def active_params(cfg, model) -> int:
    """Active parameter count (MoE: routed experts scaled by top_k/E) using
    TRUE (unpadded) dimensions."""
    from ..models.api import Model
    from ..models.params import count_params

    true_model = Model.for_config(cfg, shard=1)
    total = count_params(true_model.describe_params())
    if not cfg.n_experts:
        return total
    # routed expert params per layer (w1,w3,w2) at true expert count
    per_expert = 3 * cfg.d_model * cfg.moe_d_ff
    routed = cfg.n_layers * cfg.n_experts * per_expert
    active_frac = cfg.top_k / cfg.n_experts
    return int(total - routed + routed * active_frac)


def analyze(compiled, *, arch: str, shape, mesh_name: str, n_devices: int,
            device_kind: str, cfg, model) -> Roofline:
    from .hlo_analysis import analyze_text

    ca = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    st = analyze_text(compiled.as_text(), n_devices)
    return Roofline(
        arch=arch, shape=shape.name, mesh=mesh_name, n_devices=n_devices,
        device_kind=device_kind,
        flops_per_device=st.flops,
        bytes_per_device=st.hbm_bytes,
        collective_bytes_per_device=st.collective_bytes,
        collectives=st.collectives,
        memory={
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
        model_flops_global=model_flops(cfg, active_params(cfg, model), shape),
        cost_analysis_raw={k: float(v) for k, v in ca.items()
                           if k in ("flops", "bytes accessed")},
        loops=st.loops[:50],
    )
