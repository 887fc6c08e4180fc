"""§3 device naming and registry.

Device names follow the paper's scheme:
``/job:<job>/task:<n>/device:<kind>:<i>`` (or ``/job:localhost`` for the
single-process case).  A :class:`DeviceSet` models the devices visible to
one runtime — for the faithful eager engine these are *virtual* devices
(the paper's heterogeneous CPU/GPU workers); the compiled/pjit path maps
onto real mesh axes instead (see DESIGN.md §2).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional

import jax

_DEV_RE = re.compile(
    r"^/job:(?P<job>[a-z0-9_]+)(/task:(?P<task>\d+))?/device:(?P<kind>[a-z]+):(?P<index>\d+)$"
)


def local_kind() -> str:
    """The kind of device this process computes on ("cpu", "gpu" or
    "tpu"): JAX's default backend.  The single-device runtime names its
    device by it, so tolerance tables and kernels follow the hardware."""
    return jax.default_backend()


@dataclasses.dataclass(frozen=True)
class DeviceName:
    job: str = "localhost"
    task: int = 0
    kind: str = "cpu"
    index: int = 0

    @staticmethod
    def parse(name: str) -> "DeviceName":
        m = _DEV_RE.match(name)
        if not m:
            raise ValueError(f"bad device name {name!r}")
        return DeviceName(m.group("job"), int(m.group("task") or 0),
                          m.group("kind"), int(m.group("index")))

    def __str__(self) -> str:
        return f"/job:{self.job}/task:{self.task}/device:{self.kind}:{self.index}"


@dataclasses.dataclass
class Device:
    """One computational device: manages kernel execution + a perf model."""

    name: DeviceName
    # cost-model constants used by the §3.2.1 placement simulator
    flops_per_sec: float = 1e11
    bytes_per_sec: float = 5e10  # memory bandwidth
    memory_bytes: int = 16 << 30

    @property
    def kind(self) -> str:
        return self.name.kind


class DeviceSet:
    def __init__(self, devices: Optional[List[Device]] = None) -> None:
        self.devices: Dict[str, Device] = {}
        for d in devices or [Device(DeviceName(kind=local_kind()))]:
            self.devices[str(d.name)] = d

    @staticmethod
    def make_local(n_cpu: int = 1, n_accel: int = 0, accel_kind: str = "tpu",
                   accel_flops: float = 2e14, accel_bw: float = 8e11) -> "DeviceSet":
        devs = [Device(DeviceName(kind="cpu", index=i)) for i in range(n_cpu)]
        devs += [
            Device(DeviceName(kind=accel_kind, index=i),
                   flops_per_sec=accel_flops, bytes_per_sec=accel_bw)
            for i in range(n_accel)
        ]
        return DeviceSet(devs)

    @staticmethod
    def make_cluster(n_workers: int, devices_per_worker: int = 1,
                     kind: str = "tpu") -> "DeviceSet":
        devs = []
        for t in range(n_workers):
            for i in range(devices_per_worker):
                devs.append(Device(DeviceName(job="worker", task=t, kind=kind, index=i)))
        return DeviceSet(devs)

    def names(self) -> List[str]:
        return list(self.devices)

    def fingerprint(self) -> tuple:
        """Hashable identity of this device set, used in RunSignatures so
        swapping the Session's devices invalidates cached Executables."""
        return tuple(sorted(self.devices))

    def __getitem__(self, name: str) -> Device:
        return self.devices[name]

    def __len__(self) -> int:
        return len(self.devices)

    def feasible(self, kinds) -> List[str]:
        return [n for n, d in self.devices.items() if d.kind in kinds]

    def matches(self, constraint: Optional[str]) -> List[str]:
        """§4.3 partial constraints: a constraint is a device-name *prefix*
        (e.g. "/job:worker/task:17") or a kind pattern "device:gpu"."""
        if not constraint:
            return self.names()
        out = []
        for n in self.devices:
            if n.startswith(constraint) or constraint in n:
                out.append(n)
        return out
