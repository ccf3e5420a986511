"""HSA agents: devices as the runtime sees them.

An agent wraps one ``jax.Device`` plus the memory-region descriptors the HSA
standard exposes (here: HBM + VMEM of the target chip, or host RAM for CPU
agents).  Discovery enumerates every visible device — the paper's "detects and
manages all the accessible HSA devices visible to the framework".
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax

from repro.hw import chip_spec


@dataclasses.dataclass(frozen=True)
class MemoryRegion:
    name: str
    size_bytes: int
    kind: str                     # "global" (HBM/RAM) | "group" (VMEM/scratch)
    bandwidth_bps: float = 0.0


class Agent:
    """One kernel-dispatch-capable device."""

    def __init__(self, device: jax.Device, *, num_reconfig_regions: int = 4) -> None:
        self.device = device
        self.kind = device.platform            # "cpu" | "tpu" | "gpu"
        self.name = f"{self.kind}:{device.id}"
        self.num_reconfig_regions = num_reconfig_regions
        if self.kind == "tpu":
            chip = chip_spec(device.device_kind)   # unknown kind: KeyError
            self.regions = (
                MemoryRegion("HBM", chip.hbm_bytes, "global", chip.hbm_bw),
                MemoryRegion("VMEM", chip.vmem_bytes, "group"),
            )
        else:
            self.regions = (MemoryRegion("RAM", 16 * 1024**3, "global"),)
        self._queues: list[Any] = []

    # -- queues --------------------------------------------------------------

    def create_queue(
        self, size: int = 256, *, name: str | None = None, weight: int = 1
    ) -> "Any":
        from repro.core.hsa.queue import Queue

        q = Queue(agent=self, size=size, name=name, weight=weight)
        self._queues.append(q)
        return q

    @property
    def queues(self) -> list[Any]:
        return list(self._queues)

    # -- discovery -------------------------------------------------------------

    @staticmethod
    def discover(*, num_reconfig_regions: int = 4) -> list["Agent"]:
        return [
            Agent(d, num_reconfig_regions=num_reconfig_regions) for d in jax.devices()
        ]

    def __repr__(self) -> str:
        return f"Agent({self.name}, regions={len(self.regions)}, queues={len(self._queues)})"
