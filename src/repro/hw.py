"""Accelerator peaks, keyed by the ``device_kind`` JAX reports.

Used by the roofline analysis, the agent descriptors, and kernel BlockSpec
sizing.  A TPU whose kind is not in :data:`CHIPS` has no peaks here:
:func:`chip_spec` raises rather than lend it another chip's numbers.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    name: str
    peak_bf16_flops: float        # FLOP/s per chip
    peak_int8_ops: float          # OP/s per chip
    hbm_bytes: int                # capacity
    hbm_bw: float                 # bytes/s
    vmem_bytes: int               # on-chip vector memory
    ici_bw_per_link: float        # bytes/s per ICI link
    ici_links: int                # links per chip (2D torus -> 4)
    mxu_dim: int = 128            # systolic array edge
    clock_hz: float = 0.94e9      # derived: 197e12 / (8 * 128*128*2) ~ 0.94 GHz equiv

    @property
    def flops_per_cycle(self) -> float:
        return self.peak_bf16_flops / self.clock_hz


# Google Cloud documentation, "TPU v5e" (system architecture table): 197
# TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
# inter-chip interconnect (4 links).  VMEM is not in that table; the 128 MiB
# below has no published source and is unverified.
TPU_V5E = ChipSpec(
    name="tpu_v5e",
    peak_bf16_flops=197e12,
    peak_int8_ops=393e12,
    hbm_bytes=16 * 1024**3,
    hbm_bw=819e9,
    vmem_bytes=128 * 1024**2,
    ici_bw_per_link=50e9,
    ici_links=4,
)

#: ``jax.Device.device_kind`` -> peaks
CHIPS: dict[str, ChipSpec] = {
    "TPU v5 lite": TPU_V5E,
}


def chip_spec(device_kind: str) -> ChipSpec:
    """Peaks of the chip JAX names ``device_kind``; KeyError if unknown."""
    try:
        return CHIPS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks recorded for device kind {device_kind!r}; "
            f"known: {sorted(CHIPS)}"
        ) from None
