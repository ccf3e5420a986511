"""Published peaks of the chips the benchmark runs on, by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture table):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip.  The
same numbers as the program's ``repro.hw.CHIPS``; kept here so that a
change to the program cannot move the yardstick.  A kind that is not in the
table is an error, never a default.
"""

from __future__ import annotations

#: device_kind -> (peak bf16 FLOP/s, HBM bytes/s)
PEAKS: dict[str, tuple[float, float]] = {
    "TPU v5 lite": (197e12, 819e9),
}


def peaks(device_kind: str) -> tuple[float, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
