"""Per-chip peaks, keyed by the ``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM2 at 819 GB/s.

A kind that is not in the table, or a device that is not a TPU, is an
error: a share of the wrong chip's peak is a wrong number.
"""

from __future__ import annotations

import dataclasses

__all__ = ["ChipPeaks", "PEAKS", "peaks_for"]


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops_bf16: float  # FLOP/s
    hbm_bw: float  # bytes/s
    hbm_bytes: int  # bytes


PEAKS: dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(flops_bf16=197e12, hbm_bw=819e9, hbm_bytes=16 * 10**9),
}


class NoChip(RuntimeError):
    """The run found no accelerator the benchmark can measure."""


def peaks_for(platform: str, device_kind: str) -> ChipPeaks:
    if platform != "tpu":
        raise NoChip(f"no TPU: JAX sees {platform!r} devices")
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise NoChip(
            f"no peaks for device kind {device_kind!r}; known: {sorted(PEAKS)}"
        ) from None
