"""Per-chip hardware peaks for the roofline model, keyed by ``device_kind``.

The key is the string JAX reports as ``jax.devices()[0].device_kind``.
A kind that is not in the table is an error, never a default: a roofline
against the wrong chip's peaks is a wrong number.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s, 1,600 Gbit/s
inter-chip interconnect per chip.
"""

from __future__ import annotations

import dataclasses

__all__ = ["ChipPeaks", "PEAKS", "V5E", "peaks"]


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops_bf16: float  # FLOP/s
    ops_int8: float  # OP/s
    hbm_bw: float  # bytes/s
    hbm_bytes: int  # bytes
    ici_link_bw: float  # bytes/s per inter-chip link


V5E = "TPU v5 lite"

PEAKS: dict[str, ChipPeaks] = {
    V5E: ChipPeaks(
        flops_bf16=197e12,
        ops_int8=393e12,
        hbm_bw=819e9,
        hbm_bytes=16 * 10**9,
        ici_link_bw=1600e9 / 8 / 4,  # 1,600 Gbit/s over 4 links
    ),
}


def peaks(device_kind: str) -> ChipPeaks:
    """The peaks of ``device_kind``; KeyError for a chip not in the table."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no roofline peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None
