"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
chip-to-chip interconnect per chip.  A kind that is not in the table is an
error, never a default: a roofline share against a guessed peak is no
measurement.
"""

from __future__ import annotations

from typing import NamedTuple


class ChipPeaks(NamedTuple):
    flops: float            # FLOP/s per chip (bf16 matrix peak, the highest)
    hbm_bytes_per_s: float  # HBM bandwidth per chip
    hbm_bytes: float        # HBM capacity per chip
    ici_bytes_per_s: float  # chip-to-chip interconnect per chip


PEAKS = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bytes_per_s=819e9,
                             hbm_bytes=16e9, ici_bytes_per_s=1600e9 / 8),
}


def peaks(device_kind: str) -> ChipPeaks:
    """Peaks of one chip of ``device_kind``; raises ``KeyError`` for a kind
    the table does not list."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def least_seconds(ops: float, nbytes: float, device_kind: str,
                  chips: int = 1) -> float:
    """The least time ``chips`` chips could take for ``ops`` operations and
    ``nbytes`` of HBM traffic: the larger of the compute and memory terms."""
    p = peaks(device_kind)
    return max(ops / (chips * p.flops), nbytes / (chips * p.hbm_bytes_per_s))
