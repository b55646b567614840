"""Published per-chip peaks, keyed by the ``device_kind`` JAX reports.

Copied from the program's ``core/roofline.PEAKS`` so that no later change
to the program can move the yardstick.  A device that is not listed has
no roofline: ``peaks`` raises, it never falls back to a default.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float   # FLOP/s, the MXU's bf16 rate
    int8_ops: float     # OP/s
    hbm_bw: float       # bytes/s
    ici_bw: float       # bytes/s per chip-to-chip link
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, int8_ops=394e12, hbm_bw=819e9,
        # 1,600 Gbit/s of chip-to-chip interconnect over 4 links
        ici_bw=50e9,
        source="Google Cloud documentation, 'TPU v5e' (system "
               "architecture): 197 TFLOP/s bf16, 394 TOP/s int8, 16 GB "
               "HBM at 819 GB/s, 1,600 Gbit/s ICI"),
}


def peaks(device_kind: str) -> Peaks:
    """Peaks of ``device_kind``; KeyError for a device with none listed."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in chipbench/peaks.py") from None

