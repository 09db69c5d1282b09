"""Published peaks of each accelerator the benchmark may run on.

Keyed by ``device_kind`` as JAX reports it.  A device that is not here is
an error, never a default: a share of a peak that was guessed is no
measurement.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI
per chip.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_bf16: float        # FLOP/s per chip
    hbm_bytes_per_s: float   # bytes/s per chip
    hbm_bytes: float         # bytes per chip
    source: str


PEAKS: dict[str, Peaks] = {
    "TPU v5 lite": Peaks(197e12, 819e9, 16e9,
                         "Google Cloud documentation, TPU v5e"),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
