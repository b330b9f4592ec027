"""Published peaks of the chips the benchmark accepts, keyed by the
`device_kind` JAX reports. A kind that is not here is an error, never a
default: a roofline or utilization against a guessed peak is no number.

Source of every TPU v5e figure: Google Cloud documentation, "TPU v5e"
(cloud.google.com/tpu/docs/v5e), system architecture table: 197 TFLOP/s
bf16, 16 GB HBM2 at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect
(ICI) per chip.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,       # FLOP/s
        "hbm_bytes": 16e9,          # B
        "hbm_bw": 819e9,            # B/s
        "ici_bw": 1600e9 / 8,       # B/s per chip, all links together
    },
}


def peaks_for(kind: str) -> dict:
    """The peaks of `kind`; ValueError if the table does not list it."""
    if kind not in PEAKS:
        raise ValueError(f"no peaks listed for device kind {kind!r}; "
                         f"known: {sorted(PEAKS)}")
    return PEAKS[kind]
