"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

A copy of ``polyrl_tpu/utils/flops.py`` ``CHIP_PEAKS`` (source: Google Cloud
TPU documentation, the "System architecture" page of each generation). The
benchmark keeps its own so that a change to the program cannot move a
utilization. A device that is not in the table is an error, not a default.
"""

from __future__ import annotations

# device_kind -> (bf16 TFLOP/s, HBM GB/s, HBM bytes)
CHIP_PEAKS: dict[str, tuple[float, float, int]] = {
    "TPU v4": (275.0, 1228.0, 32 * 2**30),
    "TPU v5 lite": (197.0, 819.0, 16 * 2**30),   # v5e
    "TPU v5e": (197.0, 819.0, 16 * 2**30),
    "TPU v5": (459.0, 2765.0, 95 * 2**30),       # v5p
    "TPU v5p": (459.0, 2765.0, 95 * 2**30),
    "TPU v6 lite": (918.0, 1640.0, 32 * 2**30),  # v6e
    "TPU v6e": (918.0, 1640.0, 32 * 2**30),
}


def peaks(device_kind: str) -> dict:
    """{"flops": FLOP/s, "bytes": bytes/s} of one chip; KeyError when the
    kind has no published peak here."""
    if device_kind not in CHIP_PEAKS:
        raise KeyError(f"no published peak for device kind {device_kind!r}; "
                       "add it to benchmark/lib/peaks.py with its source")
    tflops, gbs, hbm = CHIP_PEAKS[device_kind]
    return {"flops": tflops * 1e12, "bytes": gbs * 1e9, "hbm_bytes": hbm}
