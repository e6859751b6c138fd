"""Published per-chip peaks, keyed by the `device_kind` string JAX
reports. A rate implied by a measurement is checked against these; a
kind that is not listed is an error, never a default — rating an unknown
chip at another chip's peak makes every share computed from it wrong."""

from __future__ import annotations

PEAKS: dict[str, dict[str, float]] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of
    # HBM2e at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect per chip
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks_of(device) -> dict[str, float]:
    kind = device.device_kind
    if kind not in PEAKS:
        raise ValueError(
            f"no published peaks on file for device_kind {kind!r}; add "
            f"them to curvine_tpu/tpu/peaks.py with their source")
    return PEAKS[kind]
