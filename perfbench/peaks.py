"""Published peaks of the chips this benchmark has run on, keyed by the
`device_kind` JAX reports. The benchmark's own copy: a share of a peak is
part of the yardstick, so it does not read the program's table. A kind
that is not listed is an error, never a default."""

from __future__ import annotations

PEAKS: dict[str, dict[str, float]] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB of HBM2e at 819 GB/s, 1,600 Gbit/s of inter-chip
    # interconnect per chip. The host link is PCIe; its rate is measured
    # (link.h2d_gbps), not published per chip.
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bytes_per_s": 819e9,
                    "ici_bytes_per_s": 200e9},
}


def peaks_of(device_kind: str) -> dict[str, float]:
    if device_kind not in PEAKS:
        raise ValueError(
            f"no published peaks on file for device_kind {device_kind!r}; "
            f"add them to perfbench/peaks.py with their source")
    return PEAKS[device_kind]
