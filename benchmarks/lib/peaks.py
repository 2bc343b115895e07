"""Published peaks of one chip, keyed by jax's `device_kind`.

Source: Google Cloud documentation, "TPU v5e" system architecture page:
197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s, per chip.  (FLOP/s figure
copied from bench.py:_TPU_PEAK_FLOPS; the bytes/s column is new.)
A device that is not in the table is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "hbm_bytes": 16 * 1024 ** 3},
    "TPU v5e": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                "hbm_bytes": 16 * 1024 ** 3},
}


class UnknownDevice(Exception):
    pass


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise UnknownDevice(f"no published peak for device_kind {device_kind!r}: "
                       f"add it to benchmarks/lib/peaks.py with its source")
    return PEAKS[device_kind]
