"""Published peaks of the chips the benchmark may run on, by ``device_kind``.

A device that is not in the table is an error, never a default: a share of a
peak computed against the wrong peak is a wrong number under a right name.
"""

PEAKS = {
    # Google Cloud documentation, "TPU v5e" system architecture page:
    # 197 TFLOP/s bf16 and 819 GB/s of HBM2e per chip
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e: 197 TFLOP/s "
                  "bf16, 819 GB/s HBM)",
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks recorded for device kind {device_kind!r}; "
            f"add it to benchmark/peaks.py with its source "
            f"(known: {sorted(PEAKS)})")
    return PEAKS[device_kind]
