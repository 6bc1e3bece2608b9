"""Published peaks of one chip, keyed by ``device_kind`` as jax reports it.

Source: Google Cloud documentation, "TPU v5e" (system architecture): 197
TFLOP/s in bf16, 16 GB of HBM at 819 GB/s per chip.  A device that is not
in the table is an error, never a default.
"""

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}: add it to "
            "benchmarks/harness/peaks.py with its source")
    return PEAKS[device_kind]
