"""Published peaks of one chip, keyed by JAX's `device_kind`.

Source: Google Cloud documentation, "TPU v5e" (system architecture): per chip
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s. Copied from
bench.py's DEVICE_PEAKS (PR 21). A kind that is not here is an error.
"""

DEVICE_PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
    },
}


def peaks(device_kind: str) -> dict:
    if device_kind not in DEVICE_PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return DEVICE_PEAKS[device_kind]
