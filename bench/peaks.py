"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB HBM at 819 GB/s). A device missing here is an error,
never a default.
"""
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peak table for device kind {device_kind!r}")
    return PEAKS[device_kind]


def roofline_s(flops: float, bytes_: float, device_kind: str):
    """-> (least time the chip could take, the bound that sets it)."""
    p = peaks(device_kind)
    t_c, t_m = flops / p["bf16_flops"], bytes_ / p["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
