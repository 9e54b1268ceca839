"""Published peaks of the chips the benchmark knows, keyed by the exact
`device_kind` JAX reports. A kind that is not here is an error, never a
default. Source: Google Cloud documentation, "TPU v5e" (system
architecture): 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s, per chip."""

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks_of(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
