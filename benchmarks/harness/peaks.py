"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.
A device that is not here is an error, never a default."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
    # 16 GB HBM2e at 819 GB/s, 1600 Gbit/s chip-to-chip interconnect
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                    "int8_ops_per_s": 393e12, "hbm_bytes": 16e9,
                    "ici_bits_per_s": 1600e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       "add it to benchmarks/harness/peaks.py with its source")
    return PEAKS[device_kind]
