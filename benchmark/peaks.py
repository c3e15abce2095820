"""Published peaks of the chips the benchmark has run on, keyed by the
exact ``device_kind`` JAX reports. A device that is not here is an
error, never a default.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip,
1,600 Gbit/s inter-chip interconnect per chip).
"""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e)",
    },
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; add it to benchmark/peaks.py "
                       "with its source")
    return PEAKS[device_kind]


def q4_weight_bytes(k: int, n: int) -> int:
    """Bytes one q4_0 (K, N) weight streams from HBM per use: K*N/2
    packed nibbles plus one float32 scale per 32 k."""
    return k * n // 2 + (k // 32) * n * 4
