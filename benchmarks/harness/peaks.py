"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports.  A kind that is not listed is an error: a
share of a guessed peak is worse than none."""

#: Google Cloud documentation, "TPU v5e" (system architecture page): one chip
#: has 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1600 Gbit/s ICI.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud docs, TPU v5e system architecture",
    },
}


def peaks_for(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}: add it, "
            f"with its source, to benchmarks/harness/peaks.py") from None
