"""Published peaks of the chips the benchmark runs on, keyed by the
`device_kind` JAX reports. A device that is not here is an error: no
default peak is assumed, and no share of a peak is ever computed against
a guess.

Source for "TPU v5 lite": Google Cloud documentation, "TPU v5e" system
architecture page (per chip: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of
HBM2e at 819 GB/s, 1,600 Gbit/s inter-chip interconnect)."""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud, TPU v5e (cloud.google.com/tpu/docs/v5e)",
    },
}


def peak_for(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmarks/lib/peaks.py "
            f"PEAKS ({sorted(PEAKS)}); add its published peaks with their "
            "source before measuring on it") from None
