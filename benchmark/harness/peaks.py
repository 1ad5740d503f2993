"""Published peaks of one chip, keyed by ``jax.devices()[0].device_kind``.

A device that is not in the table is an error, not a default: a utilization
against another chip's peak is not a number.
"""

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (System architecture): 197 TFLOP/s
    # bf16 and 393 TOP/s int8 per chip, 16 GB of HBM2e at 819 GB/s, 1,600
    # Gbit/s of chip-to-chip interconnect.
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bytes_per_s": 200e9,
        "source": "Google Cloud documentation, 'TPU v5e', system architecture",
    },
}


def peaks_for(device_kind):
    """The row of ``device_kind``; ``KeyError`` for a chip with no row."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}: add a row "
            "with its source to benchmark/harness/peaks.py") from None
