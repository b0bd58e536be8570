"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` string JAX reports.  One table; a device that is not in it
is an error, never a default.

Source: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s.
"""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


def peaks_for(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            "no published peaks for device_kind %r in benchmarks/harness/"
            "peaks.py (known: %s); add a row with its source"
            % (device_kind, ", ".join(sorted(PEAKS)))) from None
