"""Published peaks of the chips the benchmark may run on.

One table, keyed by `device_kind` as JAX reports it.  A device that is
not in the table is an error, never a default, and nothing in the
environment overrides an entry.
"""

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16, 16 GB of
# HBM at 819 GB/s, per chip.  jax names the chip "TPU v5 lite".
PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks_of(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError("no published peaks for device_kind %r in "
                       "benchmark/peaks.py; add a row with its source"
                       % (device_kind,)) from None
