"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` that JAX reports. A device that is not in the table is an
error, not a default: a roofline share against a guessed peak means
nothing."""

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,        # bf16 MXU peak
        "bytes_per_s": 819e9,         # HBM bandwidth
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 16 GB HBM at 819 GB/s per chip",
    },
}


def lookup(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises ``KeyError`` for a chip the
    table does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def least_time_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take for the work: the larger of
    operations over peak FLOP/s and bytes over peak bandwidth."""
    return max(flops / peak["flops_per_s"], nbytes / peak["bytes_per_s"])
