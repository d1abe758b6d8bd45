"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.

Source: Google Cloud TPU documentation, system architecture pages
"TPU v4", "TPU v5e", "TPU v5p", "TPU v6e" (per-chip peak compute in
bf16, HBM capacity and bandwidth). A kind that is not here is an error,
never a default.
"""

PEAKS = {
    # kind: bf16 FLOP/s, HBM bytes/s, HBM bytes
    "TPU v4": {"flops": 275e12, "hbm_bytes_per_s": 1228e9, "hbm_bytes": 32e9},
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
    "TPU v5": {"flops": 459e12, "hbm_bytes_per_s": 2765e9, "hbm_bytes": 95e9},
    "TPU v6 lite": {"flops": 918e12, "hbm_bytes_per_s": 1640e9, "hbm_bytes": 32e9},
}


def peaks_of(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(
            f"no peaks on record for device kind {kind!r}; add it to "
            f"benchmarks/peaks.py with its source")
    return PEAKS[kind]
