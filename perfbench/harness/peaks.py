"""Published peaks of one chip, keyed by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
819 GB/s HBM, 16 GB.  A device that is not in the table is an error,
never a default, and there is no override.
"""
from __future__ import annotations

PEAKS = {
    # device_kind as jax reports it
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16 * 1024 ** 3,
                    "source": "Google Cloud docs, TPU v5e"},
    "TPU v5e": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16 * 1024 ** 3,
                "source": "Google Cloud docs, TPU v5e"},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks on record for device_kind {device_kind!r} "
            f"(known: {sorted(PEAKS)}); add it with its source") from None
