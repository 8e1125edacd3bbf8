"""The one table of chip peaks, keyed by ``device_kind``. An unknown kind is an error."""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e" (system architecture): one chip
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add a row with its source")
    return PEAKS[device_kind]
