"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A kind that is not here is an error: a share
of a peak is never taken against a guessed or default chip."""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_s": 819e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
