"""Published peaks of each accelerator the benchmark runs on, keyed by ``device_kind``.

The one table of chip peaks the benchmark reads.  A kind that is not here
is an error, never a default.
"""
from __future__ import annotations

#: TPU v5e (Google Cloud documentation, "TPU v5e"): per chip.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict[str, float]:
    """The peaks of one chip of ``device_kind``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
