"""Published peaks of each accelerator, keyed by JAX's `device_kind`.
A device that is not in `peaks.json` is an error, never a default."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in {PEAKS_FILE.name} (have "
                       f"{sorted(table)})")
    return table[device_kind]


def least_seconds(flops: float, byts: float, device_kind: str) -> float:
    """The least time the chip could take: the larger of operations over
    peak operations/s and bytes over peak bytes/s."""
    p = peaks(device_kind)
    return max(flops / p["bf16_flops_per_s"], byts / p["hbm_bytes_per_s"])
