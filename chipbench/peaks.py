"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A device that is not in the table is an
error: a share of a peak is never taken against a guessed one."""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,      # dense bf16 FLOP/s
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(device_kind: str) -> Dict[str, object]:
    """The peak table row for ``device_kind``; KeyError when unknown."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
