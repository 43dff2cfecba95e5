"""Published peaks of each device kind, for roofline shares.

Keyed by ``jax.Device.device_kind``; a device that is not in
``peaks.json`` is an error, never a default.
"""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    """The peak rates of ``device_kind`` (``bf16_flops_per_s``,
    ``hbm_bytes_per_s``, ...)."""
    table = json.loads(TABLE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; have {sorted(table)}")
    return table[device_kind]
