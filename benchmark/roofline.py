"""Bytes a window's stages must move, and the table of peaks.

The bytes follow from the wire format's shapes alone, whatever implements
the stages: per lane the KES-signed header body (which carries the keys,
the VRF output and proof and the operational certificate), the CompactSum
KES signature, and the slot, counter and start period as 8-byte integers
with two 4-byte table indices; out come the verdict bitmasks (one bit a
lane for each of the 8 verdict classes) and the 64-byte nonce carry."""

from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")
VERDICT_MASKS = 8
NONCE_CARRY_BYTES = 64


def window_bytes(lanes: int, body_bytes: int, kes_depth: int) -> int:
    per_lane = body_bytes + (96 + 32 * kes_depth) + 3 * 8 + 2 * 4
    return (lanes * per_lane + VERDICT_MASKS * ((lanes + 7) // 8)
            + NONCE_CARRY_BYTES)


def peak(device_kind: str, what: str) -> float:
    """A published peak of the device. An unknown device is an error."""
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "benchmark/peaks.json")
    return float(table[device_kind][what])
