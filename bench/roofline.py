"""Peaks by device kind, and the least time a piece of work can take.

The peaks are ``peaks.json``'s, with their source; a device kind that is
not there is an error, never a default. A metric's reader counts the
operations and bytes its job needs whatever implements it, and takes
the floor from these.
"""
from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def peaks(device_kind: str, path: str = PEAKS) -> dict:
    """The peak rates of one device kind."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; known: {sorted(table)}")
    return table[device_kind]


def floor_seconds(flops: float, nbytes: float, peak: dict):
    """The least time for ``flops`` bf16 operations and ``nbytes`` of HBM
    traffic: the larger of the two, and which one binds."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")

