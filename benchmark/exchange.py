"""The least bytes the mesh exchange must move, and the chips' peak
inter-chip bandwidth.

Every lane that starts on a chip other than the one that owns it has to
cross the interconnect once, whatever the kernel: its int64 key and its
valid flag (a count job ships no weight). Those bytes, over the chips'
combined peak interconnect bandwidth, are the least time the exchange
can take, so that time over the measured one cannot pass 100% unless the
measured time leaves out part of the work.
"""

from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "ici_peaks.json")
BYTES_PER_LANE = 8 + 1


def ici_bytes_per_s(device_kind: str) -> float:
    """One chip's peak interconnect bandwidth; an unknown kind raises."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no interconnect peak for device kind "
                       f"{device_kind!r} in {os.path.basename(PEAKS)}")
    return table[device_kind]["ici_bytes_per_s"]


def least_bytes(remote_lanes: int) -> int:
    return remote_lanes * BYTES_PER_LANE


def roofline_pct(n_bytes: int, chips: int, device_s: float,
                 per_chip_bytes_per_s: float):
    """Share of the interconnect roofline, in %, or None without a time."""
    if device_s <= 0:
        return None
    return 100.0 * n_bytes / (chips * per_chip_bytes_per_s) / device_s
