"""The least bytes the cascade must move, and the chip's peaks.

The cascade's floor is a function of the data alone, not of the kernel
that runs it: its inputs read once, as ``run_cascade`` takes them (int64
codes and slots, a bool valid mask, and for a weighted job float64
weights, one each per emission), and each level's real output rows
written once (an int64 key and a sum: int32 counts, or float64 sums in
a weighted job). No implementation can move less, so the share of the
bandwidth roofline that this floor gives cannot pass 100% unless the
device time leaves out part of the work.
"""

from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown kind raises."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{os.path.basename(PEAKS)}")
    return table[device_kind]


def least_bytes(n_emissions: int, level_rows, weighted: bool) -> int:
    """Bytes the cascade must read and write, at the least."""
    per_emission = 8 + 8 + 1 + (8 if weighted else 0)
    per_row = 8 + (8 if weighted else 4)
    return n_emissions * per_emission + sum(level_rows) * per_row


def roofline_pct(n_bytes: int, device_s: float, hbm_bytes_per_s: float):
    """Share of the bandwidth roofline, in %, or None without a time."""
    if device_s <= 0:
        return None
    return 100.0 * n_bytes / hbm_bytes_per_s / device_s
