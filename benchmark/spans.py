"""Self time over the program's span trees.

The arithmetic of ``tools/trace_analyze.py`` (``self_times``), over the
span records that ``heatmap_tpu.obs.tracing``'s collector keeps: a
span's self time is its duration minus its direct children's, clipped
at zero.
"""

from __future__ import annotations

from collections import defaultdict


def self_times(records: list[dict]) -> dict:
    """{span_id: self seconds}."""
    child_s = defaultdict(float)
    ids = {r["span_id"] for r in records}
    for r in records:
        if r["parent_id"] in ids:
            child_s[r["parent_id"]] += r["dur_s"]
    return {r["span_id"]: max(r["dur_s"] - child_s[r["span_id"]], 0.0)
            for r in records}


def total(records: list[dict], names) -> float:
    """Summed duration of the spans with these names."""
    return sum(r["dur_s"] for r in records if r["name"] in names)


def self_total(records: list[dict], names) -> float:
    """Summed self time of the spans with these names."""
    selfs = self_times(records)
    return sum(selfs[r["span_id"]] for r in records if r["name"] in names)


def innermost_at(records: list[dict], t_s: float) -> str | None:
    """Name of the shortest span open at ``t_s`` (same clock), or None."""
    open_ = [r for r in records if r["start_s"] <= t_s < r["start_s"] + r["dur_s"]]
    return min(open_, key=lambda r: r["dur_s"])["name"] if open_ else None
