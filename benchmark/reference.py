"""Plain numpy reference of the backfill job, independent of the program.

It follows the reference Spark job (timfpark/heatmap, heatmap.py:25-129,
tile.py:16-30) and imports nothing of ``heatmap_tpu``:

- rows whose ``source`` is ``background`` are dropped;
- every point is projected to its detail-zoom Web-Mercator tile with
  the reference's formula, in the order it writes it; points outside the
  projection's range (non-finite, or off the map) count nowhere;
- every point counts toward ``all``; ids starting with ``x`` count
  nowhere else, ids starting with ``rt-`` count toward ``route``, every
  other id toward itself;
- the detail level sums each group's points per tile (one per point, or
  the point's ``value`` in a weighted job); every coarser level sums the
  level below it per parent tile, down to ``min_detail_zoom + 1``.

Levels are returned as sorted unique ``(group, row, col)`` keys packed
by :func:`pack` and the sum of each key. ``dtype`` sets the precision of
the projection and of the sums: float64 is the reference, float32 the
control that a sound comparison must refuse.
"""

from __future__ import annotations

import numpy as np

BACKGROUND = "background"
ALL = "all"
ROUTE = "route"
AXIS_BITS = 22  # rows and columns below 2^22: zooms up to 22
AXIS_MASK = (1 << AXIS_BITS) - 1


def route(user_id: str):
    """Group name of a user id besides ``all``, or None (excluded)."""
    if user_id[:1] == "x":
        return None
    if user_id[:3] == "rt-":
        return ROUTE
    return user_id


def pack(gid, row, col):
    return ((np.asarray(gid, np.int64) << (2 * AXIS_BITS))
            | (np.asarray(row, np.int64) << AXIS_BITS)
            | np.asarray(col, np.int64))


def unpack(key):
    return key >> (2 * AXIS_BITS), (key >> AXIS_BITS) & AXIS_MASK, key & AXIS_MASK


def project(lat, lon, zoom: int, dtype=np.float64):
    """(row, col, valid) at ``zoom``: reference tile.py:17,21 in order."""
    lat = np.asarray(lat, dtype)
    lon = np.asarray(lon, dtype)
    n = dtype(1 << zoom)
    pi = dtype(np.pi)
    with np.errstate(all="ignore"):
        phi = lat * pi / dtype(180)
        y = (dtype(1) - np.log(np.tan(phi) + dtype(1) / np.cos(phi)) / pi) / dtype(2)
        frow = np.floor(y * n)
        fcol = np.floor((lon + dtype(180)) / dtype(360) * n)
    valid = (np.isfinite(frow) & np.isfinite(fcol) & (frow >= 0) & (frow < n)
             & (fcol >= 0) & (fcol < n))
    row = np.where(valid, frow, 0).astype(np.int64)
    col = np.where(valid, fcol, 0).astype(np.int64)
    return row, col, valid


def _group_ids(user_ids):
    """-> (group names, per-row group id, -1 for excluded rows)."""
    first = {}
    inverse = np.fromiter((first.setdefault(u, len(first)) for u in user_ids),
                          np.int64, count=len(user_ids))
    routed = [route(u) for u in first]
    names = [ALL] + sorted({g for g in routed if g is not None})
    gid = {name: i for i, name in enumerate(names)}
    per_id = np.array([-1 if g is None else gid[g] for g in routed], np.int64)
    return names, per_id[inverse] if len(first) else inverse


def _sum_by_key(keys, weights):
    uniq, inverse = np.unique(keys, return_inverse=True)
    if weights.dtype == np.float64:
        return uniq, np.bincount(inverse, weights=weights, minlength=uniq.size)
    order = np.argsort(inverse, kind="stable")
    starts = np.flatnonzero(np.diff(inverse[order], prepend=-1))
    return uniq, np.add.reduceat(weights[order], starts)


def reference_levels(columns: dict, job: dict, dtype=np.float64,
                     sum_dtype=None):
    """-> (group names, {zoom: (sorted keys, sums)}) of one job.
    ``sum_dtype`` (``dtype`` by default) is the precision of the sums."""
    sum_dtype = dtype if sum_dtype is None else sum_dtype
    detail, lowest = job["detail_zoom"], job["min_detail_zoom"] + 1
    keep = np.asarray(columns["source"], object) != BACKGROUND
    row, col, valid = project(columns["latitude"][keep],
                              columns["longitude"][keep], detail, dtype)
    names, gid = _group_ids(columns["user_id"][keep])
    if job.get("weighted"):
        weight = np.asarray(columns["value"], np.float64)[keep]
        weight = weight.astype(sum_dtype)
    else:
        weight = np.ones(int(keep.sum()), sum_dtype)
    mine = valid & (gid >= 0)
    g = np.concatenate([np.zeros(int(valid.sum()), np.int64), gid[mine]])
    keys = pack(g, np.concatenate([row[valid], row[mine]]),
                np.concatenate([col[valid], col[mine]]))
    sums = np.concatenate([weight[valid], weight[mine]])
    levels = {}
    for zoom in range(detail, lowest - 1, -1):
        keys, sums = _sum_by_key(keys, sums)
        levels[zoom] = (keys, sums)
        g, r, c = unpack(keys)
        keys = pack(g, r >> 1, c >> 1)
    return names, levels
