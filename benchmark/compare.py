"""What decides ``correct``: the pyramid the timed job wrote, against the
numpy reference on the same rows.

The job's ``arrays:`` sink leaves one ``level_zNN.npz`` per stored level.
Two numbers are compared, each against its limit from the configuration
file:

- ``rows_wrong``: rows of the reference that the output lacks, rows of
  the output that the reference lacks (a wrong group, tile or level),
  duplicated rows, and rows whose blob fields (coarse tile, coarse
  zoom, timespan label) disagree with their detail tile. Exact: limit 0.
- ``value_err``: over the rows both hold, the largest gap between the
  output's sum and the reference's: absolute for a count job, relative
  to the reference's sum for a weighted one.
"""

from __future__ import annotations

import glob
import os

import numpy as np

from benchmark import reference


def read_levels(out_dir: str) -> dict:
    """{zoom: columns} of every level file the sink wrote."""
    levels = {}
    for path in sorted(glob.glob(os.path.join(out_dir, "level_z*.npz"))):
        with np.load(path) as z:
            cols = {k: z[k] for k in z.files}
        levels[int(cols["zoom"])] = cols
    return levels


def level_rows(out_dir: str) -> dict:
    """{zoom: rows} of the written levels."""
    return {z: len(c["value"]) for z, c in read_levels(out_dir).items()}


def compare(out_dir: str, names: list, ref_levels: dict, job: dict) -> dict:
    """-> {"rows_wrong": n, "value_err": x} of one job's output."""
    gid = {name: i for i, name in enumerate(names)}
    got = read_levels(out_dir)
    delta = job["result_delta"]
    rows_wrong = 0
    value_err = 0.0
    for zoom in sorted(set(got) | set(ref_levels)):
        want_keys, want_sums = ref_levels.get(zoom, (np.empty(0, np.int64),
                                                     np.empty(0)))
        cols = got.get(zoom)
        if cols is None:
            rows_wrong += want_keys.size
            continue
        user_names = [str(u) for u in cols["user_names"]]
        # Names the reference does not know get ids past its table.
        table = np.array([gid.get(u, len(names) + i)
                          for i, u in enumerate(user_names)], np.int64)
        user = table[cols["user_idx"]] if len(user_names) else cols["user_idx"]
        row = cols["row"].astype(np.int64)
        col = cols["col"].astype(np.int64)
        labels = np.asarray(cols["timespan_names"]).astype(str)
        bad_fields = (
            (cols["coarse_row"] != row >> delta)
            | (cols["coarse_col"] != col >> delta)
            | (labels[cols["timespan_idx"]] != "alltime")
        )
        if int(cols["coarse_zoom"]) != zoom - delta:
            bad_fields[:] = True
        rows_wrong += int(bad_fields.sum())
        keys = reference.pack(user, row, col)
        order = np.argsort(keys)
        keys, sums = keys[order], cols["value"][order].astype(np.float64)
        distinct = np.ones(keys.size, bool)
        distinct[1:] = keys[1:] != keys[:-1]
        rows_wrong += int(keys.size - distinct.sum())
        keys, sums = keys[distinct], sums[distinct]
        i_got, i_want = matches(keys, want_keys)
        rows_wrong += keys.size + want_keys.size - 2 * i_got.size
        value_err = max(value_err, gap(sums[i_got], want_sums[i_want], job))
    return {"rows_wrong": rows_wrong, "value_err": value_err}


def matches(keys, want_keys):
    """Indices of the keys two sorted unique arrays share."""
    at = np.searchsorted(want_keys, keys)
    found = at < want_keys.size
    found[found] = want_keys[at[found]] == keys[found]
    return np.flatnonzero(found), at[found]


def gap(got, want, job) -> float:
    """Largest gap of shared rows' sums: relative in a weighted job."""
    if not got.size:
        return 0.0
    want = want.astype(np.float64)
    diff = np.abs(got.astype(np.float64) - want)
    if job.get("weighted"):
        diff = diff / np.abs(want)
    return float(diff.max())


def control_readings(columns: dict, job: dict, dtype=np.float32,
                     sum_dtype=None) -> dict:
    """The numbers of the control: the reference computed in float32 in
    the program's place (or with only its sums in ``sum_dtype``),
    compared with the float64 reference."""
    names, want = reference.reference_levels(columns, job)
    _, got = reference.reference_levels(columns, job, dtype=dtype,
                                        sum_dtype=sum_dtype)
    rows_wrong, value_err = 0, 0.0
    for zoom, (want_keys, want_sums) in want.items():
        keys, sums = got.get(zoom, (np.empty(0, np.int64), np.empty(0)))
        i_got, i_want = matches(keys, want_keys)
        rows_wrong += keys.size + want_keys.size - 2 * i_got.size
        value_err = max(value_err, gap(sums[i_got], want_sums[i_want], job))
    return {"rows_wrong": rows_wrong, "value_err": value_err}


def verdict(readings: dict, limits: dict) -> tuple[bool, dict]:
    """-> (correct, {name: {"value": reading, "limit": limit}})."""
    shown = {name: {"value": readings[name], "limit": limits[name]}
             for name in limits}
    return all(readings[name] <= limits[name] for name in limits), shown
