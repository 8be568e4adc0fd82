"""The benchmark's numpy reference against the pure-Python oracle of the
reference job, and its float32 control against the reference."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))

import oracle  # noqa: E402

from benchmark import compare, reference, traffic  # noqa: E402

with open(os.path.join(REPO, "benchmark", "traffic", "fleet-day-5m.json")) as _f:
    MIX = json.load(_f)
MIX = {**MIX,
       "points_per_job": 2500, "users": 40, "excluded_users": 5,
       "route_users": 5}
JOB = {"detail_zoom": 21, "min_detail_zoom": 5, "result_delta": 5,
       "timespans": ["alltime"]}


def blobs(names, levels, delta):
    """Reference levels in the oracle's blob form."""
    out = {}
    for zoom, (keys, sums) in levels.items():
        g, r, c = reference.unpack(keys)
        for gi, ri, ci, v in zip(g.tolist(), r.tolist(), c.tolist(),
                                 sums.tolist()):
            blob = f"{names[gi]}|alltime|{zoom - delta}_{ri >> delta}_{ci >> delta}"
            out.setdefault(blob, {})[f"{zoom}_{ri}_{ci}"] = v
    return out


@pytest.mark.parametrize("weighted", [False, True], ids=["count", "weighted"])
def test_reference_matches_oracle(weighted):
    cols = traffic.make_job(MIX, 2**33 + 17, 3)
    names, levels = reference.reference_levels(cols, {**JOB,
                                                      "weighted": weighted})
    kept = cols["source"] != traffic.BACKGROUND
    locations = [
        {"tileId": oracle.tile_id(la, lo, 21), "userId": u,
         "count": v if weighted else 1.0}
        for la, lo, u, v in zip(cols["latitude"][kept].tolist(),
                                cols["longitude"][kept].tolist(),
                                cols["user_id"][kept].tolist(),
                                cols["value"][kept].tolist())]
    want = oracle.cascade(locations, 21, 5, amplify_all=False)
    got = blobs(names, levels, 5)
    assert got.keys() == want.keys()
    for blob, tiles in want.items():
        assert got[blob].keys() == tiles.keys(), blob
        np.testing.assert_allclose(
            [got[blob][t] for t in tiles], list(tiles.values()),
            rtol=1e-12 if weighted else 0, err_msg=blob)


def test_reference_routing_rules():
    assert reference.route("x-7") is None
    assert reference.route("x") is None
    assert reference.route("rt-3") == reference.ROUTE
    assert reference.route("rt") == "rt"
    assert reference.route("user-1") == "user-1"


def limits(config):
    with open(os.path.join(REPO, "benchmark", "configs", f"{config}.json")) as f:
        return json.load(f)["limits"]


@pytest.mark.parametrize("weighted,config", [(False, "refjob-z21"),
                                             (True, "dwell-z21")],
                         ids=["count", "weighted"])
def test_float32_control_is_refused(weighted, config):
    """The reference in float32, put in the program's place, misplaces
    points at z21, so the configuration's limits refuse it."""
    cols = traffic.make_job(MIX, 9, 1)
    readings = compare.control_readings(cols, {**JOB, "weighted": weighted})
    assert readings["rows_wrong"] > 0
    assert readings["value_err"] > 0
    correct, checks = compare.verdict(readings, limits(config))
    assert not correct
    assert checks["rows_wrong"] == {"value": readings["rows_wrong"],
                                    "limit": 0}


def test_float32_sums_are_refused():
    """float64 projection with float32 sums: every row lands right, and
    the value gap alone fails the weighted configuration's limit."""
    cols = traffic.make_job(MIX, 5, 2)
    readings = compare.control_readings(cols, {**JOB, "weighted": True},
                                        dtype=np.float64,
                                        sum_dtype=np.float32)
    assert readings["rows_wrong"] == 0
    assert readings["value_err"] > limits("dwell-z21")["value_err"]
    assert not compare.verdict(readings, limits("dwell-z21"))[0]
