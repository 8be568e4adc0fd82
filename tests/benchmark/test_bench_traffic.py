"""The benchmark's traffic generator: one job's rows per (seed, job index)."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import traffic  # noqa: E402

with open(os.path.join(REPO, "benchmark", "traffic", "fleet-day-5m.json")) as _f:
    MIX = json.load(_f)
MIX = {**MIX,
       "points_per_job": 20000, "users": 120, "excluded_users": 15,
       "route_users": 15}
SEEDS = [0, 7, 2**31 + 5, 2**40 + 3, -12]


def same(a, b):
    return a.keys() == b.keys() and all(
        a[k].dtype == b[k].dtype and np.array_equal(a[k], b[k]) for k in a)


@pytest.mark.parametrize("seed", SEEDS)
def test_same_seed_and_job_same_rows(seed):
    assert same(traffic.make_job(MIX, seed, 4), traffic.make_job(MIX, seed, 4))


@pytest.mark.parametrize("seed", SEEDS)
def test_other_job_or_seed_other_rows(seed):
    a = traffic.make_job(MIX, seed, 1)
    assert not np.array_equal(a["latitude"], traffic.make_job(MIX, seed, 2)["latitude"])
    assert not np.array_equal(a["latitude"],
                              traffic.make_job(MIX, seed + 1, 1)["latitude"])


@pytest.mark.parametrize("seed", SEEDS)
def test_every_job_has_the_same_shape(seed):
    """Exact background count, and every id among the kept rows: the
    cascade's shapes do not depend on the seed."""
    cols = traffic.make_job(MIX, seed, 3)
    n = MIX["points_per_job"]
    assert {len(v) for v in cols.values()} == {n}
    background = cols["source"] == traffic.BACKGROUND
    assert background.sum() == round(MIX["background_share"] * n)
    kept_ids = set(cols["user_id"][~background].tolist())
    assert kept_ids == set(traffic.user_pool(MIX).tolist())
    assert cols["timestamp"].min() >= MIX["t0_ms"]
    assert cols["timestamp"].max() < MIX["t0_ms"] + MIX["time_span_ms"]
    assert (cols["value"] > 0).all()


def test_column_source_batches_cover_the_rows_in_order():
    cols = traffic.make_job(MIX, 1, 1)
    parts = list(traffic.ColumnSource(cols).batches(6000))
    assert [len(p["latitude"]) for p in parts] == [6000, 6000, 6000, 2000]
    for k in cols:
        assert np.array_equal(np.concatenate([p[k] for p in parts]), cols[k])


@pytest.mark.parametrize("mix,match", [
    ({"points_per_job": 10}, "users"),
    ({**MIX, "background_draw": "sometimes"}, "background_draw"),
], ids=["missing", "unknown_draw"])
def test_bad_mix_is_an_error(mix, match):
    with pytest.raises(ValueError, match=match):
        traffic.make_job(mix, 1, 1)


def test_default_draws_are_the_named_ones():
    """A mix that leaves out ``background_draw`` and ``user_zipf`` makes
    the rows of one that names their defaults."""
    bare = {k: v for k, v in MIX.items()
            if k not in ("background_draw", "user_zipf")}
    named = {**bare, "background_draw": "exact_share", "user_zipf": 0}
    assert same(traffic.make_job(bare, 3, 2), traffic.make_job(named, 3, 2))


@pytest.mark.parametrize("seed", SEEDS)
def test_per_row_background_varies_the_kept_count(seed):
    mix = {**MIX, "background_draw": "per_row"}
    kept = [int((traffic.make_job(mix, seed, j)["source"]
                 != traffic.BACKGROUND).sum()) for j in range(1, 6)]
    assert len(set(kept)) > 1
    n = MIX["points_per_job"]
    share = MIX["background_share"]
    sd = (n * share * (1 - share)) ** 0.5
    assert all(abs(n - k - n * share) < 6 * sd for k in kept)
    cols = traffic.make_job(mix, seed, 1)
    kept_ids = set(cols["user_id"][cols["source"] != traffic.BACKGROUND])
    assert kept_ids == set(traffic.user_pool(MIX).tolist())


def test_zipf_users_are_skewed_and_all_present():
    mix = {**MIX, "user_zipf": 1.2}
    cols = traffic.make_job(mix, 11, 1)
    pool = traffic.user_pool(mix).tolist()
    counts = {u: 0 for u in pool}
    for u in cols["user_id"].tolist():
        counts[u] += 1
    assert min(counts.values()) >= 1
    # The first id draws 1/H of the rows (H the pool's harmonic sum).
    h = sum(1.0 / k ** 1.2 for k in range(1, len(pool) + 1))
    assert counts[pool[0]] == pytest.approx(MIX["points_per_job"] / h, rel=0.1)
    assert counts[pool[0]] > 20 * counts[pool[len(pool) // 2]]
