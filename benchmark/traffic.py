"""The one backfill traffic generator: a mix's parameters in, job columns out.

A traffic mix is a JSON file under ``benchmark/traffic/`` naming the
``driver`` module that runs it; this module makes the rows of the
``backfill`` driver's mixes, and a new mix of that kind is a new file,
never new code. Each job's rows are a pure function of ``(seed, job
index)`` and the mix, so the same seed gives the same inputs.

Parameters beyond the required ones (``REQUIRED``):

- ``background_draw``: ``"exact_share"`` (the default) marks exactly
  ``round(background_share * points_per_job)`` rows as background, at
  positions drawn from the seed, so every job keeps the same number of
  rows; ``"per_row"`` marks each row with probability
  ``background_share``, so the kept count, and with it every compiled
  shape of the cascade, differs from job to job.
- ``user_zipf``: 0 (the default) draws ids uniformly from the pool; an
  exponent ``s > 0`` draws the ``k``-th id with weight ``1 / k**s``.

Every id of the pool appears among the kept rows, so the job's group
count, a static size of the cascade, is fixed by the mix.

The columns follow the production source schema (``io/sources.py``):
float ``latitude``/``longitude``, string ``user_id`` and ``source``,
integer ``timestamp`` (epoch milliseconds) and float ``value``.
"""

from __future__ import annotations

import numpy as np

BACKGROUND = "background"
FOREGROUND = "gps"
REQUIRED = ("points_per_job", "users", "excluded_users", "route_users",
            "background_share", "center_deg", "spread_deg", "hotspot_share",
            "hotspot_spread_deg", "t0_ms", "time_span_ms", "value_mean")
BACKGROUND_DRAWS = ("exact_share", "per_row")


def check(mix: dict) -> None:
    for key in REQUIRED:
        if key not in mix:
            raise ValueError(f"backfill traffic: no {key!r}")
    draw = mix.get("background_draw", "exact_share")
    if draw not in BACKGROUND_DRAWS:
        raise ValueError(f"backfill traffic: background_draw {draw!r} is "
                         f"not one of {BACKGROUND_DRAWS}")


def user_pool(mix: dict) -> np.ndarray:
    """Plain ids, ``x-`` ids (excluded from per-user maps) and ``rt-``
    ids (pooled under ``route``), as the reference's routing rules
    distinguish them."""
    pool = ([f"user-{i}" for i in range(mix["users"])]
            + [f"x-{i}" for i in range(mix["excluded_users"])]
            + [f"rt-{i}" for i in range(mix["route_users"])])
    return np.array(pool, dtype=object)


def job_rng(seed: int, job: int) -> np.random.Generator:
    # SeedSequence takes non-negative integers of any size.
    return np.random.default_rng([seed % (1 << 64), job])


def make_job(mix: dict, seed: int, job: int) -> dict:
    """The columns of job ``job`` of run ``seed``."""
    check(mix)
    rng = job_rng(seed, job)
    n = int(mix["points_per_job"])
    pool = user_pool(mix)
    (clat, clon), (slat, slon) = mix["center_deg"], mix["spread_deg"]
    hlat, hlon = mix["hotspot_spread_deg"]

    lat = clat + rng.normal(0.0, slat, n)
    lon = clon + rng.normal(0.0, slon, n)
    hot = np.flatnonzero(rng.random(n) < mix["hotspot_share"])
    lat[hot] = clat + rng.normal(0.0, hlat, hot.size)
    lon[hot] = clon + rng.normal(0.0, hlon, hot.size)

    if mix.get("background_draw", "exact_share") == "per_row":
        background = rng.random(n) < mix["background_share"]
    else:
        n_bg = int(round(mix["background_share"] * n))
        background = np.zeros(n, bool)
        background[rng.permutation(n)[:n_bg]] = True
    kept = np.flatnonzero(~background)
    if kept.size < pool.size:
        raise ValueError(f"{kept.size} kept rows cannot hold all "
                         f"{pool.size} user ids")
    s = mix.get("user_zipf", 0)
    if s:
        weights = 1.0 / np.arange(1, pool.size + 1) ** s
        uid = rng.choice(pool.size, n, p=weights / weights.sum())
    else:
        uid = rng.integers(0, pool.size, n)
    uid[kept[:pool.size]] = rng.permutation(pool.size)

    source = np.full(n, FOREGROUND, dtype=object)
    source[background] = BACKGROUND
    return {
        "latitude": lat,
        "longitude": lon,
        "user_id": pool[uid],
        "source": source,
        "timestamp": mix["t0_ms"] + rng.integers(0, mix["time_span_ms"], n),
        "value": rng.exponential(mix["value_mean"], n),
    }


class ColumnSource:
    """In-memory source over one job's columns: the ``batches`` protocol
    of ``heatmap_tpu.io.sources.Source``, slicing without copies."""

    def __init__(self, columns: dict):
        self.columns = columns
        self.rows = len(columns["latitude"])

    def batches(self, batch_size: int = 1 << 20):
        for start in range(0, self.rows, batch_size):
            yield {k: v[start:start + batch_size]
                   for k, v in self.columns.items()}

    def close(self) -> None:
        pass
