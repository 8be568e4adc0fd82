"""Morton-range partition planning for the sharded cascade.

The uniform data-parallel path splits points round-robin, so every
shard produces partials for the WHOLE key space and the cross-chip
merge must re-aggregate full-pyramid partials. This module plans a
spatial split instead: P-1 detail-zoom Morton codes chosen from a
sampled quantile sketch of the input, so each mesh shard owns one
contiguous Z-order range. Because the pyramid parent is ``code >> 2``
(order-preserving), a contiguous detail range rolls up locally at every
level — the only keys two shards can both hold partials for are parent
tiles whose children straddle a split code, and there are at most
``P-1`` such tiles per level (``tilemath.split_boundary_codes_np``).
The cross-chip exchange therefore shrinks from full-pyramid partials to
that boundary set (arXiv 1509.00910, arXiv 1304.1835).

Skew resistance: after the initial quantile split the planner
iteratively re-splits the heaviest range at its sampled median and
merges the lightest adjacent pair, until no range holds more than
``balance_factor`` times the mean sampled mass (or the heavy range is a
single irreducible code). The result is deterministic for a fixed
sample seed.

A plan whose mass still concentrates in one range (``degenerate``) is
the signal for the dispatch layer to fall back to uniform DP rather
than serialize the job on one shard (``pipeline.batch._dp_mesh_for``).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json

import numpy as np

from heatmap_tpu import obs
from heatmap_tpu.tilemath import (
    morton_range_shards_np,
    split_boundary_codes_np,
)

#: Sampled sketch size: quantiles over 64Ki points bound the relative
#: rank error near 1/sqrt(sample) — far finer than the balance factor
#: the re-split loop enforces.
DEFAULT_SAMPLE_SIZE = 1 << 16

#: A range may hold at most this multiple of the mean sampled mass
#: before the planner re-splits it. 1.25 keeps the ISSUE's skew gate
#: (max/mean <= 2.0) with margin for sampling noise.
DEFAULT_BALANCE_FACTOR = 1.25

#: A plan is degenerate when one range holds this fraction of the
#: sampled mass after re-splitting: range sharding would serialize the
#: job on one shard, so dispatch falls back to uniform DP.
DEGENERATE_MASS = 0.9


def _range_counts(splits: np.ndarray, samp: np.ndarray) -> np.ndarray:
    """Sampled points per range under ``splits`` (sorted sample)."""
    shards = np.searchsorted(splits, samp, side="right")
    return np.bincount(shards, minlength=len(splits) + 1)


@dataclasses.dataclass(frozen=True)
class PartitionPlan:
    """A Morton-range split of the detail-zoom key space into
    ``n_shards`` contiguous ranges.

    ``splits`` are sorted detail codes; a code belongs to shard
    ``k = #{splits <= code}`` (a split opens the range to its right —
    the single ownership convention shared with ``route_counts`` and
    the on-device router, gspmd.route_on_device). Duplicate splits are legal and denote empty ranges.
    """

    detail_zoom: int
    n_shards: int
    splits: tuple  # (n_shards - 1,) sorted int detail codes
    sampled_points: int
    balance_factor: float
    shard_mass: tuple  # sampled mass fraction per shard
    resplits: int
    fingerprint: str

    @property
    def skew_ratio(self) -> float:
        """Max/mean sampled shard mass; 1.0 is perfectly balanced."""
        if not self.shard_mass or sum(self.shard_mass) <= 0:
            return 1.0
        mean = sum(self.shard_mass) / len(self.shard_mass)
        return max(self.shard_mass) / mean

    @property
    def degenerate(self) -> bool:
        """True when range sharding would serialize on one shard."""
        if self.n_shards < 2 or self.sampled_points == 0:
            return True
        nonempty = sum(1 for m in self.shard_mass if m > 0)
        return nonempty < 2 or max(self.shard_mass) >= DEGENERATE_MASS

    def shard_of_codes(self, codes) -> np.ndarray:
        """Owning shard index per detail code (int32)."""
        return morton_range_shards_np(np.asarray(self.splits, np.int64),
                                      codes)

    def boundary_codes(self, levels: int) -> np.ndarray:
        """Parent codes ``levels`` above detail straddling a split."""
        return split_boundary_codes_np(
            np.asarray(self.splits, np.int64), levels)

    def boundary_tiles_total(self, n_levels: int) -> int:
        """Straddling tiles summed over coarse levels 1..n_levels —
        the entire per-pyramid cross-shard merge key set."""
        return sum(len(self.boundary_codes(lvl))
                   for lvl in range(1, n_levels + 1))

    def code_ranges(self) -> list:
        """Per-shard ``[lo, hi)`` detail-code ranges covering the full
        ``[0, 4^detail_zoom)`` key space."""
        total = 1 << (2 * self.detail_zoom)
        edges = [0, *[int(s) for s in self.splits], total]
        return [(edges[k], edges[k + 1]) for k in range(self.n_shards)]


def plan_partition(codes, n_shards: int, *, detail_zoom: int, valid=None,
                   sample_size: int = DEFAULT_SAMPLE_SIZE, seed: int = 0,
                   balance_factor: float = DEFAULT_BALANCE_FACTOR,
                   max_resplits=None, n_levels=None) -> PartitionPlan:
    """Plan ``n_shards`` contiguous Morton ranges from sampled codes.

    Deterministic for fixed ``(codes, n_shards, seed)``. ``valid``
    masks lanes whose codes are garbage (out-of-projection points);
    they carry no mass. ``n_levels``, when given, sizes the
    boundary-tile count folded into the planned-event metrics.
    """
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    codes = np.asarray(codes, np.int64)
    if valid is not None:
        codes = codes[np.asarray(valid, bool)]
    rng = np.random.default_rng(seed)
    if len(codes) > sample_size:
        samp = codes[rng.choice(len(codes), size=sample_size,
                                replace=False)]
    else:
        samp = codes
    samp = np.sort(samp)
    m = len(samp)
    P = int(n_shards)

    resplits = 0
    if m == 0 or P == 1:
        # Nothing to learn from: geometric even split of the key space
        # (callers treat the zero-sample plan as degenerate anyway).
        total = 1 << (2 * detail_zoom)
        splits = np.asarray(
            [(i + 1) * total // P for i in range(P - 1)], np.int64)
    else:
        splits = samp[np.minimum(
            np.arange(1, P) * m // P, m - 1)].astype(np.int64)
        if max_resplits is None:
            max_resplits = 4 * P
        for _ in range(int(max_resplits)):
            c = _range_counts(splits, samp)
            worst = int(np.argmax(c))
            if c[worst] <= balance_factor * (m / P):
                break
            starts = np.concatenate(([0], np.cumsum(c)))
            sl = samp[starts[worst]:starts[worst + 1]]
            med = sl[len(sl) // 2]
            if med == sl[0]:
                # Median collides with the range's smallest code; the
                # first strictly-greater sample still moves mass left.
                gt = int(np.searchsorted(sl, sl[0], side="right"))
                if gt >= len(sl):
                    break  # single-code hotspot: irreducible
                med = sl[gt]
            cand = np.sort(np.append(splits, med))
            jm = int(np.searchsorted(cand, med))
            c2 = _range_counts(cand, samp)
            # Fund the new split by merging the lightest adjacent pair
            # (never the pair the new split just created).
            pair = c2[:-1] + c2[1:]
            pair[jm] = np.iinfo(pair.dtype).max if pair.dtype.kind in "iu" \
                else np.inf
            best_j = int(np.argmin(pair))
            if best_j == jm:
                break
            splits = np.delete(cand, best_j)
            resplits += 1

    mass = (_range_counts(splits, samp) / m if m else
            np.zeros(P, np.float64))
    payload = {"detail_zoom": int(detail_zoom), "n_shards": P,
               "splits": [int(s) for s in splits], "seed": int(seed),
               "balance_factor": float(balance_factor),
               "sampled_points": int(m)}
    fp = hashlib.sha256(
        json.dumps(payload, sort_keys=True).encode()).hexdigest()
    plan = PartitionPlan(
        detail_zoom=int(detail_zoom), n_shards=P,
        splits=tuple(int(s) for s in splits), sampled_points=int(m),
        balance_factor=float(balance_factor),
        shard_mass=tuple(float(x) for x in mass), resplits=resplits,
        fingerprint=fp)
    obs.record_partition_planned(
        plan,
        boundary_tiles=(plan.boundary_tiles_total(n_levels)
                        if n_levels is not None else None))
    return plan


def split_range_median(codes, weights, lo: int, hi: int):
    """Weighted-median split code for one hot Morton range ``[lo, hi)``.

    The write plane's rebalance uses the same move the re-split loop
    above makes — cut the heavy range at its mass median — but against
    the range's *materialized* cell codes/weights (the compacted base's
    detail rows) instead of a point sample. Returns an int split code
    ``s`` with ``lo < s < hi`` such that roughly half the in-range mass
    lands in ``[lo, s)``, or ``None`` when the range is irreducible
    (empty, or all mass on its smallest code).
    """
    codes = np.asarray(codes, np.int64)
    weights = np.asarray(weights, np.float64)
    keep = (codes >= lo) & (codes < hi) & (weights > 0)
    codes, weights = codes[keep], weights[keep]
    if len(codes) == 0:
        return None
    order = np.argsort(codes, kind="stable")
    codes, weights = codes[order], weights[order]
    cum = np.cumsum(weights)
    idx = int(np.searchsorted(cum, cum[-1] / 2.0, side="left"))
    med = int(codes[min(idx, len(codes) - 1)])
    if med <= lo:
        # All of the left half sits on the smallest code; the first
        # strictly-greater code still moves mass left (same escape the
        # planner's re-split loop takes).
        gt = int(np.searchsorted(codes, med, side="right"))
        if gt >= len(codes):
            return None  # single-code hotspot: irreducible
        med = int(codes[gt])
    if not (lo < med < hi):
        return None
    return med


@dataclasses.dataclass(frozen=True)
class RouteCounts:
    """Exact lane counts of one owner-routed exchange (the gspmd range
    program's ``mesh.route``), and the static widths they fix.

    ``pairs[s][d]`` counts the valid lanes that start on source shard
    ``s`` (the ``s``-th contiguous block of the padded stream) and that
    shard ``d`` owns. ``pair_capacity`` is the width of one send row and
    ``recv_capacity`` that of one shard's owned lanes; each is the
    largest exact count, bucketed, so no data of the plan exceeds it.
    """

    pairs: tuple
    pair_capacity: int
    recv_capacity: int

    @property
    def owned(self) -> tuple:
        """Valid lanes each shard owns."""
        return tuple(int(x) for x in np.asarray(self.pairs).sum(axis=0))

    @property
    def remote_lanes(self) -> int:
        """Lanes that leave their source shard."""
        p = np.asarray(self.pairs)
        return int(p.sum() - np.trace(p))

    @property
    def overflow_lanes(self) -> int:
        """Lanes past the static widths: 0 for widths from this count."""
        p = np.asarray(self.pairs)
        return int(np.maximum(p - self.pair_capacity, 0).sum()
                   + np.maximum(p.sum(axis=0) - self.recv_capacity, 0).sum())


def route_counts(plan: PartitionPlan, codes, valid=None,
                 bucket=None) -> RouteCounts:
    """Exact per-pair lane counts of the owner-routed exchange.

    ``codes`` is the emission stream's detail codes and ``valid`` its
    mask. The stream is padded to whole blocks of ``ceil(n / n_shards)``
    lanes (pad lanes invalid) and shard ``s`` holds the ``s``-th block.
    ``bucket`` rounds each width up (a step of a fixed ladder), so jobs
    of one size share one compiled program; the widths are never below
    the exact maxima.
    """
    codes = np.asarray(codes, np.int64)
    P = plan.n_shards
    src = np.arange(len(codes)) // max(-(-len(codes) // P), 1)
    owner = plan.shard_of_codes(codes)
    if valid is not None:
        v = np.asarray(valid, bool)
        src, owner = src[v], owner[v]
    pairs = np.bincount(src * P + owner, minlength=P * P).reshape(P, P)
    widest_pair = max(int(pairs.max()), 1)
    widest_owner = max(int(pairs.sum(axis=0).max()), 1)
    if bucket is not None:
        widest_pair = max(int(bucket(widest_pair)), widest_pair)
        widest_owner = max(int(bucket(widest_owner)), widest_owner)
    return RouteCounts(pairs=tuple(tuple(int(x) for x in r) for r in pairs),
                       pair_capacity=widest_pair,
                       recv_capacity=widest_owner)

