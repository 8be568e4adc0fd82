"""Fixed-capacity sparse aggregation: sort + segment-sum over integer keys.

The XLA-native replacement for ``reduceByKey`` (reference
heatmap.py:111): instead of a hash-partitioned shuffle, keys are sorted
on-device and reduced with a single segment scatter-add. Everything is
static-shaped (capacity chosen ahead of time, SURVEY.md §7 hard part (c)
"dynamic occupancy"), so the whole thing lives happily under ``jit``.

Scatter-add on TPU is historically slow for random indices; sorting
first turns the scatter into (mostly) sequential segment writes, which
is the TPU-friendly shape of this computation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _sentinel_for(dtype):
    return jnp.iinfo(jnp.dtype(dtype)).max


def aggregate_keys(keys, weights=None, valid=None, capacity=None, acc_dtype=None):
    """Reduce-by-key: sum ``weights`` per unique key.

    Args:
      keys: int array [N]. Any integer dtype, EXCEPT that the dtype's
        maximum value is reserved as the internal sentinel — a key equal
        to ``iinfo(dtype).max`` would be silently dropped. All tile-key
        encodings in this framework stay well below it (int32 Morton
        codes <= 2^31-2 at z15, packed int64 keys use 58 bits), so this
        only matters for caller-invented key schemes.
      weights: [N] or None (None counts occurrences in int32).
      valid: optional bool [N]; invalid lanes are excluded entirely.
      capacity: max distinct keys to emit (default N). Distinct keys
        beyond capacity are silently dropped — callers size capacity for
        their data (e.g. number of occupied tiles).
      acc_dtype: accumulator dtype (int32 for counts, f32 for weights).

    Returns:
      (unique_keys[capacity], sums[capacity], n_unique) — slots past
      n_unique hold sentinel key (intmax) and zero sum. unique_keys are
      sorted ascending, which downstream pyramid levels rely on.

      ``n_unique`` is the TRUE distinct-key count and can exceed
      ``capacity``: that is the overflow signal, meaning the largest
      ``n_unique - capacity`` keys were dropped and sums no longer total
      the input. Callers must slice with ``uniq[:min(n, capacity)]`` (or
      size capacity generously and treat ``n > capacity`` as an error).
    """
    keys = jnp.asarray(keys)
    n = keys.shape[0]
    capacity = n if capacity is None else capacity
    if acc_dtype is None:
        acc_dtype = jnp.int32 if weights is None else jnp.float32
    w = (
        jnp.ones(n, acc_dtype)
        if weights is None
        else jnp.asarray(weights, acc_dtype)
    )
    sentinel = _sentinel_for(keys.dtype)
    if valid is not None:
        keys = jnp.where(valid, keys, sentinel)
        w = jnp.where(valid, w, 0)

    # Counts (uniform weights) are exact under any summation order, so
    # the sort can be unstable; float weights keep the stable order so
    # results are reproducible against host-order oracles bit-for-bit.
    with jax.named_scope("cascade.sort"):
        order = jnp.argsort(keys, stable=weights is not None)
        sk, sw = keys[order], w[order]
    with jax.named_scope("cascade.segment-reduce"):
        return aggregate_sorted_keys(sk, sw, capacity, sentinel=sentinel)


def aggregate_sorted_keys(sorted_keys, sorted_weights, capacity, sentinel=None):
    """Segment-sum already-sorted keys (see :func:`aggregate_keys`).

    Separated out because the Morton pyramid re-aggregates the *same*
    sorted order at every level (ops/pyramid.py) — sort once, reduce L
    times.
    """
    if sentinel is None:
        sentinel = _sentinel_for(sorted_keys.dtype)
    first = jnp.concatenate(
        [
            jnp.ones((1,), bool),
            sorted_keys[1:] != sorted_keys[:-1],
        ]
    )
    # Sentinel lanes (masked-out points) must not open a segment.
    is_real = sorted_keys != sentinel
    first = first & is_real
    seg = jnp.cumsum(first.astype(jnp.int32)) - 1
    # Drop index must be out-of-bounds HIGH (capacity): negative indices
    # wrap before the mode="drop" bounds check.
    seg = jnp.where(is_real, seg, capacity)

    sums = jnp.zeros((capacity,), sorted_weights.dtype).at[seg].add(
        sorted_weights, mode="drop"
    )
    unique = (
        jnp.full((capacity,), sentinel, sorted_keys.dtype)
        .at[seg]
        .set(sorted_keys, mode="drop")
    )
    n_unique = jnp.sum(first.astype(jnp.int32))
    return unique, sums, n_unique
