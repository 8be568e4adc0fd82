"""Fixed-capacity sparse aggregation: sort + segment-sum over integer keys.

The XLA-native replacement for ``reduceByKey`` (reference
heatmap.py:111): instead of a hash-partitioned shuffle, keys are sorted
on-device and each run of equal keys is reduced in place. Everything
is static-shaped (capacity chosen ahead of time, SURVEY.md §7 hard part
(c) "dynamic occupancy"), so the whole thing lives happily under
``jit``.

The reduction of a sorted stream takes no scatter. A scatter-add into
segment slots is slow on the TPU, and emulated there for float64 sums
and int64 keys, so the sorted order is used instead: a segmented scan
leaves every segment's total on its last lane (``segment.scan``), and
a compaction moves those lanes, in order, to the front
(``segment.compact``). Both take log2(n) steps, each a dense pass over
contiguous lanes, run as loops so that a level compiles to one copy of
each step.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


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
    # the sort can be unstable; float weights keep the stable order, so
    # each segment's lanes, and the order the scan adds them in, are the
    # same on every run.
    with jax.named_scope("cascade.sort"):
        order = jnp.argsort(keys, stable=weights is not None)
        sk, sw = keys[order], w[order]
    with jax.named_scope("cascade.segment-reduce"):
        return aggregate_sorted_keys(sk, sw, capacity, sentinel=sentinel)


def _lanes_from(x, offset, pad: tuple, fill):
    """Lane ``i`` of the result holds ``x[i + offset]``, or ``fill``
    where that lane lies outside ``x``. ``offset`` may be traced; it
    lies within ``[-pad[0], pad[1]]``."""
    padded = lax.pad(x, jnp.asarray(fill, x.dtype), ((*pad, 0),))
    return lax.dynamic_slice(padded, (pad[0] + offset,), x.shape)


def _doublings(n: int) -> tuple[int, int]:
    """-> (steps, longest step): steps of 1, 2, 4, ... lanes that
    together span ``n`` lanes."""
    steps = max(n - 1, 0).bit_length()
    return steps, 1 << max(steps - 1, 0)


def _segmented_sums(opens, x):
    """Inclusive scan of ``x`` that restarts at every ``opens`` lane.

    Step ``k`` adds the running sum ``2^k`` lanes back unless a segment
    opens in between: dense passes over contiguous lanes, each lane's
    total added as a tree. The steps run as one loop, so the program
    holds one copy of the step whatever the width."""
    steps, longest = _doublings(x.shape[0])
    pad = (longest, 0)

    def step(k, carry):
        opens, x = carry
        back = -jnp.left_shift(1, k)
        x = jnp.where(opens, x, x + _lanes_from(x, back, pad, 0))
        return opens | _lanes_from(opens, back, pad, False), x

    return lax.fori_loop(0, steps, step, (opens, x))[1]


def _compact(keep, arrays):
    """Move the ``keep`` lanes of every array, in order, to the front.

    A kept lane moves down by the number of lanes before it that are
    not kept; step ``k`` moves every lane whose distance has bit ``k``
    set by ``2^k``. Taken from the low bit up, no two kept lanes ever
    meet, so each step is a dense shifted select and nothing scatters.
    Lanes past the kept count hold leftovers."""
    n = keep.shape[0]
    steps, longest = _doublings(n)
    pad = (0, longest)
    lane = lax.iota(jnp.int32, n)
    dist = jnp.where(keep, lane + 1 - jnp.cumsum(keep, dtype=jnp.int32), -1)

    def step(k, carry):
        dist, arrays = carry
        size = jnp.left_shift(1, k)
        inbound = _lanes_from(dist, size, pad, -1)
        arrive = (inbound >= 0) & ((inbound & size) != 0)
        stay = (dist >= 0) & ((dist & size) == 0)
        dist = jnp.where(arrive, inbound, jnp.where(stay, dist, -1))
        arrays = [jnp.where(arrive, _lanes_from(a, size, pad, 0), a)
                  for a in arrays]
        return dist, arrays

    return lax.fori_loop(0, steps, step, (dist, list(arrays)))[1]


def aggregate_sorted_keys(sorted_keys, sorted_weights, capacity, sentinel=None):
    """Segment-sum already-sorted keys (see :func:`aggregate_keys`).

    Separated out because the Morton pyramid re-aggregates the *same*
    sorted order at every level (ops/pyramid.py) — sort once, reduce L
    times.

    The sums come from a segmented scan in the weights' own dtype, not
    from differences of a global prefix sum, which would lose the
    relative precision of small segments behind large prefixes. The
    scan adds a segment's weights as a tree rather than left to right:
    integer and dyadic weights sum bit-identically, fractional float64
    weights to within O(log n) ulps.
    """
    if sentinel is None:
        sentinel = _sentinel_for(sorted_keys.dtype)
    n = sorted_keys.shape[0]
    # Sentinel lanes (masked-out points) sort last; they neither open
    # nor close a segment, so their weights reach no output.
    is_real = sorted_keys != sentinel
    change = sorted_keys[1:] != sorted_keys[:-1]
    first = jnp.concatenate([jnp.ones((1,), bool), change]) & is_real
    last = jnp.concatenate([change, jnp.ones((1,), bool)]) & is_real
    n_unique = jnp.sum(first.astype(jnp.int32))

    with jax.named_scope("segment.scan"):
        running = _segmented_sums(first, sorted_weights)
    with jax.named_scope("segment.compact"):
        # Each segment's last lane holds its key and total.
        unique, sums = _compact(last, [sorted_keys, running])
        if capacity <= n:
            unique, sums = unique[:capacity], sums[:capacity]
        else:
            unique = jnp.pad(unique, (0, capacity - n))
            sums = jnp.pad(sums, (0, capacity - n))
        kept = lax.iota(jnp.int32, capacity) < n_unique
        unique = jnp.where(kept, unique, sentinel)
        sums = jnp.where(kept, sums, 0)
    return unique, sums, n_unique
