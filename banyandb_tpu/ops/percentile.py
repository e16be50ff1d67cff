"""Per-group percentile/quantile kernels.

The reference has no first-class percentile aggregate (clients post-process
bucketed measures); SURVEY.md §7 step 1 promotes it to a native aggregate.
Device strategy: fixed-bucket histogram per group via one segment reduction
over the combined (group, bucket) id, then vectorized CDF inversion with
linear interpolation inside the hit bucket.  Exactness contract: within one
bucket width over [lo, hi]; callers needing exact values run sort-based
quantile on a single group.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


def group_histogram(
    key: jax.Array,
    valid: jax.Array,
    values: jax.Array,
    num_groups: int,
    lo,
    span,
    num_buckets: int = 512,
    counts: jax.Array | None = None,
) -> jax.Array:
    """-> f32 [num_groups, num_buckets] per-group counts over [lo, lo+span].

    `lo`/`span` may be traced scalars (two-pass percentile reuses one
    compiled kernel across queries). The single shared histogram kernel —
    percentile, the measure executor, and the distributed step all call
    this.

    `counts` (int32 [num_groups * num_buckets], flat): add this chunk's
    rows into it and return it, flat, instead: a scan carries one exact
    integer histogram across its chunks (the measure executor's fused
    program).  Flat, because that is the layout the device's scatter
    writes: a [G, B] carry is copied into it and back every chunk.
    """
    if (num_groups + 1) * num_buckets >= 2**31:
        # The combined (group, bucket) segment id must fit int32 or scatter
        # indices silently wrap under jit (same guard as mixed_radix_key).
        raise ValueError(
            f"num_groups={num_groups} x num_buckets={num_buckets} "
            "overflows int32 segment ids"
        )
    with jax.named_scope("bydb.histogram"):
        width = span / num_buckets
        bucket = jnp.clip(
            ((values - lo) / width).astype(jnp.int32), 0, num_buckets - 1
        )
        safe_key = jnp.where(valid, key, jnp.int32(num_groups))
        combined = safe_key * jnp.int32(num_buckets) + bucket
        if counts is not None:
            # an invalid row's id lies past the last group's: dropped
            return counts.at[combined].add(valid.astype(counts.dtype), mode="drop")
        return jax.ops.segment_sum(
            valid.astype(jnp.float32),
            combined,
            num_segments=(num_groups + 1) * num_buckets,
        ).reshape(num_groups + 1, num_buckets)[:num_groups]


class HistogramRanks(NamedTuple):
    """Where each group's quantile ranks fall in its histogram, [G, Q]
    each (``invert_histogram``)."""

    target: jax.Array  # the rank ceil(q*N), clamped to [1, max(N, 1)]
    hit: jax.Array  # the first bucket whose cumulative count reaches it
    before: jax.Array  # the count of the buckets below `hit`
    at: jax.Array  # the count in `hit`
    total: jax.Array  # N, [G, 1]


def invert_histogram(
    counts: jax.Array, quantiles, block: int = 32
) -> HistogramRanks:
    """CDF inversion of per-group histograms ``counts`` [G, B], f32 or
    int32: for each group and quantile the bucket that holds rank
    ceil(q*N) and the counts the interpolation inside it needs.  The rank
    is taken in f32; int32 counts keep the cumulative counts exact.

    Two levels, so no [G, B] cumulative sum is built and nothing is
    gathered: the rank's block of ``block`` buckets from the cumulated
    block totals, then its bucket from the cumulated counts of that one
    block (the first bucket whose cumulative count reaches the rank lies
    in the first block whose cumulative total does).  Each "first ...
    that reaches" is a count of those that do not, and each pick a masked
    sum: streaming reductions over the histogram, no scatter or gather.
    A group with no row reads bucket 0, as an argmax over no hit would."""
    q = jnp.asarray(quantiles, dtype=jnp.float32)
    g, b = counts.shape
    nblocks = -(-b // block)
    # zero buckets past the last add nothing: no rank moves into them
    padded = jnp.pad(counts, ((0, 0), (0, nblocks * block - b)))
    blocks = padded.reshape(g, nblocks, block)
    block_sum = blocks.sum(axis=-1)  # [G, nblocks]
    block_cdf = jnp.cumsum(block_sum, axis=-1)
    total = block_cdf[:, -1:]  # [G, 1]
    n = total.astype(jnp.float32)
    # Rank of the q-quantile: ceil(q*N) clamped to [1, N] so q=0 lands on the
    # min-value bucket rather than degenerating to `lo`.
    target = jnp.clip(jnp.ceil(q[None, :] * n), 1.0, jnp.maximum(n, 1.0)).astype(
        counts.dtype
    )
    rank = target[:, :, None]  # [G, Q, 1]
    # the first block whose cumulative total reaches the rank: a count
    # of those that do not (all of them only for an empty group -> 0)
    ks = jnp.arange(nblocks)
    blk = jnp.sum(block_cdf[:, None, :] < rank, axis=-1) % nblocks  # [G, Q]
    below = jnp.sum(
        jnp.where(ks < blk[:, :, None], block_sum[:, None, :], 0), axis=-1
    )
    inner = jnp.sum(  # that block's counts, [G, Q, block]
        jnp.where((ks == blk[:, :, None])[..., None], blocks[:, None], 0), axis=2
    )
    inner_cdf = below[:, :, None] + jnp.cumsum(inner, axis=-1)
    j = jnp.sum(inner_cdf < rank, axis=-1) % block
    pick = j[:, :, None] == jnp.arange(block)
    at = jnp.sum(jnp.where(pick, inner, 0), axis=-1)
    cdf_at = jnp.sum(jnp.where(pick, inner_cdf, 0), axis=-1)
    return HistogramRanks(target, blk * block + j, cdf_at - at, at, total)


def group_percentile_histogram(
    key: jax.Array,
    valid: jax.Array,
    values: jax.Array,
    num_groups: int,
    quantiles,
    *,
    lo: float,
    hi: float,
    num_buckets: int = 512,
) -> jax.Array:
    """-> f32 [num_groups, len(quantiles)] interpolated quantile estimates.

    Values are clamped into [lo, hi]; empty groups return lo.
    """
    q = jnp.asarray(quantiles, dtype=jnp.float32)
    width = (hi - lo) / num_buckets
    counts = group_histogram(
        key, valid, values, num_groups, lo, hi - lo, num_buckets
    )
    r = invert_histogram(counts, q)
    # Linear interpolation of the rank inside the hit bucket.
    frac = jnp.where(r.at > 0, (r.target - r.before) / jnp.maximum(r.at, 1.0), 0.0)
    est = lo + (r.hit.astype(jnp.float32) + jnp.clip(frac, 0.0, 1.0)) * width
    return jnp.where(r.total > 0, est, lo)
