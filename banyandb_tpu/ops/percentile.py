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
) -> jax.Array:
    """-> f32 [num_groups, num_buckets] per-group counts over [lo, lo+span].

    `lo`/`span` may be traced scalars (two-pass percentile reuses one
    compiled kernel across queries). The single shared histogram kernel —
    percentile, the measure executor, and the distributed step all call
    this.
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
        return jax.ops.segment_sum(
            valid.astype(jnp.float32),
            combined,
            num_segments=(num_groups + 1) * num_buckets,
        ).reshape(num_groups + 1, num_buckets)[:num_groups]


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

    cdf = jnp.cumsum(counts, axis=-1)  # [G, B]
    total = cdf[:, -1:]  # [G, 1]
    # Rank of the q-quantile: ceil(q*N) clamped to [1, N] so q=0 lands on the
    # min-value bucket rather than degenerating to `lo`.
    target = jnp.clip(jnp.ceil(q[None, :] * total), 1.0, jnp.maximum(total, 1.0))
    # First bucket whose cumulative count reaches the target rank.
    hit = jnp.argmax(cdf[:, None, :] >= target[:, :, None], axis=-1)  # [G, Q]
    cdf_at = jnp.take_along_axis(cdf, hit, axis=-1)
    cnt_at = jnp.take_along_axis(counts, hit, axis=-1)
    prev_cdf = cdf_at - cnt_at
    # Linear interpolation of the rank inside the hit bucket.
    frac = jnp.where(cnt_at > 0, (target - prev_cdf) / jnp.maximum(cnt_at, 1.0), 0.0)
    est = lo + (hit.astype(jnp.float32) + jnp.clip(frac, 0.0, 1.0)) * width
    return jnp.where(total > 0, est, lo)
