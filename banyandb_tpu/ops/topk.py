"""Top-N over group aggregates.

Replaces the reference's Go heap flow (pkg/flow/streaming/topn_heap.go and
the query-side re-rank in banyand/measure/topn_post_processor.go) with a
single lax.top_k over the dense per-group aggregate vector.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_SENTINEL = jnp.finfo(jnp.float32).max


def topk_groups(
    metric: jax.Array,
    nonempty: jax.Array,
    n: int,
    *,
    descending: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """(values, group_indices) of the top-n (or bottom-n) non-empty groups.

    Empty groups sort last in either direction; callers drop entries whose
    returned value is +/-inf-sentinel by checking nonempty[indices].
    """
    with jax.named_scope("bydb.topk"):
        if descending:
            m = jnp.where(nonempty, metric, -_SENTINEL)
            vals, idx = jax.lax.top_k(m, n)
        else:
            m = jnp.where(nonempty, -metric, -_SENTINEL)
            vals, idx = jax.lax.top_k(m, n)
            vals = -vals
        return vals, idx
