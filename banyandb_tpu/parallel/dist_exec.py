"""Distributed measure aggregation over a device mesh.

Map-reduce with collectives instead of proto exchange:

  per device:  mask -> group key -> segment reduce  (the "map" on one
               shard/segment slice, same kernel family as
               query/measure_exec._kernel_body)
  collective:  psum(count/sums/hist), pmin/pmax over ('shard','seg')
               — replacing the liaison's partial-merge loop
               (banyand/dquery/measure.go:156)
  post:        top-k on the now-replicated group vector, still on device

Inputs are [S, R] arrays sharded over the mesh ('shard','seg' collapsed
into the leading dim); the whole step is one jit so XLA schedules scan
and collectives together.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from banyandb_tpu import ops

_NUM_HIST_BUCKETS = 512


@dataclass(frozen=True)
class DistPlan:
    """Static signature of the distributed aggregation step."""

    tags_code: tuple[str, ...]
    fields: tuple[str, ...]
    group_tags: tuple[str, ...]
    radices: tuple[int, ...]
    num_groups: int
    eq_preds: tuple[str, ...] = ()  # tag names with eq-code predicates
    topn: int = 0
    want_hist: str = ""  # field name for percentile histograms


def map_chunk(plan: DistPlan, chunk: dict, pred_codes: dict):
    """The map half of one device chunk: mask -> group key -> segment
    reduce.  -> (GroupReduceResult, key, mask).  Shared verbatim by the
    legacy single-width step below and the fused chunked-scan step
    (query/fused_exec._fused_dist_step), so the two mesh programs cannot
    drift on predicate/key/reduction semantics."""
    valid = chunk["valid"]
    masks = [valid]
    for t in plan.eq_preds:
        masks.append(chunk["tags"][t] == pred_codes[t])
    mask = ops.mask_and(*masks)

    key_cols = [chunk["tags"][t] for t in plan.group_tags]
    if key_cols:
        key, _ = ops.mixed_radix_key(key_cols, plan.radices)
    else:
        key = jnp.zeros_like(valid, dtype=jnp.int32)

    res = ops.group_reduce(
        key, mask, chunk["fields"], plan.num_groups, want_minmax=True
    )
    return res, key, mask


def _step(plan: DistPlan, chunk: dict, pred_codes: dict, hist_lo, hist_span):
    """One device's slice -> partials -> collectives -> result.

    shard_map hands each device a [1, R] view of the sharded [D, R] input;
    flatten to [R] so segment reductions see a flat row axis.
    """
    chunk = jax.tree.map(lambda a: a.reshape(-1), chunk)
    res, key, mask = map_chunk(plan, chunk, pred_codes)

    # ---- the collective reduce: ICI replaces the proto partial hop ----
    axes = ("shard", "seg")
    count = jax.lax.psum(res.count, axes)
    sums = {f: jax.lax.psum(res.sums[f], axes) for f in plan.fields}
    mins = {f: jax.lax.pmin(res.mins[f], axes) for f in plan.fields}
    maxs = {f: jax.lax.pmax(res.maxs[f], axes) for f in plan.fields}
    out = {"count": count, "sums": sums, "mins": mins, "maxs": maxs}

    if plan.want_hist:
        hist = ops.group_histogram(
            key,
            mask,
            chunk["fields"][plan.want_hist],
            plan.num_groups,
            hist_lo,
            hist_span,
            _NUM_HIST_BUCKETS,
        )
        out["hist"] = jax.lax.psum(hist, axes)

    if plan.topn:
        mean = out["sums"][plan.fields[0]] / jnp.maximum(out["count"], 1.0)
        vals, idx = ops.topk_groups(mean, out["count"] > 0, plan.topn)
        out["top_vals"], out["top_idx"] = vals, idx
    return out


_STEP_CACHE: dict[tuple, object] = {}


def build_distributed_step(mesh: Mesh, plan: DistPlan):
    """-> jitted f(chunks, pred_codes, hist_lo, hist_span) over the mesh.

    `chunks` arrays carry a leading device dim [S*G_seg, R] sharded over
    ('shard','seg'); outputs are replicated.  Steps are memoized per
    (mesh devices, plan) so repeated queries reuse the compiled program.
    """
    cache_key = (
        tuple(d.id for d in mesh.devices.flat),
        mesh.axis_names,
        plan,
    )
    cached = _STEP_CACHE.get(cache_key)
    if cached is not None:
        return cached
    data_spec = P(("shard", "seg"))

    step = jax.shard_map(
        partial(_step, plan),
        mesh=mesh,
        in_specs=(
            {
                "valid": data_spec,
                "tags": {t: data_spec for t in plan.tags_code},
                "fields": {f: data_spec for f in plan.fields},
            },
            {t: P() for t in plan.eq_preds},
            P(),
            P(),
        ),
        out_specs=_out_specs(plan),
    )

    def run(chunks, pred_codes, hist_lo, hist_span):
        return step(chunks, pred_codes, hist_lo, hist_span)

    jitted = jax.jit(run)
    _STEP_CACHE[cache_key] = jitted
    return jitted


def _out_specs(plan: DistPlan):
    spec = {
        "count": P(),
        "sums": {f: P() for f in plan.fields},
        "mins": {f: P() for f in plan.fields},
        "maxs": {f: P() for f in plan.fields},
    }
    if plan.want_hist:
        spec["hist"] = P()
    if plan.topn:
        spec["top_vals"] = P()
        spec["top_idx"] = P()
    return spec


def stack_shard_chunks(
    mesh: Mesh,
    per_shard_rows: list[dict],
    tags: tuple[str, ...],
    fields: tuple[str, ...],
    nrows: int,
) -> dict:
    """Pack per-shard host rows into mesh-sharded [D, nrows] arrays.

    Each entry of per_shard_rows: {"tags": {t: int32[n]}, "fields":
    {f: f32[n]}} for one device slot; rows beyond nrows are dropped by the
    caller's chunking loop, rows short of nrows are padded invalid.
    """
    d = mesh.devices.size
    assert len(per_shard_rows) == d, (len(per_shard_rows), d)
    valid = np.zeros((d, nrows), dtype=bool)
    tag_arrs = {t: np.zeros((d, nrows), dtype=np.int32) for t in tags}
    field_arrs = {f: np.zeros((d, nrows), dtype=np.float32) for f in fields}
    for i, rows in enumerate(per_shard_rows):
        n = min(len(next(iter(rows["tags"].values()))) if rows["tags"] else 0, nrows)
        if rows["fields"]:
            n = min(
                n if rows["tags"] else nrows,
                *(len(v) for v in rows["fields"].values()),
            )
        valid[i, :n] = True
        for t in tags:
            tag_arrs[t][i, :n] = rows["tags"][t][:n]
        for f in fields:
            field_arrs[f][i, :n] = rows["fields"][f][:n]

    shard_spec = NamedSharding(mesh, P(("shard", "seg")))
    return {
        "valid": jax.device_put(valid, shard_spec),
        "tags": {t: jax.device_put(a, shard_spec) for t, a in tag_arrs.items()},
        "fields": {
            f: jax.device_put(a, shard_spec) for f, a in field_arrs.items()
        },
    }


def distributed_aggregate(
    mesh: Mesh,
    plan: DistPlan,
    chunks: dict,
    pred_codes: Optional[Mapping[str, int]] = None,
    hist_lo: float = 0.0,
    hist_span: float = 1.0,
):
    """Convenience wrapper: build (cached by caller) + run one step."""
    step = build_distributed_step(mesh, plan)
    codes = {
        t: jnp.int32((pred_codes or {}).get(t, -1)) for t in plan.eq_preds
    }
    return step(chunks, codes, jnp.float32(hist_lo), jnp.float32(hist_span))
