"""Group-by + aggregation kernels.

The reference aggregates with Go hash maps over decoded rows
(pkg/query/aggregation, pkg/query/vectorized/measure/groupby_agg.go).  On
TPU there is no hash table: tags are dictionary codes, so a group key is a
*mixed-radix* int32 composed from the code columns, bounded by the product
of dictionary sizes.  Aggregation is then a dense segment reduction:

- ``scatter``: jax.ops.segment_sum/min/max (XLA scatter).
- ``matmul``: one-hot(keys) @ values on the MXU in one shot — for modest
  group counts (<= ~4096) and row counts that fit a single operand.
- ``pallas``: the hand-tiled Pallas kernel (ops.pallas_kernels) for
  count/sums; min/max still ride XLA scatter.
- ``sort``: segment-sort grouping — stable sort by key, then the same
  bounded-span scatter reduction over now-contiguous group runs.  The
  high-radix regime of the hash-vs-sort crossover (arXiv 2411.13245).

All produce identical results; ``method="auto"`` resolves through
``select_group_method`` per shape and backend (sort above
SORT_GROUPS_THRESHOLD groups on any backend; below it TPU: pallas for
bounded group counts, else scatter; off-TPU: matmul for small operands,
else scatter).

Precision contract (tested by tests/test_precision.py): per-group sums
accumulate in f32 *within* a bounded row tile (<= 65536 rows for scatter,
2048 for pallas); tile partials combine across
tiles with Kahan-compensated f32, so the cross-tile error is O(eps)
independent of total row count. The one-shot ``matmul`` path is only
selected for operands <= 2^25 elements (<= ~32k rows at G=1024), where a
single f32 MXU contraction stays within ~K*eps/2 of exact. Callers
merging partials across kernel invocations (measure_exec, the cluster
combine plane) accumulate in f64 on the host. Counts are integer-valued
and exact to 2^24 per tile — far above any tile bound here.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp

def mixed_radix_key(
    columns: Sequence[jax.Array], radices: Sequence[int]
) -> tuple[jax.Array, int]:
    """Compose dictionary-code columns into a single dense group key.

    key = ((c0*r1 + c1)*r2 + c2)... ; group count = prod(radices).
    Host code recovers per-tag codes with np.unravel_index(key, radices).
    """
    assert len(columns) == len(radices) and columns
    total = 1
    for r in radices:
        total *= int(r)
    if total >= 2**31:
        # int32 keys would wrap on device and silently merge groups; callers
        # must pre-reduce cardinality (hash-bucket tags) before grouping.
        raise ValueError(
            f"group cardinality {total} overflows int32 keys; "
            "bucket the tag dictionaries first"
        )
    key = columns[0].astype(jnp.int32)
    for c, r in zip(columns[1:], radices[1:]):
        key = key * jnp.int32(r) + c.astype(jnp.int32)
    return key, total


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GroupReduceResult:
    """Per-group aggregates; arrays have leading dim num_groups."""

    count: jax.Array  # f32 [G] — valid-row count per group
    sums: Mapping[str, jax.Array]  # f32 [G] per field
    mins: Mapping[str, jax.Array]  # f32 [G] per field (+inf when empty)
    maxs: Mapping[str, jax.Array]  # f32 [G] per field (-inf when empty)

    def mean(self, field: str) -> jax.Array:
        return self.sums[field] / jnp.maximum(self.count, 1.0)

    @property
    def nonempty(self) -> jax.Array:
        return self.count > 0


def _kahan_add(s: jax.Array, c: jax.Array, x: jax.Array):
    """One compensated accumulation step; true sum ~= s - c."""
    y = x - c
    t = s + y
    return t, (t - s) - y


def _kahan_tiled_reduce(
    safe_key: jax.Array,
    validf: jax.Array,
    masked_fields: Mapping[str, jax.Array],
    num_groups: int,
    tile: int,
    partial_fn,
):
    """Shared scaffold for bounded-span accumulation (precision contract):
    pad rows to a tile multiple, scan tiles, Kahan-combine the per-tile
    [G+1] partials produced by ``partial_fn(key_t, valid_t, fields_t)``
    (ordered [count, field_0, ...]; fields arrive pre-masked by validf).
    -> (count [G], sums {name: [G]})."""
    names = sorted(masked_fields.keys())
    n = safe_key.shape[-1]
    pad = (-n) % tile
    kp = jnp.pad(safe_key, (0, pad), constant_values=num_groups)
    vp = jnp.pad(validf, (0, pad))
    fps = {nm: jnp.pad(masked_fields[nm], (0, pad)) for nm in names}

    def step(carry, xs):
        parts = partial_fn(*xs)
        return (
            tuple(_kahan_add(s, c, p) for (s, c), p in zip(carry, parts)),
            None,
        )

    zero = jnp.zeros(num_groups + 1, jnp.float32)
    init = tuple((zero, zero) for _ in range(1 + len(names)))
    tiles = (
        kp.reshape(-1, tile),
        vp.reshape(-1, tile),
        jnp.stack([fps[nm].reshape(-1, tile) for nm in names], axis=1)
        if names
        else jnp.zeros((kp.shape[0] // tile, 0, tile), jnp.float32),
    )
    out, _ = jax.lax.scan(step, init, tiles)
    count = (out[0][0] - out[0][1])[:num_groups]
    sums = {
        nm: (out[1 + i][0] - out[1 + i][1])[:num_groups]
        for i, nm in enumerate(names)
    }
    return count, sums


# High-radix crossover for hash- vs sort-based grouping.  The empirical
# study arXiv 2411.13245 finds scatter-style hash grouping wins while the
# per-group accumulator table stays cache/VMEM-resident (low-radix
# dictionary keys) and segment-sort grouping wins once the table spills
# (high-radix or unknown-cardinality keys): sorted runs stream memory
# sequentially instead of scattering over a huge [G] table.
SORT_GROUPS_THRESHOLD = 1 << 16


def select_group_method(nrows: int, num_groups: int) -> str:
    """Per-signature group-by strategy (the ``method="auto"`` policy).

    Every plan program resolves through this ONE function from the
    signature's (nrows, num_groups), so two runs of one signature can
    never pair different reduction orders — and the ``sort`` path is
    stable-sorted, keeping per-group accumulation in row order
    (bit-identical to ``scatter``).

    Each TPU branch has a cell of the benchmark on its side, and the
    ``reduce`` span reads them on one scale, gathered rows over the wait
    for the device (``rows_per_ms``; ``reduce_rows_per_ms`` in
    PERF_LEDGER.jsonl, builder's chip runs of PR 28 in PERF.md section 6
    until the ledger has the line): ``pallas`` at G = 1,000 about
    10,500 rows/ms (``svc1k.pctl-6h``), ``sort`` at G = 100,000 about
    7,000 (``topn100k.topn-24h``), ``scatter`` at G = 9,000 about 6,600
    (``ep9k.topn-6h``).  Those waits hold the whole plan program, scan-
    order tracking and the device decode included, so they rank the
    cells, not yet the methods: WHERE the crossovers lie is still not
    measured (no cell runs two methods at one G; ROADMAP S4(b)).  What
    the routing encodes is what each method needs: TPU takes the Pallas
    kernel for bounded group counts (8 group tiles at GTILE=1024: each
    extra tile re-streams the whole input from HBM) and XLA scatter
    above that; off-TPU pallas only interprets, so one-hot matmul serves
    small operands and scatter the rest.  Above SORT_GROUPS_THRESHOLD
    groups (either backend) segment-sort grouping takes over per the
    2411.13245 crossover.
    """
    if num_groups > SORT_GROUPS_THRESHOLD:
        return "sort"
    if jax.default_backend() == "tpu" and num_groups <= 8192:
        return "pallas"
    if num_groups <= 4096 and nrows * (num_groups + 1) <= 2**25:
        return "matmul"
    return "scatter"


def resolve_group_method(method: str, nrows: int, num_groups: int) -> str:
    """The method that runs for a plan asking for ``method``: ``auto``
    through ``select_group_method``, any other name as given.  What
    ``group_reduce`` dispatches on and what the ``reduce`` span and
    ``group_reduce_rows{method}`` report, so the two cannot differ."""
    if method == "auto":
        return select_group_method(nrows, num_groups)
    return method


def _scatter_reduce(
    safe_key: jax.Array,
    validf: jax.Array,
    masked_fields: Mapping[str, jax.Array],
    num_groups: int,
):
    """count/sums via XLA scatter, Kahan-tiled beyond the span bound.

    Shared by the hash (``scatter``) and segment-sort (``sort``) paths:
    fields arrive pre-masked (col * validf), rows beyond the span bound
    combine with Kahan-compensated f32 (precision contract above).
    """
    seg = jax.ops.segment_sum
    CHUNK = 65536
    if safe_key.shape[-1] <= CHUNK:
        count = seg(validf, safe_key, num_segments=num_groups + 1)[:num_groups]
        sums = {
            name: seg(col, safe_key, num_segments=num_groups + 1)[:num_groups]
            for name, col in masked_fields.items()
        }
        return count, sums

    def sc_partial(k_t, v_t, f_t):
        return [seg(v_t, k_t, num_segments=num_groups + 1)] + [
            seg(f_t[i], k_t, num_segments=num_groups + 1)
            for i in range(f_t.shape[0])
        ]

    return _kahan_tiled_reduce(
        safe_key, validf, masked_fields, num_groups, CHUNK, sc_partial
    )


def group_reduce(
    key: jax.Array,
    valid: jax.Array,
    fields: Mapping[str, jax.Array],
    num_groups: int,
    *,
    want_minmax: bool = True,
    method: str = "auto",
) -> GroupReduceResult:
    """Segment-reduce rows into per-group count/sum/min/max.

    Invalid rows are routed to a spill group (index num_groups) and dropped,
    so padding never pollutes real groups.
    """
    method = resolve_group_method(method, key.shape[-1], num_groups)
    # the device trace names the method that ran, not the one asked for
    with jax.named_scope(f"bydb.group_reduce.{method}"):
        return _group_reduce(
            key, valid, fields, num_groups, want_minmax, method
        )


def _group_reduce(
    key: jax.Array,
    valid: jax.Array,
    fields: Mapping[str, jax.Array],
    num_groups: int,
    want_minmax: bool,
    method: str,
) -> GroupReduceResult:
    validf = valid.astype(jnp.float32)
    safe_key = jnp.where(valid, key, jnp.int32(num_groups))

    if method == "matmul":
        # [N, G+1] one-hot; MXU contraction gives counts and sums in one
        # fused pass per field.  f32 accumulate keeps int-valued fields exact
        # up to 2^24 per group partial (parts are merged in f64 on host).
        groups = jax.lax.broadcasted_iota(jnp.int32, (num_groups + 1,), 0)
        onehot = (safe_key[:, None] == groups[None, :]).astype(jnp.float32)
        count = (validf @ onehot)[:num_groups]
        sums = {
            name: ((col * validf) @ onehot)[:num_groups]
            for name, col in fields.items()
        }
    elif method == "scatter":
        count, sums = _scatter_reduce(
            safe_key,
            validf,
            {nm: col * validf for nm, col in fields.items()},
            num_groups,
        )
    elif method == "sort":
        # Segment-sort grouping (the 2411.13245 high-radix regime): a
        # STABLE sort by group key makes every group a contiguous run,
        # so the reduction streams memory sequentially instead of
        # scattering over a [G] table that no longer fits close storage.
        # Stability keeps per-group accumulation in row order — within
        # the span bound the result is bit-identical to the hash path.
        order = jnp.argsort(safe_key, stable=True)
        count, sums = _scatter_reduce(
            safe_key[order],
            validf[order],
            {nm: (col * validf)[order] for nm, col in fields.items()},
            num_groups,
        )
    elif method == "pallas":
        # Hand-tiled kernel: one pass computes count + ALL field sums
        # (compiled on TPU, interpret elsewhere); min/max below still
        # ride XLA scatter.
        from banyandb_tpu.ops import pallas_kernels

        interpret = jax.default_backend() != "tpu"
        n = safe_key.shape[-1]
        pad = (-n) % pallas_kernels.TILE
        kp = jnp.pad(safe_key, (0, pad), constant_values=num_groups)
        vp = jnp.pad(valid, (0, pad))
        names = sorted(fields.keys())
        vals = (
            jnp.stack(
                [
                    jnp.pad(fields[nm].astype(jnp.float32), (0, pad))
                    for nm in names
                ]
            )
            if names
            else jnp.zeros((0, kp.shape[0]), jnp.float32)
        )
        count, sums_arr = pallas_kernels.fused_group_multi(
            kp,
            jnp.ones_like(kp, dtype=bool),
            vals,
            vp,
            num_groups=num_groups,
            interpret=interpret,
        )
        sums = {nm: sums_arr[i] for i, nm in enumerate(names)}
    else:
        raise ValueError(f"unknown group_reduce method {method!r}")

    mins: dict[str, jax.Array] = {}
    maxs: dict[str, jax.Array] = {}
    if want_minmax:
        # Invalid rows are already routed to the sliced-off spill segment by
        # safe_key, so no value masking is needed here.
        for name, col in fields.items():
            mins[name] = jax.ops.segment_min(
                col, safe_key, num_segments=num_groups + 1
            )[:num_groups]
            maxs[name] = jax.ops.segment_max(
                col, safe_key, num_segments=num_groups + 1
            )[:num_groups]

    return GroupReduceResult(count=count, sums=sums, mins=mins, maxs=maxs)
