"""Pallas TPU kernels for the scan hot loop.

The XLA path (ops.group_reduce) already fuses mask+reduce well; these
hand-written kernels exist for the cases where explicit control of VMEM
tiling wins: streaming HBM-resident row tiles through MXU one-hot
contractions computing the filtered per-group sums/count for ALL fields
at once without materializing the one-hot operand in HBM.  Grid =
(group tiles, row tiles), rows innermost: for each group tile the full
row stream is revisited (so G > GTILE costs one extra HBM pass per
additional group tile — the picker bounds this), and the accumulators
live in output blocks indexed by the group tile only (revisited by
every row step — TPU grids execute sequentially, so read-modify-write
accumulation across steps is sound).

Precision contract (shared with ops.group_reduce): each row tile's
partial is a full-precision f32 MXU contraction over TILE=2048 rows
(Precision.HIGHEST — the MXU's default single bf16 pass breaks the
contract, measured on the v5e); tile partials are
combined with Kahan-compensated f32 accumulation across grid steps, so
the cross-tile error stays O(eps) independent of row count (instead of
O(n_tiles * eps) for naive f32 accumulation).

Runs in interpret mode on CPU for correctness tests; compiled mode on
TPU (pallas_guide.md patterns: grid accumulation, @pl.when init).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE = 2048


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _out_struct(shape, dtype, *inputs) -> jax.ShapeDtypeStruct:
    """Output type of a pallas_call over ``inputs``: inside
    ``jax.shard_map`` the result varies over every manual mesh axis any
    input varies over, and shard_map's type check needs that declared
    (outside shard_map the set is empty)."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in inputs))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


# Group-dimension tile: bounds the [GTILE, TILE] one-hot operand
# (1024x2048 f32 = 8 MiB) plus the [F, GTILE] accumulator blocks in
# VMEM.  The v5e's scoped-VMEM default is 16 MiB: a 2048-wide tile's
# 16 MiB one-hot alone exceeds it (RESOURCE_EXHAUSTED at compile, seen
# on the chip at G >= 2048), 1024 compiles with up to 3 fields; shrink
# GTILE before growing anything else here.
GTILE = 1024


def _fused_kernel(
    codes_ref,
    pred_ref,
    vals_ref,
    valid_ref,
    count_ref,
    sum_ref,
    ccomp_ref,
    scomp_ref,
):
    # Grid is (group tiles, row tiles) with the row dimension innermost:
    # for a fixed group tile j the kernel streams every row tile i,
    # accumulating into the same output blocks (TPU grids run
    # sequentially, so read-modify-write across i is sound).
    j = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        count_ref[:] = jnp.zeros_like(count_ref)
        sum_ref[:] = jnp.zeros_like(sum_ref)
        ccomp_ref[:] = jnp.zeros_like(ccomp_ref)
        scomp_ref[:] = jnp.zeros_like(scomp_ref)

    codes = codes_ref[:]  # [1, TILE] int32 group codes
    pred = pred_ref[:]  # [1, TILE] int32 0/1 predicate flags
    vals = vals_ref[:]  # [F, TILE] f32
    valid = valid_ref[:]  # [1, TILE] f32 (1.0 valid)

    # predicate arrives as a per-row 0/1 flag; multiply is the AND
    mask = valid * pred.astype(jnp.float32)  # [1, TILE]

    # Mosaic cannot lower 1-D integer indexing (it becomes an unsupported
    # gather), so the one-hot is built transposed — [GTILE, TILE] with
    # row r equal to group j*GTILE + r — and contracted along TILE via
    # dot_general with a transposed RHS, which maps straight onto the MXU.
    g = count_ref.shape[1]
    gids = j * g + jax.lax.broadcasted_iota(
        jnp.int32, (g, codes.shape[1]), 0
    )
    onehot_t = (gids == codes).astype(jnp.float32)  # [GTILE, TILE]
    dn = (((1,), (1,)), ((), ()))
    # Mosaic's default f32 contraction is ONE bf16 pass.  Count operands
    # are 0/1 (exact in bf16), so the default is exact there; field
    # values are not, and a single pass rounds each to 8 mantissa bits
    # (~1e-3 relative on the chip) — the sums contract at full f32
    # precision to hold the 1e-5 contract.
    cnt_p = jax.lax.dot_general(
        mask, onehot_t, dn, preferred_element_type=jnp.float32
    )  # [1, GTILE]
    sum_p = jax.lax.dot_general(
        vals * mask,
        onehot_t,
        dn,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # [F, GTILE] — one contraction, all fields

    # Kahan-compensated add of this tile's partials into the accumulators.
    y = cnt_p - ccomp_ref[:]
    t = count_ref[:] + y
    ccomp_ref[:] = (t - count_ref[:]) - y
    count_ref[:] = t

    y = sum_p - scomp_ref[:]
    t = sum_ref[:] + y
    scomp_ref[:] = (t - sum_ref[:]) - y
    sum_ref[:] = t


@functools.partial(jax.jit, static_argnames=("num_groups", "interpret"))
def fused_group_multi(
    codes: jax.Array,
    pred_mask: jax.Array,
    values: jax.Array,
    valid: jax.Array,
    *,
    num_groups: int,
    interpret: bool = False,
):
    """Filtered per-group (count, per-field sums) in one pass.

    codes: int32 [N] group codes; pred_mask: bool [N] predicate;
    values: f32 [F, N] stacked field columns; valid: bool [N].
    N must be a TILE multiple. -> (count f32 [G], sums f32 [F, G])
    """
    n = codes.shape[0]
    assert n % TILE == 0, f"N={n} must be a multiple of {TILE}"
    nf = values.shape[0]
    if n == 0:
        # a zero-size grid dimension never invokes the kernel, so the
        # @pl.when init would never run and the outputs would be
        # whatever the allocator held — return real zeros instead
        return (
            jnp.zeros(num_groups, jnp.float32),
            jnp.zeros((nf, num_groups), jnp.float32),
        )
    if nf == 0:
        # zero-dim blocks don't lower; run a dummy field and drop it
        count, _ = fused_group_multi(
            codes,
            pred_mask,
            jnp.zeros((1, n), jnp.float32),
            valid,
            num_groups=num_groups,
            interpret=interpret,
        )
        return count, jnp.zeros((0, num_groups), jnp.float32)
    # Pad the group axis to a GTILE multiple; padded groups match no row
    # code (codes are < num_groups) and are sliced off below.
    gt = min(GTILE, _round_up(num_groups, 128))
    gpad = _round_up(num_groups, gt)
    grid = (gpad // gt, n // TILE)

    codes2 = codes.reshape(1, n)
    pred2 = pred_mask.astype(jnp.int32).reshape(1, n)
    valid2 = valid.astype(jnp.float32).reshape(1, n)

    row_spec = pl.BlockSpec((1, TILE), lambda j, i: (0, i))
    val_spec = pl.BlockSpec((nf, TILE), lambda j, i: (0, i))
    cacc_spec = pl.BlockSpec((1, gt), lambda j, i: (0, j))
    sacc_spec = pl.BlockSpec((nf, gt), lambda j, i: (0, j))

    operands = (codes2, pred2, values, valid2)
    count, total, ccomp, scomp = pl.pallas_call(
        _fused_kernel,
        grid=grid,
        in_specs=[row_spec, row_spec, val_spec, row_spec],
        out_specs=(cacc_spec, sacc_spec, cacc_spec, sacc_spec),
        out_shape=tuple(
            _out_struct((rows, gpad), jnp.float32, *operands)
            for rows in (1, nf, 1, nf)
        ),
        interpret=interpret,
        name="bydb_group_multi",
    )(*operands)
    # Fold the residual compensation back in (classic Kahan final step;
    # the compensation holds the negated running error).
    return (
        (count - ccomp)[0, :num_groups],
        (total - scomp)[:, :num_groups],
    )


@functools.partial(jax.jit, static_argnames=("num_groups", "interpret"))
def fused_group_sum(
    codes: jax.Array,
    pred_mask: jax.Array,
    values: jax.Array,
    valid: jax.Array,
    *,
    num_groups: int,
    interpret: bool = False,
):
    """Single-field convenience wrapper around fused_group_multi.

    codes: int32 [N]; pred_mask: bool [N]; values: f32 [N]; valid: bool
    [N]. -> (count f32 [G], sum f32 [G])
    """
    count, sums = fused_group_multi(
        codes,
        pred_mask,
        values.reshape(1, -1),
        valid,
        num_groups=num_groups,
        interpret=interpret,
    )
    return count, sums[0]
