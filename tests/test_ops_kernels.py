"""Golden tests: device kernels vs NumPy oracles (SURVEY.md §7 step 1)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from banyandb_tpu import ops


RNG = np.random.default_rng(7)


def test_delta_decode_matches_numpy():
    # Same layout the on-disk encoder produces: first + np.diff payload.
    vals = RNG.integers(-1000, 1000, size=257).cumsum().astype(np.int32)
    deltas = np.diff(vals).astype(np.int32)
    out = ops.delta_decode(jnp.int32(vals[0]), jnp.asarray(deltas))
    assert out.shape[-1] == len(vals)
    np.testing.assert_array_equal(np.asarray(out), vals)


def test_dod_decode_matches_numpy():
    # Regular timestamps with jitter: the delta-of-delta sweet spot.
    ts = (np.arange(500) * 1000 + RNG.integers(-3, 4, size=500)).astype(np.int32)
    deltas = np.diff(ts)
    dods = np.diff(deltas, prepend=deltas[0]).astype(np.int32)
    dods[0] = 0
    out = ops.dod_decode(jnp.int32(ts[0]), jnp.int32(deltas[0]), jnp.asarray(dods))
    assert out.shape[-1] == len(ts)
    np.testing.assert_array_equal(np.asarray(out), ts)


def test_percentile_q0_q1_edges():
    vals = np.full(100, 700.0, dtype=np.float32)
    key = jnp.zeros(100, dtype=jnp.int32)
    out = ops.group_percentile_histogram(
        key, jnp.ones(100, bool), jnp.asarray(vals), 1, [0.0, 1.0],
        lo=0.0, hi=1000.0, num_buckets=1000,
    )
    np.testing.assert_allclose(np.asarray(out)[0], [700.0, 700.0], atol=2.0)


def test_column_batch_epoch_out_of_range():
    with pytest.raises(ValueError, match="int32"):
        from banyandb_tpu.ops.blocks import ColumnBatch
        ColumnBatch.build(
            ts_millis=np.asarray([2**40], dtype=np.int64),
            epoch_millis=0,
            series_ordinal=np.asarray([0]),
            fields={},
            tag_codes={},
        )


def test_mixed_radix_overflow_raises():
    c = jnp.zeros(4, dtype=jnp.int32)
    with pytest.raises(ValueError, match="overflows"):
        ops.mixed_radix_key([c, c], [100_000, 100_000])


def test_dict_gather():
    dictionary = jnp.asarray([10.0, 20.0, 30.0], dtype=jnp.float32)
    codes = jnp.asarray([2, 0, 1, 1], dtype=jnp.int32)
    out = ops.dict_gather(dictionary, codes)
    np.testing.assert_array_equal(np.asarray(out), [30.0, 10.0, 20.0, 20.0])


def test_masks():
    col = jnp.asarray([1, 2, 3, 4, 5], dtype=jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(ops.cmp_mask(col, "ge", 3)), [False, False, True, True, True]
    )
    np.testing.assert_array_equal(
        np.asarray(ops.in_set_mask(col, [2, 5])),
        [False, True, False, False, True],
    )
    ts = jnp.asarray([0, 10, 20, 30], dtype=jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(ops.time_range_mask(ts, 10, 30)), [False, True, True, False]
    )
    m1 = ops.cmp_mask(col, "gt", 1)
    m2 = ops.cmp_mask(col, "lt", 5)
    np.testing.assert_array_equal(
        np.asarray(ops.mask_and(m1, m2)), [False, True, True, True, False]
    )
    np.testing.assert_array_equal(
        np.asarray(ops.mask_not(m1)), [True, False, False, False, False]
    )


@pytest.mark.parametrize("method", ["scatter", "matmul", "sort"])
def test_group_reduce_matches_numpy(method):
    n, g = 1024, 12
    key = RNG.integers(0, g, size=n).astype(np.int32)
    valid = RNG.random(n) > 0.2
    vals = RNG.normal(size=n).astype(np.float32) * 100

    res = ops.group_reduce(
        jnp.asarray(key),
        jnp.asarray(valid),
        {"v": jnp.asarray(vals)},
        g,
        method=method,
    )
    for gi in range(g):
        sel = (key == gi) & valid
        np.testing.assert_allclose(np.asarray(res.count)[gi], sel.sum())
        np.testing.assert_allclose(
            np.asarray(res.sums["v"])[gi], vals[sel].sum(), rtol=1e-5, atol=1e-3
        )
        if sel.any():
            np.testing.assert_allclose(np.asarray(res.mins["v"])[gi], vals[sel].min())
            np.testing.assert_allclose(np.asarray(res.maxs["v"])[gi], vals[sel].max())
            np.testing.assert_allclose(
                np.asarray(res.mean("v"))[gi], vals[sel].mean(), rtol=1e-3, atol=1e-5
            )


def test_group_reduce_sort_multi_tile():
    """n > the 65,536-row span bound with a non-divisible remainder:
    exercises the scan carry and pad path of the sorted runs (a
    single-tile case would not)."""
    n, g = 150_000, 7
    key = RNG.integers(0, g, size=n).astype(np.int32)
    valid = RNG.random(n) > 0.1
    vals = RNG.normal(size=n).astype(np.float32)
    res = ops.group_reduce(
        jnp.asarray(key), jnp.asarray(valid), {"v": jnp.asarray(vals)},
        g, method="sort",
    )
    for gi in range(g):
        sel = (key == gi) & valid
        assert float(res.count[gi]) == sel.sum()
        np.testing.assert_allclose(
            float(res.sums["v"][gi]), vals[sel].sum(), rtol=1e-4, atol=1e-2
        )


def test_group_reduce_unknown_method_raises():
    with pytest.raises(ValueError, match="unknown group_reduce method"):
        ops.group_reduce(
            jnp.zeros(8, jnp.int32), jnp.ones(8, bool), {}, 2, method="typo"
        )


def test_group_reduce_empty_groups_marked():
    key = jnp.asarray([0, 0, 2], dtype=jnp.int32)
    valid = jnp.asarray([True, True, True])
    res = ops.group_reduce(key, valid, {}, 4, want_minmax=False)
    np.testing.assert_array_equal(np.asarray(res.nonempty), [True, False, True, False])


def test_mixed_radix_key_roundtrip():
    c0 = jnp.asarray([0, 1, 2], dtype=jnp.int32)
    c1 = jnp.asarray([3, 0, 4], dtype=jnp.int32)
    key, total = ops.mixed_radix_key([c0, c1], [3, 5])
    assert total == 15
    codes = np.unravel_index(np.asarray(key), (3, 5))
    np.testing.assert_array_equal(codes[0], [0, 1, 2])
    np.testing.assert_array_equal(codes[1], [3, 0, 4])


def test_topk_groups():
    metric = jnp.asarray([5.0, 1.0, 9.0, 3.0], dtype=jnp.float32)
    nonempty = jnp.asarray([True, True, True, False])
    vals, idx = ops.topk_groups(metric, nonempty, 2)
    np.testing.assert_array_equal(np.asarray(idx), [2, 0])
    np.testing.assert_array_equal(np.asarray(vals), [9.0, 5.0])
    vals, idx = ops.topk_groups(metric, nonempty, 2, descending=False)
    np.testing.assert_array_equal(np.asarray(idx), [1, 0])
    np.testing.assert_allclose(np.asarray(vals), [1.0, 5.0])


def test_percentile_histogram_vs_numpy():
    n, g = 4096, 4
    key = RNG.integers(0, g, size=n).astype(np.int32)
    valid = np.ones(n, dtype=bool)
    vals = RNG.uniform(0, 1000, size=n).astype(np.float32)
    qs = [0.5, 0.95, 0.99]
    out = ops.group_percentile_histogram(
        jnp.asarray(key),
        jnp.asarray(valid),
        jnp.asarray(vals),
        g,
        qs,
        lo=0.0,
        hi=1000.0,
        num_buckets=1000,
    )
    for gi in range(g):
        expect = np.quantile(vals[key == gi], qs)
        np.testing.assert_allclose(
            np.asarray(out)[gi], expect, atol=3.0  # within ~3 bucket widths
        )


def test_latest_by_version():
    series = jnp.asarray([1, 1, 2, 1, 2], dtype=jnp.int32)
    ts = jnp.asarray([10, 10, 10, 20, 10], dtype=jnp.int32)
    version = jnp.asarray([1, 3, 5, 1, 2], dtype=jnp.int32)
    valid = jnp.asarray([True, True, True, True, True])
    keep = ops.latest_by_version(series, ts, version, valid)
    # (1,10) -> row1 (v3); (2,10) -> row2 (v5); (1,20) -> row3
    np.testing.assert_array_equal(np.asarray(keep), [False, True, True, True, False])


def test_latest_by_version_respects_valid():
    series = jnp.asarray([1, 1], dtype=jnp.int32)
    ts = jnp.asarray([10, 10], dtype=jnp.int32)
    version = jnp.asarray([9, 1], dtype=jnp.int32)
    valid = jnp.asarray([False, True])
    keep = ops.latest_by_version(series, ts, version, valid)
    np.testing.assert_array_equal(np.asarray(keep), [False, True])


def test_column_batch_build_and_padding():
    from banyandb_tpu.ops.blocks import ColumnBatch, pad_rows_bucket

    assert pad_rows_bucket(1) == 64
    assert pad_rows_bucket(64) == 64
    assert pad_rows_bucket(65) == 128
    assert pad_rows_bucket(8192) == 8192

    batch = ColumnBatch.build(
        ts_millis=np.asarray([1000, 2000, 3000], dtype=np.int64),
        epoch_millis=1000,
        series_ordinal=np.asarray([0, 1, 0]),
        fields={"value": np.asarray([1.5, 2.5, 3.5])},
        tag_codes={"svc": np.asarray([0, 1, 1])},
        version=np.asarray([1, 1, 2]),
    )
    assert batch.nrows == 64
    assert bool(batch.valid[2]) and not bool(batch.valid[3])
    np.testing.assert_array_equal(np.asarray(batch.ts[:3]), [0, 1000, 2000])
    # Batches are pytrees: jit works over them directly.
    summed = jax.jit(lambda b: jnp.sum(jnp.where(b.valid, b.fields["value"], 0.0)))(batch)
    np.testing.assert_allclose(float(summed), 7.5)
