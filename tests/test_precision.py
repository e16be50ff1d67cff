"""Precision contract tests (VERDICT r1 weak #6).

Per-group f32 sums over multi-Mi-row chunks must stay within ~1e-5
relative of exact f64 — guaranteed by bounded-span f32 tile partials
combined with Kahan-compensated accumulation (ops/groupby.py docstring).
The reference aggregates in exact int64/float64 Go arithmetic
(pkg/query/aggregation); this is our device-side equivalent bound.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from banyandb_tpu.ops.groupby import group_reduce

G = 64


def _mk(n, seed=11):
    rng = np.random.default_rng(seed)
    key = rng.integers(0, G, n).astype(np.int32)
    # skewed positive values with rare large outliers: the adversarial
    # case for naive f32 running sums
    vals = rng.gamma(2.0, 40.0, n).astype(np.float32)
    vals[rng.random(n) < 1e-4] = 1e6
    return key, vals


def _exact(key, vals):
    return (
        np.bincount(key, minlength=G).astype(np.float64),
        np.bincount(key, weights=vals.astype(np.float64), minlength=G),
    )


@pytest.mark.parametrize(
    "method,n",
    [
        ("scatter", 4 << 20),  # the bench's mega-chunk shape
        ("sort", 1 << 20),
        ("pallas", 1 << 15),  # interpret mode on CPU: keep it small
    ],
)
def test_group_sum_precision(method, n):
    key, vals = _mk(n)
    res = group_reduce(
        jnp.asarray(key),
        jnp.asarray(np.ones(n, bool)),
        {"v": jnp.asarray(vals)},
        G,
        want_minmax=False,
        method=method,
    )
    exact_count, exact_sum = _exact(key, vals)
    np.testing.assert_array_equal(
        np.asarray(res.count, dtype=np.float64), exact_count
    )
    np.testing.assert_allclose(
        np.asarray(res.sums["v"], dtype=np.float64), exact_sum, rtol=1e-5
    )


def test_methods_agree():
    n = 1 << 17
    key, vals = _mk(n, seed=5)
    outs = {}
    for m in ("scatter", "sort", "pallas"):
        r = group_reduce(
            jnp.asarray(key),
            jnp.asarray(np.ones(n, bool)),
            {"v": jnp.asarray(vals)},
            G,
            want_minmax=False,
            method=m,
        )
        outs[m] = np.asarray(r.sums["v"], dtype=np.float64)
    np.testing.assert_allclose(outs["scatter"], outs["sort"], rtol=1e-5)
    np.testing.assert_allclose(outs["scatter"], outs["pallas"], rtol=1e-5)
