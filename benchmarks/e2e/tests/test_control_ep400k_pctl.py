"""The controls of `correct` for `ep400k.pctl-7d`, at the cell's own size
(400,000 series x 8 daily buckets; the mix's own queries: p50 and p99 of
the 50,000 series of one drawn zone over 7 buckets, 2,800,000 points
scanned each), off the chip, in NumPy: the reference put in the
program's place with one guarantee of configs/ep400k-pctl.json broken must
come out as not correct, read by that guarantee's own `compared` number,
and the same reference keeping the guarantees must pass.

  histogram-512  the estimate a device histogram of 512 buckets over the
                 scanned range gives (the bucket of rank ceil(qN), linear
                 inside it): within the guarantee, `value_gap_tol` <= 1
  histogram-256  the same at 256 buckets, the nearest precision below the
                 512 the configuration states: `value_gap_tol` > 1
  dropped-group  the answer short of one group: `groups_gap` 1
"""

import itertools

import numpy as np
import pytest

import dataset
import traffic
from conftest import E2E
from selfcheck import load

SEEDS = [3300041011, 2147483659]
QUERIES = 2  # of each seed's stream
DAY_MS = 86400000


@pytest.fixture(scope="module", params=SEEDS)
def ep400k_pctl_answers(request):
    cfg, mix = load(E2E, "configs", "ep400k-pctl.json"), load(E2E, "traffic", "pctl-7d.json")
    ds = dataset.Dataset(cfg, request.param)
    assert (ds.series, ds.buckets, ds.points) == (400000, 8, 3200000)
    qs = list(itertools.islice(traffic.stream(mix, ds, request.param, 0), QUERIES))
    return ds, [(q, ds.answer(q)) for q in qs]


def histogram_answer(ds, q, buckets):
    """`q` answered as a device histogram of `buckets` over the scanned
    range would answer it: per series of the zone, the bucket of the
    value of rank ceil(qN), the rows below that bucket and in it, and the
    rank placed linearly inside it."""
    b0, b1 = ds.bucket_range(q["lo"], q["hi"])
    win = ds.value[b0:b1]  # [buckets in range, series]; the range is every series'
    lo, width = win.min(), (win.max() - win.min()) / buckets
    series = np.nonzero(ds._mask(q["where"]))[0]
    at = np.sort(np.clip(((win[:, series] - lo) / width).astype(np.int64), 0, buckets - 1), axis=0)
    n = at.shape[0]
    est = []
    for x in q["quantiles"]:
        rank = dataset._rank(x, n)
        hit = at[rank - 1]
        below, inside = (at < hit).sum(axis=0), (at == hit).sum(axis=0)
        est.append(lo + (hit + (rank - below) / inside) * width)
    est = np.stack(est, axis=1)
    return {"svc_%06d" % s: (n, [float(v) for v in est[i]]) for i, s in enumerate(series)}


def worst(answers, make):
    """(queries `check` refused, the widest reading of each number)."""
    refused, read = 0, {}
    for ds, q, want in answers:
        got = make(ds, q, want)
        refused += dataset.check(q, got, want) is not None
        for k, v in dataset.gaps(q, got, want).items():
            read[k] = max(read.get(k, 0), v)
    return refused, read


@pytest.fixture(scope="module")
def ep400k_pctl_queries(ep400k_pctl_answers):
    ds, qa = ep400k_pctl_answers
    return [(ds, q, want) for q, want in qa]


def test_reference_at_512_buckets_is_correct(ep400k_pctl_queries):
    assert all(
        want["points"] == 2800000 and len(want["names"]) == 50000 and q["limit"] == 400000
        and (want["count"] == 7).all()
        for _, q, want in ep400k_pctl_queries
    )
    refused, read = worst(
        ep400k_pctl_queries, lambda ds, q, want: histogram_answer(ds, q, dataset.HIST_BUCKETS)
    )
    print("512 buckets:", read)
    assert refused == 0 and not any(v > dataset.LIMITS[k] for k, v in read.items())
    assert read["value_gap_tol"] > 0.5  # the number has something to read


def test_control_256_buckets_is_not_correct(ep400k_pctl_queries):
    refused, read = worst(ep400k_pctl_queries, lambda ds, q, want: histogram_answer(ds, q, 256))
    print("256 buckets:", read)
    assert refused == len(ep400k_pctl_queries)
    assert read["value_gap_tol"] > 1.5 * dataset.LIMITS["value_gap_tol"]
    assert read["groups_gap"] == 0 and read["count_gap"] == 0


def test_control_dropped_group_is_not_correct(ep400k_pctl_queries):
    def short(ds, q, want):
        got = histogram_answer(ds, q, dataset.HIST_BUCKETS)
        got.pop(want["names"][len(want["names"]) // 2])
        return got

    refused, read = worst(ep400k_pctl_queries, short)
    assert refused == len(ep400k_pctl_queries)
    assert read["groups_gap"] == 1 and read["count_gap"] == 0


def test_every_start_the_mix_draws_reads_the_same_28_parts():
    """pctl-7d names no `warm_at`, as topn-7d: every start the mix can
    draw, first to last, covers days 1 - 7 whole and nothing of day 0 (a
    part holds one shard's rows of one day), so 7 days x 4 shards = 28
    parts whatever the start, and the one `warm_spread` query compiles
    the one shape there is.  The drawn zone changes a predicate value,
    not the program."""
    cfg, mix = load(E2E, "configs", "ep400k-pctl.json"), load(E2E, "traffic", "pctl-7d.json")
    assert "warm_at" not in mix and mix["warm_spread"] == 1
    data = cfg["data"]
    assert data["batch_rows"] == data["series"] and data["bucket_ms"] == DAY_MS
    ds = dataset.Dataset(dict(cfg, data=dict(data, series=16)), 0)
    panel = mix["panels"]["pctl"]
    assert panel["lo"] == {"draw_ms": [0, DAY_MS]} and panel["range_ms"] == 7 * DAY_MS
    assert panel["where"] == {"tag": "region", "op": "=", "value": "draw"}
    for at in (0.0, 0.5 / mix["warm_spread"], 0.25, 0.75, 1 - 1e-9):
        q = traffic.spec("pctl", panel, ds, np.random.default_rng(0), at)
        assert ds.bucket_range(q["lo"], q["hi"]) == (1, 8), at
    rng = np.random.default_rng(41)
    for _ in range(2000):
        q = traffic.spec("pctl", panel, ds, rng)
        assert ds.bucket_range(q["lo"], q["hi"]) == (1, 8), q
    assert 7 * cfg["schema"]["shards"] == 28
