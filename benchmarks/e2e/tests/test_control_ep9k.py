"""The controls of `correct` for `ep9k.topn-6h`, at the cell's own size
(9,000 series x 720 minute buckets; the mix's own queries: 360 buckets,
3,240,000 points each), off the chip, in NumPy: the reference put in the
program's place with one guarantee of configs/ep9k.json broken must come
out as not correct, read by that guarantee's own `compared` number, and
the same reference keeping the guarantees must pass with every gap 0.

  bf16-sums  the INT sums added up from values rounded to bfloat16, the
             nearest precision below the f32 tile partials that the
             configuration's 1e-5 stands for: `value_gap_tol`
  left-out   the true top 10 with its last member replaced by the 11th:
             `top_gap_tol`, and no other number
"""

import itertools

import numpy as np
import pytest

import dataset
import traffic
from conftest import E2E
from selfcheck import load

SEEDS = [2700028011, 2700028022, 2147483659]
QUERIES = 4  # of each seed's stream


@pytest.fixture(scope="module", params=SEEDS)
def ep9k_answers(request):
    cfg, mix = load(E2E, "configs", "ep9k.json"), load(E2E, "traffic", "topn-6h.json")
    ds = dataset.Dataset(cfg, request.param)
    assert (ds.series, ds.buckets, ds.points) == (9000, 720, 6480000)
    qs = list(itertools.islice(traffic.stream(mix, ds, request.param, 0), QUERIES))
    return ds, [(q, ds.answer(q)) for q in qs]


def bf16(a):
    """float64 -> the nearest-even bfloat16 value, as float64."""
    bits = a.astype(np.float32).view(np.uint32)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & np.uint32(0xFFFF0000)
    return bits.view(np.float32).astype(np.float64)


def top_of(ds, q, values, members=range(10)):
    """`q` answered from per-bucket `values` [buckets, series]: the
    predicate, the sum per series, and of the series ranked by it those
    at `members` -> {group: (count, sum)} as `dataset.answer_of` gives."""
    b0, b1 = ds.bucket_range(q["lo"], q["hi"])
    sums = np.where(ds._mask(q["where"]), values[b0:b1].sum(axis=0), -np.inf)
    best = np.argsort(-sums, kind="stable")
    return {"svc_%06d" % best[m]: (b1 - b0, float(sums[best[m]])) for m in members}


def worst(ds, qa, values, members=range(10)):
    """(queries `check` refused, the widest reading of each number)."""
    refused, read = 0, {}
    for q, want in qa:
        got = top_of(ds, q, values, members)
        refused += dataset.check(q, got, want) is not None
        for k, v in dataset.gaps(q, got, want).items():
            read[k] = max(read.get(k, 0), v)
    return refused, read


def test_reference_in_full_precision_is_correct(ep9k_answers):
    ds, qa = ep9k_answers
    assert all(
        want["points"] == 3240000 and len(want["names"]) == 7875 and q["top"] == 10
        for q, want in qa
    )
    refused, read = worst(ds, qa, ds.hits)
    assert refused == 0 and not any(read.values()), read


def test_control_bf16_sums_is_not_correct(ep9k_answers):
    ds, qa = ep9k_answers
    refused, read = worst(ds, qa, bf16(ds.hits))
    print("bf16 sums:", read)
    assert refused == len(qa)
    assert read["value_gap_tol"] > 3 * dataset.LIMITS["value_gap_tol"]
    assert read["groups_gap"] == 0 and read["count_gap"] == 0


def test_control_left_out_member_is_not_correct(ep9k_answers):
    ds, qa = ep9k_answers
    for q, _ in qa:  # the 11th is below the 10th by more than the tolerance
        (_, tenth), (_, eleventh) = top_of(ds, q, ds.hits, members=(9, 10)).values()
        assert eleventh < tenth * (1 - 2 * dataset.SUM_RTOL)
    refused, read = worst(ds, qa, ds.hits, members=(*range(9), 10))
    print("left out:", read)
    assert refused == len(qa)
    assert read["top_gap_tol"] > dataset.LIMITS["top_gap_tol"]
    assert not any(v for k, v in read.items() if k != "top_gap_tol"), read


def test_warm_at_places_end_on_a_batch_boundary():
    """topn-6h's `warm_at` starts lie less than a bucket before an hour of
    the data.  A batch of 60 buckets is snapshotted by itself, so it is
    one part per shard, and the size-tiered merge joins hours 0 - 3 and
    4 - 7: the 360 buckets read then begin or end exactly where a part
    does, the starts that read one source per shard fewer than their
    neighbours."""
    cfg, mix = load(E2E, "configs", "ep9k.json"), load(E2E, "traffic", "topn-6h.json")
    ds = dataset.Dataset(dict(cfg, data=dict(cfg["data"], series=16)), 0)
    per_batch = cfg["data"]["batch_rows"] // cfg["data"]["series"]
    assert per_batch == 60 and cfg["data"]["snapshot_every_rows"] == cfg["data"]["batch_rows"]
    firsts = []
    for at in mix["warm_at"]:
        q = traffic.spec("topn", mix["panels"]["topn"], ds, np.random.default_rng(0), at)
        b0, b1 = ds.bucket_range(q["lo"], q["hi"])
        assert b0 % per_batch == 0 and b1 - b0 == 360, (at, b0, b1)
        firsts.append(b0 // per_batch)
    assert firsts == [2, 3, 4, 5, 6]
