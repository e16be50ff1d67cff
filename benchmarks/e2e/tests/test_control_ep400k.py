"""The controls of `correct` for `ep400k.topn-7d`, at the cell's own size
(400,000 series x 8 daily buckets; the mix's own queries: 7 buckets x
every series, 2,800,000 points each), off the chip, in NumPy: the
reference put in the program's place with one guarantee of
configs/ep400k.json broken must come out as not correct, read by that
guarantee's own `compared` number, and the same reference keeping the
guarantees must pass with every gap 0.

  bf16-sums  the INT sums added up from values rounded to bfloat16, the
             nearest precision below the f32 tile partials that the
             configuration's 1e-5 stands for: `value_gap_tol`
  left-out   the true top 10 with its last member replaced by the 11th:
             `top_gap_tol`, and no other number, unless the data itself
             gives the 11th the 10th's sum
  tie        the 11th given the 10th's sum: either of the two is a right
             10th and `top_gap_tol` reads 0 (the guarantee excepts ties;
             350,000 groups of 7 small integers tie at the cut unasked)
"""

import itertools

import numpy as np
import pytest

import dataset
import traffic
from conftest import E2E
from selfcheck import load

SEEDS = [3300033011, 2147483659]
QUERIES = 2  # of each seed's stream
DAY_MS = 86400000


@pytest.fixture(scope="module", params=SEEDS)
def ep400k_answers(request):
    cfg, mix = load(E2E, "configs", "ep400k.json"), load(E2E, "traffic", "topn-7d.json")
    ds = dataset.Dataset(cfg, request.param)
    assert (ds.series, ds.buckets, ds.points) == (400000, 8, 3200000)
    qs = list(itertools.islice(traffic.stream(mix, ds, request.param, 0), QUERIES))
    return ds, [(q, ds.answer(q)) for q in qs]


def bf16(a):
    """float64 -> the nearest-even bfloat16 value, as float64."""
    bits = a.astype(np.float32).view(np.uint32)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & np.uint32(0xFFFF0000)
    return bits.view(np.float32).astype(np.float64)


def ranked(ds, q, values):
    """`q` answered from per-bucket `values` [buckets, series]: the
    predicate and the sum per series -> (buckets in range, sums with the
    series the predicate drops at -inf, series by descending sum)."""
    b0, b1 = ds.bucket_range(q["lo"], q["hi"])
    sums = np.where(ds._mask(q["where"]), values[b0:b1].sum(axis=0), -np.inf)
    return b1 - b0, sums, np.argsort(-sums, kind="stable")


def top_of(ds, q, values, members=range(10)):
    """Of the series `ranked` orders those at `members` ->
    {group: (count, sum)} as `dataset.answer_of` gives."""
    nb, sums, best = ranked(ds, q, values)
    return {"svc_%06d" % best[m]: (nb, float(sums[best[m]])) for m in members}


def worst(ds, qa, values):
    """(queries `check` refused, the widest reading of each number)."""
    refused, read = 0, {}
    for q, want in qa:
        got = top_of(ds, q, values)
        refused += dataset.check(q, got, want) is not None
        for k, v in dataset.gaps(q, got, want).items():
            read[k] = max(read.get(k, 0), v)
    return refused, read


def test_reference_in_full_precision_is_correct(ep400k_answers):
    ds, qa = ep400k_answers
    assert all(
        want["points"] == 2800000 and len(want["names"]) == 350000 and q["top"] == 10
        for q, want in qa
    )
    refused, read = worst(ds, qa, ds.hits)
    assert refused == 0 and not any(read.values()), read


def test_control_bf16_sums_is_not_correct(ep400k_answers):
    ds, qa = ep400k_answers
    refused, read = worst(ds, qa, bf16(ds.hits))
    print("bf16 sums:", read)
    assert refused == len(qa)
    assert read["value_gap_tol"] > 3 * dataset.LIMITS["value_gap_tol"]
    assert read["groups_gap"] == 0 and read["count_gap"] == 0


def test_control_left_out_member_is_not_correct_unless_it_ties(ep400k_answers):
    ds, qa = ep400k_answers
    for q, want in qa:
        _, sums, best = ranked(ds, q, ds.hits)
        tenth, eleventh = sums[best[9]], sums[best[10]]
        left_out = top_of(ds, q, ds.hits, members=(*range(9), 10))
        read = dataset.gaps(q, left_out, want)
        print("left out:", tenth, eleventh, read)
        if eleventh == tenth:  # a tie the data holds by itself: a right answer
            assert dataset.check(q, left_out, want) is None and not any(read.values()), read
        else:  # integers: the 11th is below the 10th by >= 1, over 1e-5 of <= 6,993
            assert dataset.check(q, left_out, want) is not None
            assert read["top_gap_tol"] > dataset.LIMITS["top_gap_tol"]
            assert not any(v for k, v in read.items() if k != "top_gap_tol"), read
        # the other half: against a reference whose 11th has the 10th's sum,
        # the true top 10 and the nine with the 11th in the 10th's place are
        # both right, and every number reads 0
        eleventh_name = "svc_%06d" % best[10]
        tied = dict(want, metric=np.array(want["metric"], np.float64))
        tied["metric"][want["names"].index(eleventh_name)] = tenth
        with_11th = dict(left_out)
        with_11th[eleventh_name] = (left_out[eleventh_name][0], float(tenth))
        for got in (top_of(ds, q, ds.hits), with_11th):
            read = dataset.gaps(q, got, tied)
            assert dataset.check(q, got, tied) is None, read
            assert not any(read.values()), read


def test_every_start_the_mix_draws_reads_the_same_28_parts():
    """topn-7d names no `warm_at`: a message is one day (`batch_rows` =
    `series`), the flusher runs between messages and nothing is merged
    (configs/ep400k.json `assumed`), so a part holds one shard's rows of
    one day; every start the mix can draw, first to last, covers days
    1 - 7 whole and nothing of day 0: 7 days x 4 shards = 28 parts,
    whatever the start, and the one `warm_spread` query compiles the one
    shape there is."""
    cfg, mix = load(E2E, "configs", "ep400k.json"), load(E2E, "traffic", "topn-7d.json")
    assert "warm_at" not in mix and mix["warm_spread"] == 1
    data = cfg["data"]
    assert data["batch_rows"] == data["series"] and data["bucket_ms"] == DAY_MS
    ds = dataset.Dataset(dict(cfg, data=dict(data, series=16)), 0)
    panel = mix["panels"]["topn"]
    assert panel["lo"] == {"draw_ms": [0, DAY_MS]} and panel["range_ms"] == 7 * DAY_MS
    for at in (0.0, 0.5 / mix["warm_spread"], 0.25, 0.75, 1 - 1e-9):
        q = traffic.spec("topn", panel, ds, np.random.default_rng(0), at)
        assert ds.bucket_range(q["lo"], q["hi"]) == (1, 8), at
    # and the generator's own draws, at ms resolution: the edges included
    rng = np.random.default_rng(33)
    for _ in range(2000):
        q = traffic.spec("topn", panel, ds, rng)
        assert ds.bucket_range(q["lo"], q["hi"]) == (1, 8), q
    days, shards = 7, cfg["schema"]["shards"]
    assert days * shards == 28
