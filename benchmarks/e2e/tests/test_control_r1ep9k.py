"""The controls of `correct` for `r1ep9k.topn-15m-c50`, at the cell's own
size (9,000 series x 360 minute buckets; the mix's own queries, from the
streams of several of its fifty clients: 15 buckets, 135,000 points
each), off the chip, in NumPy: the reference put in the program's place
with one guarantee of configs/r1ep9k.json broken must come out as not
correct, read by that guarantee's own `compared` number, and the same
reference keeping the guarantees must pass with every gap 0.

  bf16-sums  the INT sums added up from values rounded to bfloat16, the
             nearest precision below the f32 tile partials that the
             configuration's 1e-5 stands for: `value_gap_tol`
  left-out   the true top 10 with its last member replaced by the 11th:
             `top_gap_tol`, and no other number

Sums of at most 15 x 999 are exact in f32, so the sound reading is 0.
"""

import itertools

import numpy as np
import pytest

import dataset
import traffic
from conftest import E2E
from selfcheck import load

SEEDS = [3500035011, 3500035022, 2147483659]
CLIENTS = (0, 17, 49)  # of the mix's fifty
QUERIES = 4  # of each of those clients' streams


@pytest.fixture(scope="module", params=SEEDS)
def r1ep9k_answers(request):
    cfg = load(E2E, "configs", "r1ep9k.json")
    mix = load(E2E, "traffic", "topn-15m-c50.json")
    ds = dataset.Dataset(cfg, request.param)
    assert (ds.series, ds.buckets, ds.points) == (9000, 360, 3240000)
    qs = [
        q
        for k in CLIENTS
        for q in itertools.islice(traffic.stream(mix, ds, request.param, k), QUERIES)
    ]
    assert len({(q["lo"], q["where"]["value"]) for q in qs}) == len(qs)  # every query distinct
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


def tie_free(ds, qa):
    """The queries whose 10th and 11th sums differ by more than the
    tolerance: sums of 15 small integers tie at the cut now and then,
    the guarantee excepts ties, and either member is then right."""
    out = []
    for q, want in qa:
        (_, tenth), (_, eleventh) = top_of(ds, q, ds.hits, members=(9, 10)).values()
        if eleventh < tenth * (1 - 2 * dataset.SUM_RTOL):
            out.append((q, want))
    return out


def test_reference_in_full_precision_is_correct(r1ep9k_answers):
    ds, qa = r1ep9k_answers
    assert all(
        want["points"] == 135000 and len(want["names"]) == 7875 and q["top"] == 10
        and q["hi"] - q["lo"] == 900000
        for q, want in qa
    )
    refused, read = worst(ds, qa, ds.hits)
    assert refused == 0 and not any(read.values()), read


def test_control_bf16_sums_is_not_correct(r1ep9k_answers):
    ds, qa = r1ep9k_answers
    refused, read = worst(ds, qa, bf16(ds.hits))
    print("bf16 sums:", read)
    assert refused == len(qa)
    assert read["value_gap_tol"] > 3 * dataset.LIMITS["value_gap_tol"]
    assert read["groups_gap"] == 0 and read["count_gap"] == 0


def test_control_left_out_member_is_not_correct(r1ep9k_answers):
    ds, qa = r1ep9k_answers
    clear = tie_free(ds, qa)
    assert len(clear) >= len(qa) // 2
    refused, read = worst(ds, clear, ds.hits, members=(*range(9), 10))
    print("left out:", read)
    assert refused == len(clear)
    assert read["top_gap_tol"] > dataset.LIMITS["top_gap_tol"]
    assert not any(v for k, v in read.items() if k != "top_gap_tol"), read


def test_warm_at_places_cross_an_hour_of_the_data():
    """A message of 15 buckets is snapshotted by itself and the
    size-tiered merge joins the quarter hours four by four, so a shard
    holds six parts of one hour (60 buckets).  An even spread of 12
    starts reads one of them every time; the first five `warm_at` starts
    read two (their 15 buckets cross an hour), the sixth begins on one."""
    cfg = load(E2E, "configs", "r1ep9k.json")
    mix = load(E2E, "traffic", "topn-15m-c50.json")
    assert mix["clients"] == 50 and mix["loop"] == "closed" and mix["cycle"] == ["topn"]
    per_message = cfg["data"]["batch_rows"] // cfg["data"]["series"]
    assert per_message == 15 and cfg["data"]["snapshot_every_rows"] == cfg["data"]["batch_rows"]
    ds = dataset.Dataset(dict(cfg, data=dict(cfg["data"], series=16)), 0)
    panel = mix["panels"]["topn"]

    def hours(at):
        q = traffic.spec("topn", panel, ds, np.random.default_rng(0), at)
        b0, b1 = ds.bucket_range(q["lo"], q["hi"])
        assert b1 - b0 == 15, (at, b0, b1)
        return b0, b0 // 60, (b1 - 1) // 60

    spread = [hours((n + 0.5) / mix["warm_spread"]) for n in range(mix["warm_spread"])]
    assert all(first == last for _, first, last in spread)
    at = [hours(a) for a in mix["warm_at"]]
    assert [(first, last) for _, first, last in at[:5]] == [(0, 1), (1, 2), (2, 3), (3, 4), (4, 5)]
    assert at[5] == (300, 5, 5)
