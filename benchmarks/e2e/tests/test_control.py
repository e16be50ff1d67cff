"""The controls of `correct`, at each cell's own size (svc1k: 1,000 series
x 1,440 buckets; topn100k: 100,000 x 30; the mix's own queries), off the
chip: the reference put in the program's place with one guarantee of the
configuration broken must come out as not correct, and the same reference
keeping the guarantee must pass.

  histogram-256  percentiles read from a 256-bucket histogram: the nearest
                 precision below the 512 buckets the configuration states
  default-limit  the first 100 groups of the answer, what the server
                 returns to a text that names no LIMIT (on the chip: every
                 query of four runs refused, PERF.md section 6, PR 27)
  bf16-sums      topn-24h's INT sums added up from values rounded to
                 bfloat16, the nearest precision below the f32 partials
                 the configuration's 1e-5 stands for
"""

import itertools

import numpy as np
import pytest

import dataset
import selfcheck
import traffic
from conftest import E2E
from selfcheck import load

SEEDS = [2700000011, 2700000022, 2147483659]
QUERIES = 8  # of each seed's stream


def histogram_answer(ds, q, buckets):
    """`q` (percentile by svc) answered as a device histogram would:
    values bucketed over the scanned range, the rank's bucket found on
    the cumulated counts, linear inside it."""
    b0, b1 = ds.bucket_range(q["lo"], q["hi"])
    win = ds.value[b0:b1]  # [buckets in range, series]
    lo, width = win.min(), (win.max() - win.min()) / buckets
    at = np.clip(((win - lo) / width).astype(np.int64), 0, buckets - 1)
    out = {}
    for g, name in enumerate(ds.svc_names()):
        counts = np.bincount(at[:, g], minlength=buckets)
        cdf = np.cumsum(counts)
        est = []
        for x in q["quantiles"]:
            rank = dataset._rank(x, win.shape[0])
            hit = int(np.searchsorted(cdf, rank, "left"))
            frac = (rank - (cdf[hit] - counts[hit])) / counts[hit]
            est.append(float(lo + (hit + frac) * width))
        out[name] = (win.shape[0], est)
    return out


@pytest.fixture(scope="module", params=SEEDS)
def answers(request):
    cfg, mix = load(E2E, "configs", "svc1k.json"), load(E2E, "traffic", "pctl-6h.json")
    ds = dataset.Dataset(cfg, request.param)
    qs = list(itertools.islice(traffic.stream(mix, ds, request.param, 0), QUERIES))
    return ds, [(q, ds.answer(q)) for q in qs]


def worst(ds, answers, make):
    """(what `check` refused, the widest reading of each number) over the queries."""
    refused, read = 0, {}
    for q, want in answers:
        got = make(ds, q, want)
        refused += dataset.check(q, got, want) is not None
        for k, v in dataset.gaps(q, got, want).items():
            read[k] = max(read.get(k, 0), v)
    return refused, read


def test_reference_at_512_buckets_is_correct(answers):
    ds, qa = answers
    assert all(len(want["names"]) == 1000 and want["points"] == 360000 for _, want in qa)
    refused, read = worst(ds, qa, lambda ds, q, want: histogram_answer(ds, q, dataset.HIST_BUCKETS))
    print("512 buckets:", read)
    assert refused == 0 and not any(v > dataset.LIMITS[k] for k, v in read.items())
    assert read["value_gap_tol"] > 0.5  # the number has something to read


def test_control_256_buckets_is_not_correct(answers):
    ds, qa = answers
    refused, read = worst(ds, qa, lambda ds, q, want: histogram_answer(ds, q, 256))
    print("256 buckets:", read)
    assert refused == len(qa)
    assert read["value_gap_tol"] > 1.5 * dataset.LIMITS["value_gap_tol"]
    assert read["groups_gap"] == 0 and read["count_gap"] == 0


def test_control_default_limit_is_not_correct(answers):
    ds, qa = answers

    def first_100(ds, q, want):
        keep = want["names"][: selfcheck.DEFAULT_LIMIT]
        return {
            n: (int(want["count"][i]), [float(v) for v in want["metric"][i]])
            for i, n in enumerate(keep)
        }

    refused, read = worst(ds, qa, first_100)
    assert refused == len(qa) and read["groups_gap"] == 900 and read["value_gap_tol"] == 0


def bf16(a):
    """float64 -> the nearest-even bfloat16 value, as float64."""
    bits = a.astype(np.float32).view(np.uint32)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & np.uint32(0xFFFF0000)
    return bits.view(np.float32).astype(np.float64)


@pytest.mark.parametrize("seed", SEEDS)
def test_control_bf16_sums_is_not_correct(seed):
    cfg, mix = load(E2E, "configs", "topn100k.json"), load(E2E, "traffic", "topn-24h.json")
    ds = dataset.Dataset(cfg, seed)
    low = bf16(ds.hits)
    refused_low = 0
    read = {}
    for q in itertools.islice(traffic.stream(mix, ds, seed, 0), 4):
        want = ds.answer(q)
        assert want["points"] == 2400000
        b0, b1 = ds.bucket_range(q["lo"], q["hi"])
        for make, tally in ((ds.hits, None), (low, read)):
            sums = np.where(ds._mask(q["where"]), make[b0:b1].sum(axis=0), -np.inf)
            best = np.argsort(-sums, kind="stable")[: q["top"]]
            got = {"svc_%06d" % g: (b1 - b0, float(sums[g])) for g in best}
            if tally is None:  # the reference in full precision passes, every gap 0
                assert dataset.check(q, got, want) is None
                assert not any(dataset.gaps(q, got, want).values())
                continue
            refused_low += dataset.check(q, got, want) is not None
            for k, v in dataset.gaps(q, got, want).items():
                tally[k] = max(tally.get(k, 0), v)
    print("bf16 sums:", read)
    assert refused_low == 4 and read["value_gap_tol"] > 10 and read["count_gap"] == 0
