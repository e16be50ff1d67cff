"""The `ep400k-pctl` deployment asked its cell's percentile query, held
to a plain reference, on the CPU.

benchmarks/e2e/traffic/pctl-7d.json asks one data node of the 10M-series
estate for p50 and p99 of every endpoint of one zone over the last 7
days: `PERCENTILE(value, 0.5, 0.99) ... WHERE region = '<r>' GROUP BY svc
LIMIT 400000` over 400,000 series at day step on 4 shards.  Its G lies
over `SORT_GROUPS_THRESHOLD` (65,536), and its `[G, 512]` histogram is
what decides how the scan reaches the device: counted once (the int32
histogram the fused program carries across its chunks), the 4-chunk
bucket fits `BYDB_FUSED_MAX_MB` in one dispatch; counted once a chunk it
would not.  Here an in-process engine holds 3,000 series x 8 daily
buckets on 4 shards in 15-day segments, loaded as the benchmark loads it
(a day a message, the names as one `DictColumn`, flushed after each), and
three bounds are monkeypatched DOWN so the small size lies on the big
size's side of each: `SORT_GROUPS_THRESHOLD` 2,048, `SCAN_CHUNK` 8,192
(the 21,000 rows of 7 days are 3 chunks in the 4-bucket, as the cell's
2,800,000 are 3 of 1,048,576) and `BYDB_FUSED_MAX_MB` 8 (the carried plan
is ~7.1 MiB; four stacked histograms alone would be 23.4).  It is asked
the cell's own text the way the server's `bydbql` handler asks it; the
reference is a NumPy rank-ceil(qN) percentile on the same seeded rows,
written here and sharing nothing with benchmarks/.

Tolerances, each with its reason:
  groups, counts  exact: a count is an integer below 2**24 a chunk in
                  f32, folded in f64 on the host
  percentiles     within one of the 512 buckets over the scanned value
                  range, with 0.1% of a bucket for the f32 bucketing of
                  a value on a bucket's edge: the configuration's
                  guarantee (the server takes the range over every
                  gathered row, the predicate applies after it, so the
                  range is the 7 days' over every series)
  the paths       result JSON byte for byte: one dispatch against forced
                  chunk batches, and the device's inversion against the
                  host's over the histogram of a combinable partial
"""

import dataclasses
import json
import math

import numpy as np
import pytest

from banyandb_tpu import bydbql
from banyandb_tpu.api.model import Aggregation, GroupBy, QueryRequest, TimeRange
from banyandb_tpu.api.schema import (
    Catalog,
    Entity,
    FieldSpec,
    FieldType,
    Group,
    IntervalRule,
    Measure,
    ResourceOpts,
    SchemaRegistry,
    TagSpec,
    TagType,
)
from banyandb_tpu.models.measure import DictColumn, MeasureEngine
from banyandb_tpu.obs import Tracer
from banyandb_tpu.obs import metrics as obs_metrics
from banyandb_tpu.obs.tracer import iter_spans
from banyandb_tpu.ops import groupby
from banyandb_tpu.query import measure_exec
from banyandb_tpu.server import result_to_json
from banyandb_tpu.storage.part import ColumnData

SERIES, REGIONS, BUCKETS, SHARDS = 3000, 8, 8, 4
BUCKET_MS = 86_400_000
T0 = 1_700_006_400_000
SEED = 3_300_000_041
QUANTILES = (0.5, 0.99)
HIST_BUCKETS = 512
SORT_THRESHOLD = 2048  # SORT_GROUPS_THRESHOLD, down from 65,536
SCAN_CHUNK = 8192  # measure_exec.SCAN_CHUNK, down from 1,048,576
FUSED_MAX_MB = 8  # BYDB_FUSED_MAX_MB, down from 1,024
GROUPS = SERIES // REGIONS  # the groups one zone returns
# (region kept, first bucket, buckets in range): the cell's own range (7
# of the 8 days, from a start in the first) three times, then a shorter one
DRAWS = [(0, 1, 7), (3, 1, 7), (7, 1, 7), (5, 2, 3)]
# [G] f32 count, sum, min, max and the two scan-order arrays (a listing)
# a chunk, and the int32 ranks [G, Q, 3] once
STATS_BYTES = 6 * 4
RANKS_BYTES = len(QUANTILES) * 3 * 4
HIST_BYTES = SERIES * HIST_BUCKETS * 4


@pytest.fixture(scope="module", autouse=True)
def big_sides_of_the_bounds():
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(groupby, "SORT_GROUPS_THRESHOLD", SORT_THRESHOLD)
        mp.setattr(measure_exec, "SCAN_CHUNK", SCAN_CHUNK)
        mp.setenv("BYDB_FUSED_MAX_MB", str(FUSED_MAX_MB))
        yield


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    """-> (engine, value [BUCKETS, SERIES] f64): a day a message, each
    flushed, as benchmarks/e2e/run.py loads configs/ep400k-pctl.json."""
    root = tmp_path_factory.mktemp("ep400k-pctl")
    reg = SchemaRegistry(root / "schema")
    opts = ResourceOpts(shard_num=SHARDS, segment_interval=IntervalRule(15, "day"))
    reg.create_group(Group("g", Catalog.MEASURE, opts))
    reg.create_measure(Measure(
        group="g", name="m",
        tags=(TagSpec("svc", TagType.STRING), TagSpec("region", TagType.STRING)),
        fields=(FieldSpec("value", FieldType.FLOAT), FieldSpec("hits", FieldType.INT)),
        entity=Entity(("svc",)),
    ))
    eng = MeasureEngine(reg, root / "data")
    rng = np.random.default_rng(SEED)
    hits = rng.integers(0, 1000, (BUCKETS, SERIES), dtype=np.int64)
    value = rng.gamma(2.0, 40.0, (BUCKETS, SERIES))
    svc_names = ["svc_%06d" % i for i in range(SERIES)]
    region_names = ["r%d" % i for i in range(REGIONS)]
    svc = np.arange(SERIES, dtype=np.int32)
    for b in range(BUCKETS):
        written = eng.write_columns(
            "g", "m", ts_millis=np.full(SERIES, T0 + b * BUCKET_MS, np.int64),
            tags={
                "svc": DictColumn(svc_names, svc),
                "region": DictColumn(region_names, svc % REGIONS),
            },
            fields={"value": value[b], "hits": hits[b].astype(np.float64)},
            versions=np.ones(SERIES, np.int64),
        )
        assert written == SERIES
        eng.flush()
    yield eng, value
    eng.close()


def ql_of(region: int, b0: int, nb: int, off: int = 1) -> str:
    """The cell's text (traffic.ql_of on pctl-7d's panel); the range starts
    `off` ms after the bucket before `b0`, as traffic.py draws a start, so
    another `off` is another text over the same points."""
    lo = T0 + (b0 - 1) * BUCKET_MS + off
    hi = lo + nb * BUCKET_MS
    qs = ", ".join(repr(q) for q in QUANTILES)
    return (
        f"SELECT PERCENTILE(value, {qs}) FROM MEASURE m IN g TIME BETWEEN {lo} AND {hi} "
        f"WHERE region = 'r{region}' GROUP BY svc LIMIT 400000"
    )


def spans_of(tree: dict) -> dict:
    return {
        s["name"]: dict(s.get("tags") or {}, duration_ms=s["duration_ms"])
        for s in iter_spans(tree)
    }


def serve(eng, ql: str):
    """What server.py's `_ql` does for a measure text with "trace": true
    -> (the reply's JSON text, {span name: its tags})."""
    catalog, req = bydbql.parse_with_catalog(ql)
    assert catalog == "measure"
    tracer = Tracer("standalone:measure")
    res = eng.query(dataclasses.replace(req, trace=True), tracer=tracer)
    spans = spans_of(tracer.finish())
    res.trace = None
    return json.dumps(result_to_json(res)), spans


def answers(text: str) -> dict:
    """Reply JSON -> {group: (count, [estimates])} in reply order."""
    out = json.loads(text)
    vals = out["values"]
    return {
        g[0]: (int(c), v)
        for g, c, v in zip(out["groups"], vals["count"], vals["percentile(value)"])
    }


def reference(value: np.ndarray, region: int, b0: int, nb: int):
    """-> ({svc name: (count, [value of rank ceil(qN)])} of every series
    of the zone, one bucket's width): NumPy over the buckets in range."""
    win = value[b0:b0 + nb]
    width = (win.max() - win.min()) / HIST_BUCKETS
    ranks = [min(max(math.ceil(q * nb), 1), nb) for q in QUANTILES]
    ordered = np.sort(win, axis=0)
    want = {
        "svc_%06d" % s: (nb, [float(ordered[r - 1, s]) for r in ranks])
        for s in range(SERIES) if s % REGIONS == region
    }
    return want, width


def gaps(got: dict, want: dict, width: float) -> tuple[int, int, float]:
    """-> (groups the reply lacks or adds, the widest count difference,
    the widest value difference in buckets)."""
    shared = set(got) & set(want)
    groups = len(set(got) ^ set(want))
    counts = max((abs(got[g][0] - want[g][0]) for g in shared), default=0)
    values = max(
        (abs(a - b) / width for g in shared for a, b in zip(got[g][1], want[g][1])),
        default=0.0,
    )
    return groups, counts, values


def _counted(name: str, label: str) -> float:
    text = obs_metrics.global_meter().prometheus_text()
    return sum(
        float(line.split()[-1]) for line in text.splitlines()
        if line.startswith("banyandb_" + name) and label in line
    )


@pytest.mark.parametrize("region, b0, nb", DRAWS)
def test_every_percentile_is_the_references(store, region, b0, nb):
    """Every group of the zone, its count exact and both quantiles within
    one bucket; the histogram stays on the device: one dispatch of the
    4-bucket, the ranks of the two quantiles fetched and no histogram
    byte, and the host's part of the inversion under span `invert`; span
    `merge` decoded every group of the reply."""
    eng, value = store
    kept = _counted("percentile_hist_bytes", 'where="kept"')
    text, spans = serve(eng, ql_of(region, b0, nb, off=1 + region))
    want, width = reference(value, region, b0, nb)
    groups, counts, values = gaps(answers(text), want, width)
    assert (groups, counts) == (0, 0) and len(want) == GROUPS
    assert values <= 1.001, values
    tags = spans["reduce"]
    chunks = math.ceil(nb * SERIES / SCAN_CHUNK)
    bucket = 1 << (chunks - 1).bit_length()
    assert (tags["dispatches"], tags["chunks"], tags["chunks_skipped"]) == (
        1, chunks, bucket - chunks,
    )
    assert tags["groups"] == SERIES
    assert tags["hist_groups"] == SERIES and tags["hist_device_bytes"] == HIST_BYTES
    assert tags["hist_fetched_bytes"] == 0
    assert tags["partials_bytes"] == bucket * SERIES * STATS_BYTES + SERIES * RANKS_BYTES
    assert spans["invert"]["groups"] == GROUPS
    assert spans["merge"]["decoded_groups"] == len(json.loads(text)["groups"]) == GROUPS
    assert _counted("percentile_hist_bytes", 'where="kept"') - kept == HIST_BYTES


def test_the_comparison_refuses_256_buckets_and_a_dropped_group(store):
    """The controls: the same estimate from a 256-bucket histogram, the
    nearest precision below the configuration's 512, is out of
    tolerance; so is an answer short of one group.  The reference itself,
    and the 512-bucket estimate, pass."""
    _, value = store
    region, b0, nb = DRAWS[0]
    want, width = reference(value, region, b0, nb)
    assert gaps(want, want, width) == (0, 0, 0.0)
    win = value[b0:b0 + nb]
    lo = win.min()
    ranks = [min(max(math.ceil(q * nb), 1), nb) for q in QUANTILES]

    def estimate(buckets: int) -> dict:
        w = (win.max() - lo) / buckets
        at = np.clip(((win - lo) / w).astype(np.int64), 0, buckets - 1)
        out = {}
        for s in range(region, SERIES, REGIONS):
            counts = np.bincount(at[:, s], minlength=buckets)
            cdf = np.cumsum(counts)
            est = []
            for r in ranks:
                hit = int(np.searchsorted(cdf, r, "left"))
                est.append(float(lo + (hit + (r - cdf[hit] + counts[hit]) / counts[hit]) * w))
            out["svc_%06d" % s] = (nb, est)
        return out

    assert gaps(estimate(HIST_BUCKETS), want, width)[2] <= 1.001
    groups, counts, values = gaps(estimate(256), want, width)
    assert (groups, counts) == (0, 0) and values > 1.5, values
    short = dict(want)
    short.pop(next(iter(short)))
    assert gaps(short, want, width)[0] == 1


@pytest.mark.parametrize("region, b0, nb", DRAWS[:2])
def test_one_dispatch_and_forced_batches_answer_byte_for_byte(store, monkeypatch, region, b0, nb):
    """With no device budget every chunk is a batch of its own: three
    dispatches of the 1-bucket, the histogram handed from one to the next
    on the device and inverted by the last; the reply is the one
    dispatch's, byte for byte."""
    eng, _ = store
    one, one_spans = serve(eng, ql_of(region, b0, nb, off=11 + region))
    monkeypatch.setenv("BYDB_FUSED_MAX_MB", "0")
    batched, spans = serve(eng, ql_of(region, b0, nb, off=21 + region))
    assert batched == one
    tags = spans["reduce"]
    assert one_spans["reduce"]["dispatches"] == 1
    assert tags["dispatches"] == tags["chunks"] == 3 and tags["chunks_skipped"] == 0
    assert tags["hist_fetched_bytes"] == 0
    assert tags["partials_bytes"] == 3 * SERIES * STATS_BYTES + SERIES * RANKS_BYTES


def test_the_device_inversion_is_the_combined_histograms(store):
    """A data node's map phase keeps the histogram (the liaison combines
    partials), fetched once a query; finalized alone it answers the
    standalone reply byte for byte.  Two such partials over the shards'
    halves, at the range the liaison's two-pass agrees, combine to the
    same groups, counts and estimates, float for float; only the order
    of a listing's groups can differ there, because each partial numbers
    its scan rows from 0."""
    eng, _ = store
    region, b0, nb = DRAWS[1]
    ql = ql_of(region, b0, nb, off=31)
    standalone, _ = serve(eng, ql)
    _, req = bydbql.parse_with_catalog(ql)
    m = eng.registry.get_measure("g", "m")

    def reply(partials) -> str:
        return json.dumps(result_to_json(measure_exec.finalize_partials(m, req, partials)))

    tracer = Tracer("data:measure")
    whole = eng.query_partials(req, tracer=tracer)
    tags = spans_of(tracer.finish())["reduce"]
    assert whole.ranks is None and whole.hist.shape == (GROUPS, HIST_BUCKETS)
    assert tags["hist_fetched_bytes"] == tags["hist_device_bytes"] == HIST_BYTES
    assert reply([whole]) == standalone
    rng = (whole.hist_lo, whole.hist_span)
    halves = [
        eng.query_partials(req, shard_ids=ids, hist_range=rng) for ids in ([0, 1], [2, 3])
    ]
    assert sum(len(p.count) for p in halves) == GROUPS
    combined = reply([measure_exec.combine_partials(halves)])
    assert sorted(answers(combined).items()) == sorted(answers(standalone).items())
    with pytest.raises(ValueError, match="do not combine"):
        measure_exec.combine_partials([
            eng.query_partials(req, shard_ids=[0, 1], hist_range=rng),
            measure_exec.compute_partials(
                m, req, eng.gather_query_sources(req, shard_ids=[2, 3]), final=True
            ),
        ])


def test_a_rank_the_device_takes_off_by_one_is_settled_from_the_histogram():
    """The device takes each rank ceil(q*N) in f32, the host in f64; where
    q*N lies within f32's rounding of a whole number they differ by one
    (q = 0.3, N = 50: 16 against 15; q = 0.55, N = 100: 55 against 56).
    Where that moves the rank into another bucket, the group's histogram
    row is fetched and inverted on the host: the reply is the one the
    whole histogram gives, and only those rows crossed."""
    T = 1_700_000_000_000
    # group a: 50 rows, 15 low and 35 high; b: 100 rows, 55 low and 45 high;
    # c: 60 rows spread out, whose ranks the two agree on.  Rank 15 of a
    # and rank 55 of b end a bucket.
    low, high = np.linspace(1.0, 1.05, 55), np.linspace(90.0, 99.0, 45)
    vals = np.concatenate([low[:15], high[:35], low, high, np.linspace(0.0, 100.0, 60)])
    keys = np.repeat(np.arange(3, dtype=np.int32), [50, 100, 60])
    n = len(vals)
    src = ColumnData(
        ts=T + np.arange(n, dtype=np.int64),
        series=keys.astype(np.int64),
        version=np.ones(n, np.int64),
        tags={"svc": keys},
        fields={"lat": vals},
        dicts={"svc": [b"a", b"b", b"c"]},
    )
    m = Measure(
        group="g", name="m", tags=(TagSpec("svc", TagType.STRING),),
        fields=(FieldSpec("lat", FieldType.FLOAT),), entity=Entity(("svc",)),
    )
    req = QueryRequest(
        ("g",), "m", TimeRange(T, T + n), group_by=GroupBy(("svc",)),
        agg=Aggregation("percentile", "lat", quantiles=(0.3, 0.55)),
    )
    assert math.ceil(np.float32(0.3) * np.float32(50)) == 16 and math.ceil(0.3 * 50) == 15
    assert math.ceil(np.float32(0.55) * np.float32(100)) == 55 and math.ceil(0.55 * 100) == 56
    out = {}
    for final in (False, True):
        tracer = Tracer("q")
        with tracer.span("execute") as sp:
            p = measure_exec.compute_partials(m, req, [src], span=sp, final=final)
        tags = spans_of(tracer.finish())["reduce"]
        text = json.dumps(result_to_json(measure_exec.finalize_partials(m, req, [p])))
        out[final] = (p, tags, text)
    (p_hist, t_hist, histogram), (p_ranks, t_ranks, ranked) = out[False], out[True]
    assert ranked == histogram
    assert t_hist["hist_fetched_bytes"] == 3 * HIST_BUCKETS * 4
    assert t_ranks["hist_fetched_bytes"] == 2 * HIST_BUCKETS * 4  # a and b
    # the settled ranks are the host's own, read off the whole histogram
    _, _, hit, below, inside = measure_exec._histogram_ranks(p_hist.hist, (0.3, 0.55))
    assert np.array_equal(p_ranks.ranks, np.stack([hit, below, inside], -1))
