"""The `ep9k` deployment held to a plain reference, on the CPU.

benchmarks/e2e/configs/ep9k.json is upstream's own benchmark estate at its
endpoint tier: 9,000 series at minute step on 2 shards.  G = 9,000 lies
between the Pallas limit (8,192) and SORT_GROUPS_THRESHOLD (65,536), so
`ops.groupby.select_group_method` answers `scatter` for it on a TPU and on
the CPU alike.  Here an in-process engine is loaded through the columnar
write path with 9,000 series x 8 minute buckets drawn from a seed and asked
the cell's own BydbQL text (benchmarks/e2e/traffic/topn-6h.json) the way
the server's `bydbql` handler asks it; the reference is a NumPy loop over
the points, written here and sharing nothing with benchmarks/.

Tolerances, each with its reason:
  groups, counts  exact: a count is an integer below 2**24 per 65,536-row
                  tile in f32, folded in f64 on the host
  INT sums        SUM_RTOL = 1e-5 relative: f32 partials per tile, Kahan
                  across tiles, f64 across chunks (tests/test_precision.py);
                  with hits <= 999 over 8 buckets every partial is an integer
                  below 2**24, so the sums in fact come back exact, and a sum
                  added from bfloat16 values (8 bits of mantissa, ~2e-3 a
                  value) misses by hundreds of tolerances
  TOP 10          membership and order exact wherever the reference's sums
                  differ (they are integers: a tie is a tie, broken by the
                  order of first appearance on both sides or not compared)
"""

import dataclasses

import numpy as np
import pytest

from banyandb_tpu import bydbql
from banyandb_tpu.api.schema import (
    Catalog,
    Entity,
    FieldSpec,
    FieldType,
    Group,
    Measure,
    ResourceOpts,
    SchemaRegistry,
    TagSpec,
    TagType,
)
from banyandb_tpu.models.measure import DictColumn, MeasureEngine
from banyandb_tpu.obs import Tracer
from banyandb_tpu.obs.tracer import iter_spans
from banyandb_tpu.ops import groupby
from banyandb_tpu.query import planner
from banyandb_tpu.server import result_to_json

SERIES, REGIONS, BUCKETS, SHARDS = 9000, 8, 8, 2
BUCKET_MS = 60_000
T0 = 1_700_006_400_000
SEED = 2_700_028_001
SUM_RTOL = 1e-5
# (region left out, first bucket, buckets in range): the whole store, a range
# inside it, a single bucket; starts off the bucket edge as traffic.py draws them
DRAWS = [(0, 0, 8), (3, 1, 6), (7, 2, 5), (5, 7, 1)]


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    """-> (engine, hits [BUCKETS, SERIES] int64), the data time-major as
    the benchmark loads it: one batch per four buckets, flushed."""
    root = tmp_path_factory.mktemp("ep9k")
    reg = SchemaRegistry(root / "schema")
    reg.create_group(Group("g", Catalog.MEASURE, ResourceOpts(shard_num=SHARDS)))
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
    svc = np.tile(np.arange(SERIES, dtype=np.int32), 4)
    for b in range(0, BUCKETS, 4):
        ts = np.repeat(T0 + np.arange(b, b + 4, dtype=np.int64) * BUCKET_MS, SERIES)
        written = eng.write_columns(
            "g", "m", ts_millis=ts,
            tags={
                "svc": DictColumn(svc_names, svc),
                "region": DictColumn(region_names, svc % REGIONS),
            },
            fields={
                "value": value[b:b + 4].reshape(-1),
                "hits": hits[b:b + 4].reshape(-1).astype(np.float64),
            },
            versions=np.ones(ts.size, np.int64),
        )
        assert written == ts.size
        eng.flush()
    yield eng, hits
    eng.close()


def ql_of(region: int, b0: int, nb: int, tail: str = "TOP 10 BY hits") -> str:
    """The cell's text (traffic.ql_of on topn-6h's panel), `tail` after
    GROUP BY."""
    lo = T0 + (b0 - 1) * BUCKET_MS + 1
    hi = lo + nb * BUCKET_MS
    return (
        f"SELECT sum(hits) FROM MEASURE m IN g TIME BETWEEN {lo} AND {hi} "
        f"WHERE region != 'r{region}' GROUP BY svc {tail}"
    )


def serve(eng, ql: str):
    """What server.py's `_ql` does for a measure text with "trace": true
    -> ({group: (count, sum)} in reply order, the `reduce` span's tags)."""
    catalog, req = bydbql.parse_with_catalog(ql)
    assert catalog == "measure"
    tracer = Tracer("standalone:measure")
    res = eng.query(dataclasses.replace(req, trace=True), tracer=tracer)
    tree = tracer.finish()
    out = result_to_json(res)
    (reduce_span,) = [s for s in iter_spans(tree) if s["name"] == "reduce"]
    got = {
        g[0]: (int(c), float(v))
        for g, c, v in zip(out["groups"], out["values"]["count"], out["values"]["sum(hits)"])
    }
    assert len(got) == len(out["groups"])
    return got, reduce_span["tags"]


def reference(hits: np.ndarray, region: int, b0: int, nb: int) -> dict:
    """{svc name: (count, sum)} point by point, in plain Python ints."""
    out: dict = {}
    for b in range(b0, b0 + nb):
        for s in range(SERIES):
            if s % REGIONS == region:
                continue
            c, v = out.get(s, (0, 0))
            out[s] = (c + 1, v + int(hits[b, s]))
    return {"svc_%06d" % s: cv for s, cv in out.items()}


def compare(got: dict, want: dict, top: int | None) -> None:
    """Raises AssertionError where `got` breaks a guarantee of the
    configuration against `want`."""
    assert set(got) <= set(want), sorted(set(got) - set(want))[:5]
    for g, (count, value) in got.items():
        assert count == want[g][0], (g, count, want[g][0])
        assert abs(value - want[g][1]) <= SUM_RTOL * abs(want[g][1]), (g, value, want[g][1])
    if top is None:
        assert len(got) == len(want), (len(got), len(want))
        return
    assert len(got) == min(top, len(want))
    ranked = sorted(want.values(), key=lambda cv: -cv[1])
    cut = ranked[len(got) - 1][1]  # the reference's n-th sum
    assert all(want[g][1] >= cut for g in got), "a member below the cut"
    assert {g for g, cv in want.items() if cv[1] > cut} <= set(got), "a member left out"
    order = [want[g][1] for g in got]
    assert order == sorted(order, reverse=True), order


@pytest.mark.parametrize("region, b0, nb", DRAWS)
def test_top10_is_the_references(store, region, b0, nb):
    eng, hits = store
    got, tags = serve(eng, ql_of(region, b0, nb))
    compare(got, reference(hits, region, b0, nb), top=10)
    assert tags["groups"] == SERIES and tags["path"] == "fused"


@pytest.mark.parametrize("region, b0, nb", DRAWS[:2])
def test_every_group_and_count_is_the_references(store, region, b0, nb):
    eng, hits = store
    got, _ = serve(eng, ql_of(region, b0, nb, tail=f"LIMIT {SERIES}"))
    want = reference(hits, region, b0, nb)
    assert len(want) == SERIES - SERIES // REGIONS
    compare(got, want, top=None)
    assert [v for _, v in got.values()] == [float(want[g][1]) for g in got]  # exact, in fact


def test_the_method_taken_is_scatter(store):
    """The span says what `group_reduce` resolved, never `auto`, and
    counts the rows it was given before the predicate."""
    eng, _ = store
    _, tags = serve(eng, ql_of(2, 0, 8))
    rows = BUCKETS * SERIES
    nrows = 1 << (rows - 1).bit_length()  # measure_exec._scan_bucket
    assert groupby.select_group_method(nrows, SERIES) == "scatter"
    assert tags["group_method"] == "scatter"
    assert tags["groups"] == SERIES and tags["chunks"] == 1
    assert tags["rows_per_ms"] == pytest.approx(rows / tags["device_ms"], rel=1e-3)


def test_scatter_and_sort_give_the_same_bytes(store, monkeypatch):
    eng, _ = store
    ql = ql_of(4, 0, 8, tail=f"LIMIT {SERIES}")
    by_scatter, tags = serve(eng, ql)
    assert tags["group_method"] == "scatter"

    plan_scan = planner.plan_scan

    def sort_plan(*a, **kw):
        d = plan_scan(*a, **kw)
        d.group_method = "sort"  # -> PlanSpec.group_method
        return d

    monkeypatch.setattr(planner, "plan_scan", sort_plan)
    by_sort, tags = serve(eng, ql)
    assert tags["group_method"] == "sort" and tags["partials_cache"] != "hit"
    assert list(by_sort.items()) == list(by_scatter.items())


def bf16(a: np.ndarray) -> np.ndarray:
    """-> the nearest-even bfloat16 value of each element, as float64."""
    bits = a.astype(np.float32).view(np.uint32)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & np.uint32(0xFFFF0000)
    return bits.view(np.float32).astype(np.float64)


def test_the_comparison_refuses_bf16_sums_and_a_dropped_group(store):
    """The controls: the reference with one guarantee broken must not
    pass `compare`."""
    _, hits = store
    region, b0, nb = DRAWS[0]
    want = reference(hits, region, b0, nb)
    compare(dict(want), want, top=None)  # the reference itself passes
    low = bf16(hits[b0:b0 + nb]).sum(axis=0)
    in_bf16 = {g: (c, float(low[int(g[4:])])) for g, (c, _) in want.items()}
    with pytest.raises(AssertionError):
        compare(in_bf16, want, top=None)
    short = dict(want)
    short.pop(next(iter(short)))
    with pytest.raises(AssertionError, match=r"\(7874, 7875\)"):
        compare(short, want, top=None)
    best = sorted(want, key=lambda g: -want[g][1])
    top = {g: want[g] for g in best[:10]}
    compare(top, want, top=10)
    assert want[best[10]][1] < want[best[9]][1]  # 6,354 against 6,360 on this seed
    left_out = {g: want[g] for g in best[:9] + best[10:11]}
    with pytest.raises(AssertionError, match="a member"):
        compare(left_out, want, top=10)
