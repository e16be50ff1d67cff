"""The `ep400k` deployment held to a plain reference, on the CPU.

benchmarks/e2e/configs/ep400k.json is one data node of the 10M-series
estate BASELINE.json names: 400,000 series at day step on 4 shards, one
day a message, asked for the top endpoints of the last 7 days.  Its
group space lies over `BYDB_MAX_PERSISTENT_GROUPS` (262,144), where
`measure_exec.compute_partials` checks the persistent `DictState` before
every query and resets it only when more than half of its group space is
dead for every query it has served (ISSUE 34; until then it reset it
before every query but the first and every per-source remap table was
built again, ROADMAP S13), and over `SORT_GROUPS_THRESHOLD` (65,536), so
the group-by is `sort`.  Here both bounds are monkeypatched DOWN (1,024 and
2,048) and an in-process engine holds 3,000 series x 8 daily buckets on
4 shards in 15-day segments, so the small size is on the big size's side
of both.  It is loaded as the benchmark loads it: through the columnar
write path, a day a message with the names as one `DictColumn`, flushed
after each (the server's flusher runs every second), so every part holds
one shard's rows of one day and the names those rows use, a quarter of
the message's dictionary (`MemTable.append_bulk` enters only the values a
shard's codes name; until ISSUE 38 every part held every name, ROADMAP
S14).
It is asked the cell's own BydbQL text
(benchmarks/e2e/traffic/topn-7d.json) the way the server's `bydbql`
handler asks it; the reference is a NumPy group-sum and top-k on the same
seeded rows, written here and sharing nothing with benchmarks/.

Tolerances, each with its reason:
  groups, counts  exact: a count is an integer below 2**24 per 65,536-row
                  tile in f32, folded in f64 on the host
  INT sums        SUM_RTOL = 1e-5 relative (tests/test_precision.py); with
                  hits <= 999 over 8 buckets every partial is an integer
                  below 2**24, so the sums in fact come back exact
  TOP 10          membership and order exact wherever the reference's sums
                  differ; 2,625 groups of 7 small integers tie now and
                  then, and between groups that tie at the cut either is a
                  right answer (the guarantee excepts them)
"""

import dataclasses
import json

import numpy as np
import pytest

from banyandb_tpu import bydbql
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
from banyandb_tpu.storage.memtable import MemTable

SERIES, REGIONS, BUCKETS, SHARDS = 3000, 8, 8, 4
BUCKET_MS = 86_400_000
T0 = 1_700_006_400_000
SEED = 3_300_000_001
SUM_RTOL = 1e-5
PERSISTENT_GROUPS = 1024  # BYDB_MAX_PERSISTENT_GROUPS, down from 262,144
SORT_THRESHOLD = 2048  # SORT_GROUPS_THRESHOLD, down from 65,536
# (region left out, first bucket, buckets in range): the cell's own range (7
# of the 8 days, from a start in the first) three times, then the whole
# store and a range inside it
DRAWS = [(0, 1, 7), (3, 1, 7), (6, 1, 7), (7, 0, 8), (5, 2, 3)]


@pytest.fixture(scope="module", autouse=True)
def big_sides_of_the_bounds():
    """3,000 groups are over both bounds for every test of this file."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(measure_exec, "_MAX_PERSISTENT_GROUPS", PERSISTENT_GROUPS)
        mp.setattr(groupby, "SORT_GROUPS_THRESHOLD", SORT_THRESHOLD)
        yield


def load(root):
    """-> (engine, hits [BUCKETS, SERIES] int64): a day a message, each
    flushed, as benchmarks/e2e/run.py loads configs/ep400k.json."""
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
    return eng, hits


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    eng, hits = load(tmp_path_factory.mktemp("ep400k"))
    yield eng, hits
    eng.close()


@pytest.fixture(scope="module")
def whole_dict_store(tmp_path_factory):
    """The same rows as `store`, every part holding its message's whole
    dictionary in the message's order: what a part written before ISSUE 38
    holds (`MemTable.append_bulk` entered every value of a `DictColumn`)."""
    append_bulk = MemTable.append_bulk

    def whole_dicts(self, ts, sids, versions, tags, fields, payloads=None):
        with self._lock:
            for t, col in tags.items():
                if isinstance(col, DictColumn):
                    d = self._dicts[t]
                    for v in col.values:
                        d.setdefault(v, len(d))
        append_bulk(self, ts, sids, versions, tags, fields, payloads)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(MemTable, "append_bulk", whole_dicts)
        eng, _ = load(tmp_path_factory.mktemp("ep400k-whole"))
    yield eng
    eng.close()


def ql_of(region: int, b0: int, nb: int, tail: str = "TOP 10 BY hits", off: int = 1) -> str:
    """The cell's text (traffic.ql_of on topn-7d's panel), `tail` after
    GROUP BY; the range starts `off` ms after the bucket before `b0`, as
    traffic.py draws a start, so another `off` is another text over the
    same points."""
    lo = T0 + (b0 - 1) * BUCKET_MS + off
    hi = lo + nb * BUCKET_MS
    return (
        f"SELECT sum(hits) FROM MEASURE m IN g TIME BETWEEN {lo} AND {hi} "
        f"WHERE region != 'r{region}' GROUP BY svc {tail}"
    )


def serve(eng, ql: str):
    """What server.py's `_ql` does for a measure text with "trace": true
    -> ({group: (count, sum)} in reply order, {span name: its tags} with
    each span's `duration_ms` among them)."""
    catalog, req = bydbql.parse_with_catalog(ql)
    assert catalog == "measure"
    tracer = Tracer("standalone:measure")
    res = eng.query(dataclasses.replace(req, trace=True), tracer=tracer)
    tree = tracer.finish()
    out = result_to_json(res)
    spans = {
        s["name"]: dict(s.get("tags") or {}, duration_ms=s["duration_ms"])
        for s in iter_spans(tree)
    }
    got = {
        g[0]: (int(c), float(v))
        for g, c, v in zip(out["groups"], out["values"]["count"], out["values"]["sum(hits)"])
    }
    assert len(got) == len(out["groups"])
    return got, spans


def reference(hits: np.ndarray, region: int, b0: int, nb: int) -> dict:
    """{svc name: (count, sum)} of every series the predicate keeps: a
    NumPy sum over the buckets in range, in int64."""
    sums = hits[b0:b0 + nb].sum(axis=0)
    return {
        "svc_%06d" % s: (nb, int(sums[s])) for s in range(SERIES) if s % REGIONS != region
    }


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


def _counted(name: str) -> float:
    text = obs_metrics.global_meter().prometheus_text()
    return sum(
        float(line.split()[-1]) for line in text.splitlines()
        if line.startswith("banyandb_" + name)
    )


@pytest.mark.parametrize("region, b0, nb", DRAWS)
def test_top10_is_the_references_query_after_query(store, region, b0, nb):
    """The kept state: every query finds the group space over the bound,
    and all of it live (a part holds one shard's rows of one day and the
    names they use, so the sources of one day reference every name):
    the `DictState` is kept, every table comes from `dict_state.remaps`
    and no dictionary entry is walked.  The answer is the reference's
    every time."""
    eng, hits = store
    # whichever test runs first, the state is full; a range of its own a
    # draw, as in the cell: the kept token would serve a repeat from the cache
    serve(eng, ql_of(region, b0, nb, off=90 + region))
    resets, kept = _counted("dict_state_resets"), _counted("dict_state_kept")
    walked = _counted("source_lut_entries")
    got, spans = serve(eng, ql_of(region, b0, nb, off=1 + region))
    compare(got, reference(hits, region, b0, nb), top=10)
    gather, reduce_ = spans["gather"], spans["reduce"]
    assert reduce_["groups"] == SERIES and reduce_["path"] == "fused"
    assert gather["rows"] == nb * SERIES and gather["sources"] == nb * SHARDS
    assert gather["dict_reset"] is False and gather["dict_over_bound"] is True
    assert gather["serving_cache"] != "hit"
    assert gather["lut_entries"] == 0 and gather["dict_live_share"] == 100.0
    assert 0 <= gather["lut_ms"] <= gather["select_ms"] <= gather["duration_ms"]
    # /metrics counts what the span says
    assert _counted("dict_state_resets") - resets == 0
    assert _counted("dict_state_kept") - kept == 1
    assert _counted("source_lut_entries") - walked == 0


@pytest.mark.parametrize("region, b0, nb", DRAWS[:2])
def test_every_group_and_count_is_the_references(store, region, b0, nb):
    eng, hits = store
    got, _ = serve(eng, ql_of(region, b0, nb, tail=f"LIMIT {SERIES}"))
    want = reference(hits, region, b0, nb)
    assert len(want) == SERIES - SERIES // REGIONS
    compare(got, want, top=None)
    assert [v for _, v in got.values()] == [float(want[g][1]) for g in got]  # exact, in fact


def part_dict_sizes(eng) -> dict:
    """-> {shard: sizes of its parts' `svc` dictionaries}."""
    return {
        i: {len(part.dict_for("svc")) for part in shard.parts}
        for seg in eng._tsdb("g").segments for i, shard in enumerate(seg.shards)
    }


@pytest.mark.parametrize("tail", ["TOP 10 BY hits", f"LIMIT {SERIES}"], ids=["top10", "listing"])
@pytest.mark.parametrize("region, b0, nb", DRAWS)
def test_the_reply_is_the_whole_dictionary_stores_byte_for_byte(
    store, whole_dict_store, region, b0, nb, tail,
):
    """A part that holds only the names its rows use (a quarter of the
    message's) changes the order in which a query's global codes are
    given, not one byte of a reply: a listing and the members of a Top-N
    whose values tie are ordered by the group key's bytes.  A part written
    before ISSUE 38 stays readable."""
    eng, _ = store
    assert part_dict_sizes(whole_dict_store) == {i: {SERIES} for i in range(SHARDS)}
    sizes = part_dict_sizes(eng)  # a shard's series, the same every day
    assert all(len(s) == 1 for s in sizes.values())
    assert sum(s.pop() for s in sizes.values()) == SERIES
    ql = ql_of(region, b0, nb, tail, off=101 + region)
    replies = []
    for e in (eng, whole_dict_store):
        _, req = bydbql.parse_with_catalog(ql)
        replies.append(json.dumps(result_to_json(e.query(req))))
    assert replies[0] == replies[1]


def test_the_method_taken_is_sort(store):
    """G over SORT_GROUPS_THRESHOLD: the span says what `group_reduce`
    resolved, never `auto`, and counts the rows it was given before the
    predicate."""
    eng, _ = store
    _, spans = serve(eng, ql_of(2, 1, 7, off=11))
    tags = spans["reduce"]
    rows = 7 * SERIES
    nrows = 1 << (rows - 1).bit_length()  # measure_exec._scan_bucket
    assert groupby.select_group_method(nrows, SERIES) == "sort"
    assert tags["group_method"] == "sort"
    assert tags["groups"] == SERIES and tags["chunks"] == 1
    assert tags["rows_per_ms"] == pytest.approx(rows / tags["device_ms"], rel=1e-3)


def test_under_the_bound_the_second_query_builds_no_table(store, monkeypatch):
    """Over `BYDB_MAX_PERSISTENT_GROUPS` as under it, a state whose group
    space is live is kept: the first query over new sources builds their
    tables, the next takes them from `dict_state.remaps` and walks
    nothing.  Under the bound no count of live codes is taken and no
    `dict_live_share` tagged.  The bound must not change an answer: same
    groups, same order, same values, text for text."""
    eng, _ = store
    region, b0, nb = DRAWS[0]
    tails = ["TOP 10 BY hits", f"LIMIT {SERIES}"]
    counts = []
    note_live = measure_exec.DictState.note_live

    def counting(self, tables):
        counts.append(1)
        note_live(self, tables)

    monkeypatch.setattr(measure_exec.DictState, "note_live", counting)
    eng._dict_state("g", "m").reset()
    over = [serve(eng, ql_of(region, b0, nb, tail, off=21 + i)) for i, tail in enumerate(tails)]
    tags = [spans["gather"] for _, spans in over]
    # a day's four sources: their shards' names, SERIES together, and the
    # regions each
    assert [t["lut_entries"] for t in tags] == [nb * (SERIES + SHARDS * REGIONS), 0]
    # the first found an empty state and left it over the bound
    assert [t["dict_over_bound"] for t in tags] == [False, True]
    assert [t["dict_live_share"] for t in tags] == [100.0, 100.0] and len(counts) == 2
    monkeypatch.setattr(measure_exec, "_MAX_PERSISTENT_GROUPS", 1 << 18)
    under = [serve(eng, ql_of(region, b0, nb, tail, off=31 + i)) for i, tail in enumerate(tails)]
    assert all(spans["gather"]["dict_reset"] is False for _, spans in over + under)
    assert all(spans["gather"]["serving_cache"] != "hit" for _, spans in over + under)
    assert [spans["gather"]["lut_entries"] for _, spans in under] == [0, 0]
    assert all(spans["gather"]["dict_over_bound"] is False for _, spans in under)
    assert all("dict_live_share" not in spans["gather"] for _, spans in under)
    assert len(counts) == 2  # no count taken under the bound
    for (got_over, _), (got_under, _) in zip(over, under):
        assert list(got_over.items()) == list(got_under.items())


def test_a_state_more_than_half_dead_is_reset_once(store):
    """Churn: values that no source holds any more pile up in the
    append-only dictionary.  At twice the live size the state is still
    kept (and the answer right with G twice the live size); one value
    more and the next query over the bound resets it, exactly once: the
    query after it keeps its state, walks nothing, and G is the live
    size again."""
    eng, hits = store
    region, b0, nb = DRAWS[1]
    want = reference(hits, region, b0, nb)
    st = eng._dict_state("g", "m")
    st.reset()
    serve(eng, ql_of(region, b0, nb, off=41))
    assert st.live_seen["svc"] == SERIES
    token = st.token
    with st.lock:
        st.dicts.add_source("svc", [b"gone_%06d" % i for i in range(SERIES)])
    resets = _counted("dict_state_resets")
    got, spans = serve(eng, ql_of(region, b0, nb, off=42))
    compare(got, want, top=10)
    assert spans["reduce"]["groups"] == 2 * SERIES and st.token == token
    assert spans["gather"]["dict_reset"] is False and spans["gather"]["dict_live_share"] == 50.0
    with st.lock:
        st.dicts.add_source("svc", [b"gone_too"])
    got, spans = serve(eng, ql_of(region, b0, nb, off=43))
    compare(got, want, top=10)
    gather = spans["gather"]
    assert gather["dict_reset"] is True and gather["dict_over_bound"] is True
    assert gather["lut_entries"] == nb * (SERIES + SHARDS * REGIONS)
    assert spans["reduce"]["groups"] == SERIES and st.token != token
    assert gather["dict_live_share"] == 100.0
    token = st.token
    got, spans = serve(eng, ql_of(region, b0, nb, off=44))
    compare(got, want, top=10)
    gather = spans["gather"]
    assert gather["dict_reset"] is False and gather["dict_over_bound"] is True
    assert gather["lut_entries"] == 0 and spans["reduce"]["groups"] == SERIES
    assert st.token == token and _counted("dict_state_resets") - resets == 1


@pytest.mark.parametrize("top, per_group", [(None, 16), (10, 8)], ids=["listing", "top10"])
@pytest.mark.parametrize("scan_chunk, chunks, skipped", [(None, 1, 0), (8192, 3, 1), (4096, 6, 2)])
def test_partials_bytes_is_the_bucket_times_g_times_16(
    store, monkeypatch, scan_chunk, chunks, skipped, top, per_group,
):
    """What the `device_get` brings back for a sum: count and sum(hits),
    two `[C, G]` arrays of 4 B, and for a listing, which emits its
    groups in first-appearance order, the two scan-order arrays beside
    them; the cell's `TOP 10` reads no scan order and fetches none
    (ISSUE 36), so 8 B a group a chunk beside the listing's 16, for
    every chunk of the bucket, a padding chunk's zeros included (the
    cell: 3 real 1M-row chunks in the 4-bucket x 400,000 x 8 = 12.8 MB;
    25.6 until ISSUE 36); `absorb_ms`, the f64 fold of the real ones, is
    a part of `host_ms`.  The answer is the one-chunk answer whatever
    the chunking."""
    eng, hits = store
    if scan_chunk is not None:
        monkeypatch.setattr(measure_exec, "SCAN_CHUNK", scan_chunk)
    tail = f"LIMIT {SERIES}" if top is None else f"TOP {top} BY hits"
    got, spans = serve(eng, ql_of(6, 1, 7, tail=tail, off=61 + chunks + (top or 0)))
    compare(got, reference(hits, 6, 1, 7), top=top)
    tags = spans["reduce"]
    assert (tags["chunks"], tags["chunks_skipped"], tags["dispatches"]) == (chunks, skipped, 1)
    assert tags["scan_order_tracked"] == (top is None)
    assert tags["partials_bytes"] == (chunks + skipped) * SERIES * per_group
    assert 0 < tags["absorb_ms"] <= tags["host_ms"]
    assert spans["merge"]["groups"] == SERIES - SERIES // REGIONS


def test_top10_is_the_same_across_chunkings(store, monkeypatch):
    """The plan that tracks no scan order answers group for group, in
    order, value for value, the same in 1, 3 and 6 chunks."""
    eng, _ = store
    answers = []
    for i, scan_chunk in enumerate((None, 8192, 4096)):
        if scan_chunk is not None:
            monkeypatch.setattr(measure_exec, "SCAN_CHUNK", scan_chunk)
        got, spans = serve(eng, ql_of(6, 1, 7, off=81 + i))
        assert spans["reduce"]["scan_order_tracked"] == 0
        answers.append(list(got.items()))
    assert answers[0] == answers[1] == answers[2] and len(answers[0]) == 10


def bf16(a: np.ndarray) -> np.ndarray:
    """-> the nearest-even bfloat16 value of each element, as float64."""
    bits = a.astype(np.float32).view(np.uint32)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & np.uint32(0xFFFF0000)
    return bits.view(np.float32).astype(np.float64)


def test_the_comparison_refuses_bf16_sums_and_a_dropped_group(store):
    """The controls: the reference with one guarantee broken must not
    pass `compare`; a tie at the cut may go either way."""
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
    with pytest.raises(AssertionError, match=r"\(2624, 2625\)"):
        compare(short, want, top=None)
    best = sorted(want, key=lambda g: -want[g][1])
    top = {g: want[g] for g in best[:10]}
    compare(top, want, top=10)
    assert want[best[10]][1] < want[best[9]][1]
    left_out = {g: want[g] for g in best[:9] + best[10:11]}
    with pytest.raises(AssertionError, match="a member"):
        compare(left_out, want, top=10)
    # the 11th given the 10th's sum: either of the two is a right 10th
    tied = dict(want, **{best[10]: want[best[9]]})
    compare(top, tied, top=10)
    compare({g: tied[g] for g in best[:9] + best[10:11]}, tied, top=10)
