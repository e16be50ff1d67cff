"""The gather dedups only where keys can collide (ISSUE 26).

`measure_exec._gather_rows` skips the (series, ts) max-version dedup for
sources proven disjoint and unique (storage/part.py KeySpan) and runs it
per connected component elsewhere.  Every case here holds the result to
the always-dedup reference — `hostops.dedup_max_version` over the whole
concat — byte for byte, over an i8-wide and an i32-wide dictionary (the
local code width the snapshot keeps), and pins which rows went through
the sort (`proven_unique_share`, the `gather_rows` counter)."""

import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest

from banyandb_tpu.api.model import (
    Aggregation,
    Condition,
    GroupBy,
    QueryRequest,
    TimeRange,
)
from banyandb_tpu.api.schema import (
    Entity,
    FieldSpec,
    FieldType,
    Measure,
    TagSpec,
    TagType,
)
from banyandb_tpu.obs.metrics import global_meter
from banyandb_tpu.query import measure_exec
from banyandb_tpu.query.measure_exec import (
    GlobalDicts,
    _gather_rows,
    _host_tag_codes,
)
from banyandb_tpu.storage import encoded
from banyandb_tpu.storage.part import (
    ColumnData,
    KeyInterval,
    KeySpan,
    Part,
    PartWriter,
)
from banyandb_tpu.utils import hostops

T0 = 1_700_000_000_000
HOUR = 3_600_000
# the tag dictionary of every source: its length decides the width of
# the local codes (encoded.code_dtype); series, and so codes, stay < 40
DICTS = {
    "i8": [b"svc-%d" % i for i in range(100)],
    "i32": [b"svc-%d" % i for i in range(40_000)],
}


def _rows(series, hours, version=1):
    """One point per series per hour: (series, ts, version, svc code, v);
    `v` carries the version, so a stale winner shows in the values."""
    s, h = np.meshgrid(np.asarray(series), np.asarray(hours), indexing="ij")
    s, h = s.ravel().astype(np.int64), h.ravel().astype(np.int64)
    return dict(
        series=s,
        ts=T0 + h * HOUR,
        version=np.full(s.shape, version, dtype=np.int64),
        svc=s.astype(np.int32),
        v=(s * 1000 + h * 10 + version).astype(np.float64),
    )


def _cat(*rowsets):
    return {k: np.concatenate([r[k] for r in rowsets]) for k in rowsets[0]}


def _write(scope_dir, name, rows, svcs) -> Part:
    PartWriter.write(
        scope_dir / name,
        ts=rows["ts"],
        series=rows["series"],
        version=rows["version"],
        tag_codes={"svc": rows["svc"]},
        tag_dicts={"svc": svcs},
        fields={"v": rows["v"]},
        extra_meta={"measure": "m"},
    )
    return Part(scope_dir / name)


def _read(part: Part) -> ColumnData:
    """The part's rows as models/measure.py reads them: codes at their
    stored width."""
    return part.read(
        range(len(part.blocks)),
        tags=["svc"],
        fields=["v"],
        cached=False,
        narrow_codes=True,
    )


def _hot(scope_dir, rows, svcs) -> ColumnData:
    """A memtable / flushing-snapshot source as models/measure.py hands
    it over: rows in arrival order, conservative span, nothing proven."""
    return ColumnData(
        ts=rows["ts"],
        series=rows["series"],
        version=rows["version"],
        tags={"svc": rows["svc"]},
        fields={"v": rows["v"]},
        dicts={"svc": svcs},
        key_span=KeySpan.unproven(str(scope_dir), rows["series"], rows["ts"]),
    )


# -- the source sets ------------------------------------------------------------
# each builder -> (sources, ordinals of the sources that must be sorted)


def _disjoint_2x2(tmp, svcs):
    srcs = []
    for seg, hours in (("seg-a", (0, 12)), ("seg-b", (24, 36))):
        for shard in (0, 1):
            d = tmp / seg / f"shard-{shard}"
            series = range(shard, 40, 2)
            for k, h0 in enumerate(hours):
                part = _write(d, f"part-{k}", _rows(series, range(h0, h0 + 12)), svcs)
                assert part.meta["unique_keys"] is True
                srcs.append(_read(part))
    return srcs, []


def _double_write_in_one_part(tmp, svcs):
    d = tmp / "seg-a" / "shard-0"
    dup = _cat(_rows(range(20), range(0, 6)), _rows([3, 7], [2, 4], version=2))
    flushed = _write(d, "part-0", dup, svcs)
    assert flushed.meta["unique_keys"] is False
    srcs = [
        _read(flushed),
        _read(_write(d, "part-1", _rows(range(20), range(6, 12)), svcs)),
        _read(_write(d, "part-2", _rows(range(20), range(12, 18)), svcs)),
    ]
    return srcs, [0]


def _overlapping_rewrite(tmp, svcs):
    d = tmp / "seg-a" / "shard-0"
    srcs = [
        _read(_write(d, "part-0", _rows(range(20), range(0, 8)), svcs)),
        # rewrites (5, hour 7) and (9, hour 6) at version 2
        _read(
            _write(d, "part-1", _cat(
                _rows(range(20), range(8, 12)),
                _rows([5], [7], version=2),
                _rows([9], [6], version=2),
            ), svcs),
        ),
        _read(_write(d, "part-2", _rows(range(20), range(12, 20)), svcs)),
    ]
    return srcs, [0, 1]


def _interleaved_ranges(tmp, svcs, scopes):
    """Even series in one part, odd in another, the same hours: the key
    ranges interleave (no key is shared)."""
    a, b = (tmp / s for s in scopes)
    return [
        _read(_write(a, "part-0", _rows(range(0, 20, 2), range(0, 6)), svcs)),
        _read(_write(b, "part-1", _rows(range(1, 19, 2), range(0, 6)), svcs)),
    ]


def _same_ranges_other_scope(tmp, svcs):
    srcs = _interleaved_ranges(
        tmp, svcs, ("seg-a/shard-0", "seg-a/shard-1")
    )
    assert srcs[0].key_span.interval.intersects(srcs[1].key_span.interval)
    return srcs, []


def _same_ranges_one_scope(tmp, svcs):
    return _interleaved_ranges(
        tmp, svcs, ("seg-a/shard-0", "seg-a/shard-0")
    ), [0, 1]


def _hot_double_expose(tmp, svcs):
    """A query racing flush's second commit: the drained rows are in the
    flushing snapshot AND in the new part; the live memtable holds a
    rewrite of an older part's key."""
    d = tmp / "seg-a" / "shard-0"
    drained = _rows(range(10), range(12, 16))
    srcs = [
        _hot(d, _cat(_rows(range(10), range(16, 18)), _rows([4], [3], 2)), svcs),
        _hot(d, drained, svcs),
        _read(_write(d, "part-0", _rows(range(10), range(0, 6)), svcs)),
        _read(_write(d, "part-1", _rows(range(10), range(6, 12)), svcs)),
        _read(_write(d, "part-2", drained, svcs)),
        _read(
            _write(tmp / "seg-a" / "shard-1", "part-0", _rows(range(10, 20), range(0, 18)), svcs),
        ),
    ]
    # the memtable's rect [h3, h17] meets every source of its shard
    return srcs, [0, 1, 2, 3, 4]


def _one_source_without_span(tmp, svcs):
    srcs, _ = _disjoint_2x2(tmp, svcs)
    srcs[3] = dataclasses.replace(srcs[3], key_span=None)
    return srcs, list(range(len(srcs)))


def _metadata_without_unique_keys(tmp, svcs):
    d = tmp / "seg-a" / "shard-0"
    old = _write(d, "part-0", _rows(range(20), range(0, 6)), svcs)
    meta = json.loads((old.dir / "metadata.json").read_text())
    del meta["unique_keys"]  # as a part written before the key existed
    (old.dir / "metadata.json").write_text(json.dumps(meta))
    old = Part(old.dir)
    srcs = [
        _read(old),
        _read(_write(d, "part-1", _rows(range(20), range(6, 12)), svcs)),
    ]
    assert srcs[0].key_span.unique is False
    return srcs, [0]


CASES = {
    "disjoint-2x2": _disjoint_2x2,
    "double-write-in-one-part": _double_write_in_one_part,
    "overlapping-rewrite": _overlapping_rewrite,
    "same-ranges-other-scope": _same_ranges_other_scope,
    "same-ranges-one-scope": _same_ranges_one_scope,
    "hot-double-expose": _hot_double_expose,
    "one-source-without-span": _one_source_without_span,
    "metadata-without-unique-keys": _metadata_without_unique_keys,
}


# -- the check -------------------------------------------------------------------


def _gather(srcs, begin, end, tags_out=None):
    """-> (the gathered snapshot, its global dictionaries)."""
    gd = GlobalDicts(["svc"])
    return _gather_rows(
        srcs, ["svc"], ["v"], gd, begin, end, tags_out=tags_out
    ), gd


def _flat(cols: dict) -> dict:
    """Every array of a gathered snapshot as (dtype, bytes)."""
    out = {}
    for k, v in cols.items():
        if isinstance(v, dict):
            for kk, vv in v.items():
                out[f"{k}.{kk}"] = vv
        else:
            out[k] = v
    flat = {}
    for k, v in out.items():
        if isinstance(v, tuple):  # tags_lut: one LUT per source
            flat[k] = [(a.dtype.str, a.tobytes()) for a in v]
        elif isinstance(v, np.ndarray):
            flat[k] = (v.dtype.str, v.tobytes())
        else:
            flat[k] = str(v)  # fields_narrow: a dtype
    return flat


def _counter(kind: str) -> float:
    return (
        global_meter()
        .snapshot()["counters"]
        .get(("gather_rows", (("dedup", kind),)), 0.0)
    )


def _assert_as_always_dedup(srcs, sorted_ordinals, monkeypatch,
                            begin=T0 + HOUR, end=T0 + 30 * HOUR):
    in_range = [
        int(((s.ts >= begin) & (s.ts < end)).sum()) for s in srcs
    ]
    n = sum(in_range)
    n_sorted = sum(in_range[i] for i in sorted_ordinals)
    assert n > 0

    # the reference first, through the same function with nothing proven
    bare = [dataclasses.replace(s, key_span=None) for s in srcs]
    ref_tags: dict = {}
    ref, _ = _gather(bare, begin, end, ref_tags)
    assert ref_tags["proven_unique_share"] == 0.0

    calls = []
    real = hostops.dedup_max_version

    def counting(series, ts, version):
        calls.append(int(series.shape[0]))
        return real(series, ts, version)

    monkeypatch.setattr(hostops, "dedup_max_version", counting)
    skipped0, sorted0 = _counter("skipped"), _counter("sorted")
    tags: dict = {}
    got, gd = _gather(srcs, begin, end, tags)
    monkeypatch.setattr(hostops, "dedup_max_version", real)

    assert _flat(got) == _flat(ref)
    # ... and the reference against plain NumPy over the whole concat
    sel = [(s.ts >= begin) & (s.ts < end) for s in srcs]
    ts = np.concatenate([s.ts[m] for s, m in zip(srcs, sel)])
    series = np.concatenate([s.series[m] for s, m in zip(srcs, sel)])
    version = np.concatenate([s.version[m] for s, m in zip(srcs, sel)])
    v = np.concatenate([s.fields["v"][m] for s, m in zip(srcs, sel)])
    svc = np.concatenate(
        [np.asarray(s.dicts["svc"], dtype=object)[s.tags["svc"][m]]
         for s, m in zip(srcs, sel)]
    )
    keep = real(series, ts, version)
    assert np.array_equal(got["ts"], ts[keep])
    assert np.array_equal(got["series"], series[keep])
    assert np.array_equal(got["fields"]["v"], v[keep])
    # the tag: local codes at the dictionary's width, their values those
    # the rows were written with
    width = encoded.code_dtype(len(srcs[0].dicts["svc"]))
    assert got["tags_enc"]["svc"].dtype == width
    values = np.asarray(gd.values("svc"), dtype=object)
    assert np.array_equal(values[_host_tag_codes(got, "svc")], svc[keep])
    # the newest version of every key, once
    keys = set(zip(got["series"].tolist(), got["ts"].tolist()))
    assert len(keys) == got["ts"].shape[0] == len(set(zip(series.tolist(), ts.tolist())))
    newest: dict = {}
    for s, t, ver in zip(series.tolist(), ts.tolist(), version.tolist()):
        newest[(s, t)] = max(newest.get((s, t), 0), ver)
    got_ver = (got["fields"]["v"] % 10).astype(int).tolist()
    assert got_ver == [
        newest[k] for k in zip(got["series"].tolist(), got["ts"].tolist())
    ]

    assert sum(calls) == n_sorted, calls
    assert tags["proven_unique_share"] == round(100.0 * (n - n_sorted) / n, 3)
    assert _counter("skipped") - skipped0 == n - n_sorted
    assert _counter("sorted") - sorted0 == n_sorted
    for phase in ("select_ms", "concat_ms", "dedup_ms", "take_ms"):
        assert tags[phase] >= 0.0
    return got, tags


@pytest.mark.parametrize("width", sorted(DICTS))
@pytest.mark.parametrize("case", sorted(CASES))
def test_gather_equals_always_dedup(tmp_path, monkeypatch, case, width):
    srcs, sorted_ordinals = CASES[case](tmp_path, DICTS[width])
    _assert_as_always_dedup(srcs, sorted_ordinals, monkeypatch)


@pytest.mark.parametrize("width", sorted(DICTS))
def test_no_row_dropped_means_no_take_and_no_version(tmp_path, monkeypatch, width):
    """Proven sources: the dedup never runs, `version` is never read and
    the concatenated columns are handed on as they are."""

    class NoRead:
        def __getitem__(self, _):
            raise AssertionError("version read though nothing dedups")

    srcs, _ = _disjoint_2x2(tmp_path, DICTS[width])
    srcs = [dataclasses.replace(s, version=NoRead()) for s in srcs]
    monkeypatch.setattr(
        hostops, "dedup_max_version",
        lambda *a: pytest.fail("dedup ran over proven sources"),
    )
    tags: dict = {}
    got, _ = _gather(srcs, T0, T0 + 48 * HOUR, tags)
    assert tags["proven_unique_share"] == 100.0
    assert got["ts"].shape[0] == sum(s.ts.shape[0] for s in srcs)
    for col in (got["ts"], got["series"], got["fields"]["v"]):
        assert col.flags.owndata and col.flags.c_contiguous


def test_time_filter_decides_which_sources_connect(tmp_path, monkeypatch):
    """Components are built over the sources that have rows in range: a
    part wholly outside it does not chain its neighbours together."""
    d, svcs = tmp_path / "seg-a" / "shard-0", DICTS["i8"]
    # rewrites hour 2 and holds hour 30: its rect spans both neighbours
    bridge = _cat(_rows(range(10), [2], version=2), _rows(range(10), [30]))
    srcs = [
        _read(_write(d, "part-0", _rows(range(10), range(0, 5)), svcs)),
        _read(_write(d, "part-1", bridge, svcs)),
        _read(_write(d, "part-2", _rows(range(10), range(6, 12)), svcs)),
    ]
    # whole range: the bridge part's rect meets both neighbours
    _assert_as_always_dedup(
        srcs, [0, 1, 2], monkeypatch, begin=T0, end=T0 + 40 * HOUR
    )
    # hours 6..11: the bridge has no row there, so part-2 stands alone
    _assert_as_always_dedup(
        srcs, [], monkeypatch, begin=T0 + 6 * HOUR, end=T0 + 12 * HOUR
    )


def test_dedup_components_pairs_only_inside_a_scope():
    def span(scope, s0, s1, h0, h1, unique=True):
        return KeySpan(
            scope,
            KeyInterval.conservative(s0, s1, T0 + h0 * HOUR, T0 + h1 * HOUR),
            unique,
        )

    comps = measure_exec._dedup_components
    a = [span("x", 0, 9, 0, 5), span("x", 0, 9, 6, 9), span("y", 0, 9, 0, 5)]
    assert comps(a) == []
    # a chain: 0-1 and 1-2 intersect, 0-2 do not; 3 is another scope
    chain = [
        span("x", 0, 9, 0, 5), span("x", 0, 9, 5, 8), span("x", 0, 9, 8, 9),
        span("y", 0, 9, 0, 9),
    ]
    assert comps(chain) == [[0, 1, 2]]
    assert comps([span("x", 0, 9, 0, 5, unique=False)]) == [[0]]
    assert comps(a + [None]) == [[0, 1, 2, 3]]
    assert comps([]) == []


# -- the per-part fact -----------------------------------------------------------


def test_flush_of_duplicate_writes_is_not_unique_and_its_merge_is(tmp_path):
    from banyandb_tpu.api import Catalog, Group, ResourceOpts, SchemaRegistry
    from banyandb_tpu.models.measure import MeasureEngine

    reg = SchemaRegistry(tmp_path / "r")
    reg.create_group(Group("g", Catalog.MEASURE, ResourceOpts(shard_num=1)))
    reg.create_measure(
        Measure(
            "g", "m", (TagSpec("svc", TagType.STRING),),
            (FieldSpec("v", FieldType.INT),), Entity(("svc",)),
        )
    )
    engine = MeasureEngine(reg, tmp_path / "r" / "data")
    n = 50
    ts = T0 + np.arange(n)
    for version in (1, 2):  # the same keys twice into ONE memtable
        engine.write_columns(
            "g", "m", ts_millis=ts, tags={"svc": ["s"] * n},
            fields={"v": np.full(n, version)},
            versions=np.full(n, version, dtype=np.int64),
        )
    engine.flush()
    engine.write_columns(
        "g", "m", ts_millis=ts + n, tags={"svc": ["s"] * n},
        fields={"v": np.ones(n)}, versions=np.ones(n, dtype=np.int64),
    )
    engine.flush()
    shard = engine._tsdb("g").segments[0].shards[0]
    facts = sorted(
        (p.total_count, p.meta["unique_keys"]) for p in shard.parts
    )
    assert facts == [(n, True), (2 * n, False)]

    req = QueryRequest(
        ("g",), "m", TimeRange(T0, T0 + 2 * n),
        group_by=GroupBy(("svc",)), agg=Aggregation("sum", "v"), trace=True,
    )
    before = engine.query(req)
    assert before.values["sum(v)"] == [2.0 * n + n]  # version 2 won

    assert shard.merge(min_merge=2, max_parts=2) is not None
    (merged,) = shard.parts
    assert merged.total_count == 2 * n and merged.meta["unique_keys"] is True
    after = engine.query(req)
    assert after.values["sum(v)"] == before.values["sum(v)"]

    def share(res):
        def walk(s):
            if s["name"] == "gather":
                return s["tags"]["proven_unique_share"]
            for c in s["children"]:
                got = walk(c)
                if got is not None:
                    return got

        return walk(res.trace["span_tree"])

    # the doubled part alone was sorted (2n of 3n rows), then nothing
    assert share(before) == round(100.0 / 3, 3)
    assert share(after) == 100.0


def test_series_filtered_sources_keep_their_span(tmp_path):
    """`_series_rows` hands on a row subset: span and proof stay."""
    from banyandb_tpu.api import Catalog, Group, ResourceOpts, SchemaRegistry
    from banyandb_tpu.models.measure import MeasureEngine

    reg = SchemaRegistry(tmp_path / "r")
    reg.create_group(Group("g", Catalog.MEASURE, ResourceOpts(shard_num=2)))
    reg.create_measure(
        Measure(
            "g", "m", (TagSpec("svc", TagType.STRING),),
            (FieldSpec("v", FieldType.INT),), Entity(("svc",)),
        )
    )
    engine = MeasureEngine(reg, tmp_path / "r" / "data")
    n = 400
    for k in range(2):  # two time-disjoint flushes
        engine.write_columns(
            "g", "m", ts_millis=T0 + k * n + np.arange(n),
            tags={"svc": [f"s{i % 8}" for i in range(n)]},
            fields={"v": np.ones(n)}, versions=np.ones(n, dtype=np.int64),
        )
        engine.flush()
    engine.write_columns(  # and hot rows, newer still
        "g", "m", ts_millis=T0 + 2 * n + np.arange(n),
        tags={"svc": [f"s{i % 8}" for i in range(n)]},
        fields={"v": np.ones(n)}, versions=np.ones(n, dtype=np.int64),
    )
    req = QueryRequest(
        ("g",), "m", TimeRange(T0, T0 + 3 * n),
        criteria=Condition("svc", "eq", "s3"),
        agg=Aggregation("count", "v"), trace=True,
    )
    srcs = engine.gather_query_sources(req)
    parts = [s for s in srcs if s.cache_key[0] == "part_read"]
    hot = [s for s in srcs if s.cache_key[0] == "mem"]
    assert len(parts) == 2 and len(hot) == 1
    shards = engine._tsdb("g").segments[0].shards
    # one series lives in one shard: the one whose parts were read
    (home,) = [
        sh for sh in shards
        if str(sh.root) == str(Path(parts[0].cache_key[1]).parent)
    ]
    for s in parts:
        assert "sfilter" in s.cache_key  # a subset of the part's rows
        assert s.ts.shape[0] == n // 8
        assert s.key_span.unique is True
        assert s.key_span.scope == str(home.root)
    assert "sfilter" in hot[0].cache_key
    assert hot[0].key_span.unique is False
    assert hot[0].key_span.scope == str(home.root)
    res = engine.query(req)
    assert res.values["count"] == [3.0 * n / 8]


@pytest.mark.parametrize("flush_the_rewrite", [False, True], ids=["hot", "flushed"])
def test_rewrite_of_a_flushed_key_wins_wherever_it_lives(tmp_path, flush_the_rewrite):
    """Ingest goes on under queries: part A holds the keys at v1, the
    rewrite at v2 sits in the memtable (or in a later part).  Either way
    its span meets A's in A's own scope, so the two are sorted together."""
    from banyandb_tpu.api import Catalog, Group, ResourceOpts, SchemaRegistry
    from banyandb_tpu.models.measure import MeasureEngine

    reg = SchemaRegistry(tmp_path / "r")
    reg.create_group(Group("g", Catalog.MEASURE, ResourceOpts(shard_num=2)))
    reg.create_measure(
        Measure(
            "g", "m", (TagSpec("svc", TagType.STRING),),
            (FieldSpec("v", FieldType.INT),), Entity(("svc",)),
        )
    )
    engine = MeasureEngine(reg, tmp_path / "r" / "data")
    n = 240
    svcs = [f"s{i % 6}" for i in range(n)]
    ts = T0 + np.arange(n)
    engine.write_columns(
        "g", "m", ts_millis=ts, tags={"svc": svcs},
        fields={"v": np.ones(n)}, versions=np.ones(n, dtype=np.int64),
    )
    engine.flush()
    half = n // 2  # the first half of the keys again, at v2
    engine.write_columns(
        "g", "m", ts_millis=ts[:half], tags={"svc": svcs[:half]},
        fields={"v": np.full(half, 10.0)},
        versions=np.full(half, 2, dtype=np.int64),
    )
    if flush_the_rewrite:
        engine.flush()
    res = engine.query(
        QueryRequest(
            ("g",), "m", TimeRange(T0, T0 + n),
            agg=Aggregation("sum", "v"), trace=True,
        )
    )
    assert res.values["sum(v)"] == [10.0 * half + (n - half)]
    srcs = engine.gather_query_sources(
        QueryRequest(("g",), "m", TimeRange(T0, T0 + n), agg=Aggregation("sum", "v"))
    )
    assert all(s.key_span is not None for s in srcs)
    assert all(s.key_span.unique for s in srcs) is flush_the_rewrite
