"""Regression tests for review findings."""

import threading

import numpy as np
import pytest

from banyandb_tpu.api import (
    Aggregation,
    Catalog,
    Condition,
    DataPointValue,
    Entity,
    FieldSpec,
    FieldType,
    Group,
    GroupBy,
    Measure,
    QueryRequest,
    ResourceOpts,
    SchemaRegistry,
    TagSpec,
    TagType,
    TimeRange,
    Top,
    WriteRequest,
)
from banyandb_tpu.models.measure import MeasureEngine

T0 = 1_700_000_000_000


def _mk_engine(tmp_path, tags, shard_num=1):
    reg = SchemaRegistry(tmp_path)
    reg.create_group(Group("g", Catalog.MEASURE, ResourceOpts(shard_num=shard_num)))
    reg.create_measure(
        Measure(
            group="g",
            name="m",
            tags=tags,
            fields=(FieldSpec("v", FieldType.FLOAT),),
            entity=Entity((tags[0].name,)),
        )
    )
    return MeasureEngine(reg, tmp_path / "data")


def test_same_num_groups_different_radices_no_stale_kernel(tmp_path):
    """Two queries sharing num_groups but with different per-tag radix
    splits must not reuse each other's compiled group-key composition."""
    eng = _mk_engine(
        tmp_path, (TagSpec("a", TagType.STRING), TagSpec("b", TagType.STRING))
    )
    # Phase 1: dict sizes (2, 2) -> num_groups 4
    pts = [
        DataPointValue(T0 + i, {"a": f"a{i%2}", "b": f"b{i%2}"}, {"v": 1.0}, version=1)
        for i in range(8)
    ]
    eng.write(WriteRequest("g", "m", tuple(pts)))
    r1 = eng.query(
        QueryRequest(("g",), "m", TimeRange(T0, T0 + 100),
                     group_by=GroupBy(("a", "b")), agg=Aggregation("count", "v"))
    )
    total1 = sum(r1.values["count"])
    assert total1 == 8

    # Phase 2: same num_groups=4 via sizes (4, 1)
    eng2 = _mk_engine(
        tmp_path / "x", (TagSpec("a", TagType.STRING), TagSpec("b", TagType.STRING))
    )
    pts = [
        DataPointValue(T0 + i, {"a": f"a{i%4}", "b": "b0"}, {"v": 1.0}, version=1)
        for i in range(8)
    ]
    eng2.write(WriteRequest("g", "m", tuple(pts)))
    r2 = eng2.query(
        QueryRequest(("g",), "m", TimeRange(T0, T0 + 100),
                     group_by=GroupBy(("a", "b")), agg=Aggregation("count", "v"))
    )
    got = dict(zip(r2.groups, r2.values["count"]))
    assert got == {(f"a{i}", "b0"): 2.0 for i in range(4)}


def test_int_tag_range_predicate_beyond_int32(tmp_path):
    """Range predicates on INT tags with 64-bit values must be exact."""
    eng = _mk_engine(
        tmp_path, (TagSpec("svc", TagType.STRING), TagSpec("bytes", TagType.INT))
    )
    big = 5_000_000_000  # > 2**31
    pts = [
        DataPointValue(T0 + i, {"svc": "s", "bytes": big + i}, {"v": 1.0}, version=1)
        for i in range(10)
    ]
    eng.write(WriteRequest("g", "m", tuple(pts)))
    eng.flush()
    r = eng.query(
        QueryRequest(("g",), "m", TimeRange(T0, T0 + 100),
                     criteria=Condition("bytes", "ge", big + 7),
                     agg=Aggregation("count", "v"))
    )
    assert r.values["count"][0] == 3


def test_top_ranks_by_its_own_field(tmp_path):
    """Top.field_name must drive the ranking even when agg targets another
    field (ranking falls back to mean of the top field)."""
    eng = _mk_engine(tmp_path, (TagSpec("svc", TagType.STRING),))
    reg = eng.registry
    reg.create_measure(
        Measure(
            group="g", name="m2",
            tags=(TagSpec("svc", TagType.STRING),),
            fields=(FieldSpec("errors", FieldType.FLOAT), FieldSpec("lat", FieldType.FLOAT)),
            entity=Entity(("svc",)),
        )
    )
    # svc-0: high errors, low lat. svc-1: low errors, high lat.
    pts = [
        DataPointValue(T0 + 1, {"svc": "svc-0"}, {"errors": 100.0, "lat": 1.0}, version=1),
        DataPointValue(T0 + 2, {"svc": "svc-1"}, {"errors": 1.0, "lat": 100.0}, version=1),
    ]
    eng.write(WriteRequest("g", "m2", tuple(pts)))
    r = eng.query(
        QueryRequest(("g",), "m2", TimeRange(T0, T0 + 100),
                     group_by=GroupBy(("svc",)),
                     agg=Aggregation("sum", "errors"),
                     top=Top(1, "lat"))
    )
    assert r.groups == [("svc-1",)]  # ranked by lat, not by sum(errors)


def test_concurrent_write_and_flush_loses_nothing(tmp_path):
    eng = _mk_engine(tmp_path, (TagSpec("svc", TagType.STRING),))
    N = 400
    errs = []

    def writer(base):
        try:
            for i in range(N):
                eng.write(
                    WriteRequest(
                        "g", "m",
                        (DataPointValue(T0 + base + i, {"svc": "s"}, {"v": 1.0}, version=1),),
                    )
                )
        except Exception as e:  # pragma: no cover
            errs.append(e)

    def flusher():
        try:
            for _ in range(20):
                eng.flush()
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [
        threading.Thread(target=writer, args=(0,)),
        threading.Thread(target=writer, args=(10_000,)),
        threading.Thread(target=flusher),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    eng.flush()
    assert not errs
    r = eng.query(
        QueryRequest(("g",), "m", TimeRange(T0, T0 + 20_000),
                     agg=Aggregation("count", "v"))
    )
    assert r.values["count"][0] == 2 * N


def test_orphan_part_dir_cleaned_on_reopen(tmp_path):
    eng = _mk_engine(tmp_path, (TagSpec("svc", TagType.STRING),))
    eng.write(
        WriteRequest("g", "m", (DataPointValue(T0, {"svc": "s"}, {"v": 1.0}, version=1),))
    )
    eng.flush()
    # Simulate a crash between part write and snapshot publish: an orphan
    # dir with the NEXT epoch's name.
    shard_dirs = list((tmp_path / "data" / "measure" / "g").glob("seg-*/shard-*"))
    orphan = shard_dirs[0] / "part-0000000000000002"
    orphan.mkdir()
    (orphan / "junk").write_bytes(b"x")

    reg2 = SchemaRegistry(tmp_path)
    eng2 = MeasureEngine(reg2, tmp_path / "data")
    eng2.write(
        WriteRequest("g", "m", (DataPointValue(T0 + 1, {"svc": "s"}, {"v": 2.0}, version=1),))
    )
    assert eng2.flush()  # must not FileExistsError
    r = eng2.query(
        QueryRequest(("g",), "m", TimeRange(T0, T0 + 100), agg=Aggregation("sum", "v"))
    )
    assert r.values["sum(v)"][0] == 3.0


def test_raw_query_typo_tag_raises(tmp_path):
    eng = _mk_engine(tmp_path, (TagSpec("svc", TagType.STRING),))
    eng.write(
        WriteRequest("g", "m", (DataPointValue(T0, {"svc": "s"}, {"v": 1.0}, version=1),))
    )
    with pytest.raises(KeyError):
        eng.query(
            QueryRequest(("g",), "m", TimeRange(T0, T0 + 100),
                         criteria=Condition("svcc", "eq", "s"))
        )


# -- a gather retried while merges remove the parts it names (PR 30) -----------


def _vanishing(paths):
    """A read that finds each of `paths` gone once, in turn, then works."""
    todo = list(paths)

    def read():
        if todo:
            raise FileNotFoundError(2, "No such file or directory", todo.pop(0))
        return "rows"

    return read


def test_gather_retries_as_long_as_another_part_was_merged_away():
    from banyandb_tpu.models.measure import _retry_merged_away

    # five merges under one query (the old fixed count gave up at three)
    parts = [f"/d/shard-{k}/part-{k:016x}/timestamps.bin" for k in range(5)]
    assert _retry_merged_away(_vanishing(parts)) == "rows"


def test_gather_raises_when_the_same_part_is_missing_twice():
    from banyandb_tpu.models.measure import _retry_merged_away

    # the fresh snapshot still lists it: a loss, not a merge
    lost = "/d/shard-0/part-0000000000000003"
    read = _vanishing([f"{lost}/timestamps.bin", f"{lost}/meta.json"])
    with pytest.raises(FileNotFoundError):
        _retry_merged_away(read)
    # an error that names no file gets the old three attempts
    calls = []

    def unnamed():
        calls.append(1)
        raise FileNotFoundError("gone")

    with pytest.raises(FileNotFoundError):
        _retry_merged_away(unnamed)
    assert len(calls) == 3
