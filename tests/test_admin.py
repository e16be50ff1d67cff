"""Ops subsystems: backup/restore, protector, metrics, query tracing."""

import numpy as np
import pytest

from banyandb_tpu.admin.backup import LocalDirFS, backup, list_backups, restore
from banyandb_tpu.admin.metrics import Meter, SelfMeasureSink
from banyandb_tpu.admin.protector import MemoryProtector, ServerBusy
from banyandb_tpu.api import (
    Aggregation,
    Catalog,
    DataPointValue,
    Entity,
    FieldSpec,
    FieldType,
    Group,
    Measure,
    QueryRequest,
    ResourceOpts,
    SchemaRegistry,
    TagSpec,
    TagType,
    TimeRange,
    WriteRequest,
)
from banyandb_tpu.models.measure import MeasureEngine

T0 = 1_700_000_000_000


def _engine(tmp_path):
    reg = SchemaRegistry(tmp_path)
    reg.create_group(Group("g", Catalog.MEASURE, ResourceOpts(shard_num=1)))
    reg.create_measure(
        Measure("g", "m", (TagSpec("svc", TagType.STRING),),
                (FieldSpec("v", FieldType.FLOAT),), Entity(("svc",)))
    )
    eng = MeasureEngine(reg, tmp_path / "data")
    eng.write(WriteRequest("g", "m", tuple(
        DataPointValue(T0 + i, {"svc": f"s{i%3}"}, {"v": float(i)}, version=1)
        for i in range(100)
    )))
    eng.flush()
    return eng


def test_backup_restore_roundtrip(tmp_path):
    eng = _engine(tmp_path / "src")
    remote = LocalDirFS(tmp_path / "remote")
    stamp = backup(tmp_path / "src", remote, flush=lambda: eng.flush())
    assert list_backups(remote) == [stamp]

    n = restore(remote, stamp, tmp_path / "restored")
    assert n > 0
    reg2 = SchemaRegistry(tmp_path / "restored")
    eng2 = MeasureEngine(reg2, tmp_path / "restored" / "data")
    r = eng2.query(QueryRequest(("g",), "m", TimeRange(T0, T0 + 1000),
                                agg=Aggregation("sum", "v")))
    assert r.values["sum(v)"][0] == sum(range(100))


def test_restore_refuses_nonempty_target(tmp_path):
    eng = _engine(tmp_path / "src")
    remote = LocalDirFS(tmp_path / "remote")
    stamp = backup(tmp_path / "src", remote)
    with pytest.raises(FileExistsError):
        restore(remote, stamp, tmp_path / "src")


def test_protector_admits_and_rejects():
    p = MemoryProtector(limit_bytes=1, max_wait_s=0.1)  # below current RSS
    with pytest.raises(ServerBusy):
        p.acquire(1024)
    p2 = MemoryProtector(limit_bytes=None)  # unlimited
    p2.acquire(1 << 20)
    p2.release(1 << 20)
    # HBM budget is tracked independently of RSS
    p3 = MemoryProtector(hbm_limit_bytes=100, max_wait_s=0.05)
    p3.acquire(80, hbm=True)
    with pytest.raises(ServerBusy):
        p3.acquire(30, hbm=True)
    p3.release(80, hbm=True)
    p3.acquire(30, hbm=True)


def test_meter_and_prometheus_text():
    m = Meter("bydb")
    m.counter_add("writes", 5, {"group": "g"})
    m.gauge_set("parts", 3)
    m.observe("query_ms", 12.5)
    m.observe("query_ms", 7.5)
    text = m.prometheus_text()
    assert 'bydb_writes_total{group="g"} 5' in text
    assert "bydb_parts 3" in text
    assert "bydb_query_ms_count 2" in text
    assert "bydb_query_ms_sum 20.0" in text


def test_self_measure_sink(tmp_path):
    eng = _engine(tmp_path)
    meter = Meter()
    meter.counter_add("writes", 42)
    sink = SelfMeasureSink(meter, eng)
    n = sink.flush(now_millis=T0)
    assert n == 1
    r = eng.query(QueryRequest(("_monitoring",), "instruments",
                               TimeRange(T0, T0 + 1), limit=10))
    assert r.data_points[0]["fields"]["value"] == 42.0


def test_query_trace_in_band(tmp_path):
    eng = _engine(tmp_path)
    r = eng.query(QueryRequest(("g",), "m", TimeRange(T0, T0 + 1000),
                               agg=Aggregation("count", "v"), trace=True))
    assert r.trace is not None
    # one trace format: the span tree (the flat `spans` list is gone)
    assert "spans" not in r.trace
    tree = r.trace["span_tree"]
    names = [s["name"] for s in tree["children"]]
    assert names == ["analyze", "planner", "part_gather", "execute"]
    part_gather = tree["children"][2]
    assert part_gather["tags"]["rows"] == 100
    assert part_gather["tags"]["sources"] >= 1
    assert tree["duration_ms"] > 0
    # trace off by default
    r2 = eng.query(QueryRequest(("g",), "m", TimeRange(T0, T0 + 1000),
                                agg=Aggregation("count", "v")))
    assert r2.trace is None
