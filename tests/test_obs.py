"""The self-observability plane (docs/observability.md): hierarchical
tracer span trees + cross-node merge, exponential-bucket histogram math,
Prometheus exposition goldens, the slow-query flight recorder, and the
configurable slow threshold."""

import json

import numpy as np
import pytest

from banyandb_tpu.obs import (
    Histogram,
    Meter,
    SlowQueryRecorder,
    Span,
    Tracer,
    find_span,
)
from banyandb_tpu.obs import prom as obs_prom
from banyandb_tpu.obs.metrics import DEFAULT_BOUNDS, quantile_from_buckets
from banyandb_tpu.obs.tracer import NOOP_TRACER, iter_spans

T0 = 1_700_000_000_000


# -- span trees --------------------------------------------------------------


def _shape(node):
    """Structure golden: names + tag keys + child shapes, durations out."""
    return {
        "name": node["name"],
        "tags": sorted(node.get("tags", {})),
        "children": [_shape(c) for c in node.get("children", ())],
    }


def test_span_tree_shape_golden():
    tr = Tracer("root")
    with tr.span("plan") as p:
        p.tag("nodes", ["a", "b"])
    with tr.span("scatter:n0") as s:
        s.tag("shards", [0, 1])
        with tr.span("inner"):
            pass
    with tr.span("merge"):
        pass
    tree = tr.finish()
    # the root says whether its thread ran or waited, always; the rest
    # only for a request that asked for its tree (tests/test_tracing.py)
    assert _shape(tree) == {
        "name": "root",
        "tags": ["minflt", "off_cpu_ms", "tid"],
        "children": [
            {"name": "plan", "tags": ["nodes"], "children": []},
            {
                "name": "scatter:n0",
                "tags": ["shards"],
                "children": [{"name": "inner", "tags": [], "children": []}],
            },
            {"name": "merge", "tags": [], "children": []},
        ],
    }
    # durations: every span closed, parent covers children
    for s in iter_spans(tree):
        assert s["duration_ms"] >= 0
    assert tree["duration_ms"] >= max(
        c["duration_ms"] for c in tree["children"]
    )


def test_span_error_capture():
    tr = Tracer("root")
    with pytest.raises(ValueError):
        with tr.span("boom"):
            raise ValueError("no good")
    tree = tr.finish()
    assert tree["children"][0]["error"] == "ValueError: no good"


def test_cross_node_merge_ordering():
    """Attached node subtrees keep scatter order under their scatter
    spans — the liaison merge contract."""
    node_trees = [
        {"name": f"data:n{i}", "duration_ms": 1.0, "tags": {}, "children": []}
        for i in (2, 0, 1)  # deliberately not sorted
    ]
    tr = Tracer("liaison:measure")
    for nt in node_trees:
        with tr.span(f"scatter:{nt['name'][5:]}") as sp:
            sp.attach(nt)
    tree = tr.finish()
    scatter_names = [c["name"] for c in tree["children"]]
    assert scatter_names == ["scatter:n2", "scatter:n0", "scatter:n1"]
    grafted = [c["children"][0]["name"] for c in tree["children"]]
    assert grafted == ["data:n2", "data:n0", "data:n1"]
    # find_span resolves into grafted (plain-dict) subtrees too
    assert find_span(tree, "data:n1")["duration_ms"] == 1.0


def test_noop_tracer_absorbs_everything():
    t = NOOP_TRACER
    with t.span("x") as s:
        s.tag("k", 1).child("y").error("e")
        s.attach({"name": "z"})
    assert t.finish() == {}


def test_span_attach_ignores_empty():
    s = Span("root")
    s.attach(None)
    s.attach({})
    assert s.to_dict()["children"] == []


# -- exponential-bucket histogram math ---------------------------------------


def test_histogram_quantile_vs_exact_on_known_sample():
    """The bucket-math bound: the log-interpolated estimate stays within
    one bucket factor (2x) of the exact quantile; on this smooth sample
    it lands much closer."""
    rng = np.random.default_rng(7)
    sample = np.exp(rng.normal(2.5, 1.0, 20_000))  # ms-scale lognormal
    h = Histogram()
    for v in sample:
        h.observe(float(v))
    for q in (0.5, 0.9, 0.99):
        exact = float(np.quantile(sample, q))
        est = h.quantile(q)
        assert exact / 2 <= est <= exact * 2, (q, exact, est)
        # interpolation beats the raw bucket bound comfortably here
        assert abs(est - exact) / exact < 0.35, (q, exact, est)


def test_histogram_count_sum_and_overflow_bucket():
    h = Histogram(bounds=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 3.0, 100.0):
        h.observe(v)
    count, total, counts = h.snapshot()
    assert count == 4 and total == pytest.approx(105.0)
    assert counts == (1, 1, 1, 1)  # last is the +Inf bucket
    assert h.quantile(1.0) == 4.0  # +Inf bucket reports the last bound


def test_quantile_from_buckets_empty():
    assert quantile_from_buckets(DEFAULT_BOUNDS, [0] * 27, 0, 0.5) == 0.0


# -- Prometheus exposition ---------------------------------------------------


def test_prometheus_exposition_golden_for_buckets():
    m = Meter("bydb")
    h = m.histogram("lat_ms", {"stage": "gather"}, bounds=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 1.6, 3.0, 9.0):
        h.observe(v)
    text = m.prometheus_text()
    assert text.splitlines() == [
        'bydb_lat_ms_bucket{stage="gather",le="1"} 1',
        'bydb_lat_ms_bucket{stage="gather",le="2"} 3',
        'bydb_lat_ms_bucket{stage="gather",le="4"} 4',
        'bydb_lat_ms_bucket{stage="gather",le="+Inf"} 5',
        'bydb_lat_ms_count{stage="gather"} 5',
        'bydb_lat_ms_sum{stage="gather"} 15.6',
    ]


def test_prometheus_legacy_lines_unchanged():
    """The pre-bucket surface (counters, gauges, _count/_sum) keeps its
    exact shape — dashboards built on it must not break."""
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


def test_prom_scrape_roundtrip_recovers_quantiles():
    """Live handle -> exposition text -> obs.prom scrape: the recovered
    quantile equals the handle's own estimate (shared inversion)."""
    m = Meter("banyandb")
    h = m.histogram("query_stage_ms", {"stage": "merge"})
    rng = np.random.default_rng(3)
    for v in np.exp(rng.normal(1.0, 0.8, 5000)):
        h.observe(float(v))
    series = obs_prom.histogram_series(
        m.prometheus_text(), "banyandb_query_stage_ms"
    )
    entry = series[(("stage", "merge"),)]
    assert entry["count"] == 5000
    for q in (0.5, 0.99):
        assert obs_prom.quantile(entry, q) == pytest.approx(h.quantile(q))
    breakdown = obs_prom.stage_breakdown(m.prometheus_text())
    assert breakdown["merge"]["count"] == 5000
    assert breakdown["merge"]["p50_ms"] == pytest.approx(
        h.quantile(0.5), rel=1e-3
    )


def test_meter_histogram_handle_identity():
    m = Meter()
    h1 = m.histogram("x", {"a": "1"})
    h2 = m.histogram("x", {"a": "1"})
    h3 = m.histogram("x", {"a": "2"})
    assert h1 is h2 and h1 is not h3


# -- slow-query flight recorder ----------------------------------------------


def test_slowlog_capture_and_eviction():
    r = SlowQueryRecorder(capacity=4)
    for i in range(6):
        r.record({"name": f"q{i}", "duration_ms": float(i)})
    assert len(r) == 4
    entries = r.entries()
    # newest first; the two oldest evicted
    assert [e["name"] for e in entries] == ["q5", "q4", "q3", "q2"]
    # seq survives eviction (consumers can detect the gap)
    assert [e["seq"] for e in entries] == [6, 5, 4, 3]
    assert all("ts" in e for e in entries)
    assert [e["name"] for e in r.entries(limit=2)] == ["q5", "q4"]
    assert r.clear() == 4
    assert r.entries() == []


def test_slowlog_capacity_env(monkeypatch):
    monkeypatch.setenv("BYDB_SLOWLOG_CAPACITY", "2")
    r = SlowQueryRecorder()
    assert r.capacity == 2
    monkeypatch.setenv("BYDB_SLOWLOG_CAPACITY", "bogus")
    assert SlowQueryRecorder().capacity == 128


# -- slow threshold configuration (satellite: accesslog) ---------------------


def test_accesslog_slow_threshold_env(tmp_path, monkeypatch):
    from banyandb_tpu.admin.accesslog import AccessLog

    monkeypatch.delenv("BYDB_SLOW_QUERY_MS", raising=False)
    log = AccessLog(tmp_path / "a.log")
    assert log.slow_query_ms == AccessLog.DEFAULT_SLOW_QUERY_MS
    log.close()

    monkeypatch.setenv("BYDB_SLOW_QUERY_MS", "12.5")
    log = AccessLog(tmp_path / "b.log")
    assert log.slow_query_ms == 12.5
    log.log_query("g", "m", 20.0)  # over: slow-marked
    log.log_query("g", "m", 5.0)  # under
    log.close()
    recs = [
        json.loads(line)
        for line in (tmp_path / "b.log").read_text().splitlines()
    ]
    assert recs[0].get("slow") is True
    assert "slow" not in recs[1]

    # explicit argument beats the env
    log = AccessLog(tmp_path / "c.log", slow_query_ms=99.0)
    assert log.slow_query_ms == 99.0
    log.close()


def test_server_config_slow_query_flag(monkeypatch):
    from banyandb_tpu.server import build_config

    monkeypatch.delenv("BYDB_SLOW_QUERY_MS", raising=False)
    s = build_config().load(["--root", "/tmp/x", "--slow-query-ms", "42"])
    assert s.slow_query_ms == 42.0
    monkeypatch.setenv("BYDB_SLOW_QUERY_MS", "17")
    s = build_config().load(["--root", "/tmp/x"])
    assert s.slow_query_ms == 17.0


# -- server-level: slowlog topic + traced responses --------------------------


@pytest.fixture()
def slow_server(tmp_path):
    from banyandb_tpu.server import StandaloneServer

    srv = StandaloneServer(
        tmp_path / "srv", port=0, slow_query_ms=0.0
    )
    srv.start()
    try:
        yield srv
    finally:
        srv.stop()


def _seed_measure(srv):
    from banyandb_tpu.api import (
        Catalog,
        DataPointValue,
        Entity,
        FieldSpec,
        FieldType,
        Group,
        Measure,
        ResourceOpts,
        TagSpec,
        TagType,
        WriteRequest,
    )

    srv.registry.create_group(
        Group("g", Catalog.MEASURE, ResourceOpts(shard_num=1))
    )
    srv.registry.create_measure(
        Measure("g", "m", (TagSpec("svc", TagType.STRING),),
                (FieldSpec("v", FieldType.INT),), Entity(("svc",)))
    )
    srv.measure.write(WriteRequest("g", "m", tuple(
        DataPointValue(T0 + i, {"svc": f"s{i % 3}"}, {"v": i}, version=1)
        for i in range(50)
    )))


def test_slow_query_reaches_flight_recorder_and_cli(slow_server, capsys):
    from banyandb_tpu import cli

    srv = slow_server
    _seed_measure(srv)
    ql = f"SELECT sum(v) FROM MEASURE m IN g TIME BETWEEN {T0} AND {T0 + 100} GROUP BY svc"
    srv.bus.handle("bydbql", {"ql": ql})

    # threshold 0.0: every query is slow; the record carries the tree
    entries = srv.bus.handle("slowlog", {})["entries"]
    assert entries and entries[0]["ql"] == ql
    assert entries[0]["duration_ms"] > 0
    tree = entries[0]["span_tree"]
    assert tree["name"] == "standalone:measure"
    assert find_span(tree, "part_gather") is not None
    assert find_span(tree, "reduce") is not None
    assert "GroupByAggregate" in (entries[0]["plan"] or "")

    # the cli surface renders the same entries over the wire
    assert cli.main(["--addr", srv.addr, "slowlog", "--limit", "5"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["entries"][0]["ql"] == ql
    assert out["threshold_ms"] == 0.0

    # clear drains the ring
    assert cli.main(["--addr", srv.addr, "slowlog", "--clear"]) == 0
    assert srv.bus.handle("slowlog", {})["entries"] == []


def test_trace_response_carries_span_tree_and_metrics_buckets(slow_server):
    srv = slow_server
    _seed_measure(srv)
    ql = (
        f"SELECT sum(v) FROM MEASURE m IN g TIME BETWEEN {T0} AND "
        f"{T0 + 100} GROUP BY svc"
    )
    from banyandb_tpu.api.model import (
        Aggregation,
        GroupBy,
        QueryRequest,
        TimeRange,
    )
    from banyandb_tpu.cluster import serde

    req = QueryRequest(
        ("g",), "m", TimeRange(T0, T0 + 100),
        group_by=GroupBy(("svc",)), agg=Aggregation("sum", "v"), trace=True,
    )
    r = srv.bus.handle(
        "measure-query-raw", {"request": serde.query_request_to_json(req)}
    )
    tree = r["result"]["trace"]["span_tree"]
    assert tree["name"] == "standalone:measure"
    reduce_span = find_span(tree, "reduce")
    assert reduce_span is not None and "device_ms" in reduce_span["tags"]
    # legacy trace keys stay (test_admin pins them too)
    assert r["result"]["trace"]["plan"]
    # /metrics exposes bucketed stage histograms
    text = srv.bus.handle("metrics", {})["prometheus"]
    for stage in ("gather", "device_execute", "merge"):
        assert f'banyandb_query_stage_ms_bucket{{stage="{stage}"' in text
    assert 'banyandb_query_ms_bucket{engine="measure"' in text


def test_http_gateway_slowlog_and_metrics(tmp_path):
    import urllib.request

    from banyandb_tpu.server import StandaloneServer

    srv = StandaloneServer(
        tmp_path / "srv", port=0, http_port=0, slow_query_ms=0.0
    )
    srv.start()
    try:
        _seed_measure(srv)
        ql = (
            f"SELECT sum(v) FROM MEASURE m IN g TIME BETWEEN {T0} AND "
            f"{T0 + 100} GROUP BY svc"
        )
        srv.bus.handle("bydbql", {"ql": ql})
        base = f"http://127.0.0.1:{srv.http.port}"
        with urllib.request.urlopen(f"{base}/api/v1/slowlog?limit=3") as r:
            body = json.loads(r.read())
        assert body["entries"][0]["ql"] == ql
        assert body["entries"][0]["span_tree"]["name"] == "standalone:measure"
        with urllib.request.urlopen(f"{base}/metrics") as r:
            text = r.read().decode()
        assert "banyandb_query_stage_ms_bucket" in text
    finally:
        srv.stop()


# -- wire rendering ----------------------------------------------------------


def test_fill_trace_renders_nested_span_tree():
    from banyandb_tpu.api import pb, wire
    from banyandb_tpu.api.model import QueryResult

    res = QueryResult()
    res.trace = {
        "span_tree": {
            "name": "liaison:measure",
            "duration_ms": 12.5,
            "tags": {"combine": "host"},
            "children": [
                {
                    "name": "scatter:n0",
                    "duration_ms": 8.0,
                    "tags": {},
                    "children": [
                        {
                            "name": "data:n0",
                            "duration_ms": 7.0,
                            "tags": {"device_ms": 3.0},
                            "children": [],
                        }
                    ],
                }
            ],
        },
        "plan": "Limit(100)",
    }
    out = pb.measure_query_pb2.QueryResponse()
    wire.fill_trace(out, res)
    by_msg = {s.message: s for s in out.trace.spans}
    root = by_msg["liaison:measure"]
    assert root.duration == int(12.5 * 1e6)  # ns on the wire
    assert root.children[0].message == "scatter:n0"
    node = root.children[0].children[0]
    assert node.message == "data:n0"
    assert {t.key: t.value for t in node.tags} == {"device_ms": "3.0"}
    assert "plan: Limit(100)" in by_msg  # flat keys keep their rendering


# -- self-measure sink -------------------------------------------------------


def test_self_measure_sink_histogram_quantiles(tmp_path):
    from banyandb_tpu.admin.metrics import SelfMeasureSink
    from banyandb_tpu.api import (
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
    from banyandb_tpu.api.model import QueryRequest, TimeRange
    from banyandb_tpu.models.measure import MeasureEngine

    reg = SchemaRegistry(tmp_path)
    reg.create_group(Group("g", Catalog.MEASURE, ResourceOpts(shard_num=1)))
    reg.create_measure(
        Measure("g", "m", (TagSpec("svc", TagType.STRING),),
                (FieldSpec("v", FieldType.FLOAT),), Entity(("svc",)))
    )
    eng = MeasureEngine(reg, tmp_path / "data")
    meter = Meter()
    h = meter.histogram("lat_ms")
    for v in (1.0, 2.0, 3.0, 100.0):
        h.observe(v)
    sink = SelfMeasureSink(meter, eng, interval_s=3600)
    n = sink.flush(now_millis=T0)
    # count + sum + p50 + p99
    assert n == 4
    r = eng.query(QueryRequest(("_monitoring",), "instruments",
                               TimeRange(T0, T0 + 1), limit=10))
    kinds = {dp["tags"]["kind"]: dp["fields"]["value"] for dp in r.data_points}
    assert kinds["histogram_count"] == 4.0
    assert kinds["histogram_sum"] == pytest.approx(106.0)
    assert kinds["histogram_p50"] == pytest.approx(h.quantile(0.5))
    assert kinds["histogram_p99"] == pytest.approx(h.quantile(0.99))


def test_self_measure_sink_periodic_flusher(tmp_path):
    import time as _time

    from banyandb_tpu.admin.metrics import SelfMeasureSink
    from banyandb_tpu.api import (
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
    from banyandb_tpu.models.measure import MeasureEngine

    reg = SchemaRegistry(tmp_path)
    reg.create_group(Group("g", Catalog.MEASURE, ResourceOpts(shard_num=1)))
    reg.create_measure(
        Measure("g", "m", (TagSpec("svc", TagType.STRING),),
                (FieldSpec("v", FieldType.FLOAT),), Entity(("svc",)))
    )
    eng = MeasureEngine(reg, tmp_path / "data")
    meter = Meter()
    meter.counter_add("ticks", 1)
    sink = SelfMeasureSink(meter, eng, interval_s=0.05)
    sink.start()
    sink.start()  # idempotent
    try:
        deadline = _time.time() + 5.0
        while _time.time() < deadline:
            from banyandb_tpu.api.model import QueryRequest, TimeRange

            r = eng.query(
                QueryRequest(("_monitoring",), "instruments",
                             TimeRange(0, 1 << 60), limit=10)
            )
            if r.data_points:
                break
            _time.sleep(0.05)
        assert r.data_points, "flusher never populated _monitoring"
    finally:
        sink.stop()
    assert sink._thread is None
