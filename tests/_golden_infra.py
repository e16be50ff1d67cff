"""Shared infrastructure for replaying the reference's golden case corpus.

The reference pins query semantics with table-driven suites: Go case
registries (test/cases/{measure,stream,trace,topn}/*.go `g.Entry` lines
carrying helpers.Args), protobuf-JSON schema fixtures
(pkg/test/*/testdata), write data (test/cases/*/data/testdata), query
inputs (input/*.yaml|yml protobuf-YAML requests, time range injected
from Args{Offset,Duration} per helpers.TimeRange) and expected responses
(want/*.yaml|yml, compared with protocmp ignoring per-catalog volatile
fields).

This module parses those exact files with OUR generated protos (compiled
from the same proto tree): the Go registries are parsed into case lists
(so the replayed set can never silently drift from the reference's),
schemas are created through the real wire registry services, data is
seeded through the real write streams, and each catalog's verify
semantics (ignored fields, DisOrder sorting, WantEmpty/WantErr) are
mirrored from the corresponding data.go VerifyFn.
"""

from __future__ import annotations

import json
import re
import time
from pathlib import Path

import numpy as np
import pytest


def numpy_exec(mask, group_cols, values) -> dict:
    """The plain NumPy reduction the executor parity tests compare with
    (tests/test_fuzz_parity.py, tests/test_fused_exec.py): the values of
    the rows ``mask`` selects, by group tuple (``()`` when ungrouped),
    in row order.  One Python step per selected row, nothing shared with
    the code under test."""
    if not group_cols:
        return {(): np.asarray(values)[mask]}
    out: dict = {}
    for i in np.nonzero(mask)[0]:
        out.setdefault(tuple(c[i] for c in group_cols), []).append(values[i])
    return {k: np.asarray(v) for k, v in out.items()}


grpc = pytest.importorskip("grpc")
yaml = pytest.importorskip("yaml")

from google.protobuf import json_format, timestamp_pb2  # noqa: E402

from banyandb_tpu.api import pb  # noqa: E402

REF = Path("/root/reference")
CASES = REF / "test/cases"
MIN = 60_000
DAY = 86_400_000

ref_missing = pytest.mark.skipif(
    not CASES.exists(), reason="reference tree not available"
)

# ---------------------------------------------------------------------------
# Go case-registry parsing (measure.go / stream.go / trace.go / topn.go)
# ---------------------------------------------------------------------------

_DUR_UNITS = {
    "time.Millisecond": 1,
    "time.Second": 1000,
    "time.Minute": 60_000,
    "time.Hour": 3_600_000,
}

_ENTRY_RE = re.compile(
    r'g\.F?Entry\(\s*"([^"]*)"\s*,\s*helpers\.Args\{(.*?)\}\s*\)', re.S
)


def _go_duration_ms(expr: str) -> int:
    """Evaluate a Go duration expression like `25 * time.Minute`."""
    expr = expr.strip()
    if expr in _DUR_UNITS:
        return _DUR_UNITS[expr]
    m = re.match(r"(-?\d+)\s*\*\s*(time\.\w+)$", expr)
    if not m:
        raise ValueError(f"unsupported Go duration {expr!r}")
    return int(m.group(1)) * _DUR_UNITS[m.group(2)]


def parse_entries(go_file: Path) -> list[dict]:
    """g.Entry("name", helpers.Args{...}) lines -> case dicts.

    Unknown Args fields fail loudly: a new knob in the reference's Args
    must be taught here, not silently dropped."""
    known = {
        "Input", "Want", "Offset", "Duration", "WantEmpty", "WantErr",
        "DisOrder", "IgnoreElementID", "Stages", "Begin", "End",
    }
    out = []
    txt = go_file.read_text()
    for m in _ENTRY_RE.finditer(txt):
        name, body = m.group(1), m.group(2)
        case: dict = {"name": name}
        for fm in re.finditer(r"(\w+):\s*([^,]+?)(?:,|$)", body.strip()):
            key, val = fm.group(1), fm.group(2).strip()
            if key not in known:
                raise ValueError(f"unknown Args field {key} in {name}")
            if key in ("Input", "Want"):
                case[key.lower()] = val.strip('"')
            elif key in ("Offset", "Duration"):
                case[key.lower()] = _go_duration_ms(val)
            elif key in ("WantEmpty", "WantErr", "DisOrder", "IgnoreElementID"):
                case[key.lower()] = val == "true"
            elif key == "Stages":
                sm = re.search(r"Stages:\s*\[\]string\{([^}]*)\}", body)
                case["stages"] = (
                    [s.strip().strip('"') for s in sm.group(1).split(",")]
                    if sm
                    else []
                )
            elif key in ("Begin", "End"):
                case["absolute_range"] = True
        out.append(case)
    if not out:
        raise ValueError(f"no entries parsed from {go_file}")
    return out


# ---------------------------------------------------------------------------
# proto/yaml plumbing
# ---------------------------------------------------------------------------


def yaml_to_pb(path: Path, msg):
    """Protobuf-YAML (or -JSON: the schema fixtures are .json and may
    contain tabs, which YAML rejects) -> message."""
    text = path.read_text()
    data = (
        json.loads(text) if path.suffix == ".json" else yaml.safe_load(text)
    )
    json_format.ParseDict(data, msg, ignore_unknown_fields=False)
    return msg


def ts(ms: int) -> timestamp_pb2.Timestamp:
    return timestamp_pb2.Timestamp(
        seconds=ms // 1000, nanos=(ms % 1000) * 1_000_000
    )


def method(channel, service, name, req_cls, resp_cls, kind="unary"):
    path = f"/{service}/{name}"
    ser = req_cls.SerializeToString
    de = resp_cls.FromString
    if kind == "unary":
        return channel.unary_unary(
            path, request_serializer=ser, response_deserializer=de
        )
    return channel.stream_stream(
        path, request_serializer=ser, response_deserializer=de
    )


# ---------------------------------------------------------------------------
# schema loading (pkg/test/*/schema.go loadAllSchemas analog)
# ---------------------------------------------------------------------------


def _create(fn, req, *, ok_exists=True):
    try:
        fn(req)
    except grpc.RpcError as e:  # noqa: PERF203
        if ok_exists and e.code() == grpc.StatusCode.ALREADY_EXISTS:
            return
        raise


def load_measure_schemas(chan):
    """pkg/test/measure/testdata: groups + measures + index rules +
    bindings + topn aggregations (schema.go loadAllSchemas)."""
    rpc = pb.database_rpc_pb2
    base = REF / "pkg/test/measure/testdata"
    group_create = method(
        chan, "banyandb.database.v1.GroupRegistryService", "Create",
        rpc.GroupRegistryServiceCreateRequest,
        rpc.GroupRegistryServiceCreateResponse,
    )
    for f in sorted((base / "groups").glob("*.json")):
        req = rpc.GroupRegistryServiceCreateRequest()
        yaml_to_pb(f, req.group)
        req.group.resource_opts.replicas = 0  # single-node harness
        _create(group_create, req)
    m_create = method(
        chan, "banyandb.database.v1.MeasureRegistryService", "Create",
        rpc.MeasureRegistryServiceCreateRequest,
        rpc.MeasureRegistryServiceCreateResponse,
    )
    for f in sorted((base / "measures").glob("*.json")):
        req = rpc.MeasureRegistryServiceCreateRequest()
        yaml_to_pb(f, req.measure)
        _create(m_create, req)
    _load_rules_bindings(chan, base)
    t_create = method(
        chan, "banyandb.database.v1.TopNAggregationRegistryService", "Create",
        rpc.TopNAggregationRegistryServiceCreateRequest,
        rpc.TopNAggregationRegistryServiceCreateResponse,
    )
    for f in sorted((base / "topn_aggregations").glob("*.json")):
        req = rpc.TopNAggregationRegistryServiceCreateRequest()
        yaml_to_pb(f, req.top_n_aggregation)
        _create(t_create, req)


def _load_rules_bindings(chan, base: Path):
    rpc = pb.database_rpc_pb2
    r_create = method(
        chan, "banyandb.database.v1.IndexRuleRegistryService", "Create",
        rpc.IndexRuleRegistryServiceCreateRequest,
        rpc.IndexRuleRegistryServiceCreateResponse,
    )
    for f in sorted((base / "index_rules").glob("*.json")):
        req = rpc.IndexRuleRegistryServiceCreateRequest()
        yaml_to_pb(f, req.index_rule)
        _create(r_create, req)
    b_create = method(
        chan, "banyandb.database.v1.IndexRuleBindingRegistryService", "Create",
        rpc.IndexRuleBindingRegistryServiceCreateRequest,
        rpc.IndexRuleBindingRegistryServiceCreateResponse,
    )
    for f in sorted((base / "index_rule_bindings").glob("*.json")):
        req = rpc.IndexRuleBindingRegistryServiceCreateRequest()
        yaml_to_pb(f, req.index_rule_binding)
        _create(b_create, req)


def load_stream_schemas(chan):
    """pkg/test/stream/testdata: group.json (array) + streams + rules +
    bindings (schema.go PreloadSchema)."""
    rpc = pb.database_rpc_pb2
    base = REF / "pkg/test/stream/testdata"
    group_create = method(
        chan, "banyandb.database.v1.GroupRegistryService", "Create",
        rpc.GroupRegistryServiceCreateRequest,
        rpc.GroupRegistryServiceCreateResponse,
    )
    for raw in json.loads((base / "group.json").read_text()):
        req = rpc.GroupRegistryServiceCreateRequest()
        json_format.ParseDict(raw, req.group, ignore_unknown_fields=False)
        req.group.resource_opts.replicas = 0
        _create(group_create, req)
    s_create = method(
        chan, "banyandb.database.v1.StreamRegistryService", "Create",
        rpc.StreamRegistryServiceCreateRequest,
        rpc.StreamRegistryServiceCreateResponse,
    )
    for f in sorted((base / "streams").glob("*.json")):
        req = rpc.StreamRegistryServiceCreateRequest()
        yaml_to_pb(f, req.stream)
        _create(s_create, req)
    _load_rules_bindings(chan, base)


def load_trace_schemas(chan):
    """pkg/test/trace/testdata: groups + traces + rules + bindings."""
    rpc = pb.database_rpc_pb2
    base = REF / "pkg/test/trace/testdata"
    group_create = method(
        chan, "banyandb.database.v1.GroupRegistryService", "Create",
        rpc.GroupRegistryServiceCreateRequest,
        rpc.GroupRegistryServiceCreateResponse,
    )
    for f in sorted((base / "groups").glob("*.json")):
        req = rpc.GroupRegistryServiceCreateRequest()
        yaml_to_pb(f, req.group)
        req.group.resource_opts.replicas = 0
        _create(group_create, req)
    t_create = method(
        chan, "banyandb.database.v1.TraceRegistryService", "Create",
        rpc.TraceRegistryServiceCreateRequest,
        rpc.TraceRegistryServiceCreateResponse,
    )
    for f in sorted((base / "traces").glob("*.json")):
        req = rpc.TraceRegistryServiceCreateRequest()
        yaml_to_pb(f, req.trace)
        _create(t_create, req)
    _load_rules_bindings(chan, base)


# ---------------------------------------------------------------------------
# data seeding (test/cases/init.go analog)
# ---------------------------------------------------------------------------


def seed_measures(chan, base_ms: int):
    """init.go's measure Write calls, datafile-for-datafile."""
    interval = MIN
    writes = [
        # (measure, group, datafile, base offset ms)
        ("service_traffic", "index_mode", "service_traffic_data_old.json", -2 * DAY),
        ("service_traffic", "index_mode", "service_traffic_data.json", 0),
        ("service_traffic", "index_mode", "service_traffic_data_expired.json", -10 * DAY),
        ("service_traffic", "replicated_group", "service_traffic_data.json", 0),
        ("service_instance_traffic", "sw_metric", "service_instance_traffic_data.json", 0),
        ("service_cpm_minute", "sw_metric", "service_cpm_minute_data.json", 0),
        ("instance_clr_cpu_minute", "sw_metric", "instance_clr_cpu_minute_data.json", 0),
        ("service_instance_cpm_minute", "sw_metric", "service_instance_cpm_minute_data.json", 0),
        ("service_instance_cpm_minute", "sw_metric", "service_instance_cpm_minute_data1.json", 10_000),
        ("service_instance_cpm_minute", "sw_metric", "service_instance_cpm_minute_data2.json", 10 * MIN),
        ("service_instance_endpoint_cpm_minute", "sw_metric", "service_instance_endpoint_cpm_minute_data.json", 0),
        ("service_instance_endpoint_cpm_minute", "sw_metric", "service_instance_endpoint_cpm_minute_data1.json", 10_000),
        ("service_instance_endpoint_cpm_minute", "sw_metric", "service_instance_endpoint_cpm_minute_data2.json", 10 * MIN),
        ("service_latency_minute", "sw_metric", "service_latency_minute_data.json", 0),
        ("service_instance_latency_minute", "sw_metric", "service_instance_latency_minute_data.json", 0),
        ("service_instance_latency_minute", "sw_metric", "service_instance_latency_minute_data1.json", MIN),
        ("endpoint_traffic", "sw_metric", "endpoint_traffic.json", 0),
        ("duplicated", "exception", "duplicated.json", 0, 0),
        ("service_cpm_minute", "sw_updated", "service_cpm_minute_updated_data.json", 10 * MIN),
        ("endpoint_resp_time_minute", "sw_metric", "endpoint_resp_time_minute_data.json", 0),
        ("endpoint_resp_time_minute", "sw_metric", "endpoint_resp_time_minute_data1.json", 10_000),
        ("service_instance_metric_topn_test", "sw_metric", "service_instance_metric_topn_test_data.json", 0),
        ("service_instance_float_metric", "sw_metric", "service_instance_float_metric_data.json", 0),
    ]
    write = method(
        chan, "banyandb.measure.v1.MeasureService", "Write",
        pb.measure_write_pb2.WriteRequest, pb.measure_write_pb2.WriteResponse,
        kind="stream",
    )
    data_dir = CASES / "measure/data/testdata"

    def load(name, group, datafile, offset, iv=interval):
        rows = json.loads((data_dir / datafile).read_text())
        reqs = []
        for i, row in enumerate(rows):
            dp = pb.measure_write_pb2.DataPointValue()
            json_format.ParseDict(row, dp, ignore_unknown_fields=False)
            # data.go loadData: row i of N at base - (N-1-i) * interval
            dp.timestamp.CopyFrom(
                ts(base_ms + offset - (len(rows) - i - 1) * iv)
            )
            req = pb.measure_write_pb2.WriteRequest(
                data_point=dp, message_id=i + 1
            )
            req.metadata.name = name
            req.metadata.group = group
            reqs.append(req)
        for resp in write(iter(reqs)):
            assert resp.status in ("STATUS_SUCCEED", ""), (name, resp.status)

    for spec in writes:
        name, group, datafile, offset = spec[:4]
        iv = spec[4] if len(spec) > 4 else interval
        load(name, group, datafile, offset, iv)

    # WriteMixed (init.go tail): schema-order then spec-order writes
    base30 = base_ms + 30 * MIN
    mixed = [
        ("service_cpm_minute", "sw_spec", "service_cpm_minute_schema_order.json", None, None),
        ("service_cpm_minute", "sw_spec", "service_cpm_minute_spec_order.json",
         [("default", ["entity_id", "id"])], ["value", "total"]),
        ("service_cpm_minute", "sw_spec2", "service_cpm_minute_spec_order2.json",
         [("default", ["id", "entity_id"])], ["total", "value"]),
    ]
    reqs = []
    mid = 0
    for name, group, datafile, fam_spec, field_names in mixed:
        rows = json.loads((data_dir / datafile).read_text())
        for i, row in enumerate(rows):
            dp = pb.measure_write_pb2.DataPointValue()
            json_format.ParseDict(row, dp, ignore_unknown_fields=False)
            dp.timestamp.CopyFrom(ts(base30 - (len(rows) - i - 1) * interval))
            mid += 1
            req = pb.measure_write_pb2.WriteRequest(
                data_point=dp, message_id=mid
            )
            req.metadata.name = name
            req.metadata.group = group
            if fam_spec is not None:
                for fname, tag_names in fam_spec:
                    fs = req.data_point_spec.tag_family_spec.add(name=fname)
                    fs.tag_names.extend(tag_names)
                req.data_point_spec.field_names.extend(field_names)
            reqs.append(req)
    for resp in write(iter(reqs)):
        assert resp.status in ("STATUS_SUCCEED", ""), resp.status


_STREAM_DATA_BLOB = "YWJjMTIzIT8kKiYoKSctPUB+"


def seed_streams(chan, base_ms: int):
    """stream data.go SeedAll, file-for-file (interval 500ms)."""
    iv = 500
    write = method(
        chan, "banyandb.stream.v1.StreamService", "Write",
        pb.stream_write_pb2.WriteRequest, pb.stream_write_pb2.WriteResponse,
        kind="stream",
    )
    data_dir = CASES / "stream/data/testdata"

    def load(name, group, datafile, base, interval=iv, explicit_ids=False):
        rows = json.loads((data_dir / datafile).read_text())
        reqs = []
        counter = 0
        for row in rows:
            el = pb.stream_write_pb2.ElementValue()
            if explicit_ids:
                json_format.ParseDict(row, el, ignore_unknown_fields=False)
                eid = int(el.element_id)
            else:
                fam = el.tag_families.add()
                json_format.ParseDict(
                    row, fam, ignore_unknown_fields=False
                )
                eid = counter
                counter += 1
                el.element_id = str(eid)
                # data family (binary blob) FIRST, searchable second —
                # loadData builds [data, searchable]
                data_fam = pb.model_common_pb2.TagFamilyForWrite()
                t = data_fam.tags.add()
                import base64 as b64

                t.binary_data = b64.b64decode(_STREAM_DATA_BLOB)
                el.tag_families.insert(0, data_fam)
            el.timestamp.CopyFrom(ts(base + eid * interval))
            req = pb.stream_write_pb2.WriteRequest(
                element=el, message_id=eid + 1
            )
            req.metadata.name = name
            req.metadata.group = group
            reqs.append(req)
        for resp in write(iter(reqs)):
            assert resp.status in ("STATUS_SUCCEED", ""), (name, resp.status)

    load("sw", "default", "sw.json", base_ms)
    load("sw", "default", "sw.json", base_ms - 6 * DAY)
    load("duplicated", "default", "duplicated.json", base_ms, 0)
    load("deduplication_test", "default", "deduplication_test.json",
         base_ms, 1, explicit_ids=True)
    load("sw", "updated", "sw_updated.json", base_ms + MIN)
    # WriteMixed: schema order + two spec orders
    sw_schema = {
        "searchable": [
            "trace_id", "state", "service_id", "service_instance_id",
            "endpoint_id", "duration", "start_time", "http.method",
            "status_code", "span_id",
        ],
    }
    mixed = [
        ("sw", "default-spec", "sw_schema_order.json", None),
        ("sw", "default-spec", "sw_spec_order.json", [
            ("data", ["data_binary"]),
            ("searchable", sw_schema["searchable"]),
        ]),
        ("sw", "default-spec2", "sw_spec_order2.json", [
            ("searchable", list(reversed(sw_schema["searchable"]))),
            ("data", ["data_binary"]),
        ]),
    ]
    counter = 0
    reqs = []
    base2 = base_ms + 2 * MIN
    for name, group, datafile, spec in mixed:
        rows = json.loads((data_dir / datafile).read_text())
        for row in rows:
            el = pb.stream_write_pb2.ElementValue()
            json_format.ParseDict(row, el, ignore_unknown_fields=False)
            eid = counter
            counter += 1
            el.element_id = str(eid)
            el.timestamp.CopyFrom(ts(base2 + eid * iv))
            req = pb.stream_write_pb2.WriteRequest(
                element=el, message_id=eid + 1
            )
            req.metadata.name = name
            req.metadata.group = group
            if spec is not None:
                for fname, tag_names in spec:
                    fs = req.tag_family_spec.add(name=fname)
                    fs.tag_names.extend(tag_names)
            reqs.append(req)
    for resp in write(iter(reqs)):
        assert resp.status in ("STATUS_SUCCEED", ""), resp.status


def seed_traces(chan, base_ms: int):
    """trace data.go SeedAll, file-for-file (interval 500ms)."""
    iv = 500
    write = method(
        chan, "banyandb.trace.v1.TraceService", "Write",
        pb.trace_write_pb2.WriteRequest, pb.trace_write_pb2.WriteResponse,
        kind="stream",
    )
    data_dir = CASES / "trace/data/testdata"

    def load(name, group, datafile, base, spec_tags=None, version0=0):
        rows = json.loads((data_dir / datafile).read_text())
        reqs = []
        version = version0
        for row in rows:
            req = pb.trace_write_pb2.WriteRequest()
            req.metadata.name = name
            req.metadata.group = group
            for tag in row["tags"]:
                tv = req.tags.add()
                json_format.ParseDict(tag, tv, ignore_unknown_fields=False)
            # loadData appends the timestamp tag last
            tts = req.tags.add()
            tts.timestamp.CopyFrom(ts(base + version * iv))
            req.span = row["span"].encode()
            req.version = version
            if spec_tags is not None:
                req.tag_spec.tag_names.extend(spec_tags)
            version += 1
            reqs.append(req)
        for resp in write(iter(reqs)):
            pass  # trace write responses carry no status field to assert
        return version

    load("sw", "test-trace-group", "sw.json", base_ms)
    load("sw", "test-trace-group", "sw.json", base_ms - 6 * DAY)
    load("zipkin", "zipkinTrace", "zipkin.json", base_ms)
    load("sw", "test-trace-updated", "sw_updated.json", base_ms + MIN)
    load("sw", "test-trace-group", "sw_mixed_traces.json", base_ms + MIN)
    # WriteMixed
    base2 = base_ms + 2 * MIN
    spec1 = ["trace_id", "state", "service_id", "service_instance_id",
             "endpoint_id", "duration", "span_id", "timestamp"]
    spec2 = ["span_id", "duration", "endpoint_id", "service_instance_id",
             "service_id", "state", "trace_id", "timestamp"]
    v = load("sw", "test-trace-spec", "sw_schema_order.json", base2)
    v = load("sw", "test-trace-spec", "sw_spec_order.json", base2,
             spec_tags=spec1, version0=v)
    load("sw", "test-trace-spec2", "sw_spec_order2.json", base2,
         spec_tags=spec2, version0=v)


def seed_properties(chan):
    """init.go property tail: apply sw1/sw2 into ui_menu@sw."""
    apply = method(
        chan, "banyandb.property.v1.PropertyService", "Apply",
        pb.property_rpc_pb2.ApplyRequest, pb.property_rpc_pb2.ApplyResponse,
    )
    data_dir = CASES / "property/data/testdata"
    for fname in ("sw1", "sw2"):
        req = pb.property_rpc_pb2.ApplyRequest()
        json_format.ParseDict(
            json.loads((data_dir / f"{fname}.json").read_text()),
            req,
            ignore_unknown_fields=False,
        )
        req.property.metadata.group = "sw"
        req.property.metadata.name = "ui_menu"
        apply(req)


def base_time_ms() -> int:
    """common.go: now truncated to the minute."""
    now_ms = int(time.time() * 1000)
    return now_ms - now_ms % MIN
