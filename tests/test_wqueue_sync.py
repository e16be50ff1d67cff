"""Liaison write queue + streaming chunked sync e2e (VERDICT r1 next #5):
a liaison batches 100k points into sealed parts and ships them to data
nodes over the real banyandb.cluster.v1.ChunkedSyncService stream; the
data nodes then serve queries over the synced parts."""

import numpy as np
import pytest

grpc = pytest.importorskip("grpc")

from banyandb_tpu.api import (  # noqa: E402
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
    WriteRequest,
)
from banyandb_tpu.cluster import chunked_sync  # noqa: E402
from banyandb_tpu.cluster.data_node import DataNode  # noqa: E402
from banyandb_tpu.cluster.liaison import Liaison  # noqa: E402
from banyandb_tpu.cluster.node import NodeInfo  # noqa: E402
from banyandb_tpu.cluster.rpc import GrpcBusServer, GrpcTransport  # noqa: E402

T0 = 1_700_000_000_000
N_POINTS = 100_000


def _schema(reg):
    reg.create_group(Group("wq", Catalog.MEASURE, ResourceOpts(shard_num=2)))
    reg.create_measure(
        Measure(
            group="wq",
            name="m",
            tags=(TagSpec("svc", TagType.STRING), TagSpec("region", TagType.STRING)),
            fields=(FieldSpec("lat", FieldType.FLOAT),),
            entity=Entity(("svc",)),
        )
    )


@pytest.fixture()
def cluster(tmp_path):
    nodes, servers = [], []
    for i in range(2):
        reg = SchemaRegistry(tmp_path / f"n{i}" / "schema")
        _schema(reg)
        dn = DataNode(f"dn{i}", reg, tmp_path / f"n{i}" / "data")
        srv = GrpcBusServer(dn.bus, port=0, sync_install=dn.install_synced_parts)
        srv.start()
        nodes.append((dn, NodeInfo(f"dn{i}", srv.addr)))
        servers.append(srv)
    lreg = SchemaRegistry(tmp_path / "liaison" / "schema")
    _schema(lreg)
    transport = GrpcTransport()
    liaison = Liaison(lreg, transport, [ni for _, ni in nodes])
    liaison.probe()
    wq = liaison.enable_write_queue(tmp_path / "liaison" / "wqueue", max_rows=32768)
    yield liaison, wq, [dn for dn, _ in nodes]
    wq.stop(final_flush=False)
    transport.close()
    for srv in servers:
        srv.stop()


def test_wqueue_batches_and_ships_100k(cluster):
    liaison, wq, data_nodes = cluster
    rng = np.random.default_rng(9)
    svc_idx = rng.integers(0, 16, N_POINTS)
    lat = rng.gamma(2.0, 40.0, N_POINTS)

    B = 5000
    for s in range(0, N_POINTS, B):
        pts = tuple(
            DataPointValue(
                ts_millis=T0 + i,
                tags={"svc": f"s{svc_idx[i]}", "region": "eu"},
                fields={"lat": float(lat[i])},
                version=1,
            )
            for i in range(s, s + B)
        )
        liaison.write_measure_queued(WriteRequest("wq", "m", pts))

    # some buffers crossed max_rows and sealed already; flush the rest
    wq.flush()
    assert wq.pending_parts() == 0, "all sealed parts must have shipped"
    assert wq.buffered_rows() == 0

    # every point is queryable on the data nodes via the distributed path
    req = QueryRequest(
        groups=("wq",),
        name="m",
        time_range=TimeRange(T0, T0 + N_POINTS + 1),
        group_by=GroupBy(("svc",)),
        agg=Aggregation("count", "lat"),
    )
    res = liaison.query_measure(req)
    assert sum(res.values["count"]) == N_POINTS
    got = {g[0]: c for g, c in zip(res.groups, res.values["count"])}
    for s in range(16):
        assert got[f"s{s}"] == int((svc_idx == s).sum())

    # parts really landed on data nodes as on-disk parts (not rows)
    total_parts = 0
    for dn in data_nodes:
        for seg in dn.measure._tsdb("wq").select_segments(0, 1 << 62):
            for shard in seg.shards:
                total_parts += len(shard.parts)
    assert total_parts >= 2  # at least one sealed part per shard


def test_chunked_sync_crc_and_order_rejection(cluster, tmp_path, request):
    """Corrupted chunks are rejected with the proto's status codes."""
    from banyandb_tpu.api import pb

    liaison, wq, data_nodes = cluster
    rpcpb = pb.cluster_rpc_pb2
    addr = liaison.selector.nodes[0].addr
    chan = liaison.transport.channel(addr)
    request.addfinalizer(chan.close)
    call = chan.stream_stream(
        chunked_sync.METHOD,
        request_serializer=lambda m: m.SerializeToString(),
        response_deserializer=rpcpb.SyncPartResponse.FromString,
    )

    def bad_crc():
        req = rpcpb.SyncPartRequest(
            session_id="s1",
            chunk_index=0,
            chunk_data=b"hello",
            chunk_checksum="deadbeef",
        )
        req.metadata.group = "wq"
        req.metadata.shard_id = 0
        yield req

    resps = list(call(bad_crc()))
    assert resps[-1].status == 2  # CHECKSUM_MISMATCH

    def out_of_order():
        req = rpcpb.SyncPartRequest(
            session_id="s2",
            chunk_index=5,
            chunk_data=b"",
            chunk_checksum=chunked_sync._crc(b""),
        )
        yield req

    resps = list(call(out_of_order()))
    assert resps[-1].status == 3  # OUT_OF_ORDER


def test_wqueue_segment_boundary_split(cluster):
    """Rows spanning a segment boundary seal into separate parts so both
    segments serve their rows (silent-loss regression guard)."""
    liaison, wq, data_nodes = cluster
    day = 86_400_000
    seg_start = (T0 // day) * day
    pts = tuple(
        DataPointValue(
            ts_millis=ts,
            tags={"svc": "edge", "region": "eu"},
            fields={"lat": 1.0},
            version=1,
        )
        # 5 rows at end of day-1, 5 at start of day-2
        for ts in list(range(seg_start + day - 5, seg_start + day))
        + list(range(seg_start + day, seg_start + day + 5))
    )
    liaison.write_measure_queued(WriteRequest("wq", "m", pts))
    wq.flush()
    assert wq.pending_parts() == 0

    for begin, end, want in [
        (seg_start, seg_start + day, 5),
        (seg_start + day, seg_start + 2 * day, 5),
        (seg_start, seg_start + 2 * day, 10),
    ]:
        res = liaison.query_measure(
            QueryRequest(
                groups=("wq",),
                name="m",
                time_range=TimeRange(begin, end),
                group_by=GroupBy(("svc",)),
                agg=Aggregation("count", "lat"),
            )
        )
        assert sum(res.values["count"]) == want, (begin, end, want)


def test_wqueue_topn_observation(cluster):
    """TopN pre-aggregation sees queued writes (parts feed observe on
    install, since the queued path bypasses MeasureEngine.write)."""
    from banyandb_tpu.api.schema import TopNAggregation

    liaison, wq, data_nodes = cluster
    rule = TopNAggregation(
        group="wq",
        name="top_lat",
        source_measure="m",
        field_name="lat",
        group_by_tag_names=("svc",),
    )
    for dn in data_nodes:
        dn.registry.create_topn(rule)
        dn.measure.ensure_result_measure("wq")
    pts = tuple(
        DataPointValue(
            ts_millis=T0 + i,
            tags={"svc": f"s{i % 4}", "region": "eu"},
            fields={"lat": float(10 * (i % 4) + 1)},
            version=1,
        )
        for i in range(200)
    )
    liaison.write_measure_queued(WriteRequest("wq", "m", pts))
    wq.flush()
    observed = sum(
        len(w.sums)
        for dn in data_nodes
        for w in dn.measure.topn._windows.get(("wq", "top_lat"), {}).values()
    )
    assert observed > 0, "queued rows must reach TopN windows"


def test_wqueue_spool_recovery(tmp_path):
    """Sealed-but-unshipped parts survive a liaison restart."""
    reg = SchemaRegistry(tmp_path / "schema")
    _schema(reg)
    from banyandb_tpu.cluster.wqueue import WriteQueue

    fails = {"n": 0}

    def failing_shipper(group, shard, part_dir):
        fails["n"] += 1
        raise RuntimeError("node down")

    wq = WriteQueue(reg, tmp_path / "spool", failing_shipper)
    pts = tuple(
        DataPointValue(
            ts_millis=T0 + i, tags={"svc": "a", "region": "eu"},
            fields={"lat": 1.0}, version=1,
        )
        for i in range(100)
    )
    wq.append(WriteRequest("wq", "m", pts))
    shipped, failed = wq.flush()
    assert shipped == 0 and failed == 1
    assert wq.pending_parts() == 1

    # restart: a fresh queue over the same spool finds the sealed part
    delivered = []
    wq2 = WriteQueue(
        reg, tmp_path / "spool", lambda g, s, d: delivered.append((g, s, d))
    )
    assert wq2.pending_parts() == 1
    shipped, failed = wq2.ship_pending()
    assert shipped == 1 and failed == 0 and len(delivered) == 1


def test_wqueue_stream_catalog(cluster):
    """Stream elements batch through the write queue into payload parts
    that data nodes introduce and serve (element ids + bodies intact,
    element-index sidecars built on install)."""
    from banyandb_tpu.api.schema import IndexRule, Stream, TagSpec as TS, TagType as TT
    from banyandb_tpu.models.stream import ElementValue

    liaison, wq, data_nodes = cluster
    st = Stream(
        group="wq",
        name="logs",
        tags=(TS("svc", TT.STRING), TS("level", TT.STRING)),
        entity=("svc",),
    )
    rule = IndexRule(group="wq", name="svc_idx", tags=("svc",), type="inverted")
    liaison.registry.create_stream(st)
    liaison.registry.create_index_rule(rule)
    for dn in data_nodes:
        dn.registry.create_stream(st)
        dn.registry.create_index_rule(rule)

    elements = [
        ElementValue(
            element_id=f"e{i}",
            ts_millis=T0 + i,
            tags={"svc": f"s{i % 4}", "level": "ERROR" if i % 5 == 0 else "INFO"},
            body=f"line-{i}".encode(),
        )
        for i in range(500)
    ]
    liaison.write_stream_queued("wq", "logs", elements)
    wq.flush()
    assert wq.pending_parts() == 0

    res = liaison.query_stream(
        QueryRequest(
            groups=("wq",),
            name="logs",
            time_range=TimeRange(T0, T0 + 1000),
            criteria=Condition("level", "eq", "ERROR"),
            limit=1000,
        )
    )
    assert len(res.data_points) == 100
    sample = next(dp for dp in res.data_points if dp["element_id"] == "e0")
    assert sample["body"] == b"line-0"

    # installed stream parts carry element-index sidecars
    sidecars = 0
    for dn in data_nodes:
        for seg in dn.stream._tsdb("wq").select_segments(0, 1 << 62):
            for shard in seg.shards:
                for part in shard.parts:
                    if (part.dir / "eidx_svc.bin").exists():
                        sidecars += 1
    assert sidecars > 0


def test_wqueue_trace_catalog(cluster):
    """Spans batch through the write queue into trace parts; installed
    parts serve query-by-id and ordered (sidx) retrieval with the
    trace-id bloom sidecar present."""
    from banyandb_tpu.api.schema import Trace
    from banyandb_tpu.api.model import TimeRange as TR
    from banyandb_tpu.models.trace import BLOOM_FILE, SpanValue

    liaison, wq, data_nodes = cluster
    t = Trace(
        group="wq",
        name="sw",
        tags=(TagSpec("trace_id", TagType.STRING), TagSpec("dur", TagType.INT)),
        trace_id_tag="trace_id",
    )
    liaison.registry.create_trace(t)
    for dn in data_nodes:
        dn.registry.create_trace(t)

    spans = [
        SpanValue(
            ts_millis=T0 + i,
            tags={"trace_id": f"t{i % 20}", "dur": 10 * i},
            span=f"sp{i}".encode(),
        )
        for i in range(200)
    ]
    liaison.wqueue.append_trace("wq", "sw", spans, ordered_tags=("dur",))
    wq.flush()
    assert wq.pending_parts() == 0

    # query-by-id via the distributed plane
    got_spans = liaison.query_trace_by_id("wq", "sw", "t3")
    assert len(got_spans) == 10  # i % 20 == 3 over 200

    # installed parts carry the trace-id bloom, and sidx ordering works
    blooms = ordered = 0
    tops = []
    for dn in data_nodes:
        for seg in dn.trace._tsdb("wq").select_segments(0, 1 << 62):
            for shard in seg.shards:
                for part in shard.parts:
                    if (part.dir / BLOOM_FILE).exists():
                        blooms += 1
        ids = dn.trace.query_ordered(
            "wq", "sw", "dur", TR(T0, T0 + 1000),
            asc=False, limit=3, verify_live=False,
        )
        if ids:
            ordered += 1
            tops.extend(ids[:1])
    assert blooms > 0 and ordered > 0
    # the global slowest trace (dur=1990 -> t19) tops ITS owning node
    assert "t19" in tops


def test_sync_redelivery_is_idempotent(cluster):
    """Receiver-side dedup (ADVICE r2): a part re-shipped after a liaison
    crash between sync and its delivered.json record must not install
    twice — stream/trace payload rows have no query-time version dedup."""
    liaison, wq, data_nodes = cluster
    pts = tuple(
        DataPointValue(
            ts_millis=T0 + i,
            tags={"svc": f"s{i % 4}", "region": "eu"},
            fields={"lat": float(i)},
            version=1,
        )
        for i in range(256)
    )
    liaison.write_measure_queued(WriteRequest("wq", "m", pts))
    wq.flush()

    def part_count(dn):
        return sum(
            len(shard.parts)
            for seg in dn.measure._tsdb("wq").select_segments(0, 1 << 62)
            for shard in seg.shards
        )

    # find one installed part on a data node and re-ship it verbatim
    target = None
    for dn, ni in zip(data_nodes, liaison.selector.nodes):
        for seg in dn.measure._tsdb("wq").select_segments(0, 1 << 62):
            for si, shard in enumerate(seg.shards):
                for part in shard.parts:
                    target = (dn, ni, si, part.dir)
        if target:
            break
    assert target is not None
    dn, ni, shard_idx, part_dir = target
    before = part_count(dn)

    def dial():
        return liaison.transport.channel(ni.addr)

    for _ in range(2):  # re-deliver twice; both must be skipped
        chunked_sync.sync_part_dirs(
            dial, [part_dir], group="wq", shard_id=shard_idx
        )
    assert part_count(dn) == before

    # and the digest record survives restart-shaped reloads
    import json

    dn._installed = dict.fromkeys(
        json.loads((dn.root / ".sync-installed.json").read_text())
    )
    assert dn._installed  # persisted record was non-empty
    chunked_sync.sync_part_dirs(dial, [part_dir], group="wq", shard_id=shard_idx)
    assert part_count(dn) == before
