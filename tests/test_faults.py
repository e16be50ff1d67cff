"""Deterministic fault-injection plane + write/query-path hardening
(cluster/faults.py, docs/robustness.md).

Covers: the determinism pin (same seed+schedule -> same per-site fault
sequence), all four boundaries (rpc transport, chunked-sync stream,
spool disk I/O, kill schedule), spool high-watermark backpressure
(ServerBusy shed), ship retry backoff, uuid-idempotent part install,
graceful query degradation markers, and deadline propagation.
"""

import time
from types import SimpleNamespace

import numpy as np
import pytest

from banyandb_tpu.api import (
    Aggregation,
    Catalog,
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
from banyandb_tpu.cluster import faults
from banyandb_tpu.cluster.bus import LocalBus, Topic
from banyandb_tpu.cluster.data_node import DataNode
from banyandb_tpu.cluster.liaison import Liaison
from banyandb_tpu.cluster.node import NodeInfo
from banyandb_tpu.cluster.rpc import LocalTransport, TransportError, _SHED_TYPES
from banyandb_tpu.cluster.wqueue import WriteQueue

T0 = 1_700_000_000_000


@pytest.fixture(autouse=True)
def _clean_plane():
    faults.clear()
    yield
    faults.clear()


def _schema(reg, shard_num=3):
    reg.create_group(
        Group("fg", Catalog.MEASURE, ResourceOpts(shard_num=shard_num))
    )
    reg.create_measure(
        Measure(
            group="fg", name="m",
            tags=(TagSpec("svc", TagType.STRING),),
            fields=(FieldSpec("v", FieldType.FLOAT),),
            entity=Entity(("svc",)),
        )
    )


def _points(base, n, mod=6):
    return tuple(
        DataPointValue(
            ts_millis=T0 + base + i,
            tags={"svc": f"s{(base + i) % mod}"},
            fields={"v": 1.0},
            version=1,
        )
        for i in range(n)
    )


def _count_req(trace=False):
    return QueryRequest(
        groups=("fg",), name="m",
        time_range=TimeRange(T0, T0 + 10_000_000),
        group_by=GroupBy(("svc",)),
        agg=Aggregation("count", "v"),
        trace=trace,
    )


def _total(res):
    return int(sum(res.values.get("count", [])))


# -- plane semantics ---------------------------------------------------------


def test_spec_parse_rejects_garbage():
    with pytest.raises(ValueError):
        faults.FaultPlane("rpc")  # no kind
    with pytest.raises(ValueError):
        faults.FaultPlane("rpc=error:p")  # bad param


def test_every_after_count_semantics():
    plane = faults.FaultPlane("sync=cut:every=3:after=2:count=2")
    fired = [
        i for i in range(12) if plane.decide("sync") is not None
    ]
    # decisions 2 and 5 fire (after=2 skips 0-1, every=3 from there,
    # count=2 stops the rest)
    assert fired == [2, 5]
    assert plane.counters() == {"sync": 12}


def test_match_filter_scopes_by_detail():
    plane = faults.FaultPlane("rpc=error:every=1:match=measure-write")
    assert plane.decide("rpc", "health") is None
    act = plane.decide("rpc", "measure-write")
    assert act is not None and act.kind == "error" and act.seq == 1


def test_kill_schedule_for_harness():
    plane = faults.FaultPlane("kill=n0:at=1;kill=n2:at=1;kill=n1:at=3")
    assert plane.kills_for_cycle(1) == ["n0", "n2"]
    assert plane.kills_for_cycle(2) == []
    assert plane.kills_for_cycle(3) == ["n1"]


def test_join_leave_schedule_events_for_cycle():
    """Membership-change schedules ride the kill grammar: the harness
    reads them per cycle and performs the discovery edit + rebalance
    itself (docs/robustness.md 'Elastic cluster')."""
    plane = faults.FaultPlane(
        "kill=n0:at=1;join=n3:at=1;leave=n1:at=2;worker=w000:at=2"
    )
    assert plane.events_for_cycle(1) == {
        "kill": ["n0"], "worker": [], "join": ["n3"], "leave": [],
    }
    assert plane.events_for_cycle(2) == {
        "kill": [], "worker": ["w000"], "join": [], "leave": ["n1"],
    }
    assert plane.kills_for_cycle(1, site="join") == ["n3"]


def test_partition_blackhole_is_asymmetric():
    """partition=blackhole:src=A:dst=B drops A->B calls only: B->A (and
    A->C) stay up.  The process identity comes from set_local_node."""
    bus = LocalBus()
    bus.subscribe(Topic.HEALTH, lambda env: {"status": "ok"})
    transport = LocalTransport()
    transport.register("n0", bus)
    transport.register("n1", bus)
    faults.configure("partition=blackhole:src=liaison:dst=n1")
    try:
        faults.set_local_node("liaison")
        # liaison -> n1: blackholed, with the explicit fault marker
        with pytest.raises(TransportError, match="blackholed"):
            transport.call("local:n1", Topic.HEALTH.value, {}, timeout=1)
        # liaison -> n0: unaffected (dst filter)
        assert transport.call(
            "local:n0", Topic.HEALTH.value, {}, timeout=1
        )["status"] == "ok"
        # n1 -> n1 (the reverse direction's process): unaffected (src
        # filter) — the blackhole is asymmetric
        faults.set_local_node("n1")
        assert transport.call(
            "local:n1", Topic.HEALTH.value, {}, timeout=1
        )["status"] == "ok"
        # fired decisions land in history + the injected counter
        plane = faults.get_plane()
        assert ("partition", 0, "blackhole") in plane.history
    finally:
        faults.set_local_node("")


def test_partition_count_bounds_the_blackhole():
    """count=N caps a partition rule like any other: transient
    partitions heal."""
    bus = LocalBus()
    bus.subscribe(Topic.HEALTH, lambda env: {"status": "ok"})
    transport = LocalTransport()
    transport.register("n1", bus)
    faults.configure("partition=blackhole:src=l:dst=n1:count=2")
    try:
        faults.set_local_node("l")
        for _ in range(2):
            with pytest.raises(TransportError):
                transport.call("local:n1", Topic.HEALTH.value, {}, timeout=1)
        # healed: the rule is spent
        assert transport.call(
            "local:n1", Topic.HEALTH.value, {}, timeout=1
        )["status"] == "ok"
    finally:
        faults.set_local_node("")


def test_partition_matches_registered_grpc_addr():
    """Real-socket transports carry host:port addresses; the matcher
    learns name->addr via register_node_addr."""
    faults.configure("partition=blackhole:src=l:dst=n7")
    try:
        faults.set_local_node("l")
        plane = faults.get_plane()
        # unknown addr: no match, no fault
        plane.check_partition("l", "127.0.0.1:4711", "health")
        faults.register_node_addr("n7", "127.0.0.1:4711")
        with pytest.raises(TransportError, match="blackholed"):
            plane.check_partition("l", "127.0.0.1:4711", "health")
    finally:
        faults.set_local_node("")
        faults.clear_node_addrs()


def test_deterministic_sequence_reproduces_from_seed():
    """The acceptance pin: same seed+schedule -> identical per-site
    fault sequences, independent of other sites' traffic."""
    spec = "seed=7;rpc=error:p=0.4;rpc=delay:p=0.2:ms=1;disk=enospc:p=0.3"
    a, b = faults.FaultPlane(spec), faults.FaultPlane(spec)
    seq_a = [a.decide("rpc") for _ in range(40)]
    # b's rpc stream must not care that b's disk site is also consulted
    for i in range(40):
        b.decide("disk")
        if i % 3 == 0:
            b.decide("sync")  # unscheduled site: no draws at all
    seq_b = [b.decide("rpc") for _ in range(40)]
    assert [x and (x.kind, x.seq) for x in seq_a] == [
        x and (x.kind, x.seq) for x in seq_b
    ]
    assert a.history[:1] and [h for h in a.history if h[0] == "rpc"] == [
        h for h in b.history if h[0] == "rpc"
    ]


def test_deterministic_sequence_golden_pin():
    """Literal golden for one seed: a library change that silently
    reshuffles draws must fail loudly, because stored chaos seeds would
    stop reproducing their failures."""
    plane = faults.FaultPlane("seed=7;rpc=error:p=0.4")
    fired = [
        i for i in range(30) if plane.decide("rpc") is not None
    ]
    import random

    rng = random.Random("7/rpc")
    want = [i for i in range(30) if rng.random() < 0.4]
    assert fired == want and len(fired) >= 5


def test_env_spec_and_counter_export(monkeypatch):
    monkeypatch.setenv("BYDB_FAULTS", "seed=3;rpc=error:every=1")
    faults._INIT = False  # force re-read of the env
    plane = faults.get_plane()
    assert plane is not None and faults.active()
    with pytest.raises(TransportError):
        plane.fail_rpc("addr", "topic")
    from banyandb_tpu.obs.metrics import global_meter

    counters = global_meter().snapshot()["counters"]
    key = ("fault_injected", (("kind", "error"), ("site", "rpc")))
    assert counters.get(key, 0) >= 1


# -- rpc boundary ------------------------------------------------------------


def test_rpc_boundary_shed_error_delay(tmp_path):
    transport = LocalTransport()
    bus = LocalBus()
    bus.subscribe(Topic.HEALTH, lambda env: {"status": "ok"})
    addr = transport.register("n0", bus)

    faults.configure("rpc=shed:every=1")
    with pytest.raises(TransportError) as ei:
        transport.call(addr, Topic.HEALTH.value, {}, timeout=5)
    assert ei.value.kind == "shed"

    faults.configure("rpc=error:every=1")
    with pytest.raises(TransportError) as ei:
        transport.call(addr, Topic.HEALTH.value, {}, timeout=5)
    assert ei.value.kind == "error"

    faults.configure("rpc=delay:every=1:ms=40")
    t0 = time.perf_counter()
    r = transport.call(addr, Topic.HEALTH.value, {}, timeout=5)
    assert r["status"] == "ok"
    assert time.perf_counter() - t0 >= 0.03

    faults.clear()
    assert transport.call(addr, Topic.HEALTH.value, {}, timeout=5)


# -- sync boundary -----------------------------------------------------------


@pytest.fixture()
def sync_stack(tmp_path):
    grpc = pytest.importorskip("grpc")
    from concurrent import futures

    from banyandb_tpu.cluster import chunked_sync

    installs = []

    def install_cb(meta, parts):
        installs.append(meta.group)

    pool = futures.ThreadPoolExecutor(max_workers=2)
    server = grpc.server(pool)
    server.add_generic_rpc_handlers(
        (chunked_sync.generic_handler(install_cb),)
    )
    port = server.add_insecure_port("127.0.0.1:0")
    server.start()
    part = tmp_path / "0000000000000001-0001"
    part.mkdir()
    (part / "primary.bin").write_bytes(b"\x07" * 4096)
    yield lambda: grpc.insecure_channel(f"127.0.0.1:{port}"), part, installs
    server.stop(grace=0.2).wait()
    pool.shutdown(wait=True)


def test_sync_boundary_plane_driven(sync_stack):
    from banyandb_tpu.cluster import chunked_sync

    dial, part, installs = sync_stack

    def ship():
        return chunked_sync.sync_part_dirs(
            dial, [part], group="g", shard_id=0
        )

    faults.configure("sync=corrupt:every=1:count=1")
    with pytest.raises(TransportError, match="status=2"):  # CRC catches
        ship()
    assert installs == []

    faults.configure("sync=truncate:every=1:count=1")
    with pytest.raises(TransportError, match="status=2"):
        ship()
    assert installs == []

    # cut raises inside the request generator; grpc surfaces it as a
    # stream failure (the sender sees the stream die, not the message)
    faults.configure("sync=cut:every=1:count=1")
    with pytest.raises(TransportError):
        ship()
    assert installs == []

    # the schedule exhausted (count=1): the SAME part ships cleanly
    assert ship().success and installs == ["g"]

    # an explicitly registered injector outranks the plane
    class Inj(chunked_sync.SyncFailureInjector):
        def before_sync(self, part_dirs):
            return (True, "explicit injector wins")

    faults.configure("sync=cut:every=1")
    chunked_sync.register_failure_injector(Inj())
    try:
        with pytest.raises(TransportError, match="explicit"):
            ship()
    finally:
        chunked_sync.clear_failure_injector()


# -- disk boundary -----------------------------------------------------------


def test_disk_boundary_wqueue_seal_enospc_restores_rows(tmp_path):
    reg = SchemaRegistry(tmp_path / "schema")
    _schema(reg)
    shipped = []
    wq = WriteQueue(
        reg, tmp_path / "spool", lambda g, s, d: shipped.append(d)
    )
    # one svc -> one shard -> ONE seal key: the single disk decision
    # hits the only seal (multi-key seals decide independently)
    wq.append(WriteRequest("fg", "m", _points(0, 50, mod=1)))

    faults.configure("disk=enospc:every=1:count=1")
    with pytest.raises(OSError):
        wq.flush()
    # acked rows survived the failed seal (restored to the buffer)
    assert wq.buffered_rows() == 50 and wq.pending_parts() == 0

    faults.clear()
    wq.flush()
    assert wq.buffered_rows() == 0 and wq.pending_parts() == 0
    assert shipped, "rows lost after ENOSPC recovery"


def test_disk_boundary_wqueue_short_write_cleans_staging(tmp_path):
    reg = SchemaRegistry(tmp_path / "schema")
    _schema(reg)
    wq = WriteQueue(reg, tmp_path / "spool", lambda g, s, d: None)
    wq.append(WriteRequest("fg", "m", _points(0, 30, mod=1)))
    faults.configure("disk=short:every=1:count=1")
    with pytest.raises(OSError):
        wq.flush()
    # the torn .tmp staging dir was cleaned, rows restored
    assert not list((tmp_path / "spool").glob(".tmp*"))
    assert wq.buffered_rows() == 30
    faults.clear()
    wq.flush()
    assert wq.buffered_rows() == 0


def test_disk_boundary_handoff_short_write_skipped_at_replay(tmp_path):
    from banyandb_tpu.cluster.handoff import HandoffController

    h = HandoffController(tmp_path / "spool")
    faults.configure("disk=short:every=1:count=1")
    with pytest.raises(OSError):
        h.spool("n0", "t", {"seq": 0})
    faults.clear()
    h.spool("n0", "t", {"seq": 1})

    got = []
    done = h.replay("n0", lambda topic, env: got.append(env["seq"]))
    # the torn record is dropped (it was never acked as spooled); the
    # good one delivers
    assert got == [1] and done == 2
    assert h.pending("n0") == 0


# -- write-path hardening ----------------------------------------------------


def test_spool_watermark_backpressure_sheds(tmp_path):
    reg = SchemaRegistry(tmp_path / "schema")
    _schema(reg)

    down = {"v": True}

    def shipper(g, s, d):
        if down["v"]:
            raise RuntimeError("node down")

    wq = WriteQueue(
        reg, tmp_path / "spool", shipper,
        max_spool_bytes=1024,  # tiny watermark: one sealed part trips it
        retry_base_s=0.0,
    )
    wq.append(WriteRequest("fg", "m", _points(0, 200)))
    shipped, failed = wq.flush()
    assert shipped == 0 and failed >= 1
    assert wq.spool_bytes() > 1024

    from banyandb_tpu.admin.protector import ServerBusy

    with pytest.raises(ServerBusy):
        wq.append(WriteRequest("fg", "m", _points(200, 10)))
    # ServerBusy serializes as a structured shed rejection on the wire
    assert "ServerBusy" in _SHED_TYPES

    # drain -> admission reopens; no acked row was lost
    down["v"] = False
    wq.flush(force=True)
    assert wq.spool_bytes() == 0
    assert wq.append(WriteRequest("fg", "m", _points(200, 10))) == 10


def test_ship_retry_backoff_paces_attempts(tmp_path):
    reg = SchemaRegistry(tmp_path / "schema")
    _schema(reg)
    calls = []

    def failing(g, s, d):
        calls.append(time.monotonic())
        raise RuntimeError("still down")

    wq = WriteQueue(
        reg, tmp_path / "spool", failing,
        retry_base_s=0.2, retry_cap_s=1.0,
    )
    wq.append(WriteRequest("fg", "m", _points(0, 20, mod=1)))
    shipped, failed = wq.flush()
    assert (shipped, failed) == (0, 1) and len(calls) == 1

    # immediately due again? no: the part waits out its backoff window
    shipped, failed = wq.ship_pending()
    assert (shipped, failed) == (0, 0) and len(calls) == 1  # deferred

    time.sleep(0.3)
    shipped, failed = wq.ship_pending()
    assert failed == 1 and len(calls) == 2  # due after base*2^0 (+jitter)

    # force bypasses the clock (final flush / post-recovery drain)
    wq.ship_pending(force=True)
    assert len(calls) == 3


def test_idempotent_install_dedupes_by_part_uuid(tmp_path):
    """A re-shipped part after an ack-lost crash installs exactly once:
    the receiver keys on the sealer's part uuid (seal_session)."""
    reg = SchemaRegistry(tmp_path / "schema")
    _schema(reg, shard_num=1)
    dn = DataNode("n0", reg, tmp_path / "data")
    from banyandb_tpu.storage.part import PartWriter

    part_dir = tmp_path / "sealed" / "part-000000"
    PartWriter.write(
        part_dir,
        ts=np.asarray([T0, T0 + 1], dtype=np.int64),
        series=np.asarray([1, 1], dtype=np.uint64),
        version=np.asarray([1, 1], dtype=np.int64),
        tag_codes={"svc": np.asarray([0, 0], dtype=np.int32)},
        tag_dicts={"svc": [b"s0"]},
        fields={"v": np.asarray([1.0, 2.0])},
        extra_meta={
            "measure": "m", "group": "fg", "catalog": "measure",
            "seal_session": "cafe0001",
        },
    )
    files = {
        f.name: f.read_bytes() for f in part_dir.iterdir() if f.is_file()
    }
    meta = SimpleNamespace(group="fg", shard_id=0)
    pi = SimpleNamespace(min_timestamp=T0)

    dn.install_synced_parts(meta, [(pi, files)])
    dn.install_synced_parts(meta, [(pi, files)])  # ack-lost re-ship

    seg = dn.measure._tsdb("fg").segment_for(T0)
    assert len(seg.shards[0].parts) == 1, "uuid re-delivery double-installed"

    # same uuid, different bytes (e.g. rewritten metadata) still dedupes
    files2 = dict(files)
    files2["metadata.json"] = files["metadata.json"] + b" "
    dn.install_synced_parts(meta, [(pi, files2)])
    assert len(seg.shards[0].parts) == 1
    dn.measure.close()
    dn.stream.close()
    dn.trace.close()


# -- graceful query degradation ---------------------------------------------


def _local_cluster(tmp_path, n=3, replicas=0, budget_s=30.0):
    transport = LocalTransport()
    dns, infos = {}, []
    for i in range(n):
        reg = SchemaRegistry(tmp_path / f"n{i}" / "schema")
        _schema(reg)
        dn = DataNode(f"n{i}", reg, tmp_path / f"n{i}" / "data")
        dns[f"n{i}"] = dn
        infos.append(NodeInfo(f"n{i}", transport.register(f"n{i}", dn.bus)))
    lreg = SchemaRegistry(tmp_path / "liaison" / "schema")
    _schema(lreg)
    liaison = Liaison(
        lreg, transport, infos, replicas=replicas, query_budget_s=budget_s
    )
    liaison.probe()
    return transport, liaison, dns


def _close_all(dns):
    for dn in dns.values():
        dn.measure.close()
        dn.stream.close()
        dn.trace.close()


def test_degraded_markers_on_unreplicated_node_loss(tmp_path):
    transport, liaison, dns = _local_cluster(tmp_path, replicas=0)
    total = 120
    liaison.write_measure(WriteRequest("fg", "m", _points(0, total)))
    for dn in dns.values():
        dn.measure.flush()
    res = liaison.query_measure(_count_req())
    assert _total(res) == total and not res.degraded

    from banyandb_tpu.obs.metrics import global_meter

    key = ("query_degraded", (("engine", "measure"),))
    before = global_meter().snapshot()["counters"].get(key, 0.0)

    # node lost MID-QUERY (no probe ran): scatter fails, failover finds
    # no replica, the answer degrades with an explicit marker
    transport.unregister("n1")
    res = liaison.query_measure(_count_req(trace=True))
    assert res.degraded and res.unavailable_nodes == ["n1"]
    assert 0 < _total(res) < total
    after = global_meter().snapshot()["counters"].get(key, 0.0)
    assert after == before + 1

    # markers ride the JSON wire shape too (bus/HTTP surfaces)
    from banyandb_tpu.server import result_to_json

    j = result_to_json(res)
    assert j["degraded"] is True and j["unavailable_nodes"] == ["n1"]

    # and the span tree carries the tags for the flight recorder
    tree = res.trace["span_tree"]

    def find_tag(node, key):
        if key in (node.get("tags") or {}):
            return node["tags"][key]
        for c in node.get("children", ()):
            got = find_tag(c, key)
            if got is not None:
                return got
        return None

    assert find_tag(tree, "degraded") is True
    assert find_tag(tree, "unavailable_nodes") == ["n1"]

    # recovery: the node returns, probe revives it, result completes
    transport.register("n1", dns["n1"].bus)
    liaison.probe()
    res = liaison.query_measure(_count_req())
    assert _total(res) == total and not res.degraded
    _close_all(dns)


def test_transient_sole_replica_failure_retries_same_node(tmp_path):
    """replicas=0: a scatter leg that fails ONCE with a transient
    transport error (the wedged-channel dial this kernel hands out
    under load) must not silently degrade — with no replica to fail
    over to, the original node gets the one failover attempt, and the
    fresh call completes the result."""
    transport, liaison, dns = _local_cluster(tmp_path, replicas=0)
    total = 120
    liaison.write_measure(WriteRequest("fg", "m", _points(0, total)))
    for dn in dns.values():
        dn.measure.flush()

    real_call = transport.call
    blown = {"n": 0}

    def flaky_call(addr, topic, envelope, timeout=30.0):
        if topic == Topic.MEASURE_QUERY_PARTIAL and blown["n"] == 0:
            blown["n"] += 1
            raise TransportError("wedged channel", kind="error")
        return real_call(addr, topic, envelope, timeout=timeout)

    transport.call = flaky_call
    res = liaison.query_measure(_count_req())
    assert blown["n"] == 1, "fault did not fire"
    assert _total(res) == total
    assert not res.degraded, "transient one-shot failure must heal"
    _close_all(dns)


def test_degraded_assignment_time_skip(tmp_path):
    """A node already known dead (probe ran) degrades at PLANNING time:
    its shards are skipped, the query still answers."""
    transport, liaison, dns = _local_cluster(tmp_path, replicas=0)
    total = 120
    liaison.write_measure(WriteRequest("fg", "m", _points(0, total)))
    for dn in dns.values():
        dn.measure.flush()
    transport.unregister("n2")
    liaison.probe()  # alive set now excludes n2
    res = liaison.query_measure(_count_req())
    assert res.degraded and res.unavailable_nodes == ["n2"]
    assert 0 < _total(res) < total
    _close_all(dns)


def test_failover_covers_replicated_node_loss_without_degrading(tmp_path):
    """With replicas, a mid-query node loss fails over to the replica:
    the result is COMPLETE and must not be marked degraded."""
    transport, liaison, dns = _local_cluster(tmp_path, replicas=1)
    total = 120
    liaison.write_measure(WriteRequest("fg", "m", _points(0, total)))
    for dn in dns.values():
        dn.measure.flush()
    transport.unregister("n0")  # mid-query loss, replica still up
    res = liaison.query_measure(_count_req())
    assert _total(res) == total
    assert not res.degraded, "failover covered the loss; not degraded"
    assert "n0" not in liaison.alive  # but the peer was marked dead
    _close_all(dns)


def test_total_outage_still_raises(tmp_path):
    transport, liaison, dns = _local_cluster(tmp_path, n=2, replicas=0)
    transport.unregister("n0")
    transport.unregister("n1")
    liaison.probe()
    with pytest.raises(TransportError):
        liaison.query_measure(_count_req())
    _close_all(dns)


def test_stream_query_degrades_too(tmp_path):
    from banyandb_tpu.api.schema import Stream

    transport = LocalTransport()
    dns, infos = {}, []
    for i in range(2):
        reg = SchemaRegistry(tmp_path / f"n{i}" / "schema")
        reg.create_group(
            Group("fg", Catalog.STREAM, ResourceOpts(shard_num=2))
        )
        dn = DataNode(f"n{i}", reg, tmp_path / f"n{i}" / "data")
        dns[f"n{i}"] = dn
        infos.append(NodeInfo(f"n{i}", transport.register(f"n{i}", dn.bus)))
    lreg = SchemaRegistry(tmp_path / "liaison" / "schema")
    lreg.create_group(Group("fg", Catalog.STREAM, ResourceOpts(shard_num=2)))
    st = Stream(group="fg", name="s", tags=(TagSpec("svc", TagType.STRING),),
                entity=("svc",))
    lreg.create_stream(st)
    liaison = Liaison(lreg, transport, infos, replicas=0)
    liaison.probe()
    schema = {"group": "fg", "name": "s", "entity": ["svc"],
              "tags": [{"name": "svc", "type": "string"}],
              "trace_id_tag": ""}
    elements = [
        {"element_id": f"e{i}", "ts": T0 + i, "tags": {"svc": f"s{i % 4}"},
         "body": ""}
        for i in range(40)
    ]
    liaison.write_stream("fg", "s", schema, elements)
    transport.unregister("n1")
    liaison.probe()
    res = liaison.query_stream(
        QueryRequest(groups=("fg",), name="s",
                     time_range=TimeRange(T0, T0 + 1_000_000), limit=100)
    )
    assert res.degraded and res.unavailable_nodes == ["n1"]
    assert 0 < len(res.data_points) < 40
    _close_all(dns)


# -- deadline propagation ----------------------------------------------------


def test_deadline_stops_scatter_past_budget(tmp_path):
    """One slow node eats its slice of the budget; the next leg is
    skipped (degraded, reason=deadline) instead of wedging the query."""
    transport = LocalTransport()
    calls = {"a": 0, "b": 0}
    slow_reg = SchemaRegistry(tmp_path / "a" / "schema")
    _schema(slow_reg, shard_num=2)
    dn_a = DataNode("a", slow_reg, tmp_path / "a" / "data")
    dn_b = DataNode("b", SchemaRegistry(tmp_path / "b" / "schema"),
                    tmp_path / "b" / "data")
    _schema(dn_b.registry, shard_num=2)

    real_a = dn_a._on_measure_query_partial

    def slow_a(env):
        # answers correctly, but the REPLY arrives after the budget is
        # gone (scan fast, wire slow) — the liaison must keep a's data
        # and skip the next leg
        calls["a"] += 1
        r = real_a(env)
        time.sleep(0.35)
        return r

    dn_a.bus.subscribe(Topic.MEASURE_QUERY_PARTIAL, slow_a)

    def count_b(env):
        calls["b"] += 1
        return dn_b._on_measure_query_partial(env)

    dn_b.bus.subscribe(Topic.MEASURE_QUERY_PARTIAL, count_b)
    infos = [
        NodeInfo("a", transport.register("a", dn_a.bus)),
        NodeInfo("b", transport.register("b", dn_b.bus)),
    ]
    lreg = SchemaRegistry(tmp_path / "l" / "schema")
    _schema(lreg, shard_num=2)
    liaison = Liaison(lreg, transport, infos, replicas=0,
                      query_budget_s=0.25)
    liaison.probe()
    liaison.write_measure(WriteRequest("fg", "m", _points(0, 40)))
    for dn in (dn_a, dn_b):
        dn.measure.flush()

    res = liaison.query_measure(_count_req())
    assert calls["a"] == 1 and calls["b"] == 0, "leg ran past the deadline"
    assert res.degraded and "b" in res.unavailable_nodes
    assert _total(res) > 0  # a's data survived; b's shards are missing

    # when EVERY leg blows the budget, the aggregate cannot be honestly
    # degraded (it would fabricate zeros) — it raises kind="deadline"
    def dead_slow(env):
        time.sleep(0.3)  # burns the whole budget BEFORE the scan
        return real_a(env)

    dn_a.bus.subscribe(Topic.MEASURE_QUERY_PARTIAL, dead_slow)
    dn_b.bus.subscribe(Topic.MEASURE_QUERY_PARTIAL, dead_slow)
    with pytest.raises(TransportError) as ei:
        liaison.query_measure(_count_req())
    assert ei.value.kind == "deadline"
    for dn in (dn_a, dn_b):
        dn.measure.close()
        dn.stream.close()
        dn.trace.close()


def test_client_side_rpc_deadline_is_structured(tmp_path):
    """A liaison whose budget-clamped timeout expires must see
    kind="deadline" (its own budget ran out), never evict the slow-but-
    healthy node as dead."""
    grpc = pytest.importorskip("grpc")  # noqa: F841 - wire-level test
    from banyandb_tpu.cluster.rpc import GrpcBusServer, GrpcTransport

    bus = LocalBus()

    def slow(env):
        time.sleep(0.5)
        return {"status": "ok"}

    bus.subscribe(Topic.HEALTH, slow)
    srv = GrpcBusServer(bus, port=0)
    srv.start()
    transport = GrpcTransport()
    try:
        with pytest.raises(TransportError) as ei:
            transport.call(srv.addr, Topic.HEALTH.value, {}, timeout=0.05)
        assert ei.value.kind == "deadline"
        # the peer answers fine with a real budget
        r = transport.call(srv.addr, Topic.HEALTH.value, {}, timeout=5)
        assert r["status"] == "ok"
    finally:
        transport.close()
        srv.stop(grace=0)


def test_data_node_rejects_expired_deadline(tmp_path):
    from banyandb_tpu.cluster.faults import DeadlineExceeded

    reg = SchemaRegistry(tmp_path / "schema")
    _schema(reg)
    dn = DataNode("n0", reg, tmp_path / "data")
    with pytest.raises(DeadlineExceeded):
        dn._on_measure_query_raw({"deadline_ms": -5, "request": {}})
    # the ABSOLUTE wall deadline fires even when the send-time snapshot
    # looked healthy (budget burned in the receiver's executor queue)
    with pytest.raises(DeadlineExceeded):
        dn._on_measure_query_raw({
            "deadline_ms": 500.0,
            "deadline_unix_ms": time.time() * 1000.0 - 10.0,
            "request": {},
        })
    # over the transport the refusal is structured: kind="deadline"
    # (healthy node — the liaison must not evict it)
    transport = LocalTransport()
    addr = transport.register("n0", dn.bus)
    with pytest.raises(TransportError) as ei:
        transport.call(
            addr, Topic.MEASURE_QUERY_RAW.value,
            {"deadline_ms": 0, "request": {}}, timeout=5,
        )
    assert ei.value.kind == "deadline"
    dn.measure.close()
    dn.stream.close()
    dn.trace.close()
