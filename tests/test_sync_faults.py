"""Chunked-sync fault injection (reference: queue.go:230
ChunkedSyncFailureInjector, pub.go:301-387 eviction/shed semantics).

Every test drives the REAL wire: a grpc server hosting SyncPart and a
client shipping real part dirs, with deterministic faults injected at
the sender."""

import functools
import threading
import time
from concurrent import futures

import pytest

grpc = pytest.importorskip("grpc")

from banyandb_tpu.cluster import chunked_sync  # noqa: E402
from banyandb_tpu.cluster.rpc import TransportError  # noqa: E402


@pytest.fixture()
def sync_stack(tmp_path):
    installs = []
    lock = threading.Lock()

    def install_cb(meta, parts):
        with lock:
            installs.append((meta.group, [dict(f) for _, f in parts]))

    # own the pool: grpc never shuts down a caller-provided executor, and
    # a worker left behind (its exit otherwise rides GC timing) trips the
    # bdsan thread-parity check
    pool = futures.ThreadPoolExecutor(max_workers=4)
    server = grpc.server(pool)
    server.add_generic_rpc_handlers((chunked_sync.generic_handler(install_cb),))
    port = server.add_insecure_port("127.0.0.1:0")
    server.start()
    dial = functools.partial(grpc.insecure_channel, f"127.0.0.1:{port}")

    part = tmp_path / "0000000000000001-0001"
    part.mkdir()
    (part / "primary.bin").write_bytes(b"\x07" * 4096)
    (part / "timestamps.bin").write_bytes(b"\x01" * 512)

    yield dial, part, installs
    chunked_sync.clear_failure_injector()
    server.stop(grace=0.2).wait()
    pool.shutdown(wait=True)


def _ship(dial, part):
    return chunked_sync.sync_part_dirs(dial, [part], group="g", shard_id=0)


def test_no_injector_baseline(sync_stack):
    dial, part, installs = sync_stack
    res = _ship(dial, part)
    assert res.success and res.parts_received == 1
    assert installs[0][0] == "g"
    assert installs[0][1][0]["primary.bin"] == b"\x07" * 4096


def test_before_sync_short_circuit(sync_stack):
    dial, part, installs = sync_stack

    class Inj(chunked_sync.SyncFailureInjector):
        def before_sync(self, part_dirs):
            assert part_dirs[0].name.endswith("-0001")
            return (True, "disk cable unplugged")

    chunked_sync.register_failure_injector(Inj())
    with pytest.raises(TransportError, match="injected"):
        _ship(dial, part)
    assert installs == []  # the stream never opened

    # clearing the injector restores the path (queue.go:250 analog)
    chunked_sync.clear_failure_injector()
    assert _ship(dial, part).success


def test_corrupted_chunk_rejected_by_receiver_crc(sync_stack):
    dial, part, installs = sync_stack

    class Inj(chunked_sync.SyncFailureInjector):
        def mutate_request(self, req):
            if req.chunk_index == 0 and req.chunk_data:
                # flip bytes AFTER the checksum was computed: wire corruption
                req.chunk_data = b"\xff" + req.chunk_data[1:]
            return req

    chunked_sync.register_failure_injector(Inj())
    with pytest.raises(TransportError, match="status=2"):  # CRC mismatch
        _ship(dial, part)
    assert installs == []  # no partial install


def test_out_of_order_chunk_rejected(sync_stack):
    dial, part, installs = sync_stack

    class Inj(chunked_sync.SyncFailureInjector):
        def mutate_request(self, req):
            if req.WhichOneof("content") == "completion":
                req.chunk_index += 7  # skip ahead
            return req

    chunked_sync.register_failure_injector(Inj())
    with pytest.raises(TransportError, match="status=3"):  # OUT_OF_ORDER
        _ship(dial, part)
    assert installs == []


def test_stream_killed_mid_flight(sync_stack):
    dial, part, installs = sync_stack

    class Boom(RuntimeError):
        pass

    class Inj(chunked_sync.SyncFailureInjector):
        def mutate_request(self, req):
            if req.WhichOneof("content") == "completion":
                raise Boom("sender died before completion")
            return req

    chunked_sync.register_failure_injector(Inj())
    with pytest.raises((TransportError, Boom)):
        _ship(dial, part)
    assert installs == []  # receiver never installed a half sync

    # recovery: the same sealed part ships cleanly on retry (the spool
    # contract — a failed ship leaves the part intact for the next tick)
    chunked_sync.clear_failure_injector()
    assert _ship(dial, part).success
    assert len(installs) == 1


class _WedgedCall(grpc.RpcError):
    """A SyncPart call that has its status but whose pending receive
    never completes until its channel closes: what grpc (1.76) did to a
    stream whose peer stopped with grace=0."""

    def __init__(self, closed):
        self._closed = closed

    def add_callback(self, callback):
        return False  # the status is in already

    def code(self):
        return grpc.StatusCode.CANCELLED

    def __iter__(self):
        return self

    def __next__(self):
        self._closed.wait()
        raise self


class _WedgedChannel:
    """A channel whose SyncPart stream wedges; dial() is the channel."""

    def __init__(self):
        self.closed = threading.Event()

    def __call__(self):
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def close(self):
        self.closed.set()

    def stream_stream(self, method, **_):
        return lambda requests, timeout: _WedgedCall(self.closed)


def test_a_wedged_sync_stream_ends_within_its_grace(tmp_path):
    """A stream whose status is in but whose iteration never ends gets
    its channel closed _WEDGE_GRACE_S later and raises TransportError:
    it never holds the liaison's shipper (in the suite,
    test_chaos.py::test_streamagg_failover_windows_gap_free hung the
    driver's tier-1 run on PR 38's tree in this wait)."""
    part = tmp_path / "p-0001"
    part.mkdir()
    (part / "primary.bin").write_bytes(b"z" * 128)
    chan = _WedgedChannel()
    t0 = time.monotonic()
    with pytest.raises(TransportError, match="CANCELLED"):
        chunked_sync.sync_part_dirs(chan, [part], group="g", shard_id=0)
    assert chan.closed.is_set()
    assert time.monotonic() - t0 < chunked_sync._WEDGE_GRACE_S + 3.0


def test_sync_to_a_peer_stopped_with_grace_0_ends(tmp_path):
    """A SyncPart stream on a connected channel whose peer has just
    stopped with grace=0 raises TransportError inside its timeout and
    the wedge grace, as the liaison's shipper meets a node killed
    between its dial and its stream."""
    from banyandb_tpu.cluster.bus import LocalBus
    from banyandb_tpu.cluster.rpc import GrpcBusServer, GrpcTransport

    part = tmp_path / "p-0001"
    part.mkdir()
    (part / "primary.bin").write_bytes(b"z" * 128)
    transport = GrpcTransport()
    for _ in range(10):
        srv = GrpcBusServer(LocalBus(), sync_install=lambda meta, parts: None)
        srv.start()
        assert _ship(functools.partial(transport.channel, srv.addr), part).success
        chan = transport.channel(srv.addr)
        grpc.channel_ready_future(chan).result(timeout=10)
        srv.stop(grace=0)
        t0 = time.monotonic()
        with pytest.raises(TransportError):
            chunked_sync.sync_part_dirs(
                lambda chan=chan: chan, [part], group="g", shard_id=0,
                timeout=2.0,
            )
        # timeout 2 s + the wedge grace + slack
        assert time.monotonic() - t0 < 8.0


def test_install_failure_reported_in_band(tmp_path):
    """Receiver-side install errors surface as failed parts_results, and
    the sender raises (failed-parts quarantine trigger path)."""

    def install_cb(meta, parts):
        raise IOError("disk full on data node")

    pool = futures.ThreadPoolExecutor(max_workers=2)
    server = grpc.server(pool)
    server.add_generic_rpc_handlers((chunked_sync.generic_handler(install_cb),))
    port = server.add_insecure_port("127.0.0.1:0")
    server.start()
    dial = functools.partial(grpc.insecure_channel, f"127.0.0.1:{port}")
    part = tmp_path / "p-0001"
    part.mkdir()
    (part / "primary.bin").write_bytes(b"z" * 128)
    try:
        with pytest.raises(TransportError, match="disk full"):
            chunked_sync.sync_part_dirs(dial, [part], group="g", shard_id=0)
    finally:
        server.stop(grace=0.2).wait()
        pool.shutdown(wait=True)


# -- pub-side eviction / shed semantics under repeated failure ---------------


def test_liaison_eviction_and_shed_semantics(tmp_path):
    """Repeated hard errors evict a node from the alive set; shedding
    (DiskFull/ServerBusy) keeps it alive with spooled copies; a probe
    revives recovered nodes (pub.go:301,364,387 analog)."""
    from banyandb_tpu.admin.diskmonitor import DiskFull
    from banyandb_tpu.cluster.bus import LocalBus, Topic
    from banyandb_tpu.cluster.liaison import Liaison
    from banyandb_tpu.cluster.node import NodeInfo
    from banyandb_tpu.cluster.rpc import LocalTransport

    transport = LocalTransport()
    state = {"n1": "ok", "n2": "ok"}
    buses = {}
    for name in ("n1", "n2"):
        bus = LocalBus()

        def mk(name):
            def handler(env):
                if state[name] == "shed":
                    raise DiskFull("disk over limit")
                return {"status": "ok"}

            return handler

        bus.subscribe(Topic.MEASURE_WRITE, mk(name))
        bus.subscribe(Topic.HEALTH, mk(name))
        buses[name] = bus

    def set_dead(name, dead):  # a dead node is unreachable at the transport
        if dead:
            transport.unregister(name)
        else:
            transport.register(name, buses[name])
    from banyandb_tpu.api.schema import SchemaRegistry

    nodes = [NodeInfo(n, transport.register(n, buses[n])) for n in ("n1", "n2")]
    li = Liaison(
        SchemaRegistry(tmp_path / "reg"), transport, nodes,
        replicas=1, handoff_root=tmp_path / "spool",
    )

    env = {"request": {"group": "g", "name": "m", "points": []}}
    by_node = {n.name: env for n in nodes}
    addr_of = {n.name: n.addr for n in nodes}

    # hard failure evicts n2 from the alive set
    set_dead("n2", True)
    li._deliver_writes(Topic.MEASURE_WRITE.value, by_node, addr_of, {})
    assert li.alive == {"n1"}

    # shed keeps the node alive (it is not dead, just full)
    set_dead("n2", False)
    li.probe()
    assert li.alive == {"n1", "n2"}
    state["n1"] = "shed"
    li._deliver_writes(Topic.MEASURE_WRITE.value, by_node, addr_of, {})
    assert "n1" in li.alive  # shed != evicted

    # every replica shedding surfaces the retryable error to the caller
    state["n2"] = "shed"
    with pytest.raises(TransportError):
        li._deliver_writes(Topic.MEASURE_WRITE.value, by_node, addr_of, {})
    assert {"n1", "n2"} <= li.alive

    # recovery: probe revives a dead node once it answers health again
    state["n1"] = state["n2"] = "ok"
    set_dead("n1", True)
    set_dead("n2", True)
    with pytest.raises(TransportError):  # no replica reachable
        li._deliver_writes(Topic.MEASURE_WRITE.value, by_node, addr_of, {})
    assert li.alive == set()
    set_dead("n1", False)
    set_dead("n2", False)
    assert li.probe() == {"n1", "n2"}
