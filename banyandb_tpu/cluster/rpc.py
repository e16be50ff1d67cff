"""Message transports: in-process and gRPC.

The gRPC transport uses generic (codegen-free) handlers on one method
``/banyandb.Bus/Call`` carrying JSON envelopes — the analog of the
reference's bus-over-gRPC (banyand/queue/pub + sub) with topic dispatch
on the server side.  Chunked part sync rides the same method with binary
chunks base64'd inside the envelope (a streaming method can replace this
without changing the Bus surface).
"""

from __future__ import annotations

import json
import threading
import time
from concurrent import futures
from typing import Optional

from banyandb_tpu.cluster import faults
from banyandb_tpu.cluster.bus import LocalBus
from banyandb_tpu.obs import metrics as obs_metrics

_METHOD = "/banyandb.Bus/Call"
# handlers a GrpcBusServer runs at once: its thread pool's size.  A
# request that arrives while all of them run waits in the pool's queue
# (_TimedPool): the wait is its ``qos`` span's ``pool_wait_ms`` and an
# observation of /metrics ``rpc_pool_wait_ms``
_BUS_WORKERS = 8
# the handler this thread is running: how many of its server's handlers
# ran when it started (handler_busy) and what it waited for this worker
# (handler_pool_wait_ms)
_HANDLER = threading.local()


def handler_busy() -> int:
    """Handlers of the bus server that were running when the handler on
    THIS thread started, itself included (the ``qos`` span's
    ``rpc_busy``); 0 on a thread that runs none (a LocalTransport call).
    ``_BUS_WORKERS`` means the pool was full from then on."""
    return getattr(_HANDLER, "busy", 0)


def handler_pool_wait_ms() -> float:
    """What the handler on THIS thread waited for its worker, from
    grpc's hand-over to the pool to the worker starting it (the ``qos``
    span's ``pool_wait_ms``); 0.0 on a thread that runs none."""
    return getattr(_HANDLER, "pool_wait_ms", 0.0)


def tag_qos(tracer, adm) -> None:
    """The ``qos`` span on the obs plane: which tenant ran, how long
    admission took (always a number: microseconds when it did not
    queue), what the request waited for a worker of the bus server
    before any span was open (``pool_wait_ms``, always a number), and
    what ran beside it when it started: ``inflight`` queries admitted
    and not yet released, ``rpc_busy`` handlers of the bus server
    running, itself included in both."""
    with tracer.span("qos") as sp:
        sp.tag("tenant", adm.tenant)
        sp.tag("queued_ms", round(adm.queued_ms, 3))
        sp.tag("inflight", adm.inflight)
        sp.tag("rpc_busy", handler_busy())
        sp.tag("pool_wait_ms", round(handler_pool_wait_ms(), 3))


def _observe_rpc(side: str, topic: str, t0: float) -> None:
    """Stage-labelled fabric latency: rpc_client_ms / rpc_server_ms per
    topic.  Handle lookup is the meter's lock-free fast path; observe
    happens after the call completes, never under a transport lock."""
    obs_metrics.global_meter().histogram(
        f"rpc_{side}_ms", {"topic": topic}
    ).observe((time.perf_counter() - t0) * 1000)


class TransportError(RuntimeError):
    """kind: "error" (default), "shed" — the remote rejected the call to
    shed load (DiskFull/ServerBusy); "deadline" — the remote refused
    work whose propagated deadline already expired; or "stale_epoch" —
    the remote fenced a write stamped with a superseded placement epoch
    (cluster/placement.py): the SENDER must refresh its map and retry.
    Shed, deadline and stale-epoch rejecting nodes are healthy and must
    not be treated as dead."""

    def __init__(self, msg: str, kind: str = "error"):
        super().__init__(msg)
        self.kind = kind


# write-admission exception class names serialized as shed rejections
_SHED_TYPES = ("DiskFull", "ServerBusy")


def _error_kind(e: Exception) -> str:
    """Classify a handler exception for the wire: shed rejections,
    deadline refusals and stale-epoch fences are structured (the caller
    must NOT evict the node); everything else is a hard error."""
    name = type(e).__name__
    if name in _SHED_TYPES:
        return "shed"
    if name == "DeadlineExceeded":
        return "deadline"
    if name == "StaleEpoch":
        return "stale_epoch"
    return "error"


class LocalTransport:
    """In-process transport: addr "local:<name>" -> LocalBus.

    The standalone wiring AND the multi-node-in-one-process test trick
    (pkg/test/setup analog) both ride this.
    """

    def __init__(self):
        self._buses: dict[str, LocalBus] = {}
        self._lock = threading.Lock()

    def register(self, name: str, bus: LocalBus) -> str:
        with self._lock:
            self._buses[name] = bus
        return f"local:{name}"

    def unregister(self, name: str) -> None:
        with self._lock:
            self._buses.pop(name, None)

    def call(self, addr: str, topic: str, envelope: dict, timeout: float = 30.0) -> dict:
        assert addr.startswith("local:"), addr
        faults.maybe_fail_rpc(addr, topic)
        bus = self._buses.get(addr[6:])
        if bus is None:
            raise TransportError(f"node {addr} unreachable")
        t0 = time.perf_counter()
        try:
            return bus.handle(topic, envelope)
        except Exception as e:
            # mirror the gRPC transport's shed/deadline classification;
            # all other exceptions keep propagating raw (standalone-equal
            # behavior)
            kind = _error_kind(e)
            if kind != "error":
                raise TransportError(
                    f"{type(e).__name__}: {e}", kind=kind
                ) from e
            raise
        finally:
            _observe_rpc("client", topic, t0)


def prespawn_pool(pool) -> None:
    """Start every worker thread of a ThreadPoolExecutor NOW.

    Executor workers normally spawn lazily on first submit, which (a)
    adds thread-creation latency to the first RPCs a fresh server
    receives and (b) makes the thread population nondeterministic — the
    bdsan per-test thread-parity check needs a server's threads to exist
    when the server starts, not when the first request lands."""
    import threading as _t

    n = pool._max_workers
    barrier = _t.Barrier(n + 1)

    def hold():
        try:
            barrier.wait(timeout=10)
        except _t.BrokenBarrierError:  # pragma: no cover - degraded start
            pass

    for _ in range(n):
        pool.submit(hold)
    try:
        barrier.wait(timeout=10)
    except _t.BrokenBarrierError:  # pragma: no cover - degraded start
        pass


class _TimedPool(futures.ThreadPoolExecutor):
    """The bus server's worker pool with a clock on its queue.  grpc
    hands every RPC to the executor when it arrives, so submit -> the
    worker calling the function is exactly the wait for a worker: it
    lands in the worker's ``_HANDLER`` (handler_pool_wait_ms) and in
    /metrics ``rpc_pool_wait_ms``; ``queued`` counts the calls submitted
    and not yet started, under the server's ``_busy_lock``."""

    def __init__(self, max_workers: int, lock: threading.Lock):
        super().__init__(max_workers=max_workers)
        self._queued_lock = lock
        self.queued = 0

    def submit(self, fn, /, *args, **kwargs):
        t0 = time.perf_counter()

        def started(*a, **kw):
            wait_ms = (time.perf_counter() - t0) * 1000
            with self._queued_lock:
                self.queued -= 1
            obs_metrics.global_meter().histogram("rpc_pool_wait_ms").observe(
                wait_ms
            )
            _HANDLER.pool_wait_ms = wait_ms
            try:
                return fn(*a, **kw)
            finally:
                _HANDLER.pool_wait_ms = 0.0

        with self._queued_lock:
            self.queued += 1
        try:
            return super().submit(started, *args, **kwargs)
        except BaseException:  # refused (shut down): it never queued
            with self._queued_lock:
                self.queued -= 1
            raise


class GrpcBusServer:
    """Serves a LocalBus over gRPC generic handlers (sub.NewServer analog).

    TLS: pass cert_file+key_file for server TLS with HOT RELOAD
    (pkg/tls/reloader.go analog) — rotated PEM files take effect on the
    next handshake via utils/tls_reloader.CertReloader."""

    def __init__(
        self,
        bus: LocalBus,
        port: int = 0,
        host: str = "127.0.0.1",
        *,
        cert_file: Optional[str] = None,
        key_file: Optional[str] = None,
        sync_install=None,
        extra_handlers=(),
    ):
        """sync_install: optional callback enabling the streaming
        ChunkedSyncService on this server (cluster/chunked_sync.py).
        extra_handlers: additional generic RPC handlers to co-host (e.g.
        property repair/gossip, cluster/property_repair_rpc.py)."""
        import grpc

        self.bus = bus
        self._busy = 0  # handlers running now, under _busy_lock
        self._busy_lock = threading.Lock()

        def call_behavior(request: bytes, context) -> bytes:
            msg = json.loads(request)
            t0 = time.perf_counter()
            _HANDLER.busy = self._enter_handler()
            try:
                reply = self.bus.handle(msg["topic"], msg["envelope"])
                return json.dumps({"ok": True, "reply": reply}).encode()
            except Exception as e:  # noqa: BLE001 - errors cross the wire
                return json.dumps(
                    {
                        "ok": False,
                        "kind": _error_kind(e),
                        "error": f"{type(e).__name__}: {e}",
                    }
                ).encode()
            finally:
                _HANDLER.busy = 0
                self._leave_handler()
                _observe_rpc("server", msg.get("topic", "?"), t0)

        handler = grpc.method_handlers_generic_handler(
            "banyandb.Bus",
            {
                "Call": grpc.unary_unary_rpc_method_handler(
                    call_behavior,
                    request_deserializer=lambda b: b,
                    response_serializer=lambda b: b,
                )
            },
        )

        # The reference-shaped internal fabric (cluster/v1/rpc.proto:188,
        # banyand/queue/sub): Send is a bidi stream of topic-addressed
        # envelopes (bodies are this bus's JSON envelopes), HealthCheck
        # answers per-service status.  Wire shape matches upstream; the
        # body codec is this framework's envelope JSON rather than the
        # per-topic protos of api/data.
        from banyandb_tpu.api import pb as _pb

        cl = _pb.cluster_rpc_pb2
        wr = _pb.model_write_pb2

        def send_behavior(req_iter, context):
            # a stream holds its worker for as long as it is open
            self._enter_handler()
            try:
                for req in req_iter:
                    try:
                        reply = self.bus.handle(
                            req.topic, json.loads(req.body or b"{}")
                        )
                        yield cl.SendResponse(
                            message_id=req.message_id,
                            body=json.dumps(reply).encode(),
                            status=wr.STATUS_SUCCEED,
                        )
                    except Exception as e:  # noqa: BLE001 - errors cross the wire
                        shed = type(e).__name__ in _SHED_TYPES
                        yield cl.SendResponse(
                            message_id=req.message_id,
                            error=f"{type(e).__name__}: {e}",
                            status=(
                                wr.STATUS_INTERNAL_ERROR
                                if not shed
                                else wr.STATUS_DISK_FULL
                            ),
                        )
            finally:
                self._leave_handler()

        def health_behavior(req, context):
            known = req.service_name in self.bus.topics() or not req.service_name
            return cl.HealthCheckResponse(
                service_name=req.service_name,
                status=wr.STATUS_SUCCEED if known else wr.STATUS_NOT_FOUND,
                error="" if known else f"unknown topic {req.service_name}",
            )

        cluster_service = grpc.method_handlers_generic_handler(
            "banyandb.cluster.v1.Service",
            {
                "Send": grpc.stream_stream_rpc_method_handler(
                    send_behavior,
                    request_deserializer=cl.SendRequest.FromString,
                    response_serializer=lambda m: m.SerializeToString(),
                ),
                "HealthCheck": grpc.unary_unary_rpc_method_handler(
                    health_behavior,
                    request_deserializer=cl.HealthCheckRequest.FromString,
                    response_serializer=lambda m: m.SerializeToString(),
                ),
            },
        )
        # the server does NOT own a pool it is merely handed: keep the
        # reference so stop() can join the workers (grpc never shuts a
        # caller-provided executor down — idle worker threads would
        # otherwise outlive every stopped server, a leak the bdsan
        # thread-parity check catches)
        self._pool = _TimedPool(_BUS_WORKERS, self._busy_lock)
        self._server = grpc.server(
            self._pool,
            options=[("grpc.max_receive_message_length", 64 * 1024 * 1024),
                     ("grpc.max_send_message_length", 64 * 1024 * 1024)],
        )
        self._server.add_generic_rpc_handlers((handler, cluster_service))
        if sync_install is not None:
            from banyandb_tpu.cluster import chunked_sync

            self._server.add_generic_rpc_handlers(
                (chunked_sync.generic_handler(sync_install),)
            )
        if extra_handlers:
            self._server.add_generic_rpc_handlers(tuple(extra_handlers))
        self.tls_reloader = None
        if cert_file and key_file:
            # hot-reloading credentials (pkg/tls/reloader.go:55 analog):
            # rotated PEMs take effect on the next handshake, no restart
            from banyandb_tpu.utils.tls_reloader import CertReloader

            self.tls_reloader = CertReloader(cert_file, key_file)
            self.port = self._server.add_secure_port(
                f"{host}:{port}", self.tls_reloader.server_credentials()
            )
        else:
            self.port = self._server.add_insecure_port(f"{host}:{port}")
        self.addr = f"{host}:{self.port}"

    def _enter_handler(self) -> int:
        """-> handlers running now, this one included."""
        with self._busy_lock:
            self._busy += 1
            return self._busy

    def _leave_handler(self) -> None:
        with self._busy_lock:
            self._busy -= 1

    def handlers_busy(self) -> int:
        """Handlers running now (/metrics ``rpc_handlers_busy``)."""
        with self._busy_lock:
            return self._busy

    def pool_queued(self) -> int:
        """Calls waiting for a worker now (/metrics ``rpc_pool_queued``)."""
        with self._busy_lock:
            return self._pool.queued

    def start(self) -> None:
        prespawn_pool(self._pool)
        self._server.start()

    def stop(self, grace: float = 1.0) -> None:
        self._server.stop(grace).wait()
        self._pool.shutdown(wait=True)


class GrpcTransport:
    """Client side: per-address channels (banyand/queue/pub analog).

    TLS: pass ca_file (PEM of the server cert / CA) to dial with
    credentials; optionally override the expected server name for
    self-signed certs."""

    def __init__(
        self,
        *,
        ca_file: Optional[str] = None,
        server_name_override: Optional[str] = None,
    ):
        self._channels: dict[str, object] = {}
        self._lock = threading.Lock()
        self._ca_file = ca_file
        self._server_name_override = server_name_override

    def channel(self, addr: str):
        """-> a new channel to addr for one stream; the caller closes it
        (chunked_sync.sync_part_dirs dials through this and does).  A
        stream never rides the cached channel of call(): one opened on a
        channel that has just taken its peer's GOAWAY can wait forever,
        and the stream's wedge guard closes the channel it rides."""
        import grpc

        options = [
            ("grpc.max_receive_message_length", 64 * 1024 * 1024),
            ("grpc.max_send_message_length", 64 * 1024 * 1024),
        ]
        if not self._ca_file:
            return grpc.insecure_channel(addr, options=options)
        from pathlib import Path as _P

        creds = grpc.ssl_channel_credentials(_P(self._ca_file).read_bytes())
        if self._server_name_override:
            options.append(
                ("grpc.ssl_target_name_override", self._server_name_override)
            )
        return grpc.secure_channel(addr, creds, options=options)

    def _stub(self, addr: str):
        """-> (unary-unary stub, the channel it rides) for addr.  The
        channel is returned so a failing call can evict exactly the
        channel it used (see _evict)."""
        with self._lock:
            ch = self._channels.get(addr)
            if ch is None:
                ch = self._channels[addr] = self.channel(addr)
            return (
                ch.unary_unary(
                    _METHOD,
                    request_serializer=lambda b: b,
                    response_deserializer=lambda b: b,
                ),
                ch,
            )

    def _evict(self, addr: str, failed) -> None:
        """Drop the channel a call just failed on so the next call dials
        a fresh one.  A channel whose connect wedged can stay in
        TRANSIENT_FAILURE long after the peer is reachable — observed on
        gVisor-class kernels, where a dial racing the server's bind
        establishes at the TCP layer but the client event engine misses
        the writability event, burning the full connect timeout per
        retry — while a fresh dial to the same address connects
        instantly.  Evicting on UNAVAILABLE bounds the damage to one
        failed call.  Identity-checked (a concurrent re-dial's healthy
        replacement is never dropped) and NOT closed: a concurrent call
        may still ride it, and close() would cancel it — the dropped
        channel is released when its last user lets go."""
        with self._lock:
            if self._channels.get(addr) is failed:
                del self._channels[addr]

    def call(self, addr: str, topic: str, envelope: dict, timeout: float = 30.0) -> dict:
        import grpc

        faults.maybe_fail_rpc(addr, topic)
        stub, ch = self._stub(addr)
        payload = json.dumps({"topic": topic, "envelope": envelope}).encode()
        t0 = time.perf_counter()
        try:
            raw = stub(payload, timeout=timeout)
        except grpc.RpcError as e:
            if e.code() == grpc.StatusCode.UNAVAILABLE:
                self._evict(addr, ch)
            # a client-enforced deadline says the CALL was too slow, not
            # that the peer is dead — callers clamping timeouts to a
            # query budget (liaison _QueryGuard) must not evict healthy
            # nodes over their own budget running out
            kind = (
                "deadline"
                if e.code() == grpc.StatusCode.DEADLINE_EXCEEDED
                else "error"
            )
            raise TransportError(
                f"rpc to {addr} failed: {e.code()}", kind=kind
            ) from e
        finally:
            _observe_rpc("client", topic, t0)
        msg = json.loads(raw)
        if not msg.get("ok"):
            raise TransportError(
                msg.get("error", "remote error"),
                kind=msg.get("kind", "error"),
            )
        return msg["reply"]

    def close(self) -> None:
        with self._lock:
            for ch in self._channels.values():
                ch.close()
            self._channels.clear()
