"""Data- and liaison-role server processes (pkg/cmdsetup/{data,liaison}.go
analog): the multi-process cluster form of the standalone server.

Role topology mirrors the reference (SURVEY §1): liaisons are the user
gateway — they own schema CRUD (pushed to data nodes over the schema
plane), route writes by entity shard, and scatter/merge queries; data
nodes own storage shards behind the gRPC bus.

    # data nodes (one per process/host)
    python -m banyandb_tpu.server --role data --root /var/n0 --port 18912

    # discovery file listing the data nodes
    [{"name": "n0", "addr": "10.0.0.1:18912", "roles": ["data"]}, ...]

    # liaison (user gateway; bydbctl targets this address)
    python -m banyandb_tpu.server --role liaison --root /var/l \
        --port 17912 --discovery nodes.json --replicas 1

Both classes are the in-process composition roots the reference builds
in cmdsetup: tests boot real multi-node clusters by instantiating them
directly (the pkg/test/setup trick), production runs one per process.
"""

from __future__ import annotations

import threading
from pathlib import Path

from banyandb_tpu.api.schema import SchemaRegistry
from banyandb_tpu.cluster.bus import LocalBus, Topic
from banyandb_tpu.cluster.data_node import DataNode
from banyandb_tpu.cluster.discovery import FileDiscovery
from banyandb_tpu.cluster.liaison import Liaison
from banyandb_tpu.cluster.rpc import GrpcBusServer, GrpcTransport, tag_qos


class DataServer:
    """Data role: a DataNode behind a gRPC bus + lifecycle loops."""

    def __init__(self, root: str | Path, *, name: str = "", port: int = 0):
        # data nodes run the scan kernels: persistent XLA compile cache,
        # wired before any kernel compiles
        from banyandb_tpu.utils import compile_cache

        compile_cache.enable()
        self.root = Path(root)
        self.registry = SchemaRegistry(self.root)
        self.name = name or self.root.name or "data"
        self.node = DataNode(self.name, self.registry, self.root / "data")
        self.grpc = GrpcBusServer(self.node.bus, port=port)

    @property
    def addr(self) -> str:
        return self.grpc.addr

    def start(self) -> "DataServer":
        # data nodes run the scan kernels: bind the plan-signature store
        # and warm recorded + builtin plans before the first query lands
        from banyandb_tpu.query.precompile import default_registry

        # the partition fault site needs this process's node identity
        from banyandb_tpu.cluster import faults

        faults.set_local_node(self.name)
        reg = default_registry()
        reg.attach_store(self.root / "plan-registry.json")
        reg.warm_async()
        self.grpc.start()
        self.node.start_lifecycle()
        return self

    def stop(self) -> None:
        from banyandb_tpu.query.precompile import default_registry

        default_registry().shutdown()
        self.node.stop_lifecycle()
        self.grpc.stop()


class _LiaisonMeasureAdapter:
    """Engine-shaped facade over the liaison's distributed measure plane,
    so WireServices (built against engine call signatures) serves the
    cluster unchanged — the liaison/grpc/measure.go role."""

    def __init__(self, liaison):
        self._l = liaison

    def query(self, req, shard_ids=None):
        return self._l.query_measure(req)

    def write(self, req, _internal: bool = False) -> int:
        return self._l.write_measure(req)

    def flush(self, group=None) -> list:
        # parts materialize on data nodes' own lifecycle loops; the
        # liaison holds no local measure storage to flush
        return []


class _LiaisonStreamAdapter:
    def __init__(self, liaison, registry):
        self._l = liaison
        self._reg = registry

    def query(self, req, shard_ids=None):
        return self._l.query_stream(req)

    def write(self, group: str, name: str, elements) -> int:
        import base64

        from banyandb_tpu.api.schema import _to_jsonable

        return self._l.write_stream(
            group, name, _to_jsonable(self._reg.get_stream(group, name)),
            [
                {
                    "element_id": e.element_id,
                    "ts": e.ts_millis,
                    "tags": e.tags,
                    "body": base64.b64encode(e.body).decode(),
                }
                for e in elements
            ],
        )


class _LiaisonTraceAdapter:
    def __init__(self, liaison, registry):
        self._l = liaison
        self._reg = registry

    def get_trace(self, group: str, name: str):
        return self._reg.get_trace(group, name)

    def query(self, req, *, shard_ids=None, tracer=None):
        return self._l.query_trace(req, tracer=tracer)

    def query_by_trace_id(self, group: str, name: str, trace_id: str):
        return self._l.query_trace_by_id(group, name, trace_id)

    def write(self, group: str, name: str, spans, *, ordered_tags=()) -> int:
        import base64

        from banyandb_tpu.api.schema import _to_jsonable

        return self._l.write_trace(
            group, name, _to_jsonable(self._reg.get_trace(group, name)),
            [
                {
                    "ts": s.ts_millis,
                    "tags": s.tags,
                    "span": base64.b64encode(s.span).decode(),
                }
                for s in spans
            ],
            ordered_tags=tuple(ordered_tags),
        )


class LiaisonServer:
    """Liaison role: user-facing surfaces over the cluster fabric.

    Serves the same user topics as the standalone server (health,
    registry, writes, BydbQL, trace lookup) so bydbctl works unchanged,
    plus — via engine-shaped adapters — the reference-proto gRPC wire
    and the HTTP gateway/console.  Every handler delegates to the
    Liaison's distributed paths: schema CRUD pushes to all data nodes,
    writes route by shard with replica fan-out + handoff, queries
    scatter and merge.
    """

    PROBE_INTERVAL_S = 5.0

    def __init__(
        self,
        root: str | Path,
        discovery_file: str | Path,
        *,
        port: int = 0,
        replicas: int = 0,
        wire_port: int | None = None,
        http_port: int | None = None,
        auth_file: str | None = None,
        slow_query_ms: float | None = None,
    ):
        from banyandb_tpu.admin.accesslog import AccessLog
        from banyandb_tpu.obs import SlowQueryRecorder
        from banyandb_tpu.utils.envflag import env_float

        self.root = Path(root)
        self.registry = SchemaRegistry(self.root)
        self.transport = GrpcTransport()
        if slow_query_ms is None:
            slow_query_ms = env_float(
                "BYDB_SLOW_QUERY_MS", AccessLog.DEFAULT_SLOW_QUERY_MS
            )
        self.slow_query_ms = slow_query_ms
        self.slowlog = SlowQueryRecorder()
        # multi-tenant QoS at the gateway (docs/robustness.md
        # "Multi-tenant QoS"): the liaison is the cluster's ingest/query
        # ingress, so per-tenant quotas and weighted admission gate here
        from banyandb_tpu.qos.plane import global_qos

        self.qos = global_qos()
        self.liaison = Liaison(
            self.registry,
            self.transport,
            discovery=FileDiscovery(discovery_file),
            replicas=replicas,
            handoff_root=str(self.root / "handoff"),
            # epoch-versioned placement survives liaison restarts (and
            # is how a straggling second liaison catches up after a
            # stale-epoch rejection)
            placement_store=str(self.root / "placement.json"),
        )
        # elastic-cluster control plane (docs/robustness.md): operator
        # rebalance surface + the background replica-repair loop
        from banyandb_tpu.cluster.rebalance import Rebalancer, ReplicaRepairer

        self.rebalancer = Rebalancer(self.liaison)
        self.repairer = ReplicaRepairer(self.liaison)
        from banyandb_tpu.utils.envflag import env_float

        self.repair_interval_s = env_float("BYDB_REPAIR_INTERVAL_S", 30.0)
        self._repair_thread: threading.Thread | None = None
        # schema plane: EVERY create/update on this liaison's registry —
        # whatever surface it arrived on (bus topic, proto wire, HTTP
        # gateway) — pushes to all data nodes (liaison/grpc/registry.go
        # behavior); acks are recorded per object for barrier callers
        self._sync_acks: dict = {}
        self.registry.watch(self._on_schema_put)
        self.bus = LocalBus()
        self._register()
        self.grpc = GrpcBusServer(self.bus, port=port)
        # engine-shaped trace facade: QL execution, the proto wire and
        # the self-trace sink all share it
        self._trace_adapter = _LiaisonTraceAdapter(self.liaison, self.registry)
        from banyandb_tpu.obs.selftrace import SelfTraceSink

        self.self_trace = SelfTraceSink(
            self._trace_adapter, self.registry, node="liaison"
        )
        self.wire = None
        self.http = None
        if wire_port is not None or http_port is not None:
            from banyandb_tpu.api.grpc_server import WireServices

            self._wire_services = WireServices(
                self.registry,
                _LiaisonMeasureAdapter(self.liaison),
                _LiaisonStreamAdapter(self.liaison, self.registry),
                trace_engine=self._trace_adapter,
                node_info={"name": "liaison", "roles": ("liaison",)},
                cluster_view_fn=self._cluster_view,
            )
        if wire_port is not None:
            from banyandb_tpu.api.grpc_server import WireServer

            self.wire = WireServer(
                self._wire_services, port=wire_port, auth_file=auth_file
            )
        if http_port is not None:
            from banyandb_tpu.api.auth import AuthReloader
            from banyandb_tpu.api.http_gateway import HttpGateway

            http_auth = None
            if auth_file:
                http_auth = (
                    self.wire.auth
                    if self.wire is not None and self.wire.auth is not None
                    else AuthReloader(auth_file)
                )
            self.http = HttpGateway(
                self._wire_services, port=http_port, auth=http_auth,
                slowlog=self.slowlog,
            )
        self._stop = threading.Event()
        self._probe_thread: threading.Thread | None = None

    def _on_schema_put(self, kind: str, obj, revision: int) -> None:
        try:
            acks = self.liaison.sync_schema(kind, obj)
            self._sync_acks[(kind, self.registry._key(obj))] = acks
        except Exception:  # noqa: BLE001 - a down fabric must not fail
            # the local registry write; nodes converge via handoff/gossip
            import logging

            logging.getLogger(__name__).exception(
                "schema push failed for %s", kind
            )

    def _cluster_view(self) -> dict:
        nodes = [
            {"name": n.name, "grpc_address": n.addr, "roles": list(n.roles)}
            for n in self.liaison.selector.nodes
        ]
        return {
            "tire2": {
                "registered": nodes,
                "active": sorted(self.liaison.alive),
                "evictable": sorted(
                    {n.name for n in self.liaison.selector.nodes}
                    - self.liaison.alive
                ),
            }
        }

    @property
    def addr(self) -> str:
        return self.grpc.addr

    # -- user surface -------------------------------------------------------
    def _register(self) -> None:
        from banyandb_tpu.server import (
            TOPIC_METRICS,
            TOPIC_QL,
            TOPIC_QOS,
            TOPIC_REGISTRY,
            TOPIC_SLOWLOG,
        )

        b = self.bus
        b.subscribe(
            Topic.HEALTH,
            lambda env: {
                "status": "ok",
                "role": "liaison",
                "alive": sorted(self.liaison.alive),
            },
        )
        b.subscribe(TOPIC_REGISTRY, self._registry_op)
        b.subscribe(TOPIC_METRICS, self._metrics)
        b.subscribe(TOPIC_QOS, self._qos)
        b.subscribe(TOPIC_SLOWLOG, self._slowlog)
        b.subscribe(Topic.MEASURE_WRITE, self._measure_write)
        b.subscribe(Topic.STREAM_WRITE, self._stream_write)
        b.subscribe(Topic.TRACE_WRITE, self._trace_write)
        b.subscribe(Topic.TRACE_QUERY_BY_ID, self._trace_query_by_id)
        b.subscribe(TOPIC_QL, self._ql)
        # streaming-aggregation control plane: the liaison broadcasts a
        # dashboard-signature registration to every alive data node
        # (windows are node-local; each node backfills its own shards)
        b.subscribe("streamagg", self._streamagg)
        # elastic-cluster operator surface (cli.py rebalance
        # plan|apply|status; docs/robustness.md "Elastic cluster")
        b.subscribe("rebalance", self._rebalance)

    def _metrics(self, env: dict):
        """Liaison /metrics: the process-global meter, with the QoS
        admission gauges and tenant-labeled cache-partition rows
        refreshed first — the liaison is the cluster's admission
        ingress, so sheds/queue depth surface HERE."""
        from banyandb_tpu.obs.metrics import global_meter
        from banyandb_tpu.storage.cache import partition_stats

        meter = global_meter()
        self.qos.export_gauges(meter)
        for tenant, st in partition_stats().items():
            for k in (
                "hits", "misses", "evictions", "refused", "entries", "bytes",
            ):
                meter.gauge_set(
                    f"serving_cache_{k}", float(st[k]), {"tenant": tenant}
                )
        return {"prometheus": meter.prometheus_text()}

    def _qos(self, env: dict):
        """QoS introspection (cli.py qos), liaison edition — same reply
        shape as the standalone handler (no protector here: in-flight
        byte charges live on the write-owning roles)."""
        from banyandb_tpu.storage.cache import partition_stats

        return {
            "qos": self.qos.stats(),
            "cache_partitions": partition_stats(),
            "inflight_bytes": {},
        }

    def _rebalance(self, env: dict):
        from banyandb_tpu.cluster.rebalance import RebalancePlan

        op = env.get("op", "status")
        if op == "plan":
            plan = self.rebalancer.plan(
                env.get("nodes") or None,
                replicas=env.get("replicas"),
            )
            return {"plan": plan.to_json()}
        if op == "apply":
            plan = (
                RebalancePlan.from_json(env["plan"])
                if env.get("plan")
                else self.rebalancer.plan(
                    env.get("nodes") or None, replicas=env.get("replicas")
                )
            )
            return {"stats": self.rebalancer.apply(plan)}
        if op == "repair":
            return {"stats": self.repairer.run_once()}
        if op == "status":
            return {
                "status": self.rebalancer.status(),
                "repair": self.repairer.status(),
            }
        raise ValueError(f"bad rebalance op {op!r}")

    def _streamagg(self, env: dict):
        # same op surface as the standalone/data-node handlers (default
        # op=stats), fanned out to the alive data nodes
        op = env.get("op", "stats")
        if op == "register":
            return {
                "acks": self.liaison.register_streamagg(
                    env["group"],
                    env["measure"],
                    key_tags=tuple(env.get("key_tags", ())),
                    fields=tuple(env.get("fields", ())),
                    window_millis=env.get("window_millis"),
                    max_windows=env.get("max_windows"),
                )
            }
        if op == "unregister":
            # the autoreg eviction path reaches the liaison role too:
            # drop the broadcast registration AND the remembered copy so
            # probe() stops re-sending it to rejoining nodes
            return {
                "acks": self.liaison.unregister_streamagg(
                    env["group"],
                    env["measure"],
                    key_tags=tuple(env.get("key_tags", ())),
                    fields=tuple(env.get("fields", ())),
                    window_millis=env.get("window_millis"),
                )
            }
        if op == "stats":
            out = {}
            for n in self.liaison.selector.nodes:
                if n.name not in self.liaison.alive:
                    continue
                out[n.name] = self.liaison.transport.call(
                    n.addr, "streamagg", {"op": "stats"}, timeout=10.0
                ).get("streamagg")
            return {"streamagg": out}
        raise ValueError(f"bad streamagg op {op!r}")

    def _registry_op(self, env: dict):
        """Schema CRUD lands in the liaison registry, then pushes to every
        data node over the schema plane (liaison/grpc/registry.go analog;
        down nodes converge via handoff replay / gossip)."""
        from banyandb_tpu.api import schema as schema_mod
        from banyandb_tpu.api.schema import Stream, Trace

        op, kind = env["op"], env["kind"]
        if op == "create":
            cls = schema_mod._KINDS[kind]
            obj = schema_mod._from_jsonable(cls, env["item"])
            create = {
                "group": self.registry.create_group,
                "measure": self.registry.create_measure,
                "index_rule": self.registry.create_index_rule,
                "topn": self.registry.create_topn,
            }[kind]
            rev = create(obj)
            # the registry watcher already pushed synchronously; surface
            # its per-node acks to the caller
            acks = self._sync_acks.get((kind, self.registry._key(obj)), {})
            return {"revision": rev, "acks": {n: a.get("revision") for n, a in acks.items()}}
        if op == "create_stream":
            obj = schema_mod._from_jsonable(Stream, env["item"])
            return {"revision": self.registry.create_stream(obj)}
        if op == "create_trace":
            obj = schema_mod._from_jsonable(Trace, env["item"])
            return {"revision": self.registry.create_trace(obj)}
        if op == "list":
            if kind == "group":
                items = self.registry.list_groups()
            elif kind == "measure":
                items = self.registry.list_measures(env["group"])
            else:
                raise KeyError(kind)
            return {"items": [schema_mod._to_jsonable(i) for i in items]}
        raise KeyError(f"bad registry op {op}")

    def _measure_write(self, env: dict):
        from banyandb_tpu.cluster import serde

        req = serde.write_request_from_json(env["request"])
        # per-tenant ingest quota at the gateway: over-rate sheds with
        # the retryable ServerBusy wire kind before any fan-out work
        self.qos.admit_write(req.group, len(req.points))
        return {"written": self.liaison.write_measure(req)}

    def _stream_write(self, env: dict):
        from banyandb_tpu.api.schema import _to_jsonable

        self.qos.admit_write(env["group"], len(env["elements"]))
        n = self.liaison.write_stream(
            env["group"], env["name"],
            _to_jsonable(self.registry.get_stream(env["group"], env["name"])),
            env["elements"],
        )
        return {"written": n}

    def _trace_write(self, env: dict):
        from banyandb_tpu.api.schema import _to_jsonable

        self.qos.admit_write(env["group"], len(env["spans"]))
        n = self.liaison.write_trace(
            env["group"], env["name"],
            _to_jsonable(self.registry.get_trace(env["group"], env["name"])),
            env["spans"],
            ordered_tags=tuple(env.get("ordered_tags", ())),
        )
        return {"written": n}

    def _trace_query_by_id(self, env: dict):
        from banyandb_tpu.cluster import serde

        spans = self.liaison.query_trace_by_id(
            env["group"], env["name"], env["trace_id"]
        )
        return {"spans": serde.spans_to_json(spans)}

    def _slowlog(self, env: dict):
        from banyandb_tpu.obs.recorder import slowlog_topic_reply

        return slowlog_topic_reply(self.slowlog, env, self.slow_query_ms)

    def _ql(self, env: dict):
        import time as _time

        from banyandb_tpu import bydbql
        from banyandb_tpu.obs import Tracer
        from banyandb_tpu.server import result_to_json

        catalog, req = bydbql.parse_with_catalog(
            env["ql"], env.get("params", ())
        )
        # always-on liaison-side tracer (node subtrees only attach when
        # req.trace rode the scatter): slow distributed queries land in
        # the flight recorder with whatever tree exists
        tracer = Tracer(f"liaison:{catalog}", usage=bool(env.get("trace")))
        deadline_ms = env.get("deadline_ms")
        adm = self.qos.admit_query(
            req.groups[0] if req.groups else "",
            deadline_s=(
                float(deadline_ms) / 1000.0 if deadline_ms else None
            ),
        )
        from banyandb_tpu.qos import tenant_scope

        with adm, tenant_scope(adm.tenant):
            tag_qos(tracer, adm)
            t0 = _time.perf_counter()
            if catalog == "measure":
                res = self.liaison.query_measure(req, tracer=tracer)
            elif catalog == "stream":
                res = self.liaison.query_stream(req, tracer=tracer)
            elif catalog == "trace":
                from banyandb_tpu.query import ql_exec

                res = ql_exec.execute_trace_ql(
                    self._trace_adapter, req, tracer=tracer
                )
            else:
                raise ValueError(
                    f"liaison QL serves measure/stream/trace catalogs; "
                    f"{catalog} queries use the dedicated topics"
                )
            ms = (_time.perf_counter() - t0) * 1000
        tree = tracer.finish()

        def render_plan():
            # untraced slow query: render the DISTRIBUTED plan post-hoc
            # (only past the threshold, never on the hot path)
            from banyandb_tpu.query import logical

            if catalog == "measure":
                m = self.registry.get_measure(req.groups[0], req.name)
                return logical.analyze_measure_distributed(
                    m, req, sorted(self.liaison.alive)
                ).explain()
            if catalog == "trace":
                from banyandb_tpu.models.trace import classify_plan

                t = self.registry.get_trace(req.groups[0], req.name)
                kind = classify_plan(req, t.trace_id_tag)[0]
                return (
                    f"trace plan={kind} "
                    f"order_by={req.order_by_tag or '-'} "
                    f"limit={req.limit} offset={req.offset}"
                )
            s = self.registry.get_stream(req.groups[0], req.name)
            return logical.analyze_stream(s, req).explain()

        from banyandb_tpu.obs.recorder import record_slow_query
        from banyandb_tpu.obs.tracer import attach_tree

        record_slow_query(
            self.slowlog, self.slow_query_ms,
            engine=catalog,
            group=req.groups[0] if req.groups else "",
            name=req.name,
            duration_ms=ms,
            rows=len(res.data_points) or len(res.groups),
            span_tree=tree, ql=env["ql"],
            plan=(res.trace or {}).get("plan"),
            plan_fn=render_plan,
            tenant=adm.tenant,
        )
        # dogfood loop: slow/sampled span trees become trace rows in
        # _monitoring.self_query via the cluster's own trace write path
        self.self_trace.offer(
            engine=catalog,
            group=req.groups[0] if req.groups else "",
            name=req.name,
            duration_ms=ms,
            tree=tree,
            tenant=adm.tenant,
            ql=env["ql"],
        )
        attach_tree(res, req, tree)
        return {"result": result_to_json(res)}

    # -- lifecycle ----------------------------------------------------------
    def _probe_loop(self) -> None:
        while not self._stop.wait(self.PROBE_INTERVAL_S):
            try:
                self.liaison.refresh_nodes()
                self.liaison.probe()
            except Exception:  # noqa: BLE001 - keep probing
                import logging

                logging.getLogger(__name__).exception("liaison probe failed")

    def _repair_loop(self) -> None:
        """Anti-entropy (docs/robustness.md "Elastic cluster"): every
        interval, compare per-shard part manifests across each replica
        chain and re-ship what a replica is missing.  Skipped while a
        rebalance holds the mover lock — the move's own delta round
        covers convergence there."""
        while not self._stop.wait(self.repair_interval_s):
            try:
                if self.rebalancer._lock.acquire(blocking=False):
                    try:
                        self.repairer.run_once()
                    finally:
                        self.rebalancer._lock.release()
            except Exception:  # noqa: BLE001 - keep repairing
                import logging

                logging.getLogger(__name__).exception("replica repair failed")

    def start(self) -> "LiaisonServer":
        from banyandb_tpu.cluster import faults

        faults.set_local_node("liaison")
        self.grpc.start()
        if self.wire is not None:
            self.wire.start()
        if self.http is not None:
            self.http.start()
        self.liaison.probe()
        self._stop.clear()
        self._probe_thread = threading.Thread(
            target=self._probe_loop, name="liaison-probe", daemon=True
        )
        self._probe_thread.start()
        if self.repair_interval_s > 0:
            self._repair_thread = threading.Thread(
                target=self._repair_loop, name="bydb-repair", daemon=True
            )
            self._repair_thread.start()
        self.self_trace.start()
        return self

    def stop(self) -> None:
        self.self_trace.stop()
        self._stop.set()
        if self._probe_thread is not None:
            self._probe_thread.join(timeout=10)
        if self._repair_thread is not None:
            self._repair_thread.join(timeout=10)
            self._repair_thread = None
        if self.http is not None:
            self.http.stop()
        if self.wire is not None:
            self.wire.stop()
        self.grpc.stop()
        self.transport.close()
