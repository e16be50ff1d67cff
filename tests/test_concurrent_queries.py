"""More than one request in flight (ISSUE 35, docs/observability.md
"What runs at once"): N threads sending distinct quarter-hour TopN
queries through the server's normal handlers get the answers a serial
run and a plain NumPy loop give, and the tags and gauges that say what
ran beside a query read what each test arranged: the ``qos`` span's
``inflight`` / ``rpc_busy``, the ``reduce`` span's ``dispatches_ahead``,
the ``gather`` span's ``dict_lock_wait_ms``, and /metrics
``queries_inflight``, ``rpc_handlers_busy``,
``fused_dispatches_outstanding``, ``rpc_pool_queued`` and the histogram
``rpc_pool_wait_ms`` (ISSUE 37: the ``qos`` span's ``pool_wait_ms``, the
wait for a worker of the bus server's pool).  CPU, small size,
seeded data; every wait has a timeout."""

import base64
import sys
import threading
import time

import numpy as np
import pytest

from banyandb_tpu.cluster import rpc
from banyandb_tpu.cluster.bus import LocalBus
from banyandb_tpu.obs import metrics as obs_metrics
from banyandb_tpu.obs.tracer import find_span
from banyandb_tpu.query import fused_exec

T0 = 1_700_006_400_000
BUCKET_MS = 60_000
SERIES, REGIONS, BUCKETS = 64, 8, 120  # two hours at minute step
PER_MESSAGE = 15  # a quarter hour a message, a snapshot after each
WAIT_S = 60.0


def _b64(a: np.ndarray, dtype: str) -> str:
    return base64.b64encode(np.ascontiguousarray(a, dtype).tobytes()).decode()


@pytest.fixture(scope="module")
def estate(tmp_path_factory):
    """-> (server, hits [BUCKETS, SERIES]): the benchmark's schema at a
    small size, loaded through measure-write-cols, 8 messages a shard."""
    from banyandb_tpu.server import StandaloneServer

    srv = StandaloneServer(tmp_path_factory.mktemp("estate"), port=0)
    srv.start()
    try:
        srv.bus.handle("registry", {"op": "create", "kind": "group", "item": {
            "name": "g", "catalog": "measure",
            "resource_opts": {
                "shard_num": 2, "replicas": 0,
                "segment_interval": {"num": 1, "unit": "day"},
                "ttl": {"num": 36500, "unit": "day"}, "stages": [],
            },
        }})
        srv.bus.handle("registry", {"op": "create", "kind": "measure", "item": {
            "group": "g", "name": "m",
            "tags": [{"name": "svc", "type": "string"}, {"name": "region", "type": "string"}],
            "fields": [{"name": "value", "type": "float"}, {"name": "hits", "type": "int"}],
            "entity": {"tag_names": ["svc"]}, "interval": "", "index_mode": False,
        }})
        rng = np.random.default_rng(35)
        hits = rng.integers(0, 1000, (BUCKETS, SERIES), dtype=np.int64)
        svc = np.tile(np.arange(SERIES, dtype=np.int32), PER_MESSAGE)
        for b0 in range(0, BUCKETS, PER_MESSAGE):
            ts = np.repeat(T0 + np.arange(b0, b0 + PER_MESSAGE) * BUCKET_MS, SERIES)
            h = hits[b0:b0 + PER_MESSAGE].reshape(-1)
            ack = srv.bus.handle("measure-write-cols", {
                "group": "g", "name": "m",
                "ts": _b64(ts, "<i8"), "versions": _b64(np.ones(ts.size), "<i8"),
                "tags": {
                    "svc": {"dict": ["svc_%06d" % i for i in range(SERIES)],
                            "codes": _b64(svc, "<i4")},
                    "region": {"dict": ["r%d" % i for i in range(REGIONS)],
                               "codes": _b64(svc % REGIONS, "<i4")},
                },
                "fields": {"value": _b64(h * 0.5, "<f8"), "hits": _b64(h, "<f8")},
            })
            assert ack["written"] == ts.size
            srv.bus.handle("snapshot", {})
        yield srv, hits
    finally:
        srv.stop()


def _spec(k: int) -> tuple[int, int, int]:
    """Query k -> (lo, hi, region left out): a quarter hour from a start
    off the bucket edge, every k another one."""
    lo = T0 + (k * 7 % (BUCKETS - 16)) * BUCKET_MS + 1 + k
    return lo, lo + 15 * BUCKET_MS, k % REGIONS


def _ql(k: int) -> str:
    lo, hi, region = _spec(k)
    return (
        f"SELECT sum(hits) FROM MEASURE m IN g TIME BETWEEN {lo} AND {hi} "
        f"WHERE region != 'r{region}' GROUP BY svc TOP 10 BY hits"
    )


def _by_loop(hits: np.ndarray, k: int) -> dict:
    """Query k answered point by point -> {svc: (count, sum)} of the ten
    largest sums (no two sums of this data tie at the cut)."""
    lo, hi, region = _spec(k)
    sums: dict[int, list[int]] = {}
    for b in range(BUCKETS):
        if not lo <= T0 + b * BUCKET_MS <= hi:
            continue
        for s in range(SERIES):
            if s % REGIONS != region:
                acc = sums.setdefault(s, [0, 0])
                acc[0] += 1
                acc[1] += int(hits[b, s])
    best = sorted(sums, key=lambda s: -sums[s][1])
    assert sums[best[9]][1] != sums[best[10]][1]
    return {"svc_%06d" % s: (sums[s][0], float(sums[s][1])) for s in best[:10]}


def _answer(reply: dict) -> dict:
    res = reply["result"]
    return {
        g[0]: (int(c), float(v))
        for g, c, v in zip(res["groups"], res["values"]["count"], res["values"]["sum(hits)"])
    }


def _in_threads(n: int, work) -> list:
    """work(k) on thread k of n, all started together -> the results."""
    out, errors = [None] * n, []
    go = threading.Barrier(n)

    def run(k: int) -> None:
        try:
            go.wait(WAIT_S)
            out[k] = work(k)
        except BaseException as e:  # noqa: BLE001 - reported by the assert below
            errors.append(e)

    threads = [threading.Thread(target=run, args=(k,)) for k in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(WAIT_S)
    assert not errors and not any(t.is_alive() for t in threads), errors
    return out


def _gauge(srv, name: str) -> float:
    text = srv.bus.handle("metrics", {})["prometheus"]
    (line,) = [ln for ln in text.splitlines() if ln.startswith(f"banyandb_{name} ")]
    return float(line.split()[1])


@pytest.mark.parametrize("threads", [1, 4, 16])
def test_concurrent_answers_match_serial_and_numpy(estate, threads):
    srv, hits = estate
    per_thread = 3
    transport = rpc.GrpcTransport()

    def client(k: int) -> list:
        return [
            transport.call(srv.grpc.addr, "bydbql", {"ql": _ql(q), "trace": True}, timeout=WAIT_S)
            for q in range(k * per_thread, (k + 1) * per_thread)
        ]

    # a short switch interval interleaves the server's threads inside
    # the counters' read-modify-writes, where a lost update would show
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        replies = [r for rs in _in_threads(threads, client) for r in rs]
    finally:
        sys.setswitchinterval(interval)
    for q, reply in enumerate(replies):
        got = _answer(reply)
        assert got == _by_loop(hits, q), _ql(q)
        assert got == _answer(srv.bus.handle("bydbql", {"ql": _ql(q)}))
        qos = find_span(reply["result"]["trace"]["span_tree"], "qos")["tags"]
        assert 1 <= qos["inflight"] <= threads
        assert 1 <= qos["rpc_busy"] <= min(threads, rpc._BUS_WORKERS)
    assert _gauge(srv, "queries_inflight") == 0
    assert _gauge(srv, "fused_dispatches_outstanding") == 0
    assert _gauge(srv, "rpc_handlers_busy") == 0  # this scrape came past the bus server
    assert _gauge(srv, "rpc_pool_queued") == 0  # no lost update left a call "waiting"


def test_inflight_counts_the_queries_held_in_their_handlers(estate, monkeypatch):
    srv, _ = estate
    k = 5
    entered, release = threading.Semaphore(0), threading.Event()
    real = srv.measure.query

    def held(req, tracer=None):
        entered.release()
        assert release.wait(WAIT_S)
        return real(req, tracer=tracer)

    monkeypatch.setattr(srv.measure, "query", held)
    got: list = []
    threads = [
        threading.Thread(
            target=lambda q=q: got.append(srv.bus.handle("bydbql", {"ql": _ql(q), "trace": True}))
        )
        for q in range(k)
    ]
    for t in threads:
        t.start()
    try:
        for _ in range(k):
            assert entered.acquire(timeout=WAIT_S)
        assert _gauge(srv, "queries_inflight") == k
    finally:
        release.set()
        for t in threads:
            t.join(WAIT_S)
    assert len(got) == k and not any(t.is_alive() for t in threads)
    seen = sorted(
        find_span(r["result"]["trace"]["span_tree"], "qos")["tags"]["inflight"] for r in got
    )
    assert seen == list(range(1, k + 1))
    assert _gauge(srv, "queries_inflight") == 0
    # none of them came through the bus server: no handler of it ran
    assert all(
        find_span(r["result"]["trace"]["span_tree"], "qos")["tags"]["rpc_busy"] == 0 for r in got
    )


def test_a_query_alone_has_no_dispatch_ahead_and_waits_for_no_lock(estate):
    srv, _ = estate
    for q in range(100, 104):
        tree = srv.bus.handle("bydbql", {"ql": _ql(q), "trace": True})["result"]["trace"]["span_tree"]
        reduce_tags = find_span(tree, "reduce")["tags"]
        assert reduce_tags["path"] == "fused" and reduce_tags["dispatches"] == 1
        assert reduce_tags["dispatches_ahead"] == 0
        wait_ms = find_span(tree, "gather")["tags"]["dict_lock_wait_ms"]
        assert isinstance(wait_ms, float) and 0.0 <= wait_ms < 50.0
        assert find_span(tree, "qos")["tags"]["inflight"] == 1
    assert fused_exec.dispatches_outstanding() == 0


def test_dispatches_ahead_counts_other_queries_unfetched_dispatches(estate, monkeypatch):
    """k queries held at their device_get: the one issued n-th found
    n - 1 ahead of it, and the gauge reads k while they are held."""
    srv, _ = estate
    k = 4
    entered, release = threading.Semaphore(0), threading.Event()
    real_get = fused_exec.jax.device_get

    def held_get(x):
        entered.release()
        assert release.wait(WAIT_S)
        return real_get(x)

    srv.bus.handle("bydbql", {"ql": _ql(200)})  # the program is compiled
    monkeypatch.setattr(fused_exec.jax, "device_get", held_get)
    got: list = []
    threads = [
        threading.Thread(
            target=lambda q=q: got.append(srv.bus.handle("bydbql", {"ql": _ql(q), "trace": True}))
        )
        for q in range(201, 201 + k)
    ]
    for t in threads:
        t.start()
    try:
        for _ in range(k):
            assert entered.acquire(timeout=WAIT_S)
        assert fused_exec.dispatches_outstanding() == k
    finally:
        release.set()
        for t in threads:
            t.join(WAIT_S)
    monkeypatch.undo()
    assert len(got) == k and not any(t.is_alive() for t in threads)
    ahead = sorted(
        find_span(r["result"]["trace"]["span_tree"], "reduce")["tags"]["dispatches_ahead"]
        for r in got
    )
    assert ahead == list(range(k))
    assert _gauge(srv, "fused_dispatches_outstanding") == 0


def test_dict_lock_wait_is_the_time_another_thread_held_the_lock(estate):
    srv, _ = estate
    lock = srv.measure._dict_state("g", "m").lock
    got: list = []
    t = threading.Thread(
        target=lambda: got.append(srv.bus.handle("bydbql", {"ql": _ql(300), "trace": True}))
    )
    with lock:
        t.start()
        time.sleep(0.2)  # the query's first acquisition waits this out
    t.join(WAIT_S)
    assert got and not t.is_alive()
    tree = got[0]["result"]["trace"]["span_tree"]
    assert 150.0 <= find_span(tree, "gather")["tags"]["dict_lock_wait_ms"] < 5000.0


def _pool_wait_count() -> int:
    return obs_metrics.global_meter().histogram("rpc_pool_wait_ms").snapshot()[0]


def test_rpc_busy_stops_at_the_pool_and_the_queued_four_say_how_long_they_waited():
    """Twelve RPCs at once against a bus server of eight workers, each
    held until the test lets one go: eight handlers start (busy 1..8,
    the eighth takes the last worker) and waited for no worker; four
    wait in the pool's queue for as long as the gate stays shut
    (`rpc_pool_queued` 4), each starts into a full pool as one before it
    leaves, and reports that wait as its `handler_pool_wait_ms()`."""
    bus = LocalBus()
    seen: list[tuple[int, float]] = []
    started, gate = threading.Semaphore(0), threading.Semaphore(0)
    hold_s = 0.3

    def hold(env):
        seen.append((rpc.handler_busy(), rpc.handler_pool_wait_ms()))
        started.release()
        assert gate.acquire(timeout=WAIT_S)
        return {"n": env["n"]}

    bus.subscribe("hold", hold)
    server = rpc.GrpcBusServer(bus)
    server.start()
    transport = rpc.GrpcTransport()
    n, workers = 12, rpc._BUS_WORKERS
    observed0 = _pool_wait_count()  # after start: its prespawn calls are in
    replies: list = []
    threads = [
        threading.Thread(
            target=lambda i=i: replies.append(
                transport.call(server.addr, "hold", {"n": i}, timeout=WAIT_S)
            )
        )
        for i in range(n)
    ]
    try:
        for t in threads:
            t.start()
        for _ in range(workers):
            assert started.acquire(timeout=WAIT_S)
        assert server.handlers_busy() == workers
        assert not started.acquire(timeout=hold_s)  # the other four wait for a worker
        deadline = time.monotonic() + WAIT_S
        while server.pool_queued() < n - workers and time.monotonic() < deadline:
            time.sleep(0.01)  # grpc has handed all four to the pool by now, or soon
        assert server.pool_queued() == n - workers
        for _ in range(n - workers):
            gate.release()  # one leaves, one of the waiting starts
            assert started.acquire(timeout=WAIT_S)
        assert server.pool_queued() == 0
        for _ in range(workers):
            gate.release()
        for t in threads:
            t.join(WAIT_S)
    finally:
        for _ in range(n):
            gate.release()
        server.stop()
    assert sorted(r["n"] for r in replies) == list(range(n))
    busy = [b for b, _ in seen]
    assert sorted(busy[:workers]) == list(range(1, workers + 1))
    assert busy[workers:] == [workers] * (n - workers) and max(busy) == workers
    # the eight found a worker free (far under the hold; a loaded machine
    # may take some ms to wake one), the four waited the gate out
    assert all(0.0 <= w < hold_s * 500 for _, w in seen[:workers])
    assert all(hold_s * 1000 <= w < WAIT_S * 1000 for _, w in seen[workers:])
    assert _pool_wait_count() - observed0 == n
    assert server.handlers_busy() == 0
    assert rpc.handler_pool_wait_ms() == 0.0  # this thread runs no handler


def test_qos_span_past_the_bus_server_waited_for_no_worker(estate):
    """A call that did not come through the gRPC bus server's pool (the
    in-process transport) carries `pool_wait_ms` 0.0: always a number."""
    srv, _ = estate
    transport = rpc.LocalTransport()
    addr = transport.register("standalone", srv.bus)
    reply = transport.call(addr, "bydbql", {"ql": _ql(301), "trace": True})
    qos = find_span(reply["result"]["trace"]["span_tree"], "qos")["tags"]
    assert qos["pool_wait_ms"] == 0.0 and isinstance(qos["pool_wait_ms"], float)
    assert qos["rpc_busy"] == 0 and qos["inflight"] == 1


def test_qos_span_over_the_bus_server_carries_its_wait_and_the_gauge_reads_zero(estate):
    """Over gRPC a request alone finds a worker free: a wait of some
    microseconds, a number; nothing waits at the scrape."""
    srv, _ = estate
    reply = rpc.GrpcTransport().call(
        srv.grpc.addr, "bydbql", {"ql": _ql(302), "trace": True}, timeout=WAIT_S
    )
    qos = find_span(reply["result"]["trace"]["span_tree"], "qos")["tags"]
    assert 0.0 <= qos["pool_wait_ms"] < 1000.0 and qos["rpc_busy"] == 1
    assert _gauge(srv, "rpc_pool_queued") == 0
