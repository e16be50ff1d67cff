"""Round benchmark. Prints ONE JSON line:
{"metric", "value", "unit", "vs_baseline", "e2e", "kernel", ...}.

Two phases, composed into one line:

1. E2E (the north star, BASELINE.json "measure-query p50/p99 latency"):
   populate a real on-disk store (10M rows, 100k series, 4 shards,
   several flushed parts), boot the real standalone server, and measure
   client-observed TopN + percentile query latency over its gRPC socket
   — cold (disk part reads) and cache-warm p50/p99.  vs_baseline is the
   reference's published measure-query p50 (26.7 ms,
   docs/operation/benchmark/benchmark-single-model.md:105) over ours;
   hardware differs (their 2CPU/4GB pods vs one TPU host), the workload
   here is larger (10M rows vs their trailing 15-min window).

2. Kernel (scanned-points/sec on the named device): filter + group-by(service) +
   {count,sum,min,max,mean} + p50/p99 histogram + top-N over N_ROWS
   resident rows — the data-node scan hot loop
   (banyand/measure/query.go:594, pkg/query/vectorized).  vs_baseline
   for this sub-record is a fully-vectorized single-core NumPy executor
   running the same query on the same arrays (no per-group Python
   loops — an honest stand-in for a competent columnar executor).

Process layout: a chip belongs to one process, so the parent stays off
JAX and runs the two phases as two SEQUENTIAL children (``e2e`` then
``kernel``), each of which claims the backend that was asked for
(``utils/devices.claim_backend``: an unasked-for CPU backend is refused,
there is no CPU fallback).  Every record names platform, device_kind and
device count; the parent prints exactly one JSON line and exits non-zero
when a phase fails.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np

_REPO_DIR = os.path.dirname(os.path.abspath(__file__))

N_ROWS = int(os.environ.get("BYDB_BENCH_ROWS", 4 << 20))  # rows per device batch
N_SVC = 1024
N_REGION = 8
QS = (0.5, 0.99)
HIST_BUCKETS = 512

BUDGET_S = int(os.environ.get("BYDB_BENCH_BUDGET_S", 2100))


def _device_record() -> dict:
    """Claim this child's backend (refusing an unasked-for CPU) and
    wire the compile cache; -> the device fields every record carries."""
    from banyandb_tpu.utils import compile_cache, devices

    rt = devices.claim_backend("bench")
    compile_cache.enable()
    return {
        "platform": rt["backend"],
        "device_kind": rt["device_kind"],
        "device_count": rt["device_count"],
    }


def _host_data(n):
    rng = np.random.default_rng(3)
    return {
        "svc": rng.integers(0, N_SVC, n).astype(np.int32),
        "region": rng.integers(0, N_REGION, n).astype(np.int32),
        "latency": rng.gamma(2.0, 40.0, n).astype(np.float32),
    }


def numpy_executor(d, region_ne: int):
    """Single-core oracle: same query, pure NumPy, fully vectorized —
    no per-group Python loops, so the vs_baseline ratio is a defensible
    proxy for a competent single-core columnar executor (VERDICT r3:
    the old per-group bincount loop inflated the ratio)."""
    mask = d["region"] != region_ne
    svc = d["svc"][mask]
    lat = d["latency"][mask]
    count = np.bincount(svc, minlength=N_SVC).astype(np.float64)
    sums = np.bincount(svc, weights=lat, minlength=N_SVC)
    # min/max per group: sort once, reduceat over group boundaries
    order = np.argsort(svc, kind="stable")
    ssvc, slat = svc[order], lat[order]
    bounds = np.searchsorted(ssvc, np.arange(N_SVC + 1))
    mins = np.full(N_SVC, np.inf)
    maxs = np.full(N_SVC, -np.inf)
    nonempty = bounds[1:] > bounds[:-1]
    starts = bounds[:-1][nonempty]
    if starts.size:
        mins[nonempty] = np.minimum.reduceat(slat, starts)
        maxs[nonempty] = np.maximum.reduceat(slat, starts)
    # per-group histogram: one flat bincount on (group * B + bucket)
    lo, hi = 0.0, 1000.0
    width = (hi - lo) / HIST_BUCKETS
    bucket = np.clip(((lat - lo) / width).astype(np.int64), 0, HIST_BUCKETS - 1)
    hist = np.bincount(
        svc.astype(np.int64) * HIST_BUCKETS + bucket,
        minlength=N_SVC * HIST_BUCKETS,
    ).reshape(N_SVC, HIST_BUCKETS)
    mean = sums / np.maximum(count, 1)
    top = np.argsort(-np.where(count > 0, mean, -np.inf))[:10]
    return count, sums, mins, maxs, hist, top


def child_main() -> None:
    """Run the actual benchmark on whatever backend this process gets."""
    device = _device_record()

    import jax
    import jax.numpy as jnp

    from banyandb_tpu.query.measure_exec import (
        PlanSpec,
        _PredSpec,
        _build_kernel,
    )

    backend = device["platform"]
    n_rows = N_ROWS
    d = _host_data(n_rows)

    def mk_spec(method: str) -> PlanSpec:
        return PlanSpec(
            tags_code=("region", "svc"),
            fields=("latency",),
            preds=(_PredSpec("code", "region", "ne"),),
            group_tags=("svc",),
            radices=(N_SVC,),
            num_groups=N_SVC,
            want_minmax=True,
            hist_field="latency",
            nrows=n_rows,  # one resident mega-chunk: scan is HBM-bound
            group_method=method,
        )

    chunk = {
        "valid": jnp.asarray(np.ones(n_rows, dtype=bool)),
        "series": jnp.zeros(n_rows, jnp.int32),
        "ts": jnp.zeros(n_rows, jnp.int32),
        "tags_code": {
            "svc": jnp.asarray(d["svc"]),
            "region": jnp.asarray(d["region"]),
        },
        "fields": {"latency": jnp.asarray(d["latency"])},
    }
    pred_vals = {"p0": jnp.int32(3)}
    args = (chunk, pred_vals, jnp.float32(0.0), jnp.float32(1000.0))

    # self-tune: the scatter, tiled-MXU, and pallas paths have very
    # different profiles per backend; compile each, keep the fastest.
    probe_iters, final_iters = (3, 10) if backend != "cpu" else (1, 3)

    def timed(kernel, iters):
        out = kernel(*args)
        jax.block_until_ready(out)  # compile + warm
        t0 = time.perf_counter()
        for _ in range(iters):
            out = kernel(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters

    methods = ["scatter", "matmul_tiled"]
    if backend == "tpu":
        # compiled-mode pallas fused kernel (interpret mode would swamp CPU)
        methods.append("pallas")
    probe: dict[str, float] = {}
    kernels: dict[str, object] = {}
    for m in methods:
        # a candidate that fails to compile fails the phase: a method the
        # chip refuses is a finding, not a slower option
        kernels[m] = _build_kernel(mk_spec(m))
        probe[m] = timed(kernels[m], probe_iters)
    best = min(probe, key=probe.get)

    device_s = timed(kernels[best], final_iters)
    points_per_sec = n_rows / device_s

    # single-core NumPy baseline on the same query (1 iter is plenty)
    t0 = time.perf_counter()
    numpy_executor(d, region_ne=3)
    numpy_s = time.perf_counter() - t0

    # ---- decode microbench (ROADMAP item 3 done-bar) --------------------
    # device-side widen+remap (the compressed-ship decode stage) against
    # the host numpy widen+LUT-gather it replaces, same column — the
    # ">= 4x host baseline" claim is this ratio on a TPU run
    from banyandb_tpu.ops import decode as ops_decode

    codes8 = (d["svc"] % 128).astype(np.int8)
    lut = np.arange(128, dtype=np.int32)

    def host_decode():
        return lut[codes8.astype(np.int32)]

    t0 = time.perf_counter()
    for _ in range(final_iters):
        host_decode()
    host_dec_s = (time.perf_counter() - t0) / final_iters
    dev_codes = jnp.asarray(codes8)
    dev_lut = jnp.asarray(lut.reshape(1, -1))
    dev_ord = jnp.zeros(n_rows, jnp.int16)
    dec_fn = jax.jit(ops_decode.dict_remap)
    jax.block_until_ready(dec_fn(dev_codes, dev_lut, dev_ord))
    t0 = time.perf_counter()
    for _ in range(final_iters):
        out = dec_fn(dev_codes, dev_lut, dev_ord)
    jax.block_until_ready(out)
    dev_dec_s = (time.perf_counter() - t0) / final_iters

    print(
        json.dumps(
            {
                "metric": "measure_scan_groupby_agg_p50p99_topk",
                "value": round(points_per_sec / 1e6, 3),
                "unit": "Mpoints/s",
                "vs_baseline": round(numpy_s / device_s, 2),
                **device,
                "method": best,
                "rows": n_rows,
                "probe_ms": {m: round(s * 1e3, 2) for m, s in probe.items()},
                "decode_gpoints_per_s": round(n_rows / dev_dec_s / 1e9, 3),
                "decode_vs_host": round(host_dec_s / dev_dec_s, 2),
            }
        )
    )


def e2e_main() -> None:
    """End-to-end north-star measurement (BASELINE.json configs #2/#3/#5
    shapes): populate a REAL on-disk store (multiple flushed parts, 4
    shards, 24h span), boot the REAL standalone server over its gRPC
    socket, and measure client-observed query latency through the full
    path — BydbQL parse -> plan -> part read -> serving cache -> gather/
    dedup -> device aggregate -> combine -> JSON response.  Reports cold
    (first query after boot: disk part reads) and cache-warm p50/p99,
    comparable to the reference's published measure-query table
    (docs/operation/benchmark/benchmark-single-model.md:105)."""
    import shutil
    import tempfile
    from pathlib import Path

    import jax

    from banyandb_tpu.api import (
        Catalog,
        Entity,
        FieldSpec,
        FieldType,
        Group,
        IntervalRule,
        Measure,
        ResourceOpts,
        SchemaRegistry,
        TagSpec,
        TagType,
    )
    from banyandb_tpu.cluster.rpc import GrpcTransport
    from banyandb_tpu.models.measure import MeasureEngine
    from banyandb_tpu.server import TOPIC_METRICS, TOPIC_QL, StandaloneServer

    device = _device_record()
    n_rows = int(os.environ.get("BYDB_BENCH_E2E_ROWS", 10_000_000))
    n_series = int(os.environ.get("BYDB_BENCH_E2E_SERIES", 100_000))
    iters = int(os.environ.get("BYDB_BENCH_E2E_ITERS", 15))
    shards = 4
    T0 = 1_700_000_000_000
    span_ms = 24 * 3600 * 1000
    step = max(1, span_ms // n_rows)

    root = Path(tempfile.mkdtemp(prefix="bydb-e2e-"))
    try:
        # ---- populate: bulk columnar ingest, periodic flush => several
        # on-disk parts per shard (the layout a long-running node has) ----
        reg = SchemaRegistry(root)
        # T0 is a fixed day in the past: a TTL counted from the wall
        # clock (the 7-day default) lets the server's retention loop
        # expire the dataset while the bench is still querying it
        reg.create_group(
            Group(
                "g",
                Catalog.MEASURE,
                ResourceOpts(shard_num=shards, ttl=IntervalRule(36500, "day")),
            )
        )
        reg.create_measure(
            Measure(
                group="g",
                name="m",
                tags=(
                    TagSpec("svc", TagType.STRING),
                    TagSpec("region", TagType.STRING),
                ),
                # FLOAT mirrors the reference workload (exact-f64 host
                # aggregation); the INT field rides the DEVICE kernel
                # path, which is what the fused A/B phase measures
                fields=(
                    FieldSpec("value", FieldType.FLOAT),
                    FieldSpec("hits", FieldType.INT),
                ),
                entity=Entity(("svc",)),
            )
        )
        from banyandb_tpu.models.measure import DictColumn

        eng = MeasureEngine(reg, root / "data")
        rng = np.random.default_rng(11)
        svc_pool = [b"svc_%06d" % i for i in range(n_series)]
        region_pool = [b"r%d" % i for i in range(8)]
        batch = 1_000_000
        written = 0
        t_ing = time.perf_counter()
        while written < n_rows:
            b = min(batch, n_rows - written)
            eng.write_columns(
                "g",
                "m",
                ts_millis=T0 + (written + np.arange(b, dtype=np.int64)) * step,
                tags={
                    "svc": DictColumn(
                        svc_pool,
                        rng.integers(0, n_series, b).astype(np.int32),
                    ),
                    "region": DictColumn(
                        region_pool, rng.integers(0, 8, b).astype(np.int32)
                    ),
                },
                fields={
                    "value": rng.gamma(2.0, 40.0, b).astype(np.float64),
                    "hits": rng.integers(0, 1000, b).astype(np.float64),
                },
                versions=np.ones(b, dtype=np.int64),
            )
            written += b
            if written % (2 * batch) == 0 or written == n_rows:
                eng.flush()  # several parts per shard, not one mega-part
            print(f"# e2e ingest {written}/{n_rows}", file=sys.stderr)
        ingest_s = time.perf_counter() - t_ing
        del eng, reg  # server below re-opens the same root cold

        # ---- serve + query over the real gRPC socket --------------------
        # the autoreg LOOP stays off at boot so earlier phases measure
        # the pure scan path; the planner A/B phase below drives
        # srv.autoreg.tick() explicitly (deterministic registration)
        os.environ["BYDB_AUTOREG"] = "0"
        srv = StandaloneServer(root, port=0)
        srv.start()
        # server start kicked off the plan precompile warm thread; the
        # cold numbers below are what a client sees once boot settles,
        # so wait for warming (bounded) and report how long it took
        from banyandb_tpu.query.precompile import default_registry

        t_w = time.perf_counter()
        warm_done = default_registry().wait_warm(timeout=180.0)
        precompile_wait_ms = (time.perf_counter() - t_w) * 1000
        tr = GrpcTransport()
        end = T0 + n_rows * step + 1
        queries = {
            "topn": (
                f"SELECT mean(value) FROM MEASURE m IN g TIME BETWEEN "
                f"{T0} AND {end} GROUP BY svc TOP 10 BY value"
            ),
            "percentile": (
                f"SELECT PERCENTILE(value, 0.5, 0.99) FROM MEASURE m IN g "
                f"TIME BETWEEN {T0} AND {end} GROUP BY region"
            ),
        }

        def run(ql: str) -> float:
            # transport/QL failures raise TransportError — no result
            # inspection needed, a failed query aborts the bench
            t0 = time.perf_counter()
            tr.call(srv.addr, TOPIC_QL, {"ql": ql}, timeout=600.0)
            return (time.perf_counter() - t0) * 1000

        def cache_counters() -> dict:
            """Cache planes read from the RUNNING server over the bus
            (prometheus text), not process-local globals."""
            txt = tr.call(srv.addr, TOPIC_METRICS, {}, timeout=60.0)[
                "prometheus"
            ]
            out = {}
            for line in txt.splitlines():
                name, _, value = line.rpartition(" ")
                if any(
                    key in name
                    for key in ("_cache_", "precompile_")
                ):
                    try:
                        out[name.replace("banyandb_", "")] = float(value)
                    except ValueError:
                        pass
            return out

        def distinct_queries(count: int, seed: int = 17) -> list[str]:
            """>= `count` DISTINCT queries (varied time ranges, group
            predicates, N, quantiles) — the cache-honest warm phase: no
            two hit the same partials-cache entry, so the p50 reflects
            real per-query work, not replaying one cached answer.  The
            INT-field kinds (sum/mean over `hits`) ride the device
            kernel path; `seed` varies the set so the fused A/B legs
            never replay this phase's cache entries."""
            rq = np.random.default_rng(seed)
            span = n_rows * step
            out = []
            for i in range(count):
                b = T0 + int(rq.integers(0, span // 3))
                e = b + int(rq.integers(span // 4, span // 2))
                kind = i % 5
                if kind == 0:
                    out.append(
                        f"SELECT mean(value) FROM MEASURE m IN g TIME "
                        f"BETWEEN {b} AND {e} WHERE region != 'r{i % 8}' "
                        f"GROUP BY svc TOP {5 + 5 * (i % 4)} BY value"
                    )
                elif kind == 1:
                    out.append(
                        f"SELECT PERCENTILE(value, 0.5, 0.9{i % 10}) FROM "
                        f"MEASURE m IN g TIME BETWEEN {b} AND {e} "
                        f"GROUP BY region"
                    )
                elif kind == 2:
                    out.append(
                        f"SELECT sum(value) FROM MEASURE m IN g TIME "
                        f"BETWEEN {b} AND {e} WHERE region = 'r{i % 8}' "
                        f"GROUP BY svc TOP 10 BY value"
                    )
                elif kind == 3:
                    out.append(
                        f"SELECT sum(hits) FROM MEASURE m IN g TIME "
                        f"BETWEEN {b} AND {e} WHERE region != 'r{i % 8}' "
                        f"GROUP BY svc TOP {5 + 5 * (i % 4)} BY hits"
                    )
                else:
                    out.append(
                        f"SELECT mean(hits) FROM MEASURE m IN g TIME "
                        f"BETWEEN {b} AND {e} GROUP BY region"
                    )
            return out

        n_distinct = max(50, int(os.environ.get("BYDB_BENCH_DISTINCT", 60)))
        try:
            counters_boot = cache_counters()
            cold = {k: run(q) for k, q in queries.items()}
            warm: dict[str, list] = {k: [] for k in queries}
            for _ in range(iters):
                for k, q in queries.items():
                    warm[k].append(run(q))
            counters_pooled = cache_counters()
            distinct_ms = [run(q) for q in distinct_queries(n_distinct)]
            counters_end = cache_counters()
            # per-stage attribution scraped from the RUNNING server's
            # bucketed histograms (obs/prom.py) — gather vs device vs
            # merge p50/p99 lands in every bench artifact so TPU runs
            # (ROADMAP item 1) carry the decode/compute split built in
            from banyandb_tpu.obs import prom as obs_prom

            def metrics_text() -> str:
                return tr.call(srv.addr, TOPIC_METRICS, {}, timeout=60.0)[
                    "prometheus"
                ]

            stage_breakdown = obs_prom.stage_breakdown(metrics_text())

            def decode_counters() -> dict:
                """Device-decode evidence (ROADMAP item 3): byte and
                block COUNTS (platform-independent) — compressed-vs-dense
                shipped bytes and zone-skipped blocks, scraped from the
                RUNNING server's counters."""
                txt = metrics_text()
                shipped = obs_prom.gauge_value(
                    txt, "banyandb_decode_ship_bytes_total",
                    {"form": "shipped"},
                ) or 0.0
                dense = obs_prom.gauge_value(
                    txt, "banyandb_decode_ship_bytes_total",
                    {"form": "dense"},
                ) or 0.0
                skipped = obs_prom.gauge_value(
                    txt, "banyandb_blocks_skipped_total", {"reason": "zone"}
                ) or 0.0
                return {
                    "shipped_bytes": shipped,
                    "dense_bytes": dense,
                    "compression_ratio": round(dense / shipped, 2)
                    if shipped
                    else None,
                    "blocks_skipped_total": skipped,
                }

            # ---- staged-vs-fused A/B over the warm-distinct set ------
            # BYDB_FUSED flips LIVE on the in-process server; each leg
            # runs a FRESH distinct set (new seed => no partials-cache
            # replay from any earlier phase) and scrapes its own
            # stage_breakdown window (bucket-count deltas), so the
            # device-execute split is attributable per mode.
            n_ab = int(os.environ.get("BYDB_BENCH_AB", 30))
            # pin each leg's mode explicitly and restore the ambient
            # value after: a run launched with BYDB_FUSED=0 must still
            # measure a real fused-vs-staged A/B (and keep its ambient
            # setting for everything after this phase)
            ambient_fused = os.environ.get("BYDB_FUSED")
            try:
                # untimed per-leg warmup (distinct seed, same signature
                # population): each mode's kernels compile BEFORE its
                # timed set, so a leg whose executor never ran earlier
                # in the process doesn't charge XLA compiles to the A/B
                os.environ["BYDB_FUSED"] = "1"
                for q in distinct_queries(6, seed=37):
                    run(q)
                text_ab0 = metrics_text()
                fused_ms = [run(q) for q in distinct_queries(n_ab, seed=29)]
                text_ab1 = metrics_text()
                os.environ["BYDB_FUSED"] = "0"
                for q in distinct_queries(6, seed=41):
                    run(q)
                text_ab1 = metrics_text()
                staged_ms = [run(q) for q in distinct_queries(n_ab, seed=31)]
                text_ab2 = metrics_text()
            finally:
                if ambient_fused is None:
                    os.environ.pop("BYDB_FUSED", None)
                else:
                    os.environ["BYDB_FUSED"] = ambient_fused
            # ---- self-driving planner A/B (ISSUE 12) -----------------
            # ON = BYDB_PLANNER=1 + auto-registration (ticked inline on
            # the in-process server: hot signatures materialize with no
            # operator); OFF = BYDB_PLANNER=0 + BYDB_STREAMAGG=0, the
            # pre-planner flag-priority engine.  Mixed-selectivity
            # distinct set: eq (1/8), half in-set, no-predicate
            # (selectivity ~1 -> zone pre-pass skipped), and a
            # high-radix TopN (group-method decision).  Same-shape
            # signatures repeat across the set, which is exactly the
            # evidence autoreg mines.  Result JSON is asserted
            # byte-identical between modes (the acceptance contract).
            def mixed_queries(count: int, seed: int) -> list[str]:
                rq = np.random.default_rng(seed)
                span = n_rows * step
                out = []
                for i in range(count):
                    b = T0 + int(rq.integers(0, span // 3))
                    e = b + int(rq.integers(span // 4, span // 2))
                    kind = i % 4
                    if kind == 0:
                        out.append(
                            f"SELECT sum(hits) FROM MEASURE m IN g TIME "
                            f"BETWEEN {b} AND {e} WHERE region = "
                            f"'r{i % 8}' GROUP BY region"
                        )
                    elif kind == 1:
                        out.append(
                            f"SELECT mean(hits) FROM MEASURE m IN g TIME "
                            f"BETWEEN {b} AND {e} WHERE region IN "
                            f"('r0','r1','r2','r3') GROUP BY region"
                        )
                    elif kind == 2:
                        out.append(
                            f"SELECT sum(hits) FROM MEASURE m IN g TIME "
                            f"BETWEEN {b} AND {e} GROUP BY region"
                        )
                    else:
                        out.append(
                            f"SELECT sum(hits) FROM MEASURE m IN g TIME "
                            f"BETWEEN {b} AND {e} WHERE region = "
                            f"'r{i % 8}' GROUP BY svc TOP 10 BY hits"
                        )
                return out

            def run_served(ql: str) -> tuple:
                t0 = time.perf_counter()
                reply = tr.call(
                    srv.addr, TOPIC_QL, {"ql": ql}, timeout=600.0
                )
                return (
                    (time.perf_counter() - t0) * 1000,
                    reply.get("served", "scan"),
                )

            def planner_counts(txt0: str, txt1: str) -> dict:
                out = {}
                for p in ("materialized", "fused", "staged", "raw"):
                    c0 = obs_prom.gauge_value(
                        txt0, "banyandb_planner_decisions_total",
                        {"path": p},
                    ) or 0.0
                    c1 = obs_prom.gauge_value(
                        txt1, "banyandb_planner_decisions_total",
                        {"path": p},
                    ) or 0.0
                    if c1 - c0:
                        out[p] = int(c1 - c0)
                return out

            ambient_pl = {
                k: os.environ.get(k)
                for k in (
                    "BYDB_PLANNER",
                    "BYDB_STREAMAGG",
                    "BYDB_AUTOREG_MAX_STATE_MB",
                )
            }
            try:
                # the synthetic day's (region, svc) cardinality blows
                # the production-default 64MB state estimate by design
                # (budget behavior is covered by tests/test_planner.py);
                # this phase measures the self-driving WIN, so give the
                # loop room to keep its windows
                os.environ.setdefault("BYDB_AUTOREG_MAX_STATE_MB", "4096")
                # untimed SHAPE warmup under the baseline config: every
                # plan-spec x row-bucket combo the mixed set resolves
                # compiles before EITHER timed leg, so leg order cannot
                # charge XLA compiles to the A/B
                os.environ["BYDB_PLANNER"] = "0"
                os.environ["BYDB_STREAMAGG"] = "0"
                for q in mixed_queries(16, seed=101):
                    run(q)
                os.environ["BYDB_PLANNER"] = "1"
                os.environ["BYDB_STREAMAGG"] = "1"
                # evidence warmup + deterministic autoreg registration
                for q in mixed_queries(12, seed=53):
                    run(q)
                auto_sigs = 0
                for _ in range(10):
                    srv.autoreg.tick()
                    auto_sigs = len(srv._streamagg_signature_rows())
                    if auto_sigs >= 2:
                        break
                for q in mixed_queries(4, seed=59):
                    run(q)  # untimed: materialized path warms
                text_pl0 = metrics_text()
                on_runs = [
                    run_served(q) for q in mixed_queries(n_ab, seed=61)
                ]
                text_pl1 = metrics_text()
                os.environ["BYDB_PLANNER"] = "0"
                os.environ["BYDB_STREAMAGG"] = "0"
                for q in mixed_queries(4, seed=67):
                    run(q)
                off_runs = [
                    run_served(q) for q in mixed_queries(n_ab, seed=71)
                ]
                # byte parity between modes on the SAME queries
                parity_ok = True
                for q in mixed_queries(6, seed=73):
                    os.environ["BYDB_PLANNER"] = "1"
                    os.environ["BYDB_STREAMAGG"] = "1"
                    r_on = tr.call(
                        srv.addr, TOPIC_QL, {"ql": q}, timeout=600.0
                    )["result"]
                    os.environ["BYDB_PLANNER"] = "0"
                    os.environ["BYDB_STREAMAGG"] = "0"
                    r_off = tr.call(
                        srv.addr, TOPIC_QL, {"ql": q}, timeout=600.0
                    )["result"]
                    if json.dumps(r_on, sort_keys=True) != json.dumps(
                        r_off, sort_keys=True
                    ):
                        parity_ok = False
            finally:
                for k, v in ambient_pl.items():
                    if v is None:
                        os.environ.pop(k, None)
                    else:
                        os.environ[k] = v
            on_ms = [r[0] for r in on_runs]
            off_ms = [r[0] for r in off_runs]
            served_counts: dict = {}
            for _, s in on_runs:
                served_counts[s] = served_counts.get(s, 0) + 1
            served_counts_off: dict = {}
            for _, s in off_runs:
                served_counts_off[s] = served_counts_off.get(s, 0) + 1
            on_p50 = float(np.percentile(on_ms, 50))
            off_p50 = float(np.percentile(off_ms, 50))
            planner_ab = {
                "queries_per_mode": n_ab,
                "auto_signatures": auto_sigs,
                "autoreg_stats": srv.autoreg.stats(),
                "planner_on_p50_ms": round(on_p50, 1),
                "planner_on_p99_ms": round(
                    float(np.percentile(on_ms, 99)), 1
                ),
                "planner_off_p50_ms": round(off_p50, 1),
                "planner_off_p99_ms": round(
                    float(np.percentile(off_ms, 99)), 1
                ),
                "planner_speedup": round(off_p50 / max(on_p50, 1e-9), 2),
                "decision_counts": planner_counts(text_pl0, text_pl1),
                "served_counts_on": served_counts,
                "served_counts_off": served_counts_off,
                "result_parity": parity_ok,
            }

            fused_p50 = float(np.percentile(fused_ms, 50))
            staged_p50 = float(np.percentile(staged_ms, 50))
            fused_ab = {
                "queries_per_mode": n_ab,
                "fused_p50_ms": round(fused_p50, 1),
                "fused_p99_ms": round(float(np.percentile(fused_ms, 99)), 1),
                "staged_p50_ms": round(staged_p50, 1),
                "staged_p99_ms": round(
                    float(np.percentile(staged_ms, 99)), 1
                ),
                "fused_speedup": round(staged_p50 / max(fused_p50, 1e-9), 2),
                "stage_breakdown_fused": obs_prom.stage_breakdown_delta(
                    text_ab0, text_ab1
                ),
                "stage_breakdown_staged": obs_prom.stage_breakdown_delta(
                    text_ab1, text_ab2
                ),
            }
            # scraped while the server is still UP — the artifact print
            # below runs after srv.stop()
            decode_counters_snapshot = decode_counters()
        finally:
            tr.close()
            srv.stop()
        pooled = sorted(warm["topn"] + warm["percentile"])
        print(
            json.dumps(
                {
                    "e2e": "ok",
                    **device,
                    "rows": n_rows,
                    "series": n_series,
                    "shards": shards,
                    "span_hours": round(n_rows * step / 3_600_000, 1),
                    "ingest_points_per_s": round(n_rows / ingest_s),
                    "pipeline": os.environ.get("BYDB_PIPELINE", "1"),
                    "precompile_wait_ms": round(precompile_wait_ms, 1),
                    "precompile_done": warm_done,
                    "cold_ms": {k: round(v, 1) for k, v in cold.items()},
                    "cold_topn_ms": round(cold["topn"], 1),
                    "cold_percentile_ms": round(cold["percentile"], 1),
                    "warm_p50_ms": round(float(np.percentile(pooled, 50)), 1),
                    "warm_p99_ms": round(float(np.percentile(pooled, 99)), 1),
                    "warm_by_query_ms": {
                        k: {
                            "p50": round(float(np.percentile(v, 50)), 1),
                            "p99": round(float(np.percentile(v, 99)), 1),
                        }
                        for k, v in warm.items()
                    },
                    "iters": iters,
                    "distinct_queries": len(distinct_ms),
                    "warm_distinct_p50_ms": round(
                        float(np.percentile(distinct_ms, 50)), 1
                    ),
                    "warm_distinct_p99_ms": round(
                        float(np.percentile(distinct_ms, 99)), 1
                    ),
                    "cache_counters": {
                        "at_boot": counters_boot,
                        "after_pooled_warm": counters_pooled,
                        "after_distinct": counters_end,
                    },
                    "stage_breakdown": stage_breakdown,
                    "fused": os.environ.get("BYDB_FUSED", "1"),
                    "fused_speedup": fused_ab["fused_speedup"],
                    "fused_ab": fused_ab,
                    "planner_speedup": planner_ab["planner_speedup"],
                    "planner_ab": planner_ab,
                    "device_decode": os.environ.get(
                        "BYDB_DEVICE_DECODE", "1"
                    ),
                    "decode_counters": decode_counters_snapshot,
                }
            )
        )
    finally:
        shutil.rmtree(root, ignore_errors=True)


# ---------------------------------------------------------------------------
# Parent orchestration: JAX-free; two sequential children, one JSON line.
# ---------------------------------------------------------------------------


def _run_child(timeout_s: float, mode: str) -> dict | None:
    """Run `bench.py` in child mode on the ambient environment; return
    its parsed JSON line, or None when the phase failed.

    mode="e2e" runs the end-to-end server benchmark (key "e2e");
    mode="kernel" runs the kernel benchmark (key "metric")."""
    key = "e2e" if mode == "e2e" else "metric"
    env = dict(os.environ)
    env["_BYDB_BENCH_CHILD"] = mode
    try:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)],
            cwd=_REPO_DIR,
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
            start_new_session=True,  # killable as a group on timeout
        )
        try:
            out, err = proc.communicate(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except OSError:
                proc.kill()
            proc.wait()
            print(f"# child timed out after {timeout_s:.0f}s", file=sys.stderr)
            return None
    except OSError as e:
        print(f"# child spawn failed: {e}", file=sys.stderr)
        return None
    if err:
        sys.stderr.write(err[-4000:])
    if proc.returncode != 0:
        print(f"# {mode} child failed rc={proc.returncode}", file=sys.stderr)
        return None
    for line in reversed(out.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                rec = json.loads(line)
                if key in rec:
                    return rec
            except json.JSONDecodeError:
                continue
    print(f"# {mode} child printed no JSON record", file=sys.stderr)
    return None


REF_P50_MS = 26.7  # reference benchmark-single-model.md:105 measure-query p50


def _compose(kernel_rec: dict | None, e2e_rec: dict | None) -> dict | None:
    """One JSON line: the north star (E2E query p50) headlines when the
    end-to-end run succeeded; the kernel number always rides along."""
    if e2e_rec is not None:
        p50 = float(e2e_rec.get("warm_p50_ms") or 0) or 1e9
        return {
            "metric": "measure_query_e2e_p50_ms",
            "value": e2e_rec.get("warm_p50_ms"),
            "unit": "ms",
            "vs_baseline": round(REF_P50_MS / p50, 2),
            "baseline": (
                "reference measure-query p50=26.7ms "
                "(benchmark-single-model.md:105; 2CPU/4GB pods — "
                "different hardware, larger dataset here)"
            ),
            "platform": e2e_rec.get("platform"),
            "device_kind": e2e_rec.get("device_kind"),
            "device_count": e2e_rec.get("device_count"),
            "e2e": e2e_rec,
            "kernel": kernel_rec,
        }
    return kernel_rec


def main() -> int:
    mode = os.environ.get("_BYDB_BENCH_CHILD")
    if mode == "e2e":
        e2e_main()
        return 0
    if mode == "kernel":
        child_main()
        return 0

    # the parent never imports JAX: each child in turn is the one
    # process that holds the chip
    deadline = time.monotonic() + BUDGET_S
    e2e_rec = _run_child(max(deadline - time.monotonic(), 120), "e2e")
    kernel_rec = _run_child(max(deadline - time.monotonic(), 120), "kernel")
    final = _compose(kernel_rec, e2e_rec)
    if final is not None:
        print(json.dumps(final))
    failed = [
        name
        for name, rec in (("e2e", e2e_rec), ("kernel", kernel_rec))
        if rec is None
    ]
    if failed:
        print(f"# bench failed: phase(s) {failed}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
