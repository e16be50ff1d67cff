"""Fast CPU-only observability smoke (scripts/check.sh, both modes + CI).

Proves, on a 2-node in-process cluster in seconds, the self-observability
plane's end-to-end invariants (docs/observability.md):

1. a trace=true distributed measure query returns ONE merged span tree
   containing >= 2 per-node subtrees, each with nonzero device_ms /
   host_ms attribution and cache hit/miss tags;
2. tracing off returns byte-identical results (JSON form) to tracing on;
3. /metrics exposition carries bucketed (`_bucket`) latency histograms
   for at least the gather, device_execute and merge stages, and the
   scraped stage_breakdown (obs/prom.py) recovers nonzero quantiles;
4. the kernel audit's STATIC dispatch budget (lint/kernel/
   kernel_budgets.py, exported as `kernel_dispatch_budget` gauges)
   bounds the OBSERVED `device_execute` span count for the traced query
   — the measured plane and the predicted plane agree, which is the
   ratchet the fused whole-plan executor (ROADMAP item 2) tightens;
5. the fused whole-plan executor costs EXACTLY 1 device_execute
   dispatch per part-batch (reduce-span `path`/`dispatches` tags).

Exit 0 on success; any assertion prints a diagnostic and exits 1.
"""

from __future__ import annotations

import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")

# runnable as `python scripts/obs_smoke.py` from the repo root or CI
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

T0 = 1_700_000_000_000


def main() -> int:
    from pathlib import Path

    from banyandb_tpu.api import (
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
    from banyandb_tpu.api.model import Aggregation
    from banyandb_tpu.cluster import DataNode, Liaison, NodeInfo
    from banyandb_tpu.cluster.rpc import LocalTransport
    from banyandb_tpu.obs import find_span, global_meter
    from banyandb_tpu.obs import prom as obs_prom
    from banyandb_tpu.obs.tracer import iter_spans
    from banyandb_tpu.server import result_to_json

    root = Path(tempfile.mkdtemp(prefix="bydb-obs-smoke-"))

    def schema(reg):
        reg.create_group(
            Group("g", Catalog.MEASURE, ResourceOpts(shard_num=4))
        )
        # INT field: sum aggregates ride the DEVICE kernel path (floats
        # take the exact-f64 host path, which has no device leg to time)
        reg.create_measure(
            Measure(
                group="g", name="m",
                tags=(TagSpec("svc", TagType.STRING),),
                fields=(FieldSpec("v", FieldType.INT),),
                entity=Entity(("svc",)),
            )
        )

    transport = LocalTransport()
    nodes, datanodes = [], []
    for i in range(2):
        reg = SchemaRegistry(root / f"node{i}")
        schema(reg)
        dn = DataNode(f"data-{i}", reg, root / f"node{i}" / "data")
        nodes.append(NodeInfo(dn.name, transport.register(dn.name, dn.bus)))
        datanodes.append(dn)
    liaison_reg = SchemaRegistry(root / "liaison")
    schema(liaison_reg)
    liaison = Liaison(liaison_reg, transport, nodes)

    points = tuple(
        DataPointValue(
            T0 + i, {"svc": f"svc-{i % 16}"}, {"v": (i * 7) % 100}, version=1
        )
        for i in range(4000)
    )
    liaison.write_measure(WriteRequest("g", "m", points))
    for dn in datanodes:
        dn.measure.flush()

    req = QueryRequest(
        ("g",), "m", TimeRange(T0, T0 + 10_000),
        group_by=GroupBy(("svc",)), agg=Aggregation("sum", "v"),
        trace=True, limit=100,
    )
    from banyandb_tpu.obs import metrics as obs_metrics

    h_device = obs_metrics.stage_histogram("device_execute")
    device_spans_before = h_device.snapshot()[0]
    res = liaison.query_measure(req)
    device_spans = h_device.snapshot()[0] - device_spans_before
    tree = (res.trace or {}).get("span_tree")
    assert tree, "trace=true must attach a merged span_tree"

    # -- 1: merged tree with per-node subtrees + attribution tags ---------
    subtrees = [
        s for s in iter_spans(tree) if str(s.get("name", "")).startswith("data:")
    ]
    assert len(subtrees) >= 2, (
        f"expected >= 2 node subtrees, got {[s['name'] for s in subtrees]}"
    )
    for st in subtrees:
        reduce_span = find_span(st, "reduce")
        assert reduce_span is not None, f"{st['name']}: no reduce span"
        tags = reduce_span["tags"]
        assert tags.get("device_ms", 0) > 0, f"{st['name']}: device_ms {tags}"
        assert tags.get("host_ms", 0) > 0, f"{st['name']}: host_ms {tags}"
        assert "partials_cache" in tags, f"{st['name']}: cache tag {tags}"
        gather_span = find_span(st, "gather")
        assert gather_span is not None and "serving_cache" in gather_span["tags"], (
            f"{st['name']}: gather cache tag missing"
        )
    assert find_span(tree, "merge") is not None, "liaison merge span missing"
    print(
        f"# merged tree: {len(subtrees)} node subtrees, "
        f"root {tree['duration_ms']}ms"
    )

    # -- 2: byte-identical results, tracing on vs off ----------------------
    import dataclasses
    import json

    res_off = liaison.query_measure(dataclasses.replace(req, trace=False))
    j_on = result_to_json(res)
    j_on.pop("trace", None)
    j_off = result_to_json(res_off)
    j_off.pop("trace", None)
    b_on, b_off = json.dumps(j_on, sort_keys=True), json.dumps(j_off, sort_keys=True)
    assert b_on == b_off, "results differ with tracing on vs off"
    print(f"# parity: {len(b_on)} result bytes identical with trace on/off")

    # -- 3: bucketed stage histograms on the exposition --------------------
    text = global_meter().prometheus_text()
    for stage in ("gather", "device_execute", "merge"):
        needle = f'banyandb_query_stage_ms_bucket{{stage="{stage}"'
        assert needle in text, f"no _bucket series for stage {stage}"
    breakdown = obs_prom.stage_breakdown(text)
    for stage in ("gather", "device_execute", "merge"):
        rec = breakdown.get(stage)
        assert rec and rec["count"] > 0, f"stage_breakdown missing {stage}"
        assert rec["p50_ms"] > 0, f"{stage} p50 is zero: {rec}"
    print(f"# stage_breakdown: {breakdown}")

    # -- 4: static dispatch budget >= observed device_execute spans --------
    # The kernel audit PREDICTS at most dispatch_budget("measure") device
    # legs per part-batch; each node's reduce is one part-batch, so the
    # observed span count for the traced query is bounded by
    # budget x part-batches.  A fused executor landing with a tighter
    # budget tightens this same assertion for free.
    from banyandb_tpu.lint.kernel import kernel_budgets

    published = kernel_budgets.publish_to_meter()
    assert published > 0, "no dispatch budgets published to the meter"
    text = global_meter().prometheus_text()
    assert 'kernel_dispatch_budget{signature="fused/' in text, (
        "kernel_dispatch_budget gauges missing from the exposition"
    )
    budget = kernel_budgets.dispatch_budget("measure")
    part_batches = len(subtrees)
    assert 0 < device_spans <= budget * part_batches, (
        f"observed device_execute spans ({device_spans}) exceed the static "
        f"dispatch budget ({budget}/part-batch x {part_batches} part-"
        "batches) — the kernel audit's prediction no longer bounds the "
        "measured plane"
    )
    print(
        f"# dispatch budget: {device_spans} observed device spans <= "
        f"{budget}/part-batch x {part_batches} part-batches (static)"
    )

    # -- 5: fused whole-plan executor: 1 dispatch per part-batch -----------
    # The query must show EXACTLY one device_execute dispatch per
    # part-batch on every node's reduce span (docs/performance.md "Fused
    # whole-plan executor").
    for st in subtrees:
        tags = find_span(st, "reduce")["tags"]
        assert tags.get("path") == "fused", f"{st['name']}: path tag {tags}"
        assert tags.get("dispatches") == 1, (
            f"{st['name']}: fused part-batch cost {tags.get('dispatches')} "
            f"device_execute dispatches, want exactly 1 {tags}"
        )
    print(f"# fused: 1 dispatch/part-batch on {len(subtrees)} nodes")

    # -- 6: multi-process data plane graft (docs/performance.md) ----------
    # a BYDB_WORKERS=2 standalone server produces ONE merged tree whose
    # scatter legs carry grafted worker subtrees, and the merged
    # /metrics exposition carries worker-labeled stage histograms that
    # the shared scraper aggregates across workers
    _worker_graft_smoke()
    print("obs_smoke: OK")
    return 0


def _worker_graft_smoke() -> None:
    import json as _json

    from banyandb_tpu.api import (
        Aggregation,
        Catalog,
        Entity,
        FieldSpec,
        FieldType,
        Group,
        GroupBy,
        Measure,
        QueryRequest,
        ResourceOpts,
        TagSpec,
        TagType,
        TimeRange,
    )
    from banyandb_tpu.cluster import serde
    from banyandb_tpu.cluster.bus import Topic
    from banyandb_tpu.obs import prom as obs_prom
    from banyandb_tpu.server import StandaloneServer

    tmp = tempfile.mkdtemp(prefix="bydb-obs-workers-")
    srv = StandaloneServer(tmp, port=0, workers=2)
    try:
        srv.start()
        srv.registry.create_group(
            Group("wg", Catalog.MEASURE, ResourceOpts(shard_num=4))
        )
        srv.registry.create_measure(
            Measure(
                group="wg", name="m",
                tags=(TagSpec("svc", TagType.STRING),),
                fields=(FieldSpec("v", FieldType.FLOAT),),
                entity=Entity(("svc",)),
            )
        )
        pts = [
            {"ts": T0 + i, "tags": {"svc": f"s{i % 6}"},
             "fields": {"v": float(i % 9)}, "version": 1}
            for i in range(300)
        ]
        srv.bus.handle(
            Topic.MEASURE_WRITE.value,
            {"request": {"group": "wg", "name": "m", "points": pts}},
        )
        req = QueryRequest(
            ("wg",), "m", TimeRange(T0, T0 + 10_000),
            group_by=GroupBy(("svc",)), agg=Aggregation("sum", "v"),
            trace=True, limit=100,
        )
        res = srv.bus.handle(
            Topic.MEASURE_QUERY_RAW.value,
            {"request": serde.query_request_to_json(req)},
        )["result"]
        tree = res["trace"]["span_tree"]

        def find_all(node, pred, out):
            if isinstance(node, dict):
                if pred(node):
                    out.append(node)
                for c in node.get("children", ()) or ():
                    find_all(c, pred, out)
            return out

        legs = find_all(
            tree, lambda n: str(n.get("name", "")).startswith("scatter:w"), []
        )
        assert len(legs) >= 2, (
            f"worker scatter legs missing: {_json.dumps(tree)[:300]}"
        )
        for leg in legs:
            sub = find_all(
                leg, lambda n: str(n.get("name", "")).startswith("data:w"), []
            )
            assert sub, f"scatter leg {leg.get('name')} has no grafted subtree"
            assert find_all(sub[0], lambda n: n.get("name") == "reduce", []), (
                f"{leg.get('name')}: grafted subtree carries no reduce span"
            )
        text = srv.bus.handle("metrics", {})["prometheus"]
        assert 'worker="w000"' in text and 'worker="w001"' in text
        assert "banyandb_worker" in text or "banyandb_workers_alive" in text
        stages = obs_prom.stage_breakdown(text)
        assert stages.get("gather", {}).get("count", 0) > 0, (
            f"scraper lost worker-labeled stage series: {sorted(stages)}"
        )
        print(
            f"# worker graft: {len(legs)} scatter legs with data:w* "
            "subtrees, worker-labeled stage histograms scraped"
        )
    finally:
        srv.stop()
        import shutil

        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    try:
        raise SystemExit(main())
    except AssertionError as e:
        print(f"obs_smoke: FAILED: {e}", file=sys.stderr)
        raise SystemExit(1) from e
