"""Cost-based adaptive planner + self-driving materialization
(query/planner.py): cost-model bounds on seeded parts, BYDB_PLANNER=0/1
byte parity across the builtin signature shapes, auto-registration e2e
(hot signature -> registered window -> materialized serve-class,
eviction budget, manual survival), and the `cli.py explain` goldens.
"""

import json

import numpy as np
import pytest

from banyandb_tpu.api.model import (
    Aggregation,
    Condition,
    GroupBy,
    LogicalExpression,
    QueryRequest,
    TimeRange,
    Top,
)
from banyandb_tpu.api.schema import (
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
from banyandb_tpu.query import planner
from banyandb_tpu.server import result_to_json

T0 = 1_700_000_000_000


def _engine(tmp_path, shard_num=1) -> MeasureEngine:
    reg = SchemaRegistry(tmp_path / "schema")
    reg.create_group(
        Group("g", Catalog.MEASURE, ResourceOpts(shard_num=shard_num))
    )
    reg.create_measure(Measure(
        group="g", name="m",
        tags=(
            TagSpec("svc", TagType.STRING),
            TagSpec("region", TagType.STRING),
        ),
        fields=(
            FieldSpec("v", FieldType.INT),
            FieldSpec("lat", FieldType.FLOAT),
        ),
        entity=Entity(("svc",)),
    ))
    return MeasureEngine(reg, tmp_path / "data")


def _write(eng, n=4000, seed=0, svcs=5, regions=3, base=0):
    rng = np.random.default_rng(seed)
    ts = T0 + base + np.arange(n, dtype=np.int64) * 7
    eng.write_columns(
        "g", "m",
        ts_millis=ts,
        tags={
            "svc": [f"s{int(x)}" for x in rng.integers(0, svcs, n)],
            "region": [f"r{int(x)}" for x in rng.integers(0, regions, n)],
        },
        fields={
            "v": rng.integers(0, 100, n).astype(np.float64),
            "lat": rng.gamma(2.0, 10.0, n),
        },
        versions=np.arange(n, dtype=np.int64) + base + 1,
    )


def _req(**kw) -> QueryRequest:
    kw.setdefault("groups", ("g",))
    kw.setdefault("name", "m")
    # bounded span: grouped rescans past an int32 ts span drop rep
    # tracking (and streamagg coverage mirrors that), so cover-path
    # tests must query a realistic window
    kw.setdefault("time_range", TimeRange(T0 - 60_000, T0 + 86_400_000))
    kw.setdefault("limit", 0)
    return QueryRequest(**kw)


# -- cost model --------------------------------------------------------------


def test_estimate_rows_match_actual_on_seeded_parts(tmp_path):
    """est_rows (post time+zone pruning) must bound/track the gather:
    with no predicate it equals the exact row count; with an eq
    predicate the predicate-surviving estimate lands within 2x of the
    true match count (dict-coverage independence model)."""
    eng = _engine(tmp_path)
    _write(eng, n=4000)
    eng.flush()
    m = eng.registry.get_measure("g", "m")
    db = eng._tsdb("g")

    est = planner.estimate_scan(eng, db, m, _req(
        group_by=GroupBy(("svc",)), agg=Aggregation("sum", "v"),
    ))
    assert est.rows == 4000
    assert est.scan_rows == 4000  # nothing zone-prunable
    assert est.selectivity == 1.0

    est_eq = planner.estimate_scan(eng, db, m, _req(
        criteria=Condition("region", "eq", "r1"),
        group_by=GroupBy(("svc",)), agg=Aggregation("sum", "v"),
    ))
    # ~1/3 of rows carry r1; the dict-coverage model predicts exactly
    # 1/3 of the zone-surviving rows
    true_frac = 1 / 3
    assert est_eq.surviving_rows == pytest.approx(
        4000 * true_frac, rel=0.5
    )
    assert 0 < est_eq.selectivity < 0.6

    # a value absent from every dictionary -> zero surviving estimate
    est_miss = planner.estimate_scan(eng, db, m, _req(
        criteria=Condition("region", "eq", "nope"),
        group_by=GroupBy(("svc",)), agg=Aggregation("sum", "v"),
    ))
    assert est_miss.surviving_rows == 0


def test_group_estimate_bounded_by_rows_and_radices(tmp_path):
    eng = _engine(tmp_path)
    _write(eng, n=300, svcs=5, regions=3)
    eng.flush()
    m = eng.registry.get_measure("g", "m")
    est = planner.estimate_scan(eng, eng._tsdb("g"), m, _req(
        group_by=GroupBy(("svc", "region")), agg=Aggregation("sum", "v"),
    ))
    # true distinct groups = 15; the estimate must stay within
    # [largest single dict product, rows]
    assert 1 <= est.groups <= 300
    assert est.groups >= 15 // 4  # sane lower ballpark
    assert est.static_groups >= 15


def test_decision_skips_zone_prepass_at_full_selectivity(tmp_path):
    """No conjunctive predicate -> nothing zone-prunable -> the planner
    skips the pre-pass; a selective predicate turns it back on when the
    zone maps can actually prove blocks away."""
    eng = _engine(tmp_path)
    # two value-disjoint batches -> parts whose region dictionaries
    # differ, so an eq can zone-prune whole parts
    rng = np.random.default_rng(3)
    for part, reg_name in ((0, "east"), (1, "west")):
        n = 2000
        ts = T0 + part * 10_000_000 + np.arange(n, dtype=np.int64)
        eng.write_columns(
            "g", "m", ts_millis=ts,
            tags={
                "svc": [f"s{int(x)}" for x in rng.integers(0, 5, n)],
                "region": [reg_name] * n,
            },
            fields={
                "v": rng.integers(0, 100, n).astype(np.float64),
                "lat": rng.gamma(2.0, 10.0, n),
            },
            versions=np.arange(n, dtype=np.int64) + part * n + 1,
        )
        eng.flush()
    m = eng.registry.get_measure("g", "m")
    db = eng._tsdb("g")
    d_full = planner.plan_scan(eng, db, m, _req(
        group_by=GroupBy(("svc",)), agg=Aggregation("sum", "v"),
    ))
    assert d_full.zone_prepass is False  # selectivity ~1: skip it
    d_sel = planner.plan_scan(eng, db, m, _req(
        criteria=Condition("region", "eq", "east"),
        group_by=GroupBy(("svc",)), agg=Aggregation("sum", "v"),
    ))
    assert d_sel.zone_prepass is True
    assert d_sel.est.scan_rows <= d_full.est.scan_rows // 2 + 100


def test_group_method_override_only_when_crossover_flips(tmp_path):
    """The override exists for high-radix-but-sparse cross products:
    static product past SORT_GROUPS_THRESHOLD while the estimate stays
    below it -> hash; matching sides -> None (signature stability)."""
    from banyandb_tpu.ops.groupby import SORT_GROUPS_THRESHOLD

    eng = _engine(tmp_path)
    _write(eng, n=500)
    eng.flush()
    m = eng.registry.get_measure("g", "m")
    db = eng._tsdb("g")
    d = planner.plan_scan(eng, db, m, _req(
        group_by=GroupBy(("svc",)), agg=Aggregation("sum", "v"),
    ))
    assert d.group_method is None  # both sides resolve the same

    est = planner.ScanEstimate(
        rows=100_000, scan_rows=100_000, surviving_rows=50_000,
        groups=1000, static_groups=SORT_GROUPS_THRESHOLD * 4,
    )
    # simulate the sparse cross product: static says sort, estimate
    # says hash — the decision logic must override
    from banyandb_tpu.ops import groupby

    static = groupby.select_group_method(50_000, est.static_groups)
    dynamic = groupby.select_group_method(50_000, est.groups)
    assert static == "sort" and dynamic != "sort"


def test_planner_module_is_host_only():
    """The kernel-budget hygiene pin (docs/linting.md, the streamagg
    ingest exemption pattern): the planner is metadata-only — it must
    never import jax directly, so no device dispatch can creep into
    the planning path through this module."""
    import banyandb_tpu.query.planner as mod

    src = open(mod.__file__).read()
    assert "import jax" not in src, (
        "planner grew a jax import: give it a ratcheted kernel-budget "
        "row instead of relying on the host-only exemption"
    )


# -- BYDB_PLANNER=0/1 byte parity -------------------------------------------


def _parity_requests():
    """Query shapes mirroring the builtin signature matrix
    (precompile.builtin_plans): flat count, grouped eq+range, two-pass
    percentile, OR expression, TopN dashboard."""
    return [
        _req(agg=Aggregation("count", "v")),
        _req(
            criteria=LogicalExpression(
                "and",
                Condition("svc", "eq", "s1"),
                Condition("region", "ne", "r2"),
            ),
            group_by=GroupBy(("svc", "region")),
            agg=Aggregation("sum", "v"),
            tag_projection=("svc", "region"),
        ),
        _req(
            group_by=GroupBy(("svc",)),
            agg=Aggregation("percentile", "lat", (0.5, 0.95)),
        ),
        _req(
            criteria=LogicalExpression(
                "or",
                Condition("svc", "in", ("s1", "s2")),
                Condition("region", "eq", "r0"),
            ),
            agg=Aggregation("count", "v"),
        ),
        _req(
            criteria=Condition("region", "ne", "r9"),
            group_by=GroupBy(("svc",)),
            agg=Aggregation("mean", "v"),
            top=Top(3, "v", "desc"),
        ),
        _req(
            criteria=Condition("region", "eq", "r1"),
            group_by=GroupBy(("svc",)),
            agg=Aggregation("max", "lat"),
            order_by_ts="desc",
        ),
    ]


def test_planner_ab_byte_parity_all_builtin_shapes(tmp_path, monkeypatch):
    eng = _engine(tmp_path, shard_num=2)
    _write(eng, n=3000, seed=1)
    eng.flush()
    _write(eng, n=800, seed=2, base=50_000)  # memtable rows too
    for i, req in enumerate(_parity_requests()):
        monkeypatch.setenv("BYDB_PLANNER", "1")
        on = json.dumps(result_to_json(eng.query(req)), sort_keys=True)
        monkeypatch.setenv("BYDB_PLANNER", "0")
        off = json.dumps(result_to_json(eng.query(req)), sort_keys=True)
        assert on == off, f"parity broke on shape {i}"
    monkeypatch.setenv("BYDB_PLANNER", "1")


def test_planner_span_est_vs_actual(tmp_path, monkeypatch):
    monkeypatch.setenv("BYDB_PLANNER", "1")
    from banyandb_tpu.obs.tracer import find_span

    eng = _engine(tmp_path)
    _write(eng, n=2000)
    eng.flush()
    res = eng.query(_req(
        criteria=Condition("region", "eq", "r1"),
        group_by=GroupBy(("svc",)),
        agg=Aggregation("sum", "v"),
        trace=True,
    ))
    span = find_span(res.trace["span_tree"], "planner")
    assert span is not None
    tags = span["tags"]
    assert tags["path"] == "fused"
    assert tags["actual_rows"] == 2000  # eq masks on device, gather=all
    assert tags["est_rows"] == 2000
    assert 0 < tags["est_surviving"] <= 2000
    assert "est_groups" in tags and "zone_prepass" in tags


# -- auto-registration -------------------------------------------------------


def test_signature_of_eligibility():
    sig = planner.signature_of(_req(
        criteria=Condition("region", "eq", "r1"),
        group_by=GroupBy(("svc",)),
        agg=Aggregation("sum", "v"),
    ))
    assert sig == ("g", "m", ("region", "svc"), ("v",))
    # OR trees, percentile, range ops, raw rows: not eligible
    assert planner.signature_of(_req(
        criteria=LogicalExpression(
            "or", Condition("svc", "eq", "a"), Condition("svc", "eq", "b")
        ),
        agg=Aggregation("sum", "v"),
    )) is None
    assert planner.signature_of(_req(
        group_by=GroupBy(("svc",)),
        agg=Aggregation("percentile", "v", (0.5,)),
    )) is None
    assert planner.signature_of(_req(
        criteria=Condition("v", "gt", 5), agg=Aggregation("sum", "v"),
    )) is None
    assert planner.signature_of(_req()) is None  # raw scan


class _Stats:
    """Minimal SignatureStats stand-in with a settable snapshot."""

    def __init__(self):
        self.counts = {}

    def snapshot(self):
        return dict(self.counts)


def _registrar(tmp_path, eng, stats=None, **kw):
    sa = eng.streamagg
    return planner.AutoRegistrar(
        tmp_path / "autoreg.json",
        sig_stats=stats,
        register_fn=lambda g, m, kt, f: sa.register(
            g, m, key_tags=kt, fields=f, origin="auto"
        ),
        unregister_fn=lambda g, m, kt, f: sa.unregister(
            g, m, key_tags=kt, fields=f
        ),
        stats_fn=lambda: sa.stats()["signatures"],
        **kw,
    )


def test_autoreg_registers_hot_signature_and_serves_materialized(
    tmp_path, monkeypatch
):
    monkeypatch.setenv("BYDB_STREAMAGG", "1")
    eng = _engine(tmp_path)
    _write(eng, n=2000)
    eng.flush()
    stats = _Stats()
    ar = _registrar(tmp_path, eng, stats)
    req = _req(
        criteria=Condition("region", "eq", "r1"),
        group_by=GroupBy(("svc",)),
        agg=Aggregation("sum", "v"),
    )
    key = planner.signature_of(req)
    stats.counts[key] = 5  # hot: past BYDB_AUTOREG_MIN_HITS
    made = ar.tick()
    assert made == 1
    rows = eng.streamagg.stats()["signatures"]
    assert len(rows) == 1 and rows[0]["origin"] == "auto"
    # the covered query now folds windows: serve-class materialized
    from banyandb_tpu.obs.tracer import find_span

    res = eng.query(_req(
        criteria=Condition("region", "eq", "r1"),
        group_by=GroupBy(("svc",)), agg=Aggregation("sum", "v"),
        trace=True,
    ))
    sa_span = find_span(res.trace["span_tree"], "streamagg")
    assert sa_span is not None
    assert sa_span["tags"]["coverage"] in ("covered", "partial")
    # parity of the materialized answer vs rescan
    monkeypatch.setenv("BYDB_STREAMAGG", "0")
    off = json.dumps(result_to_json(eng.query(req)), sort_keys=True)
    monkeypatch.setenv("BYDB_STREAMAGG", "1")
    on = json.dumps(result_to_json(eng.query(req)), sort_keys=True)
    assert on == off


def test_autoreg_budget_evicts_lru_auto_never_manual(
    tmp_path, monkeypatch
):
    monkeypatch.setenv("BYDB_STREAMAGG", "1")
    monkeypatch.setenv("BYDB_AUTOREG_MAX_SIGNATURES", "2")
    eng = _engine(tmp_path)
    _write(eng, n=500)
    eng.flush()
    # manual registration: must survive any budget pressure
    eng.streamagg.register(
        "g", "m", key_tags=("svc",), fields=("v",), origin="manual"
    )
    stats = _Stats()
    ar = _registrar(tmp_path, eng, stats)
    sigs = [
        ("g", "m", ("region", "svc"), ("v",)),
        ("g", "m", ("region",), ("v",)),
        ("g", "m", ("region", "svc"), ("lat", "v")),
    ]
    # three hot auto candidates against a budget of 2 auto slots
    for i, key in enumerate(sigs):
        stats.counts[key] = 10 - i
        ar.tick()
    rows = eng.streamagg.stats()["signatures"]
    by_origin = {}
    for r in rows:
        by_origin.setdefault(r["origin"], []).append(r)
    assert len(by_origin.get("manual", [])) == 1  # never evicted
    assert len(by_origin.get("auto", [])) <= 2  # budget honored
    assert ar.evicted_total >= 1


def test_autoreg_persistence_survives_restart(tmp_path, monkeypatch):
    monkeypatch.setenv("BYDB_STREAMAGG", "1")
    eng = _engine(tmp_path)
    _write(eng, n=500)
    eng.flush()
    stats = _Stats()
    ar = _registrar(tmp_path, eng, stats)
    key = ("g", "m", ("region", "svc"), ("v",))
    stats.counts[key] = 7
    ar.tick()
    assert ar.registered_total == 1
    ar.stop()
    # a fresh registrar over the same store neither re-learns from
    # scratch nor forgets which signatures were ITS OWN
    ar2 = _registrar(tmp_path, eng, _Stats())
    assert ar2._hits[key]["hits"] >= 7
    assert key in ar2._auto


def test_autoreg_rejected_signature_is_forgotten(tmp_path):
    eng = _engine(tmp_path)
    stats = _Stats()
    ar = _registrar(tmp_path, eng, stats)
    bad = ("g", "m", ("nope_tag",), ("v",))
    stats.counts[bad] = 9
    assert ar.tick() == 0
    assert ar.errors == 1
    assert bad not in ar._hits  # no infinite retry


def test_plan_registry_evidence_feeds_autoreg(tmp_path, monkeypatch):
    """The second mining surface: a measure PlanSpec recorded WITH
    context converts into the same signature key."""
    monkeypatch.setenv("BYDB_PRECOMPILE", "1")
    from banyandb_tpu.query.precompile import PrecompileRegistry

    eng = _engine(tmp_path)
    _write(eng, n=300)
    eng.flush()
    reg = PrecompileRegistry()
    from banyandb_tpu.query.measure_exec import PlanSpec, _PredSpec

    spec = PlanSpec(
        tags_code=("region", "svc"),
        fields=("v",),
        preds=(_PredSpec("code", "region", "eq"),),
        group_tags=("svc",),
        radices=(5,),
        num_groups=5,
        want_minmax=True,
        nrows=8192,
    )
    for _ in range(4):
        reg.record("measure", spec, context=("g", "m"))
    ar = _registrar(tmp_path, eng, None, plan_registry=reg)
    assert ar.tick() == 1
    rows = eng.streamagg.stats()["signatures"]
    assert rows and rows[0]["key_tags"] == ["region", "svc"]


def test_plan_registry_persists_hits_and_context(tmp_path):
    """Satellite: frequency-weighted persistence with hit/age stats —
    counts, last-hit and measure context survive the store round-trip
    and rank the hottest signature first."""
    from banyandb_tpu.query.measure_exec import PlanSpec
    from banyandb_tpu.query.precompile import PrecompileRegistry

    import os

    os.environ["BYDB_PRECOMPILE"] = "1"
    try:
        a = PlanSpec(
            tags_code=(), fields=("v",), preds=(), group_tags=(),
            radices=(), num_groups=1, want_minmax=True, nrows=8192,
        )
        b = PlanSpec(
            tags_code=(), fields=("w",), preds=(), group_tags=(),
            radices=(), num_groups=1, want_minmax=True, nrows=8192,
        )
        r1 = PrecompileRegistry()
        r1.attach_store(tmp_path / "plans.json")
        r1.record("measure", a, context=("g", "m"))
        for _ in range(3):
            r1.record("measure", b, context=("g", "m"))
        r1._save()
        r2 = PrecompileRegistry()
        r2.attach_store(tmp_path / "plans.json")
        sigs = r2.signatures()
        assert sigs[0] == ("measure", b)  # frequency-weighted order
        ev = r2.evidence()
        assert ev[0][2] >= 3 and ev[0][3] == ("g", "m")
    finally:
        os.environ["BYDB_PRECOMPILE"] = "0"


# -- streamagg unregister ----------------------------------------------------


def test_streamagg_unregister_drops_state_and_falls_back(
    tmp_path, monkeypatch
):
    monkeypatch.setenv("BYDB_STREAMAGG", "1")
    eng = _engine(tmp_path)
    _write(eng, n=800)
    eng.flush()
    eng.streamagg.register(
        "g", "m", key_tags=("region", "svc"), fields=("v",)
    )
    req = _req(
        criteria=Condition("region", "eq", "r1"),
        group_by=GroupBy(("svc",)), agg=Aggregation("sum", "v"),
    )
    m = eng.registry.get_measure("g", "m")
    assert eng.streamagg.plan_cover(m, req) is not None
    assert eng.streamagg.unregister(
        "g", "m", key_tags=("region", "svc"), fields=("v",)
    )
    assert eng.streamagg.plan_cover(m, req) is None
    assert not eng.streamagg.unregister(
        "g", "m", key_tags=("region", "svc"), fields=("v",)
    )
    # persisted registry no longer reloads it
    import banyandb_tpu.utils.fs as fs

    doc = fs.read_json(eng.streamagg._store)
    assert doc["signatures"] == []


# -- explain -----------------------------------------------------------------


def _golden_reply(path="fused", served="scan"):
    return {
        "served": served,
        "result": {
            "groups": [["s1"]],
            "values": {"sum(v)": [42.0]},
            "data_points": [],
            "trace": {
                "plan": (
                    "GroupByAggregate [group_by=svc, agg=sum(v)]\n"
                    "  IndexScan [measure=g.m]"
                ),
                "span_tree": {
                    "name": "standalone:measure",
                    "duration_ms": 5.0,
                    "tags": {},
                    "children": [
                        {
                            "name": "planner",
                            "duration_ms": 0.2,
                            "tags": {
                                "path": path,
                                "est_rows": 1200,
                                "est_surviving": 400,
                                "est_groups": 5,
                                "selectivity": 0.333,
                                "zone_prepass": True,
                                "group_method": "auto",
                                "parts": 2,
                                "actual_rows": 1180,
                            },
                            "children": [],
                        },
                        {
                            "name": "execute",
                            "duration_ms": 4.0,
                            "tags": {},
                            "children": [
                                {
                                    "name": "reduce",
                                    "duration_ms": 3.0,
                                    "tags": {"path": path},
                                    "children": [],
                                }
                            ],
                        },
                    ],
                },
            },
        },
    }


EXPLAIN_GOLDEN = """\
plan:
  GroupByAggregate [group_by=svc, agg=sum(v)]
    IndexScan [measure=g.m]
path: fused (served: scan)
planner:
  estimated rows: 1200  actual rows: 1180
  estimated groups: 5  group method: auto
  selectivity: 0.333  zone pre-pass: on  parts: 2"""


def test_explain_golden_scan():
    from banyandb_tpu.cli import render_explain

    assert render_explain(_golden_reply()) == EXPLAIN_GOLDEN


def test_explain_golden_materialized():
    from banyandb_tpu.cli import render_explain

    reply = _golden_reply(served="materialized")
    reply["result"]["trace"]["span_tree"]["children"] = [
        {
            "name": "streamagg",
            "duration_ms": 0.5,
            "tags": {
                "signature": "g/m[region,svc]@60000ms",
                "coverage": "covered",
                "windows": 4,
            },
            "children": [],
        }
    ]
    out = render_explain(reply)
    assert "path: materialized (served: materialized)" in out
    assert "signature: g/m[region,svc]@60000ms" in out
    assert "coverage: covered  windows: 4" in out
    assert "planner: (no scan planned" in out


def test_explain_live_engine_round_trip(tmp_path, monkeypatch):
    """End-to-end: a traced reply rendered through render_explain names
    the real chosen path and real row counts."""
    monkeypatch.setenv("BYDB_PLANNER", "1")
    from banyandb_tpu.cli import render_explain

    eng = _engine(tmp_path)
    _write(eng, n=1000)
    eng.flush()
    res = eng.query(_req(
        criteria=Condition("region", "eq", "r1"),
        group_by=GroupBy(("svc",)), agg=Aggregation("sum", "v"),
        trace=True,
    ))
    reply = {"result": result_to_json(res), "served": "scan"}
    out = render_explain(reply)
    assert "actual rows: 1000" in out
    assert "path: fused (served: scan)" in out
