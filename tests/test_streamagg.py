"""Continuous streaming aggregation (query/streamagg.py): materialized
rolling windows updated at ingest, answering covered dashboard
signatures byte-identically to the full rescan (`BYDB_STREAMAGG` A/B).
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
from banyandb_tpu.server import result_to_json

T0 = 1_700_000_000_000


def _schema(reg, shard_num=2):
    reg.create_group(
        Group("g", Catalog.MEASURE, ResourceOpts(shard_num=shard_num))
    )
    reg.create_measure(Measure(
        group="g", name="m",
        tags=(
            TagSpec("svc", TagType.STRING),
            TagSpec("region", TagType.STRING),
        ),
        fields=(FieldSpec("v", FieldType.FLOAT),),
        entity=Entity(("svc",)),
    ))


def _engine(tmp_path, shard_num=2) -> MeasureEngine:
    reg = SchemaRegistry(tmp_path / "schema")
    _schema(reg, shard_num)
    return MeasureEngine(reg, tmp_path / "data")


def _write(eng, base, n, seed=0, group="g", name="m"):
    rng = np.random.default_rng(seed)
    ts = T0 + base + np.arange(n, dtype=np.int64)
    eng.write_columns(
        group, name,
        ts_millis=ts,
        tags={
            "svc": [f"s{int(x)}" for x in rng.integers(0, 5, n)],
            "region": [f"r{int(x)}" for x in rng.integers(0, 3, n)],
        },
        fields={"v": rng.integers(0, 100, n).astype(np.float64)},
        versions=np.arange(n, dtype=np.int64) + base + 1,
    )


def _ab(eng, req, monkeypatch):
    """(materialized JSON, rescan JSON) for one request."""
    monkeypatch.setenv("BYDB_STREAMAGG", "1")
    on = json.dumps(result_to_json(eng.query(req)), sort_keys=True)
    monkeypatch.setenv("BYDB_STREAMAGG", "0")
    off = json.dumps(result_to_json(eng.query(req)), sort_keys=True)
    monkeypatch.setenv("BYDB_STREAMAGG", "1")
    return on, off


@pytest.fixture()
def eng(tmp_path):
    e = _engine(tmp_path)
    yield e
    e.close()


def _register(e, key_tags=("region", "svc"), **kw):
    return e.streamagg.register(
        "g", "m", key_tags=key_tags, fields=("v",),
        window_millis=kw.pop("window_millis", 1000), **kw,
    )


PARITY_REQS = [
    QueryRequest(
        groups=("g",), name="m", time_range=TimeRange(T0, T0 + 4000),
        group_by=GroupBy(("svc",)), agg=Aggregation("count", "v"),
    ),
    QueryRequest(  # unaligned head+tail -> bounded rescans combine
        groups=("g",), name="m", time_range=TimeRange(T0 + 137, T0 + 3791),
        group_by=GroupBy(("svc",)), agg=Aggregation("mean", "v"),
        criteria=Condition("region", "eq", "r1"),
    ),
    QueryRequest(  # flat aggregate with key-tag filter
        groups=("g",), name="m", time_range=TimeRange(T0, T0 + 50_000),
        agg=Aggregation("sum", "v"), criteria=Condition("svc", "eq", "s2"),
    ),
    QueryRequest(  # in + ne predicates filter state keys
        groups=("g",), name="m", time_range=TimeRange(T0, T0 + 4000),
        group_by=GroupBy(("region",)), agg=Aggregation("min", "v"),
        criteria=LogicalExpression(
            "and",
            Condition("svc", "in", ("s1", "s3")),
            Condition("region", "ne", "r0"),
        ),
    ),
    QueryRequest(  # TopN ranking over folded groups
        groups=("g",), name="m", time_range=TimeRange(T0, T0 + 4000),
        group_by=GroupBy(("svc",)), agg=Aggregation("mean", "v"),
        top=Top(3, "v"),
    ),
    QueryRequest(  # paging over first-appearance order
        groups=("g",), name="m", time_range=TimeRange(T0, T0 + 4000),
        group_by=GroupBy(("svc",)), agg=Aggregation("count", "v"),
        limit=2, offset=1,
    ),
    QueryRequest(  # ORDER BY time DESC flips the rep key direction
        groups=("g",), name="m", time_range=TimeRange(T0, T0 + 4000),
        group_by=GroupBy(("svc",)), agg=Aggregation("count", "v"),
        order_by_ts="desc",
    ),
    QueryRequest(  # empty range: the flat group still reports
        groups=("g",), name="m",
        time_range=TimeRange(T0 + 10_000_000, T0 + 20_000_000),
        agg=Aggregation("count", "v"),
    ),
    QueryRequest(  # percentile falls back whole, incl. range round
        groups=("g",), name="m", time_range=TimeRange(T0, T0 + 4000),
        group_by=GroupBy(("svc",)),
        agg=Aggregation("percentile", "v", (0.5, 0.99)),
    ),
]


def test_ab_parity_matrix(eng, monkeypatch):
    """Every covered/partial/fallback shape is byte-identical to the
    rescan, over a parts + memtable mix spanning window rotations."""
    _write(eng, 0, 1200, seed=1)  # pre-registration -> backfill
    info = _register(eng)
    assert info["rows"] == 1200
    _write(eng, 1200, 1500, seed=2)
    eng.flush()
    _write(eng, 2700, 800, seed=3)
    for i, req in enumerate(PARITY_REQS):
        on, off = _ab(eng, req, monkeypatch)
        assert on == off, f"req {i}: {on} != {off}"


def test_materialized_actually_serves(eng, monkeypatch):
    """The covered path runs (not a silent fallback): the reads counter
    moves and the span tree carries a streamagg node."""
    from banyandb_tpu.obs.metrics import global_meter
    from banyandb_tpu.obs.tracer import Tracer

    _write(eng, 0, 2500, seed=1)
    _register(eng)
    monkeypatch.setenv("BYDB_STREAMAGG", "1")
    before = dict(global_meter().snapshot()["counters"])
    tracer = Tracer("t")
    eng.query(PARITY_REQS[0], tracer=tracer)
    after = global_meter().snapshot()["counters"]
    moved = [
        k for k in after
        if k[0] == "streamagg_reads"
        and after[k] > before.get(k, 0)
        and dict(k[1]).get("kind") in ("covered", "partial")
    ]
    assert moved, "covered read did not count"
    names = []

    def walk(n):
        if isinstance(n, dict):
            names.append(n.get("name"))
            for c in n.get("children", ()) or ():
                walk(c)

    walk(tracer.finish())
    assert "streamagg" in names


def test_flag_off_never_folds(eng, monkeypatch):
    _write(eng, 0, 1500, seed=1)
    _register(eng)
    monkeypatch.setenv("BYDB_STREAMAGG", "0")
    assert eng.streamagg.plan_cover(
        eng.registry.get_measure("g", "m"), PARITY_REQS[0]
    ) is None


def test_plan_cover_fallback_shapes(eng, monkeypatch):
    """Shapes windows cannot express fall back (cover is None) instead
    of answering wrong."""
    _write(eng, 0, 1500, seed=1)
    _register(eng)
    monkeypatch.setenv("BYDB_STREAMAGG", "1")
    m = eng.registry.get_measure("g", "m")
    base = dict(
        groups=("g",), name="m", time_range=TimeRange(T0, T0 + 4000),
        group_by=GroupBy(("svc",)), agg=Aggregation("count", "v"),
    )
    covered = QueryRequest(**base)
    assert eng.streamagg.plan_cover(m, covered) is not None
    fallbacks = [
        # OR criteria cannot filter state keys
        QueryRequest(**{**base, "criteria": LogicalExpression(
            "or",
            Condition("svc", "eq", "s1"),
            Condition("region", "eq", "r1"),
        )}),
        # range predicate op
        QueryRequest(**{**base, "criteria": Condition("svc", "ge", "s1")}),
        # percentile
        QueryRequest(**{**base, "agg": Aggregation(
            "percentile", "v", (0.5,)
        )}),
        # representative (projected-but-not-grouped) tag needs row state
        QueryRequest(**{**base, "tag_projection": ("region",)}),
        # sub-window range: no full window to fold
        QueryRequest(**{
            **base, "time_range": TimeRange(T0 + 100, T0 + 900),
        }),
        # unknown aggregate field -> not materialized
        QueryRequest(**{**base, "agg": Aggregation("count", "nope")}),
    ]
    for i, req in enumerate(fallbacks):
        assert eng.streamagg.plan_cover(m, req) is None, f"shape {i}"
    # ... and the fallback shapes still answer identically via rescan
    on, off = _ab(eng, fallbacks[0], monkeypatch)
    assert on == off


def test_register_validation(eng):
    with pytest.raises(KeyError):
        _register(eng, key_tags=("nope",))
    with pytest.raises(KeyError):
        eng.streamagg.register(
            "g", "m", key_tags=("svc",), fields=("nope",),
            window_millis=1000,
        )
    with pytest.raises(ValueError):
        # window must divide the segment interval (1 day)
        eng.streamagg.register(
            "g", "m", key_tags=("svc",), fields=("v",),
            window_millis=7000,
        )
    # idempotent re-register returns the live signature
    a = _register(eng)
    b = _register(eng)
    assert a["signature"] == b["signature"]


def test_auto_registration_over_budget_refused_before_backfill(
    tmp_path, monkeypatch
):
    """The autoreg state budget gates an AUTO signature before its
    backfill (from part/memtable metadata), not by eviction after it: an
    over-budget signature never gathers a row and is never installed.
    Manual registrations are the operator's own memory decision."""
    e = _engine(tmp_path)
    try:
        _write(e, 0, 3000)  # 3 s of data, 5 svc x 3 regions
        e.flush()
        _write(e, 3000, 500, seed=1)  # + memtable rows
        sa = e.streamagg
        spec_kw = dict(key_tags=("region", "svc"), fields=("v",),
                       window_millis=1000)
        from banyandb_tpu.query.streamagg import SigSpec

        est = sa._estimate_states(
            SigSpec("g", "m", ("region", "svc"), ("v",), 1000)
        )
        # (3 regions x the 3 services of the fuller shard) keys x 2
        # shards x 4 windows — metadata only, and under the 3500 rows
        assert est == 9 * 2 * 4
        monkeypatch.setattr(
            sa, "_backfill_snapshot",
            lambda spec: pytest.fail("backfill ran for a refused signature"),
        )
        monkeypatch.setenv("BYDB_AUTOREG_MAX_STATE_MB", "0")
        with pytest.raises(ValueError, match="over the 0 MB autoreg budget"):
            sa.register("g", "m", origin="auto", **spec_kw)
        assert sa.stats()["signatures"] == []
        monkeypatch.undo()
        info = sa.register("g", "m", origin="auto", **spec_kw)
        assert info["origin"] == "auto" and 0 < info["states"] <= est
    finally:
        e.close()


def test_late_rows_within_horizon_stay_consistent(eng, monkeypatch):
    """A late row landing in a kept (non-evicted) window re-accumulates
    and the fold still matches the rescan."""
    _write(eng, 0, 1000, seed=1)
    _register(eng)
    _write(eng, 2000, 1000, seed=2)  # watermark advances 2 windows
    # late rows: event time behind the watermark, into a kept window
    # (fresh (series, ts) keys — windows assume append-only ingest)
    _write(eng, 1000, 50, seed=3)
    on, off = _ab(eng, PARITY_REQS[0], monkeypatch)
    assert on == off


def test_eviction_advances_horizon_and_head_rescans(eng, monkeypatch):
    _write(eng, 0, 1000, seed=1)
    _register(eng, key_tags=("svc",), max_windows=2)
    _write(eng, 1000, 4000, seed=2)  # 5 windows total -> 3 evicted
    st = eng.streamagg.stats()["signatures"][0]
    assert st["windows"] == 2
    assert st["covered_from"] == T0 + 3000
    # very-late rows below the horizon drop (counted), never corrupt
    before = st["late_dropped"]
    _write(eng, 100, 10, seed=3)
    st = eng.streamagg.stats()["signatures"][0]
    assert st["late_dropped"] == before + 10
    req = QueryRequest(
        groups=("g",), name="m", time_range=TimeRange(T0, T0 + 5000),
        group_by=GroupBy(("svc",)), agg=Aggregation("sum", "v"),
    )
    on, off = _ab(eng, req, monkeypatch)
    assert on == off


def test_store_round_trip_rebuilds_from_parts(tmp_path, monkeypatch):
    """Restart path: a fresh engine over the same root reloads the
    persisted signature and BACKFILLS from surviving parts — the fold
    equals the rescan oracle (gap-free, no double count)."""
    e1 = _engine(tmp_path)
    _write(e1, 0, 2000, seed=1)
    _register(e1)
    e1.flush()  # memtable rows become parts (survive the "restart")
    e1.close()
    e2 = MeasureEngine(SchemaRegistry(tmp_path / "schema"), tmp_path / "data")
    st = e2.streamagg.stats()
    assert len(st["signatures"]) == 1 and st["rows"] == 2000
    on, off = _ab(e2, PARITY_REQS[0], monkeypatch)
    assert on == off
    e2.close()


def test_cluster_shard_subset_fold(tmp_path, monkeypatch):
    """query_partials folds ONLY the scatter's shard subset; the
    finalize over per-shard partials equals the rescan's."""
    from banyandb_tpu.query import measure_exec

    e = _engine(tmp_path, shard_num=3)
    _write(e, 0, 3000, seed=1)
    _register(e)
    m = e.registry.get_measure("g", "m")
    req = PARITY_REQS[0]

    def run():
        parts = [
            e.query_partials(req, shard_ids={s}) for s in range(3)
        ]
        return json.dumps(result_to_json(
            measure_exec.finalize_partials(m, req, parts)
        ), sort_keys=True)

    monkeypatch.setenv("BYDB_STREAMAGG", "1")
    on = run()
    monkeypatch.setenv("BYDB_STREAMAGG", "0")
    off = run()
    assert on == off
    e.close()


def test_partials_wire_round_trip(tmp_path, monkeypatch):
    """Folded partials survive the cluster wire codec (the liaison
    combine consumes exactly what serde reconstructs)."""
    from banyandb_tpu.cluster import serde
    from banyandb_tpu.query import measure_exec

    e = _engine(tmp_path)
    _write(e, 0, 2000, seed=1)
    _register(e)
    monkeypatch.setenv("BYDB_STREAMAGG", "1")
    req = PARITY_REQS[0]
    p = e.query_partials(req)
    m = e.registry.get_measure("g", "m")
    wire = serde.partials_from_json(
        json.loads(json.dumps(serde.partials_to_json(p)))
    )
    a = result_to_json(measure_exec.finalize_partials(m, req, [p]))
    b = result_to_json(measure_exec.finalize_partials(m, req, [wire]))
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)
    e.close()


def test_row_write_path_feeds_windows(eng, monkeypatch):
    """The per-point write() path (direct data-node writes) updates
    windows identically to the columnar path."""
    from banyandb_tpu.api.model import DataPointValue, WriteRequest

    _register(eng)
    pts = tuple(
        DataPointValue(
            ts_millis=T0 + i,
            tags={"svc": f"s{i % 4}", "region": f"r{i % 2}"},
            fields={"v": float(i % 7)},
            version=i + 1,
        )
        for i in range(2500)
    )
    eng.write(WriteRequest("g", "m", pts))
    assert eng.streamagg.stats()["rows"] == 2500
    on, off = _ab(eng, PARITY_REQS[0], monkeypatch)
    assert on == off


def test_coverage_lost_falls_back_not_undercounts(eng, monkeypatch):
    """A Cover planned before an eviction advanced the horizon must NOT
    fold (the evicted windows' rows would silently vanish): answer()
    returns None and the engine query falls back to the full rescan."""
    monkeypatch.setenv("BYDB_STREAMAGG", "1")
    _write(eng, 0, 1000, seed=1)
    _register(eng, key_tags=("svc",), max_windows=3)
    m = eng.registry.get_measure("g", "m")
    req = QueryRequest(
        groups=("g",), name="m", time_range=TimeRange(T0, T0 + 10_000),
        group_by=GroupBy(("svc",)), agg=Aggregation("count", "v"),
    )
    cover = eng.streamagg.plan_cover(m, req)
    assert cover is not None
    _write(eng, 1000, 5000, seed=2)  # evicts past the planned cov_lo
    sig = cover.sig
    assert sig.covered_from > cover.cov_lo  # the race happened
    assert eng.streamagg.answer(
        cover, rescan=lambda b, e: pytest.fail("rescan before fold"),
    ) is None
    # the full query path re-plans (fresh horizon) and stays exact
    on, off = _ab(eng, req, monkeypatch)
    assert on == off
    assert sum(
        json.loads(on)["values"]["count"]
    ) == 6000  # nothing lost to the stale cover


def test_backfilled_part_install_hook_is_noop(eng, monkeypatch):
    """A part consumed by the registration backfill whose install hook
    races past building=False must not apply twice (the data-node
    re-ship/registration interleaving)."""
    _write(eng, 0, 1500, seed=1)
    eng.flush()  # rows become a part the backfill will consume
    _register(eng, key_tags=("svc",))
    sig = next(iter(eng.streamagg._sigs.values()))
    assert sig.backfill_parts, "backfill recorded no part identities"
    part_id = next(iter(sig.backfill_parts))
    rows_before = eng.streamagg.stats()["rows"]
    # replay the install hook for a backfilled part: must be a no-op
    n = 100
    eng.streamagg.observe(
        "g", "m",
        ts=T0 + np.arange(n, dtype=np.int64),
        series=np.arange(n, dtype=np.int64),
        versions=np.arange(n, dtype=np.int64) + 1,
        shards=np.zeros(n, dtype=np.int64),
        tag_col=lambda t: np.full(n, b"s1", dtype=object),
        field_col=lambda f: np.ones(n, dtype=np.float64),
        part_id=part_id,
    )
    assert eng.streamagg.stats()["rows"] == rows_before
    on, off = _ab(eng, PARITY_REQS[0], monkeypatch)
    assert on == off


def test_equal_ts_tie_break_matches_rescan(eng, monkeypatch):
    """Groups whose first rows share one timestamp: the fold's arrival-
    order seq must reproduce the rescan's row-order tie-break for
    live-ingested (memtable) rows AND for backfilled rows (where the
    backfill applies in gather order).  A flush re-sorts part rows by
    (series, ts), so tie order after a flush is implementation-defined
    on BOTH paths — deliberately not asserted."""
    _register(eng, key_tags=("svc",))
    # one batch, REVERSE-sorted svc order, ts shared ACROSS groups
    # (ties between groups; (series, ts) keys stay unique)
    n = 6
    eng.write_columns(
        "g", "m",
        ts_millis=np.asarray([T0, T0, T0, T0 + 1, T0 + 1, T0 + 1]),
        tags={
            "svc": ["s9", "s5", "s1", "s9", "s5", "s1"],
            "region": ["r0"] * n,
        },
        fields={"v": np.arange(n, dtype=np.float64)},
        versions=np.arange(n, dtype=np.int64) + 1,
    )
    eng.write_columns(  # advance the watermark so T0's window closes
        "g", "m",
        ts_millis=T0 + 2000 + np.arange(4, dtype=np.int64),
        tags={"svc": ["s1"] * 4, "region": ["r0"] * 4},
        fields={"v": np.ones(4)},
        versions=np.arange(4, dtype=np.int64) + 100,
    )
    req = QueryRequest(
        groups=("g",), name="m", time_range=TimeRange(T0, T0 + 3000),
        group_by=GroupBy(("svc",)), agg=Aggregation("count", "v"),
    )
    on, off = _ab(eng, req, monkeypatch)
    assert on == off, f"live-ingest tie order diverged\n{on}\n{off}"
    # backfill path: a fresh engine over flushed parts applies rows in
    # gather order — the exact order the rescan reads
    eng.flush()


def test_equal_ts_tie_break_backfill_matches_rescan(tmp_path, monkeypatch):
    e = _engine(tmp_path)
    n = 6
    e.write_columns(
        "g", "m",
        ts_millis=np.asarray([T0, T0, T0, T0 + 1, T0 + 1, T0 + 1]),
        tags={
            "svc": ["s9", "s5", "s1", "s9", "s5", "s1"],
            "region": ["r0"] * n,
        },
        fields={"v": np.arange(n, dtype=np.float64)},
        versions=np.arange(n, dtype=np.int64) + 1,
    )
    e.flush()
    _register(e, key_tags=("svc",))  # backfill consumes the sorted part
    req = QueryRequest(
        groups=("g",), name="m", time_range=TimeRange(T0, T0 + 2000),
        group_by=GroupBy(("svc",)), agg=Aggregation("count", "v"),
    )
    on, off = _ab(e, req, monkeypatch)
    assert on == off, f"backfill tie order diverged\n{on}\n{off}"
    e.close()


def test_liaison_rebroadcasts_registration_on_rejoin(tmp_path):
    """A data node that was down at register time receives the
    signature at the next probe that sees it alive (its own persisted
    registry cannot cover what it never received)."""
    from banyandb_tpu.api.schema import Catalog, Group as _G, ResourceOpts
    from banyandb_tpu.cluster.data_node import DataNode
    from banyandb_tpu.cluster.liaison import Liaison
    from banyandb_tpu.cluster.node import NodeInfo
    from banyandb_tpu.cluster.rpc import LocalTransport

    transport = LocalTransport()
    dns, infos = [], []
    for i in range(2):
        reg = SchemaRegistry(tmp_path / f"n{i}" / "schema")
        _schema(reg)
        dn = DataNode(f"n{i}", reg, tmp_path / f"n{i}" / "data")
        dns.append(dn)
        infos.append(
            NodeInfo(f"n{i}", transport.register(f"n{i}", dn.bus))
        )
    lreg = SchemaRegistry(tmp_path / "l" / "schema")
    _schema(lreg)
    liaison = Liaison(lreg, transport, infos, replicas=0)
    transport.unregister("n1")  # n1 is down at registration time
    liaison.probe()
    acks = liaison.register_streamagg(
        "g", "m", key_tags=("svc",), fields=("v",), window_millis=1000
    )
    assert set(acks) == {"n0"}
    assert not dns[1].measure.streamagg.stats()["signatures"]
    # n1 rejoins: the next probe catches it up
    transport.register("n1", dns[1].bus)
    liaison.probe()
    st = dns[1].measure.streamagg.stats()
    assert len(st["signatures"]) == 1, st
    for dn in dns:
        dn.measure.close()
        dn.stream.close()
        dn.trace.close()


def test_served_classification():
    from banyandb_tpu.server import _served_class

    sa = {"name": "q", "children": [
        {"name": "streamagg", "tags": {"coverage": "partial"},
         "children": []},
    ]}
    lost = {"name": "q", "children": [
        {"name": "streamagg", "tags": {"coverage": "lost"},
         "children": []},
        {"name": "execute", "children": [
            {"name": "reduce", "tags": {"partials_cache": "miss"}},
        ]},
    ]}
    hit = {"name": "q", "children": [
        {"name": "execute", "children": [
            {"name": "reduce", "tags": {"partials_cache": "hit"}},
        ]},
    ]}
    miss = {"name": "q", "children": [
        {"name": "execute", "children": [
            {"name": "reduce", "tags": {"partials_cache": "miss"}},
        ]},
    ]}
    assert _served_class(sa) == "materialized"
    assert _served_class(lost) == "scan"  # fallback is NOT materialized
    assert _served_class(hit) == "replay"
    assert _served_class(miss) == "scan"
    assert _served_class({"name": "q", "children": []}) == "scan"


def test_ingest_update_path_is_host_only():
    """The kernel-budget hygiene pin (docs/linting.md): streamagg's
    ingest-side update path is the documented HOST-ONLY exemption — it
    must never import jax, so no device dispatch can creep into the
    write path through this module."""
    import banyandb_tpu.query.streamagg as mod

    src = open(mod.__file__).read()
    assert "import jax" not in src, (
        "streamagg grew a jax import: give it a ratcheted kernel-budget "
        "row (lint/kernel/kernel_budgets.py) instead of relying on the "
        "host-only exemption"
    )
