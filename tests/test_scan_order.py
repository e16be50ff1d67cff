"""Scan-order tracking runs only for a query whose answer reads it
(ISSUE 36, ROADMAP S4(a)).

``measure_exec.compute_partials`` turns ``PlanSpec.want_rep`` on for a
listing (grouped, no ``TOP``: groups emit, and ``LIMIT`` / ``OFFSET``
page, in first-appearance order) and for any plan that projects a tag
it does not group by (the representative row).  A ``TOP n`` that
projects no such tag reads neither, so its program holds no
``bydb.rep`` and brings back no ``rep_ts`` / ``rep_row``.

The data: 64 services whose sums of ``hits`` are distinct in the head
and tie at the cut of a ``TOP 10`` in both directions, rows in a
shuffled time order so that first appearance is neither code nor name
order, rows of region ``r0`` beside them that the predicate drops.  The
references are NumPy over the same rows and share nothing with the
program.
"""

import dataclasses
import json

import numpy as np
import pytest

from banyandb_tpu.api.model import (
    Aggregation,
    Condition,
    GroupBy,
    QueryRequest,
    TimeRange,
    Top,
)
from banyandb_tpu.api.schema import (
    Entity,
    FieldSpec,
    FieldType,
    Measure,
    TagSpec,
    TagType,
)
from banyandb_tpu.cluster import serde
from banyandb_tpu.obs import metrics as obs_metrics
from banyandb_tpu.obs.tracer import Tracer, iter_spans
from banyandb_tpu.query import measure_exec
from banyandb_tpu.query.measure_exec import compute_partials, finalize_partials
from banyandb_tpu.query.planner import PlanDecision
from banyandb_tpu.server import result_to_json
from banyandb_tpu.storage.cache import reset_global_cache
from banyandb_tpu.storage.part import ColumnData

T0 = 1_700_000_000_000
SVCS, ZONES = 64, 5
SCAN_CHUNK = 2048  # 3 real chunks in the 4-bucket, as `topn100k.topn-24h` runs
METHODS = ["scatter", "pallas", "sort"]
# what each row's `hits` is: an exact int ships as a narrow `fields_enc`
# column, a half as f32 `fields`; sums keep their ties either way
HITS = [pytest.param(1.0, id="int"), pytest.param(0.5, id="float")]

MEASURE = Measure(
    group="g",
    name="m",
    tags=(
        TagSpec("svc", TagType.STRING),
        TagSpec("region", TagType.STRING),
        TagSpec("zone", TagType.STRING),
    ),
    fields=(FieldSpec("hits", FieldType.INT),),
    entity=Entity(("svc",)),
)


def _sums() -> np.ndarray:
    """Sum of hits per service code: eight distinct at the top, six that
    tie at the cut of TOP 10; seven distinct at the bottom, five that tie
    at the cut of TOP 10 asc; the rest distinct in between."""
    sums = 40 + np.arange(SVCS, dtype=np.int64)
    sums[[3, 17, 29, 31, 44, 58, 60, 9]] = [200, 199, 198, 197, 196, 195, 194, 193]
    sums[[10, 50, 20, 41, 33, 63]] = 150
    sums[[5, 12, 27, 36, 48, 55, 62]] = [11, 12, 13, 14, 15, 16, 17]
    sums[[1, 22, 39, 46, 57]] = 30
    return sums


def _rows():
    """-> dict of per-row columns, in stored order (ts shuffled)."""
    rng = np.random.default_rng(36)
    svc = np.repeat(np.arange(SVCS), _sums())  # hits = 1 a row: sum = count
    dropped = rng.integers(0, SVCS, 500)  # region r0: the predicate drops them
    svc = np.concatenate([svc, dropped])
    n = len(svc)
    region = np.concatenate(
        [rng.integers(1, 4, n - len(dropped)), np.zeros(len(dropped), np.int64)]
    )
    order = rng.permutation(n)
    svc, region = svc[order], region[order]
    return {
        "ts": T0 + rng.permutation(n).astype(np.int64),
        "svc": svc.astype(np.int32),
        "region": region.astype(np.int32),
        "zone": rng.integers(0, ZONES, n).astype(np.int32),
        "hits": np.ones(n, dtype=np.float64),
    }


ROWS = _rows()
N = len(ROWS["ts"])
# names in another order than the codes: a tie resolves by name
SVC_NAMES = [b"s%04d" % i for i in np.random.default_rng(7).permutation(SVCS)]
REGION_NAMES = [b"r%d" % i for i in range(4)]
ZONE_NAMES = [b"z%d" % i for i in range(ZONES)]


def _source(idx: np.ndarray, cache_key=None, hits=1.0) -> ColumnData:
    return ColumnData(
        ts=ROWS["ts"][idx],
        series=ROWS["svc"][idx].astype(np.int64),
        version=np.ones(len(idx), dtype=np.int64),
        tags={t: ROWS[t][idx] for t in ("svc", "region", "zone")},
        fields={"hits": ROWS["hits"][idx] * hits},
        dicts={"svc": SVC_NAMES, "region": REGION_NAMES, "zone": ZONE_NAMES},
        cache_key=cache_key,
    )


def _request(**kw) -> QueryRequest:
    defaults = dict(
        groups=("g",),
        name="m",
        time_range=TimeRange(T0, T0 + N),
        criteria=Condition("region", "ne", "r0"),
        group_by=GroupBy(("svc",)),
        agg=Aggregation("sum", "hits"),
    )
    defaults.update(kw)
    return QueryRequest(**defaults)


def _ask(req, srcs, monkeypatch, method=None, dict_state=None):
    """-> (partials, {span name: its tags}, QueryResult) of `req` over
    `srcs` in several chunks."""
    monkeypatch.setattr(measure_exec, "SCAN_CHUNK", SCAN_CHUNK)
    hints = PlanDecision(group_method=method) if method else None
    tr = Tracer("t")
    with tr.span("q") as sp:
        p = compute_partials(
            MEASURE, req, srcs, dict_state=dict_state, span=sp, plan_hints=hints
        )
        res = finalize_partials(MEASURE, req, [p], dict_state=dict_state)
    spans = {s["name"]: s.get("tags") or {} for s in iter_spans(tr.finish())}
    return p, spans, res


def _answer(req, srcs, monkeypatch, method=None):
    """-> (partials, reduce span tags, result JSON text)."""
    p, spans, res = _ask(req, srcs, monkeypatch, method)
    return p, spans["reduce"], json.dumps(result_to_json(res), sort_keys=True)


def _force_tracking(monkeypatch) -> list:
    """Answer with tracking on in the spec whatever the request reads, as
    every grouped query ran until ISSUE 36 -> the specs as
    ``compute_partials`` built them."""
    real = measure_exec._reduce_partials
    built = []

    def tracked(measure, chunks_np, conds, expr, pred_vals, spec, *args, **kw):
        built.append(spec)
        spec = dataclasses.replace(spec, want_rep=True)
        # the epoch a tracking plan's ts is shipped relative to: computed
        # only where the request's own plan tracks
        args = list(args)
        args[7] = int(chunks_np["ts"].min())
        return real(measure, chunks_np, conds, expr, pred_vals, spec, *args, **kw)

    monkeypatch.setattr(measure_exec, "_reduce_partials", tracked)
    return built


def _first_seen(desc: bool = False) -> dict:
    """{svc code: its first row} of the rows the predicate keeps, in the
    ts-asc scan (the last under ORDER BY time DESC): a NumPy reference."""
    keep = np.nonzero(ROWS["region"] != 0)[0]
    ts = ROWS["ts"][keep]
    keep = keep[np.argsort(-ts if desc else ts, kind="stable")]
    first = {}
    for row in keep:
        first.setdefault(int(ROWS["svc"][row]), int(row))
    return first


def _counted(mode: str) -> float:
    text = obs_metrics.global_meter().prometheus_text()
    return sum(
        float(line.split()[-1]) for line in text.splitlines()
        if line.startswith("banyandb_plans_scan_order") and f'mode="{mode}"' in line
    )


# -- (1) a TOP n answer does not read the key ---------------------------------


@pytest.mark.parametrize("hits", HITS)
@pytest.mark.parametrize("sort", ["desc", "asc"])
@pytest.mark.parametrize("method", METHODS)
def test_topn_answer_is_the_tracked_plans_byte_for_byte(method, sort, hits, monkeypatch):
    """Groups, order and values of a TOP 10 (ties at the cut on both
    sides) are those of the same query answered with tracking forced on
    in the spec, for each group-by method and with the field shipped as
    a narrow int and as f32; the plan that does not track fetches 8 B a
    group a chunk, the tracked one 16, and its batches ship neither ts
    nor row."""
    req = _request(top=Top(10, "hits", sort))
    srcs = [_source(np.arange(N), hits=hits)]
    skipped = _counted("skipped")
    p, spans, res = _ask(req, srcs, monkeypatch, method)
    tags, answer = spans["reduce"], json.dumps(result_to_json(res), sort_keys=True)
    assert _counted("skipped") - skipped == 1
    assert tags["group_method"] == method and (tags["chunks"], tags["chunks_skipped"]) == (3, 1)
    assert tags["scan_order_tracked"] == 0 and p.rep_key is None
    assert tags["partials_bytes"] == 4 * SVCS * 8
    # beside the tag and field columns, the 4-bucket's valid mask alone
    slots = 4 * SCAN_CHUNK
    assert spans["decode"]["packed_bytes"] - spans["decode"]["shipped_bytes"] == slots
    built = _force_tracking(monkeypatch)
    tracked = _counted("tracked")
    p_on, spans_on, res_on = _ask(req, srcs, monkeypatch, method)
    tags_on = spans_on["reduce"]
    answer_on = json.dumps(result_to_json(res_on), sort_keys=True)
    assert [s.want_rep for s in built] == [False]
    assert _counted("tracked") - tracked == 1
    assert tags_on["scan_order_tracked"] == 1 and p_on.rep_key is not None
    assert tags_on["partials_bytes"] == 4 * SVCS * 16
    # and its ts and row, 4 B a slot each
    dec_on = spans_on["decode"]
    assert dec_on["packed_bytes"] - dec_on["shipped_bytes"] == 9 * slots
    assert answer == answer_on
    for a, b in ((p.count, p_on.count), (p.codes, p_on.codes), (p.sums["hits"], p_on.sums["hits"])):
        assert a.tobytes() == b.tobytes()
    # and it is the reference's: the ten (the cut's ties by name), in order
    got = json.loads(answer)
    sums = _sums()
    ranked = sorted(range(SVCS), key=lambda s: (sums[s] if sort == "asc" else -sums[s]))
    cut = sums[ranked[9]]
    head = [s for s in ranked[:10] if sums[s] != cut]
    tied = sorted((s for s in range(SVCS) if sums[s] == cut), key=lambda s: SVC_NAMES[s])
    want = head + tied[: 10 - len(head)]
    assert 0 < len(head) < 10 < len(head) + len(tied)
    assert got["groups"] == [[SVC_NAMES[s].decode()] for s in want]
    assert got["values"]["sum(hits)"] == [float(sums[s] * hits) for s in want]


def test_order_by_time_desc_is_the_same_topn_program(monkeypatch):
    """ORDER BY time DESC means nothing to a plan that does not track:
    one spec, so one compiled program and one partials-cache entry; a
    listing still splits in two."""
    built = _force_tracking(monkeypatch)
    srcs = [_source(np.arange(N))]
    for order in ("", "desc"):
        _answer(_request(top=Top(10, "hits"), order_by_ts=order), srcs, monkeypatch)
        _answer(_request(limit=SVCS, order_by_ts=order), srcs, monkeypatch)
    top_asc, list_asc, top_desc, list_desc = built
    assert top_asc == top_desc and (top_asc.want_rep, top_asc.rep_desc) == (False, False)
    assert (list_asc.want_rep, list_asc.rep_desc) == (True, False)
    assert (list_desc.want_rep, list_desc.rep_desc) == (True, True)


# -- (2) what reads the key keeps it ------------------------------------------


@pytest.mark.parametrize("order", ["", "desc"])
def test_topn_that_projects_a_tag_keeps_its_representative_row(order, monkeypatch):
    """A TOP n that projects a tag it does not group by still tracks,
    and each group carries the tag of its first scanned row."""
    req = _request(top=Top(10, "hits"), tag_projection=("svc", "zone"), order_by_ts=order)
    srcs = [_source(np.arange(N))]
    p, spans, res = _ask(req, srcs, monkeypatch)
    assert spans["reduce"]["scan_order_tracked"] == 1 and p.rep_key is not None
    assert len(res.groups) == 10 and set(res.rep_tags) == {"zone"}
    first = _first_seen(desc=order == "desc")
    code_of = {name.decode(): s for s, name in enumerate(SVC_NAMES)}
    want = [ZONE_NAMES[ROWS["zone"][first[code_of[g]]]].decode() for (g,) in res.groups]
    assert res.rep_tags["zone"] == want
    # the ranking is the plain Top-N's
    _, _, plain = _ask(_request(top=Top(10, "hits"), order_by_ts=order), srcs, monkeypatch)
    assert plain.groups == res.groups and not plain.rep_tags


@pytest.mark.parametrize("hits", HITS)
@pytest.mark.parametrize("order", ["", "desc"])
@pytest.mark.parametrize("method", METHODS)
def test_listing_pages_in_first_appearance_order(method, order, hits, monkeypatch):
    """A listing tracks: LIMIT / OFFSET page through the groups in the
    order their first row appears in the scan, asc and ORDER BY time
    DESC, with the field shipped as a narrow int and as f32."""
    first = _first_seen(desc=order == "desc")
    ts = ROWS["ts"]
    by_first = sorted(first, key=lambda s: -ts[first[s]] if order == "desc" else ts[first[s]])
    want = [[SVC_NAMES[s].decode()] for s in by_first]
    assert len(want) == SVCS and want != sorted(want)
    srcs = [_source(np.arange(N), hits=hits)]
    pages = []
    for offset, limit in ((0, 10), (10, 30), (40, SVCS)):
        req = _request(limit=limit, offset=offset, order_by_ts=order)
        p, tags, answer = _answer(req, srcs, monkeypatch, method)
        assert tags["scan_order_tracked"] == 1 and tags["group_method"] == method
        assert tags["partials_bytes"] == 4 * SVCS * 16
        pages += json.loads(answer)["groups"]
    assert pages == want


def test_ungrouped_aggregate_with_a_projected_tag_keeps_its_row(monkeypatch):
    """No GROUP BY: tracked only when a tag is projected, and the one
    output row then carries the first scanned row's."""
    srcs = [_source(np.arange(N))]
    p, tags, _ = _answer(_request(group_by=None), srcs, monkeypatch)
    assert tags["scan_order_tracked"] == 0 and p.rep_key is None
    _, spans, res = _ask(_request(group_by=None, tag_projection=("zone",)), srcs, monkeypatch)
    assert spans["reduce"]["scan_order_tracked"] == 1
    keep = np.nonzero(ROWS["region"] != 0)[0]
    row = keep[np.argmin(ROWS["ts"][keep])]
    assert res.rep_tags == {"zone": [ZONE_NAMES[ROWS["zone"][row]].decode()]}


# -- (4) one gather, two partials-cache entries -------------------------------


def test_topn_and_listing_over_one_gather_do_not_share_partials(monkeypatch):
    """The partials-cache key holds the spec: a Top-N's entry, which has
    no scan-order key, is never served to a listing over the same
    gather (nor the other way round), and each is served its own."""
    reset_global_cache()
    state = measure_exec.DictState()
    half = N // 2
    srcs = [
        _source(np.arange(half), cache_key=("scan-order", 0)),
        _source(np.arange(half, N), cache_key=("scan-order", 1)),
    ]
    top, listing = _request(top=Top(10, "hits")), _request(limit=SVCS)

    def ask(req):
        p, spans, res = _ask(req, srcs, monkeypatch, dict_state=state)
        return p, spans, result_to_json(res)["groups"]

    try:
        p_top, spans, top_groups = ask(top)
        assert spans["reduce"]["partials_cache"] == "miss" and p_top.rep_key is None
        p_list, spans, list_groups = ask(listing)
        assert spans["gather"]["serving_cache"] == "hit"  # one gather ...
        assert spans["reduce"]["partials_cache"] == "miss"  # ... two reductions
        assert spans["reduce"]["scan_order_tracked"] == 1 and p_list.rep_key is not None
        first = _first_seen()
        by_first = sorted(first, key=lambda s: ROWS["ts"][first[s]])
        assert list_groups == [[SVC_NAMES[s].decode()] for s in by_first]
        # each is served its own entry, and the answers stay
        for req, p_first, groups in ((top, p_top, top_groups), (listing, p_list, list_groups)):
            p_again, spans, again = ask(req)
            assert spans["reduce"]["partials_cache"] == "hit"
            assert p_again is p_first and again == groups
    finally:
        reset_global_cache()


# -- (5) the liaison's half ----------------------------------------------------


@pytest.mark.parametrize("sort", ["desc", "asc"])
def test_two_nodes_keyless_partials_combine_to_the_standalone_topn(sort, monkeypatch):
    """Each data node answers the Top-N request without a scan-order key,
    serde leaves the key out, and the liaison's combine + finalize over
    the two gives the standalone answer."""
    req = serde.query_request_from_json(
        serde.query_request_to_json(_request(top=Top(10, "hits", sort)))
    )
    assert req.top == Top(10, "hits", sort)
    nodes = [_source(np.arange(0, N, 2)), _source(np.arange(1, N, 2))]
    _, _, standalone = _answer(req, nodes, monkeypatch)
    wired = []
    for src in nodes:
        p, tags, _ = _answer(req, [src], monkeypatch)
        assert tags["scan_order_tracked"] == 0 and p.rep_key is None
        env = json.loads(json.dumps(serde.partials_to_json(p)))
        assert "rep_key" not in env and "rep_vals" not in env
        wired.append(serde.partials_from_json(env))
    assert all(p.rep_key is None and p.rep_vals is None for p in wired)
    combined = measure_exec.combine_partials(wired)
    assert combined.rep_key is None and len(combined.groups) == SVCS
    res = finalize_partials(MEASURE, req, wired)
    assert json.dumps(result_to_json(res), sort_keys=True) == standalone
    assert len(res.groups) == 10
