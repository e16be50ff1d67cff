"""Fused whole-plan executor (ISSUE 8, ISSUE 30): one XLA program per
plan signature (query/fused_exec), the only executor of a measure plan.

Covers:
- byte-parity of a scan run as ONE dispatch against the same scan run in
  chunk batches (the over-budget route, forced by
  ``BYDB_FUSED_MAX_MB=0``: batches of one chunk) — partials array bytes
  AND finalized result JSON — across EVERY builtin plan signature,
  single- and multi-chunk part-batches, incl. a high-radix plan that
  selects the segment-sort group-by; single-chunk scans (where the two
  sides are one program) are also held to a plain NumPy reduction;
- hash- vs sort-based group-by selection pinned per builtin signature
  (ops.groupby.select_group_method) and the sort method's bitwise
  equality with the hash/scatter path;
- mid-stream decode-error propagation on both routes;
- ``plan_batches``: the budget decides the batching, batches never share
  a device-cache entry;
- fused-signature precompile-registry round-trip, store persistence,
  warming into the fused kernel cache, and a store written before every
  resolution recorded a ``fused`` row;
- the mesh fused dist step (chunked collective program) agreeing with
  the legacy single-width step.
"""

import dataclasses
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
    Entity,
    FieldSpec,
    FieldType,
    Measure,
    TagSpec,
    TagType,
)
from banyandb_tpu.query import fused_exec, measure_exec
from banyandb_tpu.query.measure_exec import compute_partials, finalize_partials
from banyandb_tpu.query.planner import PlanDecision
from banyandb_tpu.storage.part import ColumnData
from tests._golden_infra import numpy_exec

T0 = 1_700_000_000_000


def _int_bytes(i: int) -> bytes:
    return i.to_bytes(8, "little", signed=True)


def _source(n: int, step: int, tags: dict, fields: dict) -> ColumnData:
    return ColumnData(
        ts=T0 + np.arange(n, dtype=np.int64) * step,
        series=np.arange(n, dtype=np.int64) % 64,
        version=np.ones(n, dtype=np.int64),
        tags={t: codes for t, (_v, codes) in tags.items()},
        fields=dict(fields),
        dicts={t: vals for t, (vals, _c) in tags.items()},
    )


def _measure(tags, fields) -> Measure:
    return Measure(
        group="g",
        name="m",
        tags=tuple(TagSpec(n, t) for n, t in tags),
        fields=tuple(FieldSpec(n, t) for n, t in fields),
        entity=Entity((tags[0][0],)),
    )


def _scenarios():
    """(name, measure, request, sources): the builtin plan population,
    mirroring lint/kernel/dispatch.py's scenario synthesis."""
    rng = np.random.default_rng(7)

    def svc_dict(k):
        return [b"s%04d" % i for i in range(k)]

    out = []

    n = 8192
    m = _measure([("svc", TagType.STRING)], [("v", FieldType.INT)])
    src = _source(
        n,
        1,
        {"svc": (svc_dict(4), rng.integers(0, 4, n).astype(np.int32))},
        {"v": rng.integers(0, 100, n).astype(np.float64)},
    )
    out.append(
        (
            "flat-count",
            m,
            QueryRequest(
                ("g",), "m", TimeRange(T0, T0 + n), field_projection=("v",)
            ),
            [src],
        )
    )

    m = _measure(
        [("svc", TagType.STRING), ("region", TagType.INT)],
        [("v", FieldType.INT)],
    )
    src = _source(
        n,
        1,
        {
            "svc": (svc_dict(8), rng.integers(0, 8, n).astype(np.int32)),
            "region": (
                [_int_bytes(i) for i in range(4)],
                rng.integers(0, 4, n).astype(np.int32),
            ),
        },
        {"v": rng.integers(0, 100, n).astype(np.float64)},
    )
    out.append(
        (
            "group-eq-lut",
            m,
            QueryRequest(
                ("g",),
                "m",
                TimeRange(T0, T0 + n),
                criteria=LogicalExpression(
                    "and",
                    Condition("svc", "eq", "s0003"),
                    Condition("region", "le", 2),
                ),
                group_by=GroupBy(("svc", "region")),
                field_projection=("v",),
                agg=Aggregation("mean", "v"),
            ),
            [src],
        )
    )

    n_pct, step = 65536, 32769
    m = _measure([("svc", TagType.STRING)], [("lat", FieldType.FLOAT)])
    src = _source(
        n_pct,
        step,
        {"svc": (svc_dict(16), rng.integers(0, 16, n_pct).astype(np.int32))},
        {"lat": rng.random(n_pct).astype(np.float64) * 100},
    )
    out.append(
        (
            "percentile-hist",
            m,
            QueryRequest(
                ("g",),
                "m",
                TimeRange(T0, T0 + n_pct * step + 1),
                group_by=GroupBy(("svc",)),
                agg=Aggregation("percentile", "lat", quantiles=(0.5, 0.99)),
            ),
            [src],
        )
    )

    m = _measure([("svc", TagType.STRING)], [("v", FieldType.INT)])
    src = _source(
        n,
        1,
        {"svc": (svc_dict(8), rng.integers(0, 8, n).astype(np.int32))},
        {"v": rng.integers(0, 100, n).astype(np.float64)},
    )
    out.append(
        (
            "or-expr",
            m,
            QueryRequest(
                ("g",),
                "m",
                TimeRange(T0, T0 + n),
                criteria=LogicalExpression(
                    "or",
                    Condition(
                        "svc", "in", ("s0000", "s0001", "s0002", "s0003")
                    ),
                    Condition("svc", "eq", "s0000"),
                ),
                agg=Aggregation("sum", "v"),
            ),
            [src],
        )
    )

    n_top = 65536
    m = _measure(
        [("svc", TagType.STRING), ("region", TagType.STRING)],
        [("value", FieldType.INT)],
    )
    src = _source(
        n_top,
        1,
        {
            "svc": (
                svc_dict(1024),
                rng.integers(0, 1024, n_top).astype(np.int32),
            ),
            "region": (
                [b"r%d" % i for i in range(8)],
                rng.integers(0, 8, n_top).astype(np.int32),
            ),
        },
        {"value": rng.integers(0, 100, n_top).astype(np.float64)},
    )
    out.append(
        (
            "topn-dashboard",
            m,
            QueryRequest(
                ("g",),
                "m",
                TimeRange(T0, T0 + n_top),
                criteria=Condition("region", "ne", "r0"),
                group_by=GroupBy(("svc",)),
                top=Top(10, "value"),
            ),
            [src],
        )
    )
    return out


def _partial_bytes(p) -> bytes:
    return p.content_bytes()  # the shared parity oracle (Partials)


def _result_json(m, req, partial) -> str:
    from banyandb_tpu.server import result_to_json

    res = finalize_partials(m, req, [partial])
    return json.dumps(result_to_json(res), sort_keys=True)


# the two routes of one scan, as the device budget in MB that selects
# them: the default (the whole scan in one dispatch), or nothing
# (batches of one chunk)
ONE_BATCH, CHUNK_BATCHES = None, 0
SIDES = [
    pytest.param(ONE_BATCH, id="one-batch"),
    pytest.param(CHUNK_BATCHES, id="chunk-batches"),
]


def _run(m, req, srcs, max_mb, monkeypatch, **kw):
    from banyandb_tpu.obs.tracer import Tracer

    if max_mb is None:
        monkeypatch.delenv("BYDB_FUSED_MAX_MB", raising=False)
    else:
        monkeypatch.setenv("BYDB_FUSED_MAX_MB", str(max_mb))
    tr = Tracer("t")
    with tr.span("q") as sp:
        p = compute_partials(m, req, srcs, span=sp, **kw)
    tags = _reduce_tags(tr.finish())
    return p, tags


def _reduce_tags(tree: dict):
    if tree.get("name") == "reduce":
        return tree["tags"]
    for c in tree.get("children", ()):
        hit = _reduce_tags(c)
        if hit is not None:
            return hit
    return None


def _tag_values(src, tag):
    """Decoded values of one tag column (INT tags as ints)."""
    vals = [
        v.decode() if len(v) != 8 else int.from_bytes(v, "little", signed=True)
        for v in src.dicts[tag]
    ]
    return np.asarray(vals, dtype=object)[src.tags[tag]]


def _numpy_mask(c, src) -> np.ndarray:
    if c is None:
        return np.ones(len(src.ts), dtype=bool)
    if isinstance(c, LogicalExpression):
        left, right = _numpy_mask(c.left, src), _numpy_mask(c.right, src)
        return left & right if c.op == "and" else left | right
    col = _tag_values(src, c.name)
    if c.op == "in":
        return np.isin(col, list(c.value))
    return {
        "eq": lambda: col == c.value,
        "ne": lambda: col != c.value,
        "le": lambda: col <= c.value,
    }[c.op]()


def _assert_matches_numpy(m, req, srcs, p):
    """Hold one single-source partial to the plain NumPy reduction of
    the same rows: groups and counts exact, INT sums rtol 1e-5, min/max
    to f32, a percentile within one histogram bucket (chip_smoke.py's
    oracle holds a served answer to the same)."""
    (src,) = srcs
    group_tags = tuple(req.group_by.tag_names) if req.group_by else ()
    in_range = (src.ts >= req.time_range.begin_millis) & (
        src.ts < req.time_range.end_millis
    )
    mask = in_range & _numpy_mask(req.criteria, src)
    (field,) = p.sums
    want = numpy_exec(
        mask,
        [src.dicts[t] and np.asarray(src.dicts[t], dtype=object)[src.tags[t]]
         for t in group_tags],
        src.fields[field],
    )
    want = {k: v for k, v in want.items() if len(v) or not group_tags}
    got = {g: i for i, g in enumerate(p.groups)}
    assert set(got) == set(want)
    for g, vals in want.items():
        i = got[g]
        assert p.count[i] == len(vals)
        np.testing.assert_allclose(p.sums[field][i], vals.sum(), rtol=1e-5)
        if len(vals) and np.isfinite(p.mins[field][i]):
            assert p.mins[field][i] == np.float32(vals.min())
            assert p.maxs[field][i] == np.float32(vals.max())
    if req.agg is not None and req.agg.function == "percentile":
        res = finalize_partials(m, req, [p])
        bucket = p.hist_span / measure_exec._NUM_HIST_BUCKETS
        (name,) = [k for k in res.values if k != "count"]
        for g, estimates in zip(res.groups, res.values[name]):
            vals = np.sort(want[tuple(v.encode() for v in g)])
            for q, est in zip(req.agg.quantiles, estimates):
                rank = min(max(int(np.ceil(q * len(vals))), 1), len(vals))
                assert abs(est - vals[rank - 1]) <= bucket * 1.001


@pytest.mark.parametrize(
    "name", [s[0] for s in _scenarios()]
)
def test_parity_all_builtin_signatures(name, monkeypatch):
    """Byte-identical partials + result JSON, one dispatch vs chunk
    batches, for every builtin plan signature; each is one chunk, so the
    two sides run one program and the NumPy reduction is the judge."""
    m, req, srcs = next(
        (m, r, s) for n, m, r, s in _scenarios() if n == name
    )
    p_one, t_one = _run(m, req, srcs, ONE_BATCH, monkeypatch)
    p_bat, t_bat = _run(m, req, srcs, CHUNK_BATCHES, monkeypatch)
    assert t_one["path"] == "fused" and t_bat["path"] == "fused"
    assert t_one["chunks"] == 1
    assert t_one["dispatches"] == 1 and t_bat["dispatches"] == 1
    assert _partial_bytes(p_one) == _partial_bytes(p_bat)
    assert _result_json(m, req, p_one) == _result_json(m, req, p_bat)
    _assert_matches_numpy(m, req, srcs, p_one)


@pytest.mark.parametrize("name", [s[0] for s in _scenarios()])
@pytest.mark.parametrize("max_mb", SIDES)
def test_device_decode_parity_all_builtin_signatures(name, max_mb, monkeypatch):
    """``BYDB_DEVICE_DECODE=1`` (compressed ship + in-kernel decode,
    ISSUE 9) is byte-identical to ``=0`` on partials bytes AND result
    JSON for every builtin plan signature, on both routes, and equal to
    the NumPy reduction."""
    m, req, srcs = next(
        (m, r, s) for n, m, r, s in _scenarios() if n == name
    )
    monkeypatch.setenv("BYDB_DEVICE_DECODE", "0")
    p_dense, _ = _run(m, req, srcs, max_mb, monkeypatch)
    monkeypatch.setenv("BYDB_DEVICE_DECODE", "1")
    p_dec, t_dec = _run(m, req, srcs, max_mb, monkeypatch)
    assert t_dec["dispatches"] == 1  # decode fused into the one program
    assert _partial_bytes(p_dense) == _partial_bytes(p_dec)
    assert _result_json(m, req, p_dense) == _result_json(m, req, p_dec)
    _assert_matches_numpy(m, req, srcs, p_dec)


def test_device_decode_multichunk_parity(monkeypatch):
    """Compressed ship over a multi-chunk part-batch: still one fused
    dispatch, byte-identical to the dense multi-chunk run."""
    monkeypatch.setattr(measure_exec, "SCAN_CHUNK", 2048)
    name, m, req, srcs = _scenarios()[1]
    monkeypatch.setenv("BYDB_DEVICE_DECODE", "0")
    p_dense, _ = _run(m, req, srcs, ONE_BATCH, monkeypatch)
    monkeypatch.setenv("BYDB_DEVICE_DECODE", "1")
    p_dec, t_dec = _run(m, req, srcs, ONE_BATCH, monkeypatch)
    assert t_dec["chunks"] == 4 and t_dec["dispatches"] == 1
    assert _partial_bytes(p_dense) == _partial_bytes(p_dec)


def test_multichunk_parity_one_dispatch(monkeypatch):
    """A part-batch spanning several scan chunks fuses into ONE dispatch;
    over the budget it is one dispatch a chunk, byte-identical."""
    monkeypatch.setattr(measure_exec, "SCAN_CHUNK", 2048)
    name, m, req, srcs = _scenarios()[1]  # grouped eq+lut, n=8192
    p_one, t_one = _run(m, req, srcs, ONE_BATCH, monkeypatch)
    p_bat, t_bat = _run(m, req, srcs, CHUNK_BATCHES, monkeypatch)
    assert t_one["path"] == "fused" and t_bat["path"] == "fused"
    assert t_one["chunks"] == 4 and t_one["dispatches"] == 1
    assert t_bat["chunks"] == 4 and t_bat["dispatches"] == 4
    assert _partial_bytes(p_one) == _partial_bytes(p_bat)
    assert _result_json(m, req, p_one) == _result_json(m, req, p_bat)
    _assert_matches_numpy(m, req, srcs, p_one)


@pytest.mark.parametrize("name", [s[0] for s in _scenarios()])
def test_multichunk_batches_parity_all_builtin_signatures(name, monkeypatch):
    """Every builtin signature over several chunks: the over-budget
    route gives byte-identical partials and result JSON, one dispatch a
    chunk."""
    monkeypatch.setattr(measure_exec, "SCAN_CHUNK", 2048)
    m, req, srcs = next(
        (m, r, s) for n, m, r, s in _scenarios() if n == name
    )
    p_one, t_one = _run(m, req, srcs, ONE_BATCH, monkeypatch)
    p_bat, t_bat = _run(m, req, srcs, CHUNK_BATCHES, monkeypatch)
    assert t_one["chunks"] > 1 and t_one["dispatches"] == 1
    assert t_bat["dispatches"] == t_bat["chunks"] == t_one["chunks"]
    assert _partial_bytes(p_one) == _partial_bytes(p_bat)
    assert _result_json(m, req, p_one) == _result_json(m, req, p_bat)


@pytest.mark.parametrize("scan_chunk", [None, 2048], ids=["one-chunk", "multi-chunk"])
@pytest.mark.parametrize("max_mb", SIDES)
def test_percentile_ranks_parity(scan_chunk, max_mb, monkeypatch):
    """A percentile partial finalized alone holds the device's ranks of
    its quantiles, not the histogram: byte-identical (ranks included)
    whatever the batching, no histogram byte fetched, and the result
    JSON is the one the combinable partial's histogram gives."""
    if scan_chunk is not None:
        monkeypatch.setattr(measure_exec, "SCAN_CHUNK", scan_chunk)
    m, req, srcs = next(
        (m, r, s) for n, m, r, s in _scenarios() if n == "percentile-hist"
    )
    p_one, _ = _run(m, req, srcs, ONE_BATCH, monkeypatch, final=True)
    p_fin, t_fin = _run(m, req, srcs, max_mb, monkeypatch, final=True)
    p_hist, t_hist = _run(m, req, srcs, max_mb, monkeypatch)
    assert p_fin.hist is None and p_fin.ranks.dtype == np.int32
    assert p_fin.ranks.shape == (len(p_fin.count), 2, 3)
    assert p_fin.ranks_q == req.agg.quantiles
    assert p_hist.ranks is None and p_hist.hist.shape == (len(p_hist.count), 512)
    assert _partial_bytes(p_fin) == _partial_bytes(p_one)
    assert _partial_bytes(p_fin) != _partial_bytes(p_hist)
    assert t_fin["hist_fetched_bytes"] == 0
    assert t_hist["hist_fetched_bytes"] == t_hist["hist_device_bytes"] == 16 * 512 * 4
    assert t_fin["dispatches"] == t_hist["dispatches"]
    assert _result_json(m, req, p_fin) == _result_json(m, req, p_hist)
    _assert_matches_numpy(m, req, srcs, p_fin)


def _three_chunk_scan():
    rng = np.random.default_rng(3)
    n = 3 * 2048
    m = _measure([("svc", TagType.STRING)], [("v", FieldType.INT)])
    src = _source(
        n,
        1,
        {"svc": ([b"a", b"b"], rng.integers(0, 2, n).astype(np.int32))},
        {"v": rng.integers(0, 100, n).astype(np.float64)},
    )
    req = QueryRequest(
        ("g",),
        "m",
        TimeRange(T0, T0 + n),
        group_by=GroupBy(("svc",)),
        agg=Aggregation("sum", "v"),
    )
    return m, req, [src]


def test_nonbucket_chunk_count_parity(monkeypatch):
    """3 real chunks ride a 4-chunk bucket: the padded all-invalid chunk
    must not perturb results."""
    monkeypatch.setattr(measure_exec, "SCAN_CHUNK", 2048)
    m, req, srcs = _three_chunk_scan()
    p_one, t_one = _run(m, req, srcs, ONE_BATCH, monkeypatch)
    p_bat, t_bat = _run(m, req, srcs, CHUNK_BATCHES, monkeypatch)
    assert t_one["chunks"] == 3 and t_one["dispatches"] == 1
    assert t_bat["chunks"] == 3 and t_bat["dispatches"] == 3
    assert _partial_bytes(p_one) == _partial_bytes(p_bat)
    _assert_matches_numpy(m, req, srcs, p_one)


# -- padding chunks are skipped on the device (ISSUE 31) ----------------------


def _padded_scan(name):
    """-> (m, req, srcs, kw, chunks, skipped): a scan at 2,048 rows a
    chunk whose bucket holds more chunks than the scan has."""
    if name == "3in4":  # 3 real chunks ride the 4-bucket
        return (*_three_chunk_scan(), {}, 3, 1)
    _, m, req, srcs = _scenarios()[1]  # grouped eq+lut, n=8192
    if name == "4in8":  # the planner's hint rounds the 4-bucket up
        return m, req, srcs, {"plan_hints": PlanDecision(chunk_bucket=8)}, 4, 4
    _, m, req, srcs = _scenarios()[0]
    return m, req, srcs, {}, 4, 0  # "4in4": nothing to skip


PADDED = ["3in4", "4in8", "4in4"]


@pytest.mark.parametrize("name", PADDED)
def test_scan_body_runs_once_per_real_chunk(name, monkeypatch):
    """The per-chunk body (decode + filter + group + aggregate) runs for
    the chunks that hold a row and for no other: the padding of the
    bucket is branched past on the device, not computed and masked."""
    import jax

    calls = []
    real_body = fused_exec._kernel_body

    def counting_body(spec):
        body = real_body(spec)

        def counted(chunk, pred_vals, hist_lo, hist_span, hist=None):
            jax.debug.callback(lambda: calls.append(1))
            return body(chunk, pred_vals, hist_lo, hist_span, hist)

        return counted

    monkeypatch.setattr(fused_exec, "_kernel_body", counting_body)
    monkeypatch.setattr(fused_exec, "_KERNEL_CACHE", {})
    monkeypatch.setattr(measure_exec, "SCAN_CHUNK", 2048)
    m, req, srcs, kw, chunks, skipped = _padded_scan(name)
    _, tags = _run(m, req, srcs, ONE_BATCH, monkeypatch, **kw)
    jax.effects_barrier()
    assert tags["dispatches"] == 1 and tags["chunks"] == chunks
    (fspec,) = fused_exec._KERNEL_CACHE
    assert fspec.num_chunks == chunks + skipped
    assert len(calls) == chunks


@pytest.mark.parametrize("name", PADDED)
def test_reduce_span_and_counter_count_skipped_chunks(name, monkeypatch):
    """Span ``reduce`` carries ``chunks_skipped`` beside ``chunks`` and
    ``fused_chunks{kind}`` counts the same chunks."""
    from banyandb_tpu.obs import metrics as obs_metrics

    def counted(kind):
        return obs_metrics.global_meter().snapshot()["counters"].get(
            ("fused_chunks", (("kind", kind),)), 0.0
        )

    monkeypatch.setattr(measure_exec, "SCAN_CHUNK", 2048)
    m, req, srcs, kw, chunks, skipped = _padded_scan(name)
    before = counted("run"), counted("skipped")
    _, tags = _run(m, req, srcs, ONE_BATCH, monkeypatch, **kw)
    assert (tags["chunks"], tags["chunks_skipped"]) == (chunks, skipped)
    assert counted("run") - before[0] == chunks
    assert counted("skipped") - before[1] == skipped
    # over the budget the hint is dropped and every batch is one real chunk
    _, tags = _run(m, req, srcs, CHUNK_BATCHES, monkeypatch, **kw)
    assert (tags["chunks"], tags["chunks_skipped"]) == (chunks, 0)


def _spoil_padding(monkeypatch):
    """Have ``_stacked_chunks`` fill every per-row column of the padding
    chunks with garbage (``valid`` stays False there) -> the number of
    chunks spoiled, one entry a batch."""
    import jax.numpy as jnp

    real_stacked = fused_exec._stacked_chunks
    spoiled = []

    def stacked(cols, spans, spec, num_chunks, *args, **kwargs):
        rng = np.random.default_rng(99)

        def spoil(arr):
            arr = np.array(arr)
            pad = arr[len(spans):]
            if arr.dtype.kind == "f":
                pad[...] = rng.normal(0, 1e6, pad.shape)
            else:
                info = np.iinfo(arr.dtype)
                pad[...] = rng.integers(
                    info.min, info.max, pad.shape, dtype=arr.dtype
                )
            return jnp.asarray(arr)

        out = real_stacked(cols, spans, spec, num_chunks, *args, **kwargs)
        spoiled.append(num_chunks - len(spans))
        for key, leaf in out.items():
            if key in ("valid", "tags_lut"):  # the predicate; per batch
                continue
            out[key] = (
                {k: spoil(v) for k, v in leaf.items()}
                if isinstance(leaf, dict)
                else spoil(leaf)
            )
        return out

    monkeypatch.setattr(fused_exec, "_stacked_chunks", stacked)
    return spoiled


@pytest.mark.parametrize("decode", ["0", "1"], ids=["dense", "compressed"])
@pytest.mark.parametrize(
    "plan", ["matmul", "scatter", "sort", "pallas", "percentile"]
)
def test_garbage_in_padding_chunks_changes_nothing(plan, decode, monkeypatch):
    """4 real chunks in the 8-bucket whose 4 padding chunks hold garbage
    in every column: partials byte-identical to the same scan run as
    exact one-chunk batches, for each group-by method and a percentile
    plan, in both ship forms."""
    monkeypatch.setenv("BYDB_DEVICE_DECODE", decode)
    if plan == "percentile":
        monkeypatch.setattr(measure_exec, "SCAN_CHUNK", 16384)
        _, m, req, srcs = _scenarios()[2]
        hints = PlanDecision(chunk_bucket=8)
    else:
        monkeypatch.setattr(measure_exec, "SCAN_CHUNK", 2048)
        _, m, req, srcs = _scenarios()[1]
        hints = PlanDecision(chunk_bucket=8, group_method=plan)
    p_exact, t_exact = _run(
        m, req, srcs, CHUNK_BATCHES, monkeypatch, plan_hints=hints
    )
    assert t_exact["dispatches"] == 4 and t_exact["chunks_skipped"] == 0
    spoiled = _spoil_padding(monkeypatch)
    p_pad, t_pad = _run(m, req, srcs, ONE_BATCH, monkeypatch, plan_hints=hints)
    assert spoiled == [4] and t_pad["chunks_skipped"] == 4
    assert t_pad["dispatches"] == 1
    if plan != "percentile":
        assert t_pad["group_method"] == t_exact["group_method"] == plan
    assert _partial_bytes(p_pad) == _partial_bytes(p_exact)
    assert _result_json(m, req, p_pad) == _result_json(m, req, p_exact)


# -- group-by strategy selection ---------------------------------------------


def test_group_method_selection_pinned_per_signature():
    """The hash-vs-sort crossover is a deterministic function of the
    plan signature: pinned per builtin (CPU backend) + the high-radix
    sort regime."""
    from banyandb_tpu.ops.groupby import (
        SORT_GROUPS_THRESHOLD,
        select_group_method,
    )
    from banyandb_tpu.query import precompile

    want = {
        "measure/flat-count": "matmul",
        "measure/group-eq-lut": "matmul",
        "measure/percentile-hist": "matmul",
        "measure/or-expr": "matmul",
        "measure/topn-dashboard": "scatter",
    }
    got = {
        name: select_group_method(spec.nrows, max(spec.num_groups, 1))
        for name, spec in precompile.builtin_plans()
    }
    assert got == want, got
    # high-radix / unknown-cardinality keys: segment-sort grouping
    assert select_group_method(65536, SORT_GROUPS_THRESHOLD + 1) == "sort"
    assert select_group_method(65536, SORT_GROUPS_THRESHOLD) != "sort"


def test_sort_method_bitwise_matches_scatter():
    from banyandb_tpu import ops

    rng = np.random.default_rng(11)
    n, g = 8192, 300
    key = rng.integers(0, g, n).astype(np.int32)
    valid = rng.random(n) < 0.9
    fields = {"v": (rng.random(n) * 1e3).astype(np.float32)}
    import jax.numpy as jnp

    a = ops.group_reduce(
        jnp.asarray(key), jnp.asarray(valid), {"v": jnp.asarray(fields["v"])},
        g, method="scatter",
    )
    b = ops.group_reduce(
        jnp.asarray(key), jnp.asarray(valid), {"v": jnp.asarray(fields["v"])},
        g, method="sort",
    )
    for x, y in (
        (a.count, b.count),
        (a.sums["v"], b.sums["v"]),
        (a.mins["v"], b.mins["v"]),
        (a.maxs["v"], b.maxs["v"]),
    ):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_high_radix_sort_plan_parity(monkeypatch):
    """A plan whose group cardinality crosses SORT_GROUPS_THRESHOLD
    resolves the sort strategy on both routes, byte-identical, and
    agrees with the NumPy reduction."""
    from banyandb_tpu.ops.groupby import SORT_GROUPS_THRESHOLD

    rng = np.random.default_rng(13)
    n = 4096
    k = SORT_GROUPS_THRESHOLD + 8
    m = _measure([("svc", TagType.STRING)], [("v", FieldType.INT)])
    src = _source(
        n,
        1,
        {
            "svc": (
                [b"s%06d" % i for i in range(k)],
                rng.integers(0, k, n).astype(np.int32),
            )
        },
        {"v": rng.integers(0, 100, n).astype(np.float64)},
    )
    req = QueryRequest(
        ("g",),
        "m",
        TimeRange(T0, T0 + n),
        group_by=GroupBy(("svc",)),
        agg=Aggregation("sum", "v"),
        limit=32,
    )
    p_one, t_one = _run(m, req, [src], ONE_BATCH, monkeypatch)
    p_bat, _ = _run(m, req, [src], CHUNK_BATCHES, monkeypatch)
    assert t_one["group_method"] == "sort"
    assert _partial_bytes(p_one) == _partial_bytes(p_bat)
    assert _result_json(m, req, p_one) == _result_json(m, req, p_bat)
    _assert_matches_numpy(m, req, [src], p_one)


# -- the device budget ---------------------------------------------------------------


def test_over_budget_runs_in_chunk_batches(monkeypatch):
    """A scan whose stacked footprint passes ``BYDB_FUSED_MAX_MB`` runs
    the same program in batches of the largest power-of-two chunk count
    that fits: here 4 chunks of ~2 MB against 4 MB = 2 dispatches of 2."""
    monkeypatch.setattr(measure_exec, "SCAN_CHUNK", 65536)
    rng = np.random.default_rng(23)
    n = 4 * 65536
    m = _measure([("svc", TagType.STRING)], [("v", FieldType.INT)])
    src = _source(
        n,
        1,
        {"svc": ([b"a", b"b", b"c"], rng.integers(0, 3, n).astype(np.int32))},
        {"v": rng.integers(0, 100, n).astype(np.float64)},
    )
    req = QueryRequest(
        ("g",),
        "m",
        TimeRange(T0, T0 + n),
        group_by=GroupBy(("svc",)),
        agg=Aggregation("sum", "v"),
    )
    p_one, t_one = _run(m, req, [src], ONE_BATCH, monkeypatch)
    assert t_one["chunks"] == 4 and t_one["dispatches"] == 1
    p_two, t_two = _run(m, req, [src], 4, monkeypatch)
    assert t_two["path"] == "fused"
    assert t_two["chunks"] == 4 and t_two["dispatches"] == 2
    assert _partial_bytes(p_one) == _partial_bytes(p_two)
    assert _result_json(m, req, p_one) == _result_json(m, req, p_two)
    _assert_matches_numpy(m, req, [src], p_two)


def test_plan_batches_follow_the_budget(monkeypatch):
    """One function decides the batching, from the footprint estimate
    and the budget alone."""
    spec = measure_exec.PlanSpec(
        tags_code=(),
        fields=("v",),
        preds=(),
        group_tags=(),
        radices=(),
        num_groups=1,
        want_minmax=True,
        nrows=1 << 20,
    )
    spans = [(k << 20, (k + 1) << 20) for k in range(5)]
    # footprint estimate grows with the chunk bucket
    per_chunk = fused_exec.estimate_bytes(spec, 1)
    assert fused_exec.estimate_bytes(spec, 8) == 8 * per_chunk
    # the mask and one field: no key column but `valid` (key_columns)
    assert 8 << 20 < per_chunk < 16 << 20

    assert fused_exec.plan_batches(spec, []) == (1, [])
    # under the budget: one batch, today's bucket rule and hint
    assert fused_exec.plan_batches(spec, spans) == (8, [spans])
    assert fused_exec.plan_batches(spec, spans[:3], min_bucket=8) == (
        8,
        [spans[:3]],
    )
    # over it: the largest power of two that fits, a short last batch
    monkeypatch.setenv("BYDB_FUSED_MAX_MB", "32")
    assert fused_exec.plan_batches(spec, spans) == (
        2,
        [spans[0:2], spans[2:4], spans[4:5]],
    )
    # the hint is dropped where the rounded-up bucket would not fit
    assert fused_exec.plan_batches(spec, spans[:2], min_bucket=4) == (
        2,
        [spans[:2]],
    )
    # a single chunk over the budget still runs
    monkeypatch.setenv("BYDB_FUSED_MAX_MB", "0")
    assert fused_exec.plan_batches(spec, spans) == (
        1,
        [[sp] for sp in spans],
    )


def test_batches_do_not_share_a_device_cache_entry(monkeypatch):
    """Two batches of one bucket are two device-cache entries (the key
    carries the batch's row span): a second run reads every batch's own
    rows back from the cache."""
    from banyandb_tpu.storage import cache

    monkeypatch.setattr(measure_exec, "SCAN_CHUNK", 2048)
    m, req, srcs = _three_chunk_scan()
    srcs = [dataclasses.replace(srcs[0], cache_key=("part", "p1"))]
    cache.reset_global_cache()
    dicts = measure_exec.DictState()  # one gather identity for both runs
    try:
        p_one, _ = _run(m, req, srcs, ONE_BATCH, monkeypatch)
        p_bat, t_bat = _run(
            m, req, srcs, CHUNK_BATCHES, monkeypatch, dict_state=dicts
        )
        assert t_bat["dispatches"] == 3 and t_bat["device_cache"] == "built"
        dev = cache.device_cache()
        keys = [
            k for k in (*dev._unproven, *dev._proven) if k[0] == "fused_chunks"
        ]
        assert len(keys) == 3 and len({k[2:4] for k in keys}) == 3
        # the reduce again (its partials dropped), inputs from the cache
        cache.global_cache().clear()
        p_again, t_again = _run(
            m, req, srcs, CHUNK_BATCHES, monkeypatch, dict_state=dicts
        )
    finally:
        cache.reset_global_cache()
    assert _partial_bytes(p_bat) == _partial_bytes(p_one)
    assert t_again["dispatches"] == 3 and t_again["device_cache"] == "hit"
    assert _partial_bytes(p_again) == _partial_bytes(p_one)


# -- a batch holds the key columns its program reads ---------------------------


# plan -> (scenario, the request's changes, the key columns its batch holds)
KEY_PLANS = {
    # TOP 10 that projects no tag: no scan order, so neither ts nor row
    "topn": ("topn-dashboard", {}, {"valid"}),
    # grouped, no TOP: groups emit in first-appearance order
    "listing": ("topn-dashboard", {"top": None, "agg": Aggregation("mean", "value")},
                {"ts", "valid", "row"}),
    # no GROUP BY: the key is zeros of the mask's shape
    "ungrouped": ("or-expr", {}, {"valid"}),
}
NON_KEY = {"tags_code", "tags_enc", "tags_lut", "src_ord", "fields", "fields_enc"}


def _capture_batches(monkeypatch) -> list:
    """Record every stacked batch -> the list of them."""
    real_stacked = fused_exec._stacked_chunks
    seen = []

    def stacked(*args, **kwargs):
        out = real_stacked(*args, **kwargs)
        seen.append(out)
        return out

    monkeypatch.setattr(fused_exec, "_stacked_chunks", stacked)
    return seen


@pytest.mark.parametrize("decode", ["0", "1"], ids=["dense", "compressed"])
@pytest.mark.parametrize("plan", sorted(KEY_PLANS))
def test_batch_holds_the_key_columns_its_program_reads(plan, decode, monkeypatch):
    """A batch pads and ships `valid`, and `ts` and `row` only for a plan
    that tracks scan order, beside its tag and field columns; never the
    series id.  The answer is byte-identical to the one over a batch
    that ships all three, and is the NumPy reduction's."""
    monkeypatch.setenv("BYDB_DEVICE_DECODE", decode)
    monkeypatch.setattr(measure_exec, "SCAN_CHUNK", 16384)
    scenario, changes, want = KEY_PLANS[plan]
    m, req, srcs = next((m, r, s) for n, m, r, s in _scenarios() if n == scenario)
    req = dataclasses.replace(req, **changes)
    seen = _capture_batches(monkeypatch)
    p, tags = _run(m, req, srcs, ONE_BATCH, monkeypatch)
    assert tags["scan_order_tracked"] == int("ts" in want)
    assert [set(b) - NON_KEY for b in seen] == [want]
    assert {"tags_code", "fields"} <= set(seen[0])
    monkeypatch.setattr(fused_exec, "key_columns", lambda spec: ("ts", "valid", "row"))
    p_all, _ = _run(m, req, srcs, ONE_BATCH, monkeypatch)
    assert [set(b) - NON_KEY for b in seen[1:]] == [{"ts", "valid", "row"}]
    assert _partial_bytes(p) == _partial_bytes(p_all)
    assert _result_json(m, req, p) == _result_json(m, req, p_all)
    _assert_matches_numpy(m, req, srcs, p)


@pytest.mark.parametrize("plan", sorted(KEY_PLANS))
def test_decode_span_and_counter_count_the_key_columns(plan, monkeypatch):
    """The `decode` span's `packed_bytes` is every array padded and
    shipped: `shipped_bytes` (tag and field columns) and the key columns,
    1 B a slot for `valid` and 4 each for `ts` and `row`;
    `fused_key_columns{column, mode}` counts each key column once a plan."""
    import jax

    from banyandb_tpu.obs import metrics as obs_metrics
    from banyandb_tpu.obs.tracer import Tracer, iter_spans

    def counted():
        snap = obs_metrics.global_meter().snapshot()["counters"]
        return {
            (c, mode): snap.get(
                ("fused_key_columns", (("column", c), ("mode", mode))), 0.0
            )
            for c in ("ts", "series", "row")
            for mode in ("shipped", "skipped")
        }

    monkeypatch.setenv("BYDB_DEVICE_DECODE", "0")
    monkeypatch.setattr(measure_exec, "SCAN_CHUNK", 16384)
    scenario, changes, want = KEY_PLANS[plan]
    m, req, srcs = next((m, r, s) for n, m, r, s in _scenarios() if n == scenario)
    req = dataclasses.replace(req, **changes)
    seen = _capture_batches(monkeypatch)
    before = counted()
    tr = Tracer("t")
    with tr.span("q") as sp:
        compute_partials(m, req, srcs, span=sp)
    (dec,) = [s["tags"] for s in iter_spans(tr.finish()) if s["name"] == "decode"]
    (batch,) = seen
    assert dec["packed_bytes"] == sum(
        a.nbytes for a in jax.tree_util.tree_leaves(batch)
    )
    slots = batch["valid"].size
    assert dec["packed_bytes"] - dec["shipped_bytes"] == slots * (
        1 + (8 if "ts" in want else 0)
    )
    delta = {k: v - before[k] for k, v in counted().items()}
    assert delta == {
        (c, mode): float((mode == "shipped") == (c in want))
        for c in ("ts", "series", "row")
        for mode in ("shipped", "skipped")
    }


def test_topn_then_listing_over_one_cached_gather(monkeypatch):
    """The device cache keys a batch by the key columns it holds: a
    no-rep Top-N's batch, which has no `ts` or `row`, is not served to a
    listing over the same gather; the listing builds its own, and a
    second listing is served that one."""
    from banyandb_tpu.storage import cache

    monkeypatch.setattr(measure_exec, "SCAN_CHUNK", 16384)
    _, m, top, srcs = next(s for s in _scenarios() if s[0] == "topn-dashboard")
    listing = dataclasses.replace(top, top=None, agg=Aggregation("mean", "value"))
    srcs = [dataclasses.replace(srcs[0], cache_key=("part", "keys"))]
    seen = _capture_batches(monkeypatch)
    cache.reset_global_cache()
    dicts = measure_exec.DictState()
    try:
        _, t_top = _run(m, top, srcs, ONE_BATCH, monkeypatch, dict_state=dicts)
        p_list, t_list = _run(m, listing, srcs, ONE_BATCH, monkeypatch, dict_state=dicts)
        cache.global_cache().clear()  # the partials: reduce again
        p_again, t_again = _run(m, listing, srcs, ONE_BATCH, monkeypatch, dict_state=dicts)
    finally:
        cache.reset_global_cache()
    assert (t_top["device_cache"], t_list["device_cache"]) == ("built", "built")
    assert t_again["device_cache"] == "hit"
    assert [set(b) - NON_KEY for b in seen] == [{"valid"}, {"ts", "valid", "row"}]
    assert t_list["scan_order_tracked"] == 1 and p_list.rep_key is not None
    fresh, _ = _run(m, listing, srcs, ONE_BATCH, monkeypatch)
    assert _partial_bytes(p_list) == _partial_bytes(fresh) == _partial_bytes(p_again)
    assert _result_json(m, listing, p_list) == _result_json(m, listing, fresh)


def test_chunk_count_bucket_powers_of_two():
    assert [fused_exec.chunk_count_bucket(c) for c in (1, 2, 3, 5, 8, 9)] == [
        1,
        2,
        4,
        8,
        8,
        16,
    ]


# -- mid-stream decode-error propagation -------------------------------------


class _ExplodingCol(np.ndarray):
    """Raises once a chunk past the first is sliced — the mid-stream
    decode failure shape (a later part's block failing to decode)."""

    def __getitem__(self, item):
        if isinstance(item, slice) and (item.start or 0) >= 2048:
            raise ValueError("decode failed mid-stream")
        return super().__getitem__(item)


@pytest.mark.parametrize("max_mb", SIDES)
def test_midstream_decode_error_propagates_identically(max_mb, monkeypatch):
    monkeypatch.setattr(measure_exec, "SCAN_CHUNK", 2048)
    rng = np.random.default_rng(5)
    n = 8192
    m = _measure([("svc", TagType.STRING)], [("v", FieldType.INT)])
    src = _source(
        n,
        1,
        {"svc": ([b"a", b"b"], rng.integers(0, 2, n).astype(np.int32))},
        {"v": rng.integers(0, 100, n).astype(np.float64)},
    )
    req = QueryRequest(
        ("g",), "m", TimeRange(T0, T0 + n), field_projection=("v",)
    )

    real_gather = measure_exec._gather_rows

    def exploding_gather(*args, **kwargs):
        cols = real_gather(*args, **kwargs)
        cols["fields"] = {
            f: a.view(_ExplodingCol) for f, a in cols["fields"].items()
        }
        return cols

    monkeypatch.setattr(measure_exec, "_gather_rows", exploding_gather)
    if max_mb is not None:
        monkeypatch.setenv("BYDB_FUSED_MAX_MB", str(max_mb))
    with pytest.raises(ValueError, match="decode failed mid-stream"):
        compute_partials(m, req, [src])


# -- precompile registry -----------------------------------------------------


def test_fused_signature_recorded_and_persisted(monkeypatch, tmp_path):
    from banyandb_tpu.query import precompile

    monkeypatch.setenv("BYDB_PRECOMPILE", "1")
    r = precompile.PrecompileRegistry()
    monkeypatch.setattr(precompile, "_registry", r)
    name, m, req, srcs = _scenarios()[0]
    _run(m, req, srcs, ONE_BATCH, monkeypatch)
    fused_sigs = [s for kind, s in r.signatures() if kind == "fused"]
    assert len(fused_sigs) == 1
    assert isinstance(fused_sigs[0], fused_exec.FusedSpec)
    assert fused_sigs[0].num_chunks == 1

    # JSON round-trip (incl. the nested PlanSpec) + store persistence
    doc = precompile.spec_to_json("fused", fused_sigs[0])
    kind2, spec2 = precompile.spec_from_json(json.loads(json.dumps(doc)))
    assert kind2 == "fused" and spec2 == fused_sigs[0]
    assert hash(spec2) == hash(fused_sigs[0])
    store_path = tmp_path / "plan-registry.json"
    r.attach_store(store_path)
    r2 = precompile.PrecompileRegistry()
    r2.attach_store(store_path)
    assert ("fused", fused_sigs[0]) in set(r2.signatures())


def test_registry_warm_compiles_fused_kernel(monkeypatch):
    from banyandb_tpu.query import precompile

    monkeypatch.setenv("BYDB_PRECOMPILE", "1")
    monkeypatch.setattr(fused_exec, "_KERNEL_CACHE", {})
    r = precompile.PrecompileRegistry()
    fspec = precompile.builtin_fused()[0][1]
    assert r.warm(sigs=[("fused", fspec)]) == 1 and r.errors == 0
    assert fspec in fused_exec._KERNEL_CACHE


def test_registry_loads_parent_measure_rows(monkeypatch, tmp_path):
    """A ``plan-registry.json`` written before ISSUE 30 holds ``kind:
    "measure"`` rows: they load, stay the autoreg's evidence, and warm as
    their plan's one-chunk program only where no ``fused`` row of the
    plan is stored."""
    from banyandb_tpu.query import precompile

    monkeypatch.setenv("BYDB_PRECOMPILE", "1")
    monkeypatch.setattr(fused_exec, "_KERNEL_CACHE", {})
    plans = dict(precompile.builtin_plans())
    alone = plans["measure/flat-count"]
    paired = plans["measure/or-expr"]
    paired_fused = fused_exec.FusedSpec(plan=paired, num_chunks=2)
    store = tmp_path / "plan-registry.json"
    store.write_text(json.dumps({"signatures": [
        {**precompile.spec_to_json("measure", alone), "count": 7,
         "last_hit_ms": 1, "context": ["g", "m"]},
        {**precompile.spec_to_json("measure", paired), "count": 5,
         "last_hit_ms": 1, "context": ["g", "m"]},
        {**precompile.spec_to_json("fused", paired_fused), "count": 5,
         "last_hit_ms": 1},
    ]}))
    r = precompile.PrecompileRegistry()
    r.attach_store(store)
    assert [(k, s, c) for k, s, _n, c in r.evidence() if k == "measure"] == [
        ("measure", alone, ("g", "m")),
        ("measure", paired, ("g", "m")),
    ]
    assert r.warm(include_builtin=False) == 2 and r.errors == 0
    assert set(fused_exec._KERNEL_CACHE) == {
        fused_exec.FusedSpec(plan=alone, num_chunks=1),
        paired_fused,
    }


def test_builtin_fused_mirror_builtin_plans():
    from banyandb_tpu.query import precompile

    plans = dict(precompile.builtin_plans())
    fused = dict(precompile.builtin_fused())
    assert {n.replace("fused/", "measure/") for n in fused} == set(plans)
    for name, fspec in fused.items():
        assert fspec.num_chunks == 1
        assert fspec.plan == plans[name.replace("fused/", "measure/")]


# -- mesh fused dist step ----------------------------------------------------


def test_fused_dist_step_matches_legacy_step():
    """The chunked collective program agrees with the legacy
    single-width mesh step on the same packed rows (count/min/max exact,
    sums within f32 reassociation tolerance)."""
    import jax

    from banyandb_tpu.parallel import dist_exec
    from banyandb_tpu.parallel import mesh as pmesh

    rng = np.random.default_rng(17)
    plan = dist_exec.DistPlan(
        tags_code=("svc",),
        fields=("v",),
        group_tags=("svc",),
        radices=(16,),
        num_groups=16,
        topn=4,
    )
    mesh = pmesh.make_mesh(1)
    n = 4096
    rows = [
        {
            "tags": {"svc": rng.integers(0, 16, n).astype(np.int32)},
            "fields": {"v": rng.random(n).astype(np.float32) * 100},
        }
    ]
    chunks = dist_exec.stack_shard_chunks(mesh, rows, ("svc",), ("v",), n)
    legacy = jax.device_get(
        dist_exec.distributed_aggregate(mesh, plan, chunks)
    )
    fused = jax.device_get(
        fused_exec.fused_distributed_aggregate(mesh, plan, 4, chunks)
    )
    assert np.array_equal(legacy["count"], fused["count"])
    assert np.array_equal(legacy["mins"]["v"], fused["mins"]["v"])
    assert np.array_equal(legacy["maxs"]["v"], fused["maxs"]["v"])
    np.testing.assert_allclose(
        legacy["sums"]["v"], fused["sums"]["v"], rtol=1e-6
    )
    assert set(np.asarray(legacy["top_idx"]).tolist()) == set(
        np.asarray(fused["top_idx"]).tolist()
    )


def test_fused_dist_single_chunk_bitwise():
    """num_chunks=1 reduces to the legacy step exactly (Kahan from zero
    is the identity)."""
    import jax

    from banyandb_tpu.parallel import dist_exec
    from banyandb_tpu.parallel import mesh as pmesh

    rng = np.random.default_rng(19)
    plan = dist_exec.DistPlan(
        tags_code=("svc",),
        fields=("v",),
        group_tags=("svc",),
        radices=(8,),
        num_groups=8,
    )
    mesh = pmesh.make_mesh(1)
    n = 2048
    rows = [
        {
            "tags": {"svc": rng.integers(0, 8, n).astype(np.int32)},
            "fields": {"v": rng.random(n).astype(np.float32)},
        }
    ]
    chunks = dist_exec.stack_shard_chunks(mesh, rows, ("svc",), ("v",), n)
    legacy = jax.device_get(
        dist_exec.distributed_aggregate(mesh, plan, chunks)
    )
    fused = jax.device_get(
        fused_exec.fused_distributed_aggregate(mesh, plan, 1, chunks)
    )
    for k in ("count",):
        assert np.array_equal(legacy[k], fused[k])
    for k in ("sums", "mins", "maxs"):
        assert np.array_equal(legacy[k]["v"], fused[k]["v"])
