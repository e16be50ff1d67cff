"""Device-side decode + zone-map block skipping (ISSUE 9, ROADMAP item 3).

Covers:
- the ops/decode kernels that were previously entirely uncalled:
  delta_decode (empty/single-row/boundary deltas), dod_decode,
  dict_gather's OOB clip guard, dict_remap, widen_codes, ints_to_f32,
  decode_chunk pass-through vs compressed decode;
- narrow width decisions: encode/decode_dict_codes_narrow at the
  i8/i16/i32 downcast boundaries, storage/encoded.narrow_int_dtype
  edges (non-integral, NaN, +-2^7/2^15 boundaries);
- ``BYDB_DEVICE_DECODE`` A/B byte-parity (partials bytes + result JSON)
  over multi-source gathers with mixed dictionary widths, absent tag
  columns (schema evolution) and part-backed sources, in one dispatch
  and in chunk batches;
- zone maps: written at flush AND merge, select_blocks skipping with
  identical results, the ``blocks_skipped_total{reason=zone}`` counter,
  whole-part exclusion, OR criteria disabling pruning;
- back-compat: a pre-upgrade fixture part (zone maps stripped) loads,
  scans without skipping, and `cli.py dump measure` reports the
  zone-map presence either way.
"""

import json
import os

import numpy as np
import pytest

from banyandb_tpu.api.model import (
    Aggregation,
    Condition,
    GroupBy,
    LogicalExpression,
    QueryRequest,
    TimeRange,
)
from banyandb_tpu.api.schema import (
    Entity,
    FieldSpec,
    FieldType,
    Measure,
    TagSpec,
    TagType,
)
from banyandb_tpu.query.measure_exec import (
    _host_tag_codes,
    compute_partials,
    finalize_partials,
)
from banyandb_tpu.storage import encoded
from banyandb_tpu.storage.part import ColumnData, Part, PartWriter
from banyandb_tpu.utils import compress as zst
from banyandb_tpu.utils import encoding as enc

T0 = 1_700_000_000_000


# -- ops/decode kernels ------------------------------------------------------


def test_delta_decode_roundtrips_encoder():
    import jax.numpy as jnp

    from banyandb_tpu import ops

    vals = np.array([5, 7, 7, 100, -3, 2**31 - 1], dtype=np.int64)
    blob = enc.encode_int64(vals)
    assert blob[0] == 1  # delta mode
    deltas = np.diff(vals)
    out = np.asarray(ops.delta_decode(int(vals[0]), jnp.asarray(deltas, jnp.int32)))
    assert np.array_equal(out, vals.astype(np.int32))


def test_delta_decode_single_row_no_deltas():
    """A 1-row block stores no deltas: decode of an empty delta payload
    is just [first]."""
    import jax.numpy as jnp

    from banyandb_tpu import ops

    out = np.asarray(ops.delta_decode(42, jnp.zeros((0,), jnp.int32)))
    assert out.tolist() == [42]


def test_delta_decode_downcast_boundary_values():
    """Deltas at the i8/i16 signed boundaries survive the downcast and
    the device cumsum exactly (the i8->i32 widen boundary class)."""
    import jax.numpy as jnp

    from banyandb_tpu import ops

    for lo, hi in ((-128, 127), (-32768, 32767)):
        vals = np.cumsum(
            np.array([0, hi, lo, hi, lo, hi], dtype=np.int64)
        ) + 1000
        blob = enc.encode_int64(vals)
        host = enc.decode_int64(blob, len(vals))
        assert np.array_equal(host, vals)
        dev = np.asarray(
            ops.delta_decode(
                int(vals[0]), jnp.asarray(np.diff(vals), jnp.int32)
            )
        )
        assert np.array_equal(dev, vals.astype(np.int32))


def test_delta_decode_rejects_unrebased_i64_first():
    """An absolute-timestamp `first` cannot ride the i32 decode width:
    explicit error instead of silent mod-2^32 wrap."""
    import jax.numpy as jnp

    from banyandb_tpu import ops

    with pytest.raises(ValueError, match="rebase"):
        ops.delta_decode(T0, jnp.ones(7, jnp.int8))


def test_dod_decode_matches_reference_shape():
    import jax.numpy as jnp

    from banyandb_tpu import ops

    # series with linear trend: dods are zero after the first delta
    vals = np.arange(10, dtype=np.int64) * 7 + 3
    deltas = np.diff(vals)
    dods = np.diff(deltas, prepend=deltas[0]) - 0  # dods[0]=0 convention
    dods[0] = 0
    out = np.asarray(
        ops.dod_decode(int(vals[0]), int(deltas[0]), jnp.asarray(dods, jnp.int32))
    )
    assert np.array_equal(out, vals.astype(np.int32))


def test_dict_gather_oob_guard_clips():
    import jax.numpy as jnp

    from banyandb_tpu import ops

    d = jnp.asarray([10, 20, 30], jnp.int32)
    codes = jnp.asarray([0, 2, 7, -4], jnp.int32)  # 7/-4 are corrupt
    out = np.asarray(ops.dict_gather(d, codes))
    assert out.tolist() == [10, 30, 30, 10]  # clipped, never wrapped


def test_dict_remap_multi_source():
    import jax.numpy as jnp

    from banyandb_tpu import ops

    lut2d = jnp.asarray(encoded.pack_luts([[5, 6], [7, 8, 9]]))
    codes = jnp.asarray(np.array([0, 1, 0, 2, 1], np.int8))
    src = jnp.asarray(np.array([0, 0, 1, 1, 1], np.int16))
    out = np.asarray(ops.dict_remap(codes, lut2d, src))
    assert out.tolist() == [5, 6, 7, 9, 8]
    assert out.dtype == np.int32


def test_widen_and_f32_convert_exact():
    import jax.numpy as jnp

    from banyandb_tpu import ops

    narrow = jnp.asarray(np.array([-128, 127, 0], np.int8))
    assert np.asarray(ops.widen_codes(narrow)).dtype == np.int32
    ints = jnp.asarray(np.array([-32768, 32767, -1], np.int16))
    f = np.asarray(ops.ints_to_f32(ints))
    assert f.dtype == np.float32
    assert np.array_equal(f, np.array([-32768.0, 32767.0, -1.0], np.float32))


def test_decode_chunk_passthrough_and_compressed():
    import jax.numpy as jnp

    from banyandb_tpu import ops

    plain = {"valid": jnp.ones(4, bool), "tags_code": {}, "fields": {}}
    assert ops.decode_chunk(plain) is plain  # canonical chunks untouched
    chunk = {
        "valid": jnp.ones(4, bool),
        "tags_enc": {"svc": jnp.asarray(np.array([0, 1, 0, 1], np.int8))},
        "tags_lut": {"svc": jnp.asarray(encoded.pack_luts([[3, 4]]))},
        "src_ord": jnp.zeros(4, jnp.int16),
        "fields": {},
        "fields_enc": {"v": jnp.asarray(np.array([1, -2, 3, 4], np.int16))},
    }
    out = ops.decode_chunk(chunk)
    assert "tags_enc" not in out and "src_ord" not in out
    assert np.asarray(out["tags_code"]["svc"]).tolist() == [3, 4, 3, 4]
    assert np.asarray(out["fields"]["v"]).dtype == np.float32
    assert np.asarray(out["fields"]["v"]).tolist() == [1.0, -2.0, 3.0, 4.0]


# -- narrow widths -----------------------------------------------------------


def test_dict_codes_narrow_width_boundaries():
    for hi, dtype in ((127, np.int8), (128, np.int16), (32768, np.int32)):
        codes = np.array([0, hi], dtype=np.int64)
        blob = enc.encode_dict_codes(codes)
        narrow = enc.decode_dict_codes_narrow(blob, 2)
        assert narrow.dtype == dtype, (hi, narrow.dtype)
        assert np.array_equal(narrow.astype(np.int64), codes)
        # the widened form is unchanged
        assert np.array_equal(
            enc.decode_dict_codes(blob, 2), codes.astype(np.int32)
        )


def test_code_dtype_from_dict_len():
    assert encoded.code_dtype(1) == np.int8
    assert encoded.code_dtype(128) == np.int8
    assert encoded.code_dtype(129) == np.int16
    assert encoded.code_dtype(1 << 15) == np.int16
    assert encoded.code_dtype((1 << 15) + 1) == np.int32


def test_narrow_int_dtype_edges():
    nd = encoded.narrow_int_dtype
    assert nd(np.zeros(0)) == np.int8  # empty ships at minimum width
    assert nd(np.array([-128.0, 127.0])) == np.int8
    assert nd(np.array([128.0])) == np.int16
    assert nd(np.array([-32768.0, 32767.0])) == np.int16
    assert nd(np.array([32768.0])) is None  # i32 ship wins nothing
    assert nd(np.array([1.5])) is None  # non-integral -> dense f32
    assert nd(np.array([1.0, np.nan])) is None
    assert nd(np.array([np.inf])) is None


def _narrow_whole_column(values):
    """narrow_int_dtype as it was, over the whole column at once."""
    if values.size == 0:
        return np.dtype(np.int8)
    if not np.isfinite(values).all():
        return None
    if not (values == np.rint(values)).all():
        return None
    if np.signbit(values[values == 0.0]).any():
        return None
    lo, hi = float(values.min()), float(values.max())
    if -(1 << 7) <= lo and hi < 1 << 7:
        return np.dtype(np.int8)
    if -(1 << 15) <= lo and hi < 1 << 15:
        return np.dtype(np.int16)
    return None


@pytest.mark.parametrize("where", ["first", "boundary", "last"])
@pytest.mark.parametrize(
    "odd_one",
    [None, 127.0, 128.0, -129.0, 32767.0, 32768.0, -32769.0, 0.5, -0.0,
     np.nan, -np.inf],
    ids=str,
)
def test_narrow_int_dtype_blockwise_scan_is_the_whole_column_scan(
    odd_one, where
):
    """The scan runs in blocks and leaves early; the one value that
    decides may sit in any of them."""
    n = 2 * encoded._SCAN_ROWS + 17
    values = np.random.default_rng(5).integers(-100, 100, n).astype(np.float64)
    if odd_one is not None:
        at = {"first": 3, "boundary": encoded._SCAN_ROWS, "last": n - 1}
        values[at[where]] = odd_one
    assert encoded.narrow_int_dtype(values) == _narrow_whole_column(values)


def test_pack_luts_shapes():
    out = encoded.pack_luts([])
    assert out.shape == (1, 1)
    out = encoded.pack_luts([np.arange(3), np.arange(5)])
    assert out.shape == (2, 8)  # S pow2, L pow2
    assert out.dtype == np.int32
    out3 = encoded.pack_luts([np.arange(1)] * 3)
    assert out3.shape == (4, 1)


# -- gather-level A/B parity -------------------------------------------------


def _measure(fields=(("v", FieldType.INT),)):
    return Measure(
        group="g",
        name="m",
        tags=(TagSpec("svc", TagType.STRING),),
        fields=tuple(FieldSpec(n, t) for n, t in fields),
        entity=Entity(("svc",)),
    )


def _src(n, dict_sz, seed, toff=0, with_tag=True):
    r = np.random.default_rng(seed)
    return ColumnData(
        ts=T0 + toff + np.arange(n, dtype=np.int64),
        series=np.arange(n, dtype=np.int64) % 16,
        version=np.ones(n, dtype=np.int64),
        tags=(
            {"svc": r.integers(0, dict_sz, n).astype(np.int32)}
            if with_tag
            else {}
        ),
        fields={"v": r.integers(-100, 20000, n).astype(np.float64)},
        dicts=(
            {"svc": [b"x%05d" % i for i in range(dict_sz)]}
            if with_tag
            else {}
        ),
    )


def _partial_bytes(p) -> bytes:
    return p.content_bytes()  # the shared parity oracle (Partials)


def _result_json(m, req, p) -> str:
    from banyandb_tpu.server import result_to_json

    return json.dumps(
        result_to_json(finalize_partials(m, req, [p])), sort_keys=True
    )


@pytest.mark.parametrize(
    "batches", [False, True], ids=["one-batch", "chunk-batches"]
)
def test_decode_parity_multi_source_mixed_widths(batches, monkeypatch):
    """3 sources with i8/i16/i32-wide dictionaries, real remap, absent
    column in one source: compressed ship == dense ship byte-for-byte."""
    m = _measure()
    srcs = [
        _src(3000, 5, 1),
        _src(3000, 300, 2, toff=4000),
        _src(500, 40000, 3, toff=8000),
        _src(200, 4, 4, toff=9000, with_tag=False),  # schema evolution
    ]
    req = QueryRequest(
        ("g",),
        "m",
        TimeRange(T0, T0 + 10_000),
        group_by=GroupBy(("svc",)),
        agg=Aggregation("sum", "v"),
        limit=7,
    )
    if batches:  # 6,700 rows over the budget: four one-chunk batches
        monkeypatch.setenv("BYDB_FUSED_MAX_MB", "0")
        from banyandb_tpu.query import measure_exec

        monkeypatch.setattr(measure_exec, "SCAN_CHUNK", 2048)
    monkeypatch.setenv("BYDB_DEVICE_DECODE", "0")
    p_dense = compute_partials(m, req, srcs)
    monkeypatch.setenv("BYDB_DEVICE_DECODE", "1")
    p_dec = compute_partials(m, req, srcs)
    assert _partial_bytes(p_dense) == _partial_bytes(p_dec)
    assert _result_json(m, req, p_dense) == _result_json(m, req, p_dec)


def test_decode_parity_rep_tags_and_float_path(monkeypatch):
    """Representative-tag decode and the exact-f64 float aggregate path
    both materialize host codes through the compressed form."""
    m = Measure(
        group="g",
        name="m",
        tags=(TagSpec("svc", TagType.STRING), TagSpec("az", TagType.STRING)),
        fields=(FieldSpec("lat", FieldType.FLOAT),),
        entity=Entity(("svc",)),
    )
    r = np.random.default_rng(9)
    n = 2048
    src = ColumnData(
        ts=T0 + np.arange(n, dtype=np.int64),
        series=np.arange(n, dtype=np.int64) % 8,
        version=np.ones(n, dtype=np.int64),
        tags={
            "svc": r.integers(0, 6, n).astype(np.int32),
            "az": r.integers(0, 3, n).astype(np.int32),
        },
        fields={"lat": r.random(n) * 9.7},
        dicts={
            "svc": [b"s%d" % i for i in range(6)],
            "az": [b"az-%d" % i for i in range(3)],
        },
    )
    req = QueryRequest(
        ("g",),
        "m",
        TimeRange(T0, T0 + n),
        group_by=GroupBy(("svc",)),
        tag_projection=("svc", "az"),
        agg=Aggregation("mean", "lat"),
    )
    outs = []
    for flag in ("0", "1"):
        monkeypatch.setenv("BYDB_DEVICE_DECODE", flag)
        p = compute_partials(m, req, [src])
        outs.append((_partial_bytes(p), _result_json(m, req, p)))
    assert outs[0] == outs[1]
    assert p.rep_vals and "az" in p.rep_vals  # rep decode ran


def test_host_tag_codes_matches_dense(monkeypatch):
    from banyandb_tpu.query.measure_exec import GlobalDicts, _gather_rows

    srcs = [_src(1000, 5, 1), _src(1000, 300, 2, toff=2000)]
    outs = {}
    for decode in (False, True):
        gd = GlobalDicts(["svc"])
        outs[decode] = _gather_rows(
            srcs, ["svc"], ["v"], gd, T0, T0 + 5000, device_decode=decode
        )
    dense = outs[False]["tags_code"]["svc"]
    assert np.array_equal(_host_tag_codes(outs[True], "svc"), dense)
    rows = np.array([0, 5, 999, 1500])
    assert np.array_equal(
        _host_tag_codes(outs[True], "svc", rows), dense[rows]
    )
    # narrow form really is narrow
    assert outs[True]["tags_enc"]["svc"].dtype.itemsize < 4


def test_part_backed_narrow_read_parity(tmp_path, monkeypatch):
    """Part.read(narrow_codes=True) keeps stored widths; the query over
    it is byte-identical to the widened read."""
    n = 10_000
    r = np.random.default_rng(11)
    PartWriter.write(
        tmp_path / "part-1",
        ts=T0 + np.arange(n, dtype=np.int64),
        series=np.zeros(n, dtype=np.int64),
        version=np.ones(n, dtype=np.int64),
        tag_codes={"svc": r.integers(0, 7, n).astype(np.int32)},
        tag_dicts={"svc": [b"s%d" % i for i in range(7)]},
        fields={"v": r.integers(0, 90, n).astype(np.float64)},
        extra_meta={"measure": "m"},
    )
    part = Part(tmp_path / "part-1")
    blocks = part.select_blocks(T0, T0 + n)
    narrow = part.read(blocks, tags=["svc"], fields=["v"], narrow_codes=True)
    wide = part.read(blocks, tags=["svc"], fields=["v"])
    assert narrow.tags["svc"].dtype == np.int8
    assert wide.tags["svc"].dtype == np.int32
    assert np.array_equal(narrow.tags["svc"], wide.tags["svc"].astype(np.int8))

    m = _measure()
    req = QueryRequest(
        ("g",),
        "m",
        TimeRange(T0, T0 + n),
        group_by=GroupBy(("svc",)),
        agg=Aggregation("sum", "v"),
    )
    monkeypatch.setenv("BYDB_DEVICE_DECODE", "1")
    p_n = compute_partials(m, req, [narrow])
    monkeypatch.setenv("BYDB_DEVICE_DECODE", "0")
    p_w = compute_partials(m, req, [wide])
    assert _partial_bytes(p_n) == _partial_bytes(p_w)


# -- zone maps ---------------------------------------------------------------


def _selective_part(tmp_path, name="part-1", rare_rows=40):
    """3-block part where dict code 1 ('rare') lives only in block 0."""
    n = 20_000
    codes = np.zeros(n, dtype=np.int32)
    codes[:rare_rows] = 1
    PartWriter.write(
        tmp_path / name,
        ts=T0 + np.arange(n, dtype=np.int64),
        series=np.zeros(n, dtype=np.int64),
        version=np.ones(n, dtype=np.int64),
        tag_codes={"svc": codes},
        tag_dicts={"svc": [b"common", b"rare"]},
        fields={"v": np.arange(n, dtype=np.float64)},
        extra_meta={"measure": "m"},
    )
    return Part(tmp_path / name)


def _skip_count() -> float:
    from banyandb_tpu.obs.metrics import global_meter

    return (
        global_meter()
        .snapshot()["counters"]
        .get(("blocks_skipped", (("reason", "zone"),)), 0.0)
    )


def test_zone_maps_written_and_skip(tmp_path):
    part = _selective_part(tmp_path)
    assert part.has_zone_maps()
    assert len(part.blocks) == 3
    assert part.blocks[0]["zones"]["tag_svc"] == [0, 1]
    assert part.blocks[1]["zones"]["tag_svc"] == [0, 0]
    assert "field_v" in part.blocks[0]["zones"]

    before = _skip_count()
    pruned = part.select_blocks(
        T0, T0 + 10**9, zone_preds=[("tag_svc", np.asarray([1]))]
    )
    assert pruned == [0]
    assert _skip_count() == before + 2
    # a no-information predicate column never skips
    assert (
        part.select_blocks(
            T0, T0 + 10**9, zone_preds=[("tag_other", np.asarray([1]))]
        )
        == [0, 1, 2]
    )


def test_zone_skip_results_identical_engine(tmp_path, monkeypatch):
    """Engine-level: selective eq query with zone skipping on vs off —
    identical JSON, skip counter grows, rare value found."""
    from banyandb_tpu.api import (
        Catalog,
        Group,
        ResourceOpts,
        SchemaRegistry,
    )
    from banyandb_tpu.models.measure import MeasureEngine

    n = 20_000
    # az is NOT the entity tag: series pruning cannot help, so a
    # selective az predicate is exactly the zone-map case (the entity
    # path already prunes via the series index)
    az = ["common"] * n
    for i in range(25):
        az[i] = "rare"
    reg = SchemaRegistry(tmp_path / "zs")
    reg.create_group(Group("g", Catalog.MEASURE, ResourceOpts(shard_num=1)))
    reg.create_measure(
        Measure(
            "g",
            "m",
            (TagSpec("svc", TagType.STRING), TagSpec("az", TagType.STRING)),
            (FieldSpec("v", FieldType.INT),),
            Entity(("svc",)),
        )
    )
    engine = MeasureEngine(reg, tmp_path / "zs" / "data")
    engine.write_columns(
        "g",
        "m",
        ts_millis=T0 + np.arange(n),
        tags={"svc": ["s"] * n, "az": az},
        fields={"v": np.ones(n)},
        versions=np.ones(n, dtype=np.int64),
    )
    engine.flush()
    req = QueryRequest(
        ("g",),
        "m",
        TimeRange(T0, T0 + n),
        criteria=Condition("az", "eq", "rare"),
        agg=Aggregation("count", "v"),
    )

    monkeypatch.setenv("BYDB_ZONE_SKIP", "0")
    full = engine.query(req)
    before = _skip_count()
    monkeypatch.setenv("BYDB_ZONE_SKIP", "1")
    pruned = engine.query(req)
    assert pruned.values["count"] == full.values["count"] == [25.0]
    assert _skip_count() > before, "no block was zone-skipped"

    # a value absent from every dictionary excludes whole parts (and
    # still returns an empty-but-well-formed result)
    miss = engine.query(
        QueryRequest(
            ("g",),
            "m",
            TimeRange(T0, T0 + n),
            criteria=Condition("az", "eq", "no-such-zone"),
            agg=Aggregation("count", "v"),
        )
    )
    assert miss.values["count"] == [0.0]

    # OR criteria: pruning must be disabled (conservative), results exact
    either = engine.query(
        QueryRequest(
            ("g",),
            "m",
            TimeRange(T0, T0 + n),
            criteria=LogicalExpression(
                "or",
                Condition("az", "eq", "rare"),
                Condition("az", "eq", "common"),
            ),
            agg=Aggregation("count", "v"),
        )
    )
    assert either.values["count"] == [float(n)]


def test_zone_skip_never_resurrects_stale_versions(tmp_path, monkeypatch):
    """The dedup-safety gate: part A holds (series, ts) v1 with
    az='rare'; part B holds the SAME key at v2 with az='common'.  Part
    B's dictionary lacks 'rare', so naive zone/part pruning would drop
    it — and v1 (matching!) would resurrect.  The key-interval overlap
    check must force part B to be read, making the query return 0 in
    BOTH zone-skip modes."""
    from banyandb_tpu.api import Catalog, Group, ResourceOpts, SchemaRegistry
    from banyandb_tpu.models.measure import MeasureEngine

    reg = SchemaRegistry(tmp_path / "vz")
    reg.create_group(Group("g", Catalog.MEASURE, ResourceOpts(shard_num=1)))
    reg.create_measure(
        Measure(
            "g",
            "m",
            (TagSpec("svc", TagType.STRING), TagSpec("az", TagType.STRING)),
            (FieldSpec("v", FieldType.INT),),
            Entity(("svc",)),
        )
    )
    engine = MeasureEngine(reg, tmp_path / "vz" / "data")
    n = 9000  # 2 blocks per part
    ts = T0 + np.arange(n)
    engine.write_columns(
        "g", "m", ts_millis=ts,
        tags={"svc": ["s"] * n, "az": ["rare"] * n},
        fields={"v": np.ones(n)},
        versions=np.ones(n, dtype=np.int64),
    )
    engine.flush()  # part A: every row az='rare' @ v1
    engine.write_columns(
        "g", "m", ts_millis=ts,
        tags={"svc": ["s"] * n, "az": ["common"] * n},
        fields={"v": np.ones(n)},
        versions=np.full(n, 2, dtype=np.int64),
    )
    engine.flush()  # part B: same keys overwritten az='common' @ v2
    req = QueryRequest(
        ("g",),
        "m",
        TimeRange(T0, T0 + n),
        criteria=Condition("az", "eq", "rare"),
        agg=Aggregation("count", "v"),
    )
    for flag in ("0", "1"):
        monkeypatch.setenv("BYDB_ZONE_SKIP", flag)
        r = engine.query(req)
        assert r.values["count"] == [0.0], (flag, r.values)


def test_rewrite_in_a_later_part_is_deduped_not_proven_away(tmp_path):
    """The gather's dedup skip (measure_exec._dedup_components) rests on
    the same key intervals: part A holds the keys at v1, part B rewrites
    them at v2, part C is time-disjoint.  A and B intersect, so they are
    sorted together and v2 alone answers; only C's rows skip the sort."""
    from banyandb_tpu.api import Catalog, Group, ResourceOpts, SchemaRegistry
    from banyandb_tpu.models.measure import MeasureEngine

    reg = SchemaRegistry(tmp_path / "rw")
    reg.create_group(Group("g", Catalog.MEASURE, ResourceOpts(shard_num=1)))
    reg.create_measure(
        Measure(
            "g", "m", (TagSpec("svc", TagType.STRING),),
            (FieldSpec("v", FieldType.INT),), Entity(("svc",)),
        )
    )
    engine = MeasureEngine(reg, tmp_path / "rw" / "data")
    n = 1000
    for version, base, value in ((1, 0, 1.0), (2, 0, 100.0), (1, n, 1.0)):
        engine.write_columns(
            "g", "m", ts_millis=T0 + base + np.arange(n),
            tags={"svc": ["s"] * n}, fields={"v": np.full(n, value)},
            versions=np.full(n, version, dtype=np.int64),
        )
        engine.flush()
    r = engine.query(
        QueryRequest(
            ("g",), "m", TimeRange(T0, T0 + 2 * n),
            agg=Aggregation("sum", "v"), trace=True,
        )
    )
    assert r.values["sum(v)"] == [100.0 * n + n]  # v1 of A never counts

    def gather_tags(span):
        if span["name"] == "gather":
            return span["tags"]
        for c in span["children"]:
            tags = gather_tags(c)
            if tags is not None:
                return tags

    tags = gather_tags(r.trace["span_tree"])
    assert tags["proven_unique_share"] == round(100.0 / 3, 3)
    assert tags["rows"] == 2 * n and tags["sources"] == 3


def test_zone_skip_safety_gate_blocks_overlapping_marked_blocks(tmp_path):
    """select_blocks drops a marked block only when its key interval
    cannot intersect a kept source (version dedup could otherwise flip
    results); overlapping extra intervals force the read."""
    from banyandb_tpu.storage.part import KeyInterval

    part = _selective_part(tmp_path)
    preds = [("tag_svc", np.asarray([1]))]
    before = _skip_count()
    # an external kept source covering the same keys as block 1 — e.g.
    # a memtable or another part holding newer versions
    overlap = KeyInterval.conservative(0, 0, T0 + 9000, T0 + 9100)
    sel = part.select_blocks(
        T0, T0 + 10**9, zone_preds=preds, extra_intervals=[overlap]
    )
    assert 1 in sel  # marked but overlap-gated: must be read
    assert 2 not in sel  # disjoint from everything kept: skipped
    assert _skip_count() == before + 1
    # fully disjoint external interval changes nothing
    far = KeyInterval.conservative(99, 99, T0, T0 + 1)
    sel = part.select_blocks(
        T0, T0 + 10**9, zone_preds=preds, extra_intervals=[far]
    )
    assert sel == [0]


def test_zone_maps_survive_merge(tmp_path):
    from banyandb_tpu.storage.merge import merge_columns

    p1 = _selective_part(tmp_path, "part-1")
    p2 = _selective_part(tmp_path, "part-2", rare_rows=10)
    cols, extra = merge_columns([p1, p2])
    PartWriter.write(
        tmp_path / "part-3",
        ts=cols.ts,
        series=cols.series,
        version=cols.version,
        tag_codes=cols.tags,
        tag_dicts=cols.dicts,
        fields=cols.fields,
        extra_meta=extra,
    )
    merged = Part(tmp_path / "part-3")
    assert merged.has_zone_maps()


# -- back-compat: pre-upgrade parts (no zone maps) ---------------------------


def _strip_zones(part_dir):
    """Rewrite primary.bin without the `zones` key — byte-faithful to a
    part written before the zone-map format upgrade."""
    with open(part_dir / "primary.bin", "rb") as f:
        blocks = json.loads(zst.decompress(f.read()))
    for b in blocks:
        b.pop("zones", None)
    (part_dir / "primary.bin").write_bytes(
        zst.compress(json.dumps(blocks).encode())
    )


def test_pre_upgrade_part_loads_scans_never_skips(tmp_path, monkeypatch):
    _selective_part(tmp_path)
    _strip_zones(tmp_path / "part-1")
    part = Part(tmp_path / "part-1")
    assert not part.has_zone_maps()
    # zone predicates are a no-op: nothing skipped, no error
    before = _skip_count()
    sel = part.select_blocks(
        T0, T0 + 10**9, zone_preds=[("tag_svc", np.asarray([1]))]
    )
    assert sel == [0, 1, 2]
    assert _skip_count() == before
    # and the full query path over the fixture still answers correctly
    m = _measure()
    req = QueryRequest(
        ("g",),
        "m",
        TimeRange(T0, T0 + 10**9),
        criteria=Condition("svc", "eq", "rare"),
        agg=Aggregation("count", "v"),
    )
    monkeypatch.setenv("BYDB_DEVICE_DECODE", "1")
    src = part.read(sel, tags=["svc"], fields=["v"], narrow_codes=True)
    p = compute_partials(m, req, [src])
    assert p.count.sum() == 40.0


def test_cli_dump_reports_zone_presence(tmp_path, capsys):
    from banyandb_tpu import cli

    _selective_part(tmp_path)
    assert cli.main(["dump", "measure", str(tmp_path / "part-1")]) in (0, None)
    doc = json.loads(capsys.readouterr().out)
    assert doc["zone_maps"] is True
    assert "zones" in doc["blocks"][0]

    _strip_zones(tmp_path / "part-1")
    assert cli.main(["dump", "measure", str(tmp_path / "part-1")]) in (0, None)
    doc = json.loads(capsys.readouterr().out)
    assert doc["zone_maps"] is False
    assert "zones" not in doc["blocks"][0]

    # kind mismatch is an explicit error, not a KeyError
    assert cli.main(["dump", "stream", str(tmp_path / "part-1")]) == 2


# -- precompile warm covers the compressed ship form -------------------------


@pytest.mark.parametrize("want_rep", [True, False], ids=["rep", "no-rep"])
@pytest.mark.parametrize("compressed", [True, False], ids=["compressed", "dense"])
def test_warm_structs_match_production_compressed_chunks(
    compressed, want_rep, monkeypatch
):
    """The cold-start contract: the canonical warm structs
    (precompile.fused_decode_chunk_struct under the default flag,
    fused_chunk_struct in the dense form) must have EXACTLY the pytree
    structure, shapes and dtypes the pad/ship stage produces for
    canonical-width data, for a plan that tracks scan order (its batch
    holds ts and row) and one that does not — else warming compiles a
    trace production never hits."""
    import dataclasses

    import jax

    from banyandb_tpu.query import fused_exec, precompile
    from banyandb_tpu.query.measure_exec import GlobalDicts, _gather_rows

    name, spec = precompile.builtin_plans()[1]  # measure/group-eq-lut
    spec = dataclasses.replace(spec, want_rep=want_rep)
    n = spec.nrows
    r = np.random.default_rng(31)
    src = ColumnData(
        ts=T0 + np.arange(n, dtype=np.int64),
        series=np.arange(n, dtype=np.int64) % 64,
        version=np.ones(n, dtype=np.int64),
        tags={
            "svc": r.integers(0, 8, n).astype(np.int32),
            "region": r.integers(0, 4, n).astype(np.int32),
        },
        fields={"v": r.integers(0, 30_000, n).astype(np.float64)},  # i16
        dicts={
            "svc": [b"s%d" % i for i in range(8)],
            "region": [b"r%d" % i for i in range(4)],
        },
    )
    gd = GlobalDicts(["region", "svc"])
    cols = _gather_rows(
        [src], ["region", "svc"], ["v"], gd, T0, T0 + n,
        device_decode=compressed,
    )

    def spec_of(tree):
        return jax.tree_util.tree_map(
            lambda a: (tuple(a.shape), str(a.dtype)), tree
        )

    fspec = fused_exec.FusedSpec(plan=spec, num_chunks=1)
    stacked = fused_exec._stacked_chunks(cols, [(0, n)], spec, 1, T0)
    struct = (
        precompile.fused_decode_chunk_struct
        if compressed
        else precompile.fused_chunk_struct
    )
    fwant = jax.tree_util.tree_map(
        lambda s: (tuple(s.shape), str(s.dtype)), struct(fspec)
    )
    assert spec_of(stacked) == fwant
    assert ("ts" in stacked) == ("row" in stacked) == want_rep
    assert "series" not in stacked


def test_warm_dispatches_both_ship_forms(monkeypatch):
    """warm() under BYDB_DEVICE_DECODE=1 compiles the dense AND the
    compressed form of each builtin program (jit re-specializes per
    pytree structure, so both need a boot-time trace); a ``measure``
    evidence row of the same plan compiles nothing of its own."""
    from banyandb_tpu.query import fused_exec, precompile

    monkeypatch.setenv("BYDB_PRECOMPILE", "1")
    monkeypatch.setenv("BYDB_DEVICE_DECODE", "1")
    monkeypatch.setattr(fused_exec, "_KERNEL_CACHE", {})
    r = precompile.PrecompileRegistry()
    spec = precompile.builtin_plans()[0][1]
    fspec = precompile.builtin_fused()[0][1]
    assert r.warm(sigs=[("measure", spec), ("fused", fspec)]) == 1
    assert r.errors == 0
    assert list(fused_exec._KERNEL_CACHE) == [fspec]
    # one compiled entry per ship form
    assert fused_exec._KERNEL_CACHE[fspec]._cache_size() == 2


# -- decode span + counters --------------------------------------------------


def test_decode_span_and_ship_counters(monkeypatch):
    from banyandb_tpu.obs.metrics import global_meter
    from banyandb_tpu.obs.tracer import Tracer

    m = _measure()
    src = _src(5000, 5, 21)
    req = QueryRequest(
        ("g",),
        "m",
        TimeRange(T0, T0 + 5000),
        group_by=GroupBy(("svc",)),
        agg=Aggregation("sum", "v"),
    )

    def decode_span(tree):
        if tree.get("name") == "decode":
            return tree
        for c in tree.get("children", ()):
            hit = decode_span(c)
            if hit is not None:
                return hit
        return None

    monkeypatch.setenv("BYDB_DEVICE_DECODE", "1")
    tr = Tracer("t")
    with tr.span("q") as sp:
        compute_partials(m, req, [src], span=sp)
    tags = decode_span(tr.finish())["tags"]
    assert tags["mode"] == "device"
    assert 0 < tags["shipped_bytes"] < tags["dense_bytes"]
    counters = global_meter().snapshot()["counters"]
    assert counters.get(("decode_ship_bytes", (("form", "shipped"),), ), 0) > 0

    monkeypatch.setenv("BYDB_DEVICE_DECODE", "0")
    tr = Tracer("t")
    with tr.span("q") as sp:
        compute_partials(m, req, [src], span=sp)
    tags = decode_span(tr.finish())["tags"]
    assert tags["mode"] == "host"
    assert tags["shipped_bytes"] == tags["dense_bytes"] > 0
