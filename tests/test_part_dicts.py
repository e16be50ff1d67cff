"""A part's dictionary holds the values its rows use (ISSUE 38, ROADMAP S14).

A columnar write (`MeasureEngine.write_columns`) carries one dictionary a
tag for the whole message (`DictColumn`) and hands every shard its rows
with that whole dictionary.  `MemTable.append_bulk` enters into the
shard's dictionary only the values the shard's codes name, as the row path
(`MemTable.append`) enters only the values it sees; so a memtable, every
part flushed from it and every part merged from those hold exactly the
distinct values their rows use.  Until ISSUE 38 every part of a 4-shard
store held all of its message's names, four times what its rows use.
"""

import numpy as np
import pytest

from banyandb_tpu.api import (
    Catalog,
    DataPointValue,
    Entity,
    FieldSpec,
    FieldType,
    Group,
    Measure,
    ResourceOpts,
    SchemaRegistry,
    TagSpec,
    TagType,
    WriteRequest,
)
from banyandb_tpu.models.measure import DictColumn, MeasureEngine
from banyandb_tpu.obs import metrics as obs_metrics

SERIES, REGIONS = 600, 8
T0 = 1_700_006_400_000
DAY_MS = 86_400_000
NAMES = ["svc_%06d" % i for i in range(SERIES)]
REGION_NAMES = ["r%d" % i for i in range(REGIONS)]
TAGS = ("svc", "region")


def _engine(tmp_path, sub: str, shards: int) -> MeasureEngine:
    reg = SchemaRegistry(tmp_path / sub / "schema")
    reg.create_group(Group("g", Catalog.MEASURE, ResourceOpts(shard_num=shards)))
    reg.create_measure(Measure(
        group="g", name="m",
        tags=(TagSpec("svc", TagType.STRING), TagSpec("region", TagType.STRING)),
        fields=(FieldSpec("hits", FieldType.INT),),
        entity=Entity(("svc",)),
    ))
    return MeasureEngine(reg, tmp_path / sub / "data")


def _day_series(day: int) -> np.ndarray:
    """The series a day's message holds: all of them on day 0, every
    third one after it, so a merged part's names are a union of unequal
    sets."""
    return np.arange(SERIES) if day == 0 else np.arange(day % 3, SERIES, 3)


def _write_bulk(eng: MeasureEngine, day: int) -> None:
    svc = _day_series(day).astype(np.int32)
    eng.write_columns(
        "g", "m",
        ts_millis=np.full(svc.size, T0 + day * 60_000, np.int64),
        tags={"svc": DictColumn(NAMES, svc), "region": DictColumn(REGION_NAMES, svc % REGIONS)},
        fields={"hits": (svc % 997).astype(np.float64)},
        versions=np.ones(svc.size, np.int64),
    )


def _write_rows(eng: MeasureEngine, day: int) -> None:
    eng.write(WriteRequest("g", "m", tuple(
        DataPointValue(T0 + day * 60_000, {"svc": NAMES[s], "region": REGION_NAMES[s % REGIONS]},
                       {"hits": float(s % 997)}, version=1)
        for s in _day_series(day).tolist()
    )))


def _sources(eng: MeasureEngine):
    """-> [(shard index, part or None, ColumnData)]: every flushed part and
    every memtable of every segment."""
    out = []
    for seg in eng._tsdb("g").segments:
        for i, shard in enumerate(seg.shards):
            for part in shard.parts:
                cols = part.read(range(len(part.blocks)), tags=TAGS, cached=False)
                out.append((i, part, cols))
            out.extend((i, None, cols) for cols in shard.hot_columns("m"))
    return out


def _by_shard(eng: MeasureEngine) -> dict:
    """-> {shard index: {tag: set of dictionary values}} over all sources,
    after asserting that each source's dictionary is exactly the distinct
    values its rows use, each once."""
    out: dict = {}
    for i, part, cols in _sources(eng):
        for t in TAGS:
            d = list(part.dict_for(t) if part is not None else cols.dicts[t])
            used = {d[c] for c in np.unique(cols.tags[t]).tolist()}
            assert len(d) == len(set(d)) and set(d) == used, (i, t, len(d), len(used))
            out.setdefault(i, {}).setdefault(t, set()).update(d)
    return out


def _counted(kind: str) -> float:
    text = obs_metrics.global_meter().prometheus_text()
    return sum(
        float(line.split()[-1]) for line in text.splitlines()
        if line.startswith("banyandb_dict_values_entered") and f'kind="{kind}"' in line
    )


@pytest.mark.parametrize("shards", [1, 2, 4])
def test_a_flushed_part_holds_the_values_its_rows_use(tmp_path, shards):
    """Two messages, each flushed: every part's dictionary is the names
    and regions of its own rows; a shard's names are its own series,
    disjoint from every other shard's, and together all of them."""
    eng = _engine(tmp_path, "bulk", shards)
    for day in range(2):
        _write_bulk(eng, day)
        eng.flush()
    parts = [s for s in _sources(eng) if s[1] is not None]
    assert len(parts) == 2 * shards
    names = [d["svc"] for d in _by_shard(eng).values()]
    assert sum(len(n) for n in names) == SERIES and set().union(*names) == {n.encode() for n in NAMES}
    largest = max(len(p.dict_for("svc")) for _, p, _ in parts)
    assert largest < SERIES if shards > 1 else largest == SERIES
    eng.close()


@pytest.mark.parametrize("form", ["memtable", "merged", "row-path"])
def test_every_form_holds_the_bulk_paths_dictionaries(tmp_path, form):
    """The same rows over 4 shards, held another way, give every shard the
    dictionaries the bulk path's flushed parts give it: the memtable's
    snapshot before a flush, the part merged from three flushed days, and
    the parts of the row path."""
    bulk = _engine(tmp_path, "bulk", 4)
    other = _engine(tmp_path, form, 4)
    for day in range(3):
        _write_bulk(bulk, day)
        bulk.flush()
        (_write_rows if form == "row-path" else _write_bulk)(other, day)
        if form == "merged":
            other.flush()
    if form == "merged":
        for seg in other._tsdb("g").segments:
            for shard in seg.shards:
                assert shard.merge(min_merge=3, max_parts=3) is not None
                assert len(shard.parts) == 1
    elif form == "row-path":
        other.flush()
    else:
        assert all(part is None for _, part, _ in _sources(other))
    want = _by_shard(bulk)
    assert _by_shard(other) == want and len(want) == 4
    for eng in (bulk, other):
        eng.close()


@pytest.mark.parametrize("shards", [1, 2, 4])
def test_dict_values_entered_counts_used_and_unused(tmp_path, shards):
    """`/metrics` `dict_values_entered{kind}`: of the values each shard's
    memtable is handed, those its rows name (`used`, the sum of the
    dictionaries' sizes) and the rest (`unused`); a message of every
    series leaves 1 - 1/shards of the names out.  The row path hands no
    `DictColumn` and counts nothing."""
    eng = _engine(tmp_path, "count", shards)
    used, unused = _counted("used"), _counted("unused")
    _write_bulk(eng, 0)
    d_used, d_unused = _counted("used") - used, _counted("unused") - unused
    sizes = {t: 0 for t in TAGS}
    for _, _, cols in _sources(eng):
        for t in TAGS:
            sizes[t] += len(cols.dicts[t])
    assert sizes["svc"] == SERIES
    assert d_used == sizes["svc"] + sizes["region"]
    assert d_used + d_unused == shards * (SERIES + REGIONS)
    # the names: 1 - 1/shards left out, 75% on 4 shards
    assert d_unused == (shards - 1) * SERIES + shards * REGIONS - sizes["region"]
    used, unused = _counted("used"), _counted("unused")
    _write_rows(eng, 1)
    assert (_counted("used"), _counted("unused")) == (used, unused)
    eng.close()
