"""`finalize_partials` decodes the groups it returns a column at a time,
and answers exactly what the per-group decode answered.

The reference keeps that decode: a Python step a returned group
(`Partials.group_key` + `filter.decode_tag_value` a value, the schema's
tag looked up a value), over the returned groups in an order computed
here from the partial alone, so the order is pinned too.
"""

from __future__ import annotations

import numpy as np
import pytest

from banyandb_tpu.api.model import Aggregation, QueryRequest, TimeRange, Top
from banyandb_tpu.api.schema import (
    Entity,
    FieldSpec,
    FieldType,
    Measure,
    TagSpec,
    TagType,
)
from banyandb_tpu.obs.tracer import Tracer, iter_spans
from banyandb_tpu.query import filter as qfilter
from banyandb_tpu.query import measure_exec

MEASURE = Measure(
    group="g",
    name="m",
    tags=(
        TagSpec("svc", TagType.STRING),
        TagSpec("zone", TagType.INT),
        TagSpec("blob", TagType.DATA_BINARY),
        TagSpec("host", TagType.STRING),
    ),
    fields=(FieldSpec("v", FieldType.INT),),
    entity=Entity(("svc",)),
)


def _values(tag: str, n: int, rng) -> list[bytes]:
    """n distinct raw values of the tag's type, with the awkward ones in:
    invalid UTF-8, an empty INT (reads 0), negative INTs."""
    if tag in ("svc", "host"):
        vals = [b"%s_%06d" % (tag.encode(), i) for i in range(n)]
        vals[1 % n] = b"\xff\xfebad-utf8"
        if n > 2:
            vals[2] = "µ-svc".encode()
    elif tag == "zone":
        vals = [int(i - n // 2).to_bytes(8, "little", signed=True) for i in range(n)]
        vals[0] = b""
    else:
        vals = [bytes(rng.integers(0, 256, 5, dtype=np.uint8)) + b"%d" % i for i in range(n)]
    return vals


def _partial(path: str, tags: tuple, k: int, seed: int, empty=(), rep=False, rep_desc=False):
    """A partial of k groups over `tags`, dictionary codes shuffled; groups
    in `empty` have count 0.  `path` "codes" keeps codes + dictionary
    snapshots (the standalone form), "tuples" materialized value tuples
    (the combine form)."""
    rng = np.random.default_rng(seed)
    count = np.arange(1, k + 1, dtype=np.float64)
    count[list(empty)] = 0.0
    dicts = {t: _values(t, k + 3, rng) for t in tags}
    codes = np.stack(
        [rng.permutation(k + 3)[:k] for _ in tags], axis=1
    ).astype(np.int32) if tags else np.zeros((k, 0), np.int32)
    kw = {}
    if rep:
        ts = rng.integers(0, 50, k)  # ties in ts, broken by the row
        kw = dict(
            rep_key=np.stack([ts, rng.permutation(k)], axis=1).astype(np.int64),
            rep_desc=rep_desc,
            rep_vals={
                "host": [
                    None if i % 3 == 0 else v
                    for i, v in enumerate(_values("host", k, rng))
                ],
                "zone": [None] * k,
            },
        )
    sums = {"v": rng.integers(0, 4, k).astype(np.float64)}  # Top-N ties
    stats = dict(count=count, sums=sums, mins={"v": sums["v"]}, maxs={"v": sums["v"]}, **kw)
    if path == "codes":
        return measure_exec.Partials(
            group_tags=tags, codes=codes, group_values=dicts, **stats
        )
    groups = [
        tuple(dicts[t][int(codes[i, j])] for j, t in enumerate(tags)) for i in range(k)
    ]
    return measure_exec.Partials(group_tags=tags, groups=groups, **stats)


def _request(top=None, offset=0, limit=0):
    return QueryRequest(
        groups=("g",), name="m", time_range=TimeRange(0, 1),
        agg=Aggregation("sum", "v"), top=top, offset=offset, limit=limit,
    )


def _one(*args, **kw):
    """-> partials(path): one partial, `_partial(path, *args, **kw)`."""
    return lambda path: [_partial(path, *args, **kw)]


SVC_ZONE = ("svc", "zone")

# name -> (partials(path) -> list[Partials], request)
SHAPES = {
    "string-many": (_one(("svc",), 3000, 1, empty=(5, 77)), _request()),
    "int": (_one(("zone",), 40, 2), _request()),
    "binary-kept": (_one(("blob",), 25, 3), _request()),
    "two-tags": (_one(SVC_ZONE, 500, 4), _request()),
    "no-group-tags": (_one((), 1, 5), _request()),
    "zero-groups": (_one(("svc",), 6, 6, empty=range(6)), _request()),
    "no-partial-groups": (_one(("svc",), 0, 7), _request()),
    "one-group": (_one(SVC_ZONE, 9, 8, empty=(0, 1, 2, 4, 5, 6, 7, 8)), _request()),
    "limit-one": (_one(("svc",), 50, 9), _request(limit=1)),
    "offset-limit": (_one(SVC_ZONE, 200, 10), _request(offset=7, limit=11)),
    "offset-past-end": (_one(("svc",), 10, 11), _request(offset=10)),
    "top-n": (_one(SVC_ZONE, 400, 12), _request(top=Top(10, "v"))),
    "top-n-asc": (_one(("svc",), 400, 13), _request(top=Top(10, "v", "asc"))),
    "top-one": (_one(("svc",), 30, 14), _request(top=Top(1, "v"))),
    "listing-rep-key": (_one(("svc",), 300, 15, rep=True), _request()),
    "listing-rep-key-desc": (
        _one(("svc", "blob"), 300, 16, rep=True, rep_desc=True),
        _request(offset=3, limit=40),
    ),
    "rep-vals-one-group": (_one(("svc",), 4, 17, rep=True), _request(limit=1)),
    "combined": (
        lambda path: [_partial(path, SVC_ZONE, 120, 18), _partial(path, SVC_ZONE, 90, 18)],
        _request(),
    ),
}


def _expected_ids(p: measure_exec.Partials, request: QueryRequest) -> list[int]:
    """The returned groups' ids in reply order, from the partial alone."""
    ids = [g for g in range(len(p.count)) if p.count[g] > 0]
    if not p.group_tags:
        ids = [0]
    elif request.top:
        sign = 1.0 if request.top.field_value_sort == "asc" else -1.0
        metric = p.sums[request.top.field_name]
        ids = sorted(ids, key=lambda g: (sign * metric[g], p.group_key(g)))
        ids = ids[: request.top.number]
    elif p.rep_key is not None:
        ids = sorted(ids, key=lambda g: tuple(int(x) for x in p.rep_key[g]), reverse=p.rep_desc)
    else:
        ids = sorted(ids, key=p.group_key)
    ids = ids[request.offset:]
    return ids[: request.limit] if request.limit else ids


def _per_group_decode(measure, p: measure_exec.Partials, ids: list[int]):
    """The decode finalize ran before: a Python call chain a group."""
    groups = []
    for g in ids:
        raw = p.group_key(int(g))
        groups.append(
            tuple(
                qfilter.decode_tag_value(v, measure.tag(t).type)
                for t, v in zip(p.group_tags, raw)
            )
        )
    rep_tags = {}
    if p.rep_vals:
        for t, vals in p.rep_vals.items():
            rep_tags[t] = [
                (
                    qfilter.decode_tag_value(vals[int(g)], measure.tag(t).type)
                    if vals[int(g)] is not None
                    else None
                )
                for g in ids
            ]
    return groups, rep_tags


@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("path", ["codes", "tuples"])
def test_columnar_decode_is_the_per_group_decode(path, shape):
    build, request = SHAPES[shape]
    tracer = Tracer("test")
    with tracer.span("execute") as sp:
        got = measure_exec.finalize_partials(MEASURE, request, build(path), span=sp)
    merge = next(s for s in iter_spans(tracer.finish()) if s["name"] == "merge")

    parts = build(path)
    p = measure_exec.combine_partials(parts) if len(parts) > 1 else parts[0]
    ids = _expected_ids(p, request)
    groups, rep_tags = _per_group_decode(MEASURE, p, ids)

    assert got.groups == groups
    assert all(type(g) is tuple for g in got.groups)
    assert got.rep_tags == rep_tags
    assert got.values == {
        "sum(v)": [float(p.sums["v"][g]) for g in ids],
        "count": [float(p.count[g]) for g in ids],
    }
    assert merge["tags"]["decoded_groups"] == len(ids)


@pytest.mark.parametrize("tag_type", list(TagType))
def test_a_column_decodes_as_its_values_do(tag_type):
    raws = [
        b"", b"\x00", b"abc", b"\xff\xfe", "µ".encode(),
        (-7).to_bytes(8, "little", signed=True), (2**40).to_bytes(8, "little", signed=True),
    ]
    assert qfilter.decode_tag_column(raws, tag_type) == [
        qfilter.decode_tag_value(v, tag_type) for v in raws
    ]
    assert qfilter.decode_tag_column((), tag_type) == []
