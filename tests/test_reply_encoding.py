"""`server.result_to_json`: a column of JSON-native values goes to
json.dumps without a Python call a value, and the reply is the walked
reply's, as objects and byte for byte.

The reference is the per-value walk every column took before: a copy of
it lives here, so the encoder is held to what replies always were.
"""

from __future__ import annotations

import base64
import enum
import json

import numpy as np
import pytest

from banyandb_tpu.api.model import QueryResult
from banyandb_tpu.obs import metrics as obs_metrics
from banyandb_tpu.server import result_to_json


def _walk(v):
    if isinstance(v, bytes):
        return base64.b64encode(v).decode()
    if isinstance(v, dict):
        return {k: _walk(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_walk(x) for x in v]
    return v


def _reference(res: QueryResult) -> dict:
    """The walked encoder: every group, value and rep tag through `_walk`."""
    out = {
        "groups": [_walk(list(g)) for g in res.groups],
        "values": {k: _walk(list(vs)) for k, vs in res.values.items()},
        "data_points": [_walk(dp) for dp in res.data_points],
    }
    if res.rep_tags:
        out["rep_tags"] = {t: _walk(list(vs)) for t, vs in res.rep_tags.items()}
    if res.trace is not None:
        out["trace"] = res.trace
    if getattr(res, "degraded", False):
        out["degraded"] = True
        out["unavailable_nodes"] = sorted(res.unavailable_nodes)
    return out


class _Kind(enum.IntEnum):
    A = 1
    B = 2


def _pctl(k: int, q: int = 2) -> QueryResult:
    rng = np.random.default_rng(k)
    return QueryResult(
        groups=[("svc_%06d" % i,) for i in range(k)],
        values={
            "percentile(value)": rng.random((k, q)).tolist(),
            "count": rng.integers(1, 9, k).astype(np.float64).tolist(),
        },
    )


# name -> (QueryResult, columns expected native, columns expected walked)
CASES = {
    "string-keys-percentile": (_pctl(500, 3), 3, 0),
    "string-keys-percentile-50k": (_pctl(50_000), 3, 0),
    "int-keys": (
        QueryResult(
            groups=[(i, i % 7) for i in range(40)],
            values={"sum(v)": [float(i) * 0.5 for i in range(40)]},
        ),
        2, 0,
    ),
    "binary-group-tag": (
        QueryResult(
            groups=[(b"\x00\xffraw%d" % i, "s") for i in range(5)],
            values={"count": [1.0] * 5},
        ),
        1, 1,
    ),
    "rep-tags-with-none": (
        QueryResult(
            groups=[("a",), ("b",), ("c",)],
            values={"max(v)": [3.0, None, 1.5]},
            rep_tags={"region": ["eu", None, "us"], "pod": [None, None, None]},
        ),
        4, 0,
    ),
    "bool-values": (
        QueryResult(
            groups=[(True,), (False,)],
            values={"flag": [True, False], "n": [1, 0]},
        ),
        3, 0,
    ),
    "np-float64-in-a-column": (
        QueryResult(
            groups=[("a",), ("b",)],
            values={"sum(v)": [1.0, np.float64(2.5)], "count": [1.0, 2.0]},
        ),
        2, 1,
    ),
    "np-float64-in-a-row": (
        QueryResult(
            groups=[("a",), ("b",)],
            values={"percentile(v)": [[1.0, 2.0], [np.float64(3.0), 4.0]]},
        ),
        1, 1,
    ),
    "int-enum-key": (
        QueryResult(groups=[(_Kind.A,), (_Kind.B,)], values={"count": [1, 2]}),
        1, 1,
    ),
    "mixed-tuples": (
        QueryResult(
            groups=[("a", 1, 2.5, True, None), ("b", -3, float("inf"), False, "x")],
            values={
                "pair": [(1, "one"), (2.0, None)],
                "nested-lists": [[1, 2], (3, 4)],
            },
        ),
        3, 0,
    ),
    "mixed-scalars-and-rows": (
        QueryResult(groups=[("a",), ["b"]], values={"v": [1.0, [2.0, 3.0]]}),
        1, 1,
    ),
    "dict-in-a-column": (
        QueryResult(groups=[("a",)], values={"v": [{"k": b"\x01"}]}),
        1, 1,
    ),
    "empty-columns": (
        QueryResult(groups=[], values={"count": [], "p": []}, rep_tags={"r": []}),
        4, 0,
    ),
    "empty-group-rows": (
        QueryResult(groups=[(), ()], values={"count": [3.0, 4.0]}),
        2, 0,
    ),
    "stream-data-points-with-bytes": (
        QueryResult(
            data_points=[
                {"ts": 1, "tags": {"body": b"\x00\x01\x02", "svc": "a"}},
                {"ts": 2, "tags": {"body": b"", "svc": None}, "seq": [1, b"z"]},
            ],
        ),
        1, 0,
    ),
    "degraded": (
        QueryResult(
            groups=[("a",), ("b",)],
            values={"count": [1.0, 2.0]},
            degraded=True,
            unavailable_nodes=["node-2", "node-1"],
        ),
        2, 0,
    ),
    "with-trace": (
        QueryResult(
            groups=[("a",)],
            values={"count": [1.0]},
            trace={"name": "root", "duration_ms": 1.5, "children": []},
        ),
        2, 0,
    ),
}


def _counted(path: str) -> float:
    text = obs_metrics.global_meter().prometheus_text()
    return sum(
        float(line.split()[-1]) for line in text.splitlines()
        if line.startswith("banyandb_reply_columns") and f'path="{path}"' in line
    )


@pytest.mark.parametrize("case", sorted(CASES))
def test_reply_equals_the_walked_reply(case):
    """Equal dicts (lists, never tuples), byte-identical JSON, and bytes
    as base64 wherever they sit."""
    res, _, _ = CASES[case]
    got, want = result_to_json(res), _reference(res)
    assert got == want
    assert json.dumps(got) == json.dumps(want)
    assert json.dumps(got, sort_keys=True) == json.dumps(want, sort_keys=True)

    def no_tuples(v):
        if isinstance(v, tuple):
            return False
        if isinstance(v, list):
            return all(map(no_tuples, v))
        if isinstance(v, dict):
            return all(map(no_tuples, v.values()))
        return True

    assert no_tuples({k: v for k, v in got.items() if k != "trace"})


@pytest.mark.parametrize("case", sorted(CASES))
def test_reply_columns_counts_each_column_by_path(case):
    """`/metrics` `reply_columns{path}`: one count a column (groups, each
    value column, each rep tag), `native` where a type scan found only
    values json.dumps takes as they are, `walked` otherwise."""
    res, n_native, n_walked = CASES[case]
    native, walked = _counted("native"), _counted("walked")
    result_to_json(res)
    assert _counted("native") - native == n_native
    assert _counted("walked") - walked == n_walked


def test_reply_does_not_alias_the_result_outer_lists():
    """The reply's column lists are fresh: growing one leaves the
    QueryResult as it was."""
    res = _pctl(4)
    out = result_to_json(res)
    out["groups"].append(["x"])
    out["values"]["count"].append(9.0)
    out["values"]["percentile(value)"].append([0.0, 0.0])
    assert len(res.groups) == 4
    assert len(res.values["count"]) == 4
    assert len(res.values["percentile(value)"]) == 4
    assert out["groups"][0] == ["svc_000000"]
