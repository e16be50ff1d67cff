"""Randomized query fuzzer: device executor vs a NumPy reference executor.

The soak/replay-diff analog (docs/soak/g5d-phase-d-summary.md: 576 runs,
0 divergences): N random queries over one dataset, each executed by the
TPU path AND by an independent pure-NumPy implementation; exact match on
counts/min/max/groups, tolerance on float sums/means.
"""

import numpy as np
import pytest

from banyandb_tpu.api import (
    Aggregation,
    Catalog,
    Condition,
    DataPointValue,
    Entity,
    FieldSpec,
    FieldType,
    Group,
    GroupBy,
    LogicalExpression,
    Measure,
    QueryRequest,
    ResourceOpts,
    SchemaRegistry,
    TagSpec,
    TagType,
    TimeRange,
    WriteRequest,
)
from banyandb_tpu.models.measure import MeasureEngine
from tests._golden_infra import numpy_exec

T0 = 1_700_000_000_000
N = 3000
N_QUERIES = 40

RNG = np.random.default_rng(1234)


@pytest.fixture(scope="module")
def dataset(tmp_path_factory):
    root = tmp_path_factory.mktemp("fuzz")
    reg = SchemaRegistry(root)
    reg.create_group(Group("g", Catalog.MEASURE, ResourceOpts(shard_num=2)))
    reg.create_measure(
        Measure(
            group="g", name="m",
            tags=(
                TagSpec("svc", TagType.STRING),
                TagSpec("region", TagType.STRING),
                TagSpec("code", TagType.INT),
            ),
            fields=(FieldSpec("v", FieldType.FLOAT),),
            entity=Entity(("svc",)),
        )
    )
    eng = MeasureEngine(reg, root / "data")
    data = {
        "svc": RNG.integers(0, 8, N),
        "region": RNG.integers(0, 4, N),
        "code": RNG.choice([200, 301, 404, 500, 503], N),
        "v": np.round(RNG.gamma(2.0, 40.0, N), 3),
        "ts": T0 + RNG.permutation(N),
    }
    eng.write(WriteRequest("g", "m", tuple(
        DataPointValue(
            int(data["ts"][i]),
            {"svc": f"s{data['svc'][i]}", "region": f"r{data['region'][i]}",
             "code": int(data["code"][i])},
            {"v": float(data["v"][i])},
            version=1,
        )
        for i in range(N)
    )))
    eng.flush()
    return eng, data


def _random_request():
    lo = int(RNG.integers(0, N // 2))
    hi = int(RNG.integers(N // 2, N + 1))
    conds = []
    if RNG.random() < 0.5:
        conds.append(Condition("svc", RNG.choice(["eq", "ne"]), f"s{RNG.integers(0, 10)}"))
    if RNG.random() < 0.4:
        vals = [f"r{i}" for i in RNG.choice(4, size=RNG.integers(1, 3), replace=False)]
        conds.append(Condition("region", RNG.choice(["in", "not_in"]), vals))
    if RNG.random() < 0.4:
        conds.append(Condition("code", RNG.choice(["lt", "le", "gt", "ge"]),
                               int(RNG.choice([200, 301, 404, 500]))))
    criteria = None
    for c in conds:
        criteria = c if criteria is None else LogicalExpression("and", criteria, c)
    gb_choices = [None, ("svc",), ("region",), ("svc", "region")]
    group_by = gb_choices[RNG.integers(0, len(gb_choices))]
    fn = RNG.choice(["count", "sum", "min", "max", "mean"])
    return QueryRequest(
        ("g",), "m", TimeRange(T0 + lo, T0 + hi),
        criteria=criteria,
        group_by=GroupBy(group_by) if group_by else None,
        agg=Aggregation(fn, "v"),
        limit=0,
    ), (lo, hi), conds, group_by, fn


def _numpy_exec(data, lo, hi, conds, group_by, fn):
    mask = (data["ts"] >= T0 + lo) & (data["ts"] < T0 + hi)
    for c in conds:
        if c.name == "svc":
            m = np.char.add("s", data["svc"].astype(str)) == c.value
            mask &= m if c.op == "eq" else ~m
        elif c.name == "region":
            m = np.isin(np.char.add("r", data["region"].astype(str)), c.value)
            mask &= m if c.op == "in" else ~m
        else:
            cmp = {"lt": np.less, "le": np.less_equal,
                   "gt": np.greater, "ge": np.greater_equal}[c.op]
            mask &= cmp(data["code"], c.value)
    keys = {
        "svc": np.char.add("s", data["svc"].astype(str)),
        "region": np.char.add("r", data["region"].astype(str)),
    }
    return numpy_exec(mask, [keys[t] for t in group_by or ()], data["v"])


def test_fuzz_device_vs_numpy(dataset):
    eng, data = dataset
    divergences = []
    for q in range(N_QUERIES):
        req, (lo, hi), conds, group_by, fn = _random_request()
        res = eng.query(req)
        oracle = _numpy_exec(data, lo, hi, conds, group_by, fn)
        got = dict(zip(res.groups, res.values[f"{fn}(v)"]))
        expect = {}
        for k, vals in oracle.items():
            if len(vals) == 0:
                continue
            expect[k] = {
                "count": float(len(vals)), "sum": vals.sum(),
                "min": vals.min(), "max": vals.max(), "mean": vals.mean(),
            }[fn]
        if group_by is None:
            # ungrouped always reports one row (0 for empty)
            e = expect.get((), 0.0 if fn == "count" else None)
            g = got.get((), None)
            if e is None:
                continue  # empty + non-count: value is degenerate
            if not np.isclose(g, e, rtol=1e-4, atol=1e-3):
                divergences.append((q, (), g, e))
            continue
        if set(got) != set(expect):
            divergences.append((q, "groups", sorted(got), sorted(expect)))
            continue
        for k in expect:
            if not np.isclose(got[k], expect[k], rtol=1e-4, atol=1e-3):
                divergences.append((q, k, got[k], expect[k]))
    assert not divergences, divergences[:5]
