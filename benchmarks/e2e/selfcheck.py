"""selfcheck.py - the yardstick held to values worked out another way.

    python benchmarks/e2e/selfcheck.py

Runs on the CPU in seconds, off JAX and without a server: the trace
reduction against a hand-computed case and a recorded fixture, the
oracle against a brute-force loop on a 2,000-point data set, `check`
against answers spoiled on purpose, every reader kind on a canned span
tree, and every file under configs/, traffic/, metrics/ and every entry
of BENCHMARK.json for names, units and cross-references, and that no
cell's panel asks for more groups than its LIMIT lets the server return.
It is the rehearsal gate before a chip call; tests/ under this directory
runs each check as a pytest case.
"""

from __future__ import annotations

import glob
import json
import math
import os
import re
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

import dataset  # noqa: E402
import readers  # noqa: E402
import tracereduce  # noqa: E402
import traffic  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# groups a measure query returns when its text names no LIMIT
# (banyandb_tpu/bydbql.py:232, as upstream's measure query defaults)
DEFAULT_LIMIT = 100


def load(*parts: str):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def expect(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


# -- trace reduction -----------------------------------------------------------------


def check_trace_by_hand() -> None:
    def ev(name, s, d):
        return {"device": "/device:TPU:0", "name": name, "start_ns": s, "dur_ns": d}

    events = [
        ev("%a.1 = f32[8]{0} fusion(f32[8]{0:T(8)} %p), kind=kLoop", 0, 10),
        ev("%b.2 = f32[8]{0} copy-start(f32[8]{0} %a.1)", 5, 15),  # overlaps a
        ev("%w.3 = (s32[]{:T(128)}, f32[8]{0:T(8)S(1)}) while((s32[]) %t), body=%r", 30, 10),
        ev("%a.1 = f32[8]{0} fusion(f32[8]{0:T(8)} %p), kind=kLoop", 32, 3),  # inside w
        ev("%c.4 = f32[8]{0} fusion(f32[8]{0} %w.3), kind=kLoop", 50, 10),
    ]
    red = tracereduce.reduce_events(events, 100)
    # by hand: [0,20) + [30,40) + [50,60) = 40 ns busy of 100
    expect(red["busy_s"] == 40e-9, f"busy {red['busy_s']}")
    expect(abs(red["idle_share"] - 0.6) < 1e-12, f"idle {red['idle_share']}")
    expect(
        red["gaps"]["/device:TPU:0"] == [(20, 30), (40, 50), (60, 100)],
        f"gaps {red['gaps']}",
    )
    ops = dict((k, round(v * 1e9)) for k, v in red["device_ops"])
    expect(
        ops == {"%b.2 copy-start": 15, "%a.1 fusion": 13, "%w.3 while": 10, "%c.4 fusion": 10},
        f"ops {ops}",
    )
    # one query: root 60 ns from t=0 (part_gather 22, execute 30 = gather 10
    # + reduce 20); the clock is in seconds, the gaps in ns
    tree = {"name": "root", "duration_ms": 60e-6, "children": [
        {"name": "part_gather", "duration_ms": 22e-6, "children": []},
        {"name": "execute", "duration_ms": 30e-6, "children": [
            {"name": "gather", "duration_ms": 10e-6, "children": []},
            {"name": "reduce", "duration_ms": 20e-6, "children": []},
        ]},
    ]}
    tl = tracereduce.span_timeline(tree, 0.0)
    got = tracereduce.attribute_gaps(red["gaps"]["/device:TPU:0"], 0.0, [tl], min_gap_ns=1)
    # gap mids 25, 45, 80 ns -> gather (22..32), reduce (32..52), nothing open
    want = {"between queries": 40e-9, "gather": 10e-9, "reduce": 10e-9}
    expect(
        {k: round(v, 15) for k, v in got} == {k: round(v, 15) for k, v in want.items()},
        f"gap attribution {got}",
    )


def sweep_busy_ns(events: list[dict]) -> int:
    """Busy time by counting open events at every edge: another
    algorithm than tracereduce's interval merge."""
    edges = []
    for e in events:
        if e["dur_ns"] > 0:
            edges.append((e["start_ns"], 1))
            edges.append((e["start_ns"] + e["dur_ns"], -1))
    edges.sort(key=lambda x: (x[0], -x[1]))
    busy = depth = 0
    last = None
    for t, d in edges:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


def check_trace_fixture() -> None:
    events = load(HERE, "fixtures", "xla_ops_300.json")
    want = load(HERE, "fixtures", "xla_ops_300.expected.json")
    red = tracereduce.reduce_events(events, want["window_ns"])
    expect(len(events) == want["events"], "fixture length")
    expect(round(red["busy_s"] * 1e9) == sweep_busy_ns(events), "busy != sweep-line busy")
    expect(round(red["busy_s"] * 1e9) == want["busy_ns"], f"busy {red['busy_s']}")
    expect(abs(red["idle_share"] - want["idle_share"]) < 1e-9, f"idle {red['idle_share']}")
    expect(
        [k for k, _ in red["device_ops"][:3]] == want["top3"],
        f"top ops {red['device_ops'][:3]}",
    )
    (gaps,) = red["gaps"].values()
    expect(
        sum(e - s for s, e in gaps) + want["busy_ns"] == want["window_ns"],
        "gaps + busy != window",
    )


# -- the oracle ----------------------------------------------------------------------------

SMALL = {"data": {
    "series": 50, "regions": 4, "buckets": 40, "bucket_ms": 60000, "t0_ms": 1700006400000,
    "batch_rows": 700, "snapshot_every_rows": 1400,
    "hits": {"dist": "uniform_int", "lo": 0, "hi": 999},
    "value": {"dist": "gamma", "shape": 2.0, "scale": 40.0},
}}


def over_limit(q: dict, got: dict, want: dict) -> list[str]:
    """The numbers of `dataset.gaps` that read over their limit."""
    return [k for k, v in dataset.gaps(q, got, want).items() if v > dataset.LIMITS[k]]


def brute(ds: dataset.Dataset, q: dict) -> dict:
    """The query answered point by point: {group: (count, value)}."""
    rows = ds.rows(0, ds.points)
    groups: dict[str, list[float]] = {}
    for i in range(ds.points):
        if not q["lo"] <= rows["ts"][i] <= q["hi"]:
            continue
        svc, region = "svc_%06d" % rows["svc"][i], "r%d" % rows["region"][i]
        w = q.get("where")
        if w:
            have = svc if w["tag"] == "svc" else region
            if (have == w["value"]) != (w["op"] == "="):
                continue
        key = svc if q["group_by"] == "svc" else region
        groups.setdefault(key, []).append(float(rows[q["field"]][i]))
    out = {}
    for g, vals in groups.items():
        n = len(vals)
        if q["agg"] == "percentile":
            s = sorted(vals)
            v = [s[min(max(math.ceil(x * n), 1), n) - 1] for x in q["quantiles"]]
        elif q["agg"] == "count":
            v = float(n)
        elif q["agg"] == "mean":
            v = sum(vals) / n
        else:
            v = {"sum": sum, "min": min, "max": max}[q["agg"]](vals)
        out[g] = (n, v)
    if q.get("top"):
        best = sorted(out, key=lambda g: (-out[g][1], g))[: q["top"]]
        out = {g: out[g] for g in best}
    return out


def check_oracle() -> None:
    ds = dataset.Dataset(SMALL, seed=2147483659)
    expect(ds.points == 2000, "the small data set holds 2,000 points")
    rows = ds.rows(0, ds.points)
    expect(bool((np.diff(rows["ts"]) >= 0).all()), "rows arrive time-major")
    expect(
        dataset.Dataset(SMALL, seed=2147483659).hits.tobytes() == ds.hits.tobytes()
        and dataset.Dataset(SMALL, seed=7).hits.tobytes() != ds.hits.tobytes(),
        "the same seed gives the same data, another seed other data",
    )
    lo, hi = ds.t0 + 5 * 60000 + 17, ds.t0 + 29 * 60000 + 17
    base = {"lo": lo, "hi": hi}
    specs = [
        dict(base, agg="sum", field="hits", group_by="svc", top=10,
             where={"tag": "region", "op": "!=", "value": "r2"}),
        dict(base, agg="sum", field="hits", group_by="region"),
        dict(base, agg="count", field="hits", group_by="region"),
        dict(base, agg="percentile", field="value", quantiles=[0.5, 0.99], group_by="svc"),
        dict(base, agg="percentile", field="value", quantiles=[0.5, 0.99], group_by="region"),
        dict(base, agg="mean", field="value", group_by="svc", top=5),
        dict(base, agg="max", field="value", group_by="region",
             where={"tag": "region", "op": "=", "value": "r1"}),
        dict(base, agg="min", field="hits", group_by="svc",
             where={"tag": "svc", "op": "=", "value": "svc_000007"}),
    ]
    for q in specs:
        want = ds.answer(q)
        got = brute(ds, q)
        expect(want["points"] == 24 * 50, f"points in range: {want['points']}")
        why = dataset.check(q, got, want)
        expect(why is None, f"oracle and brute force differ on {q}: {why}")
        expect(not over_limit(q, got, want), f"gaps over their limit on {q}")
        if q.get("top"):
            i = {n: k for k, n in enumerate(want["names"])}
            top = sorted(want["names"], key=lambda g: (-want["metric"][i[g]], g))[: q["top"]]
            expect(set(top) == set(got), f"top membership on {q}")
    # answers spoiled on purpose must be refused
    q = specs[0]
    want, good = ds.answer(q), brute(ds, specs[0])
    g0 = next(iter(good))
    bad_count = dict(good, **{g0: (good[g0][0] + 1, good[g0][1])})
    bad_value = dict(good, **{g0: (good[g0][0], good[g0][1] * (1 + 1e-4))})
    outsider = min(
        (n for n in want["names"] if n not in good),
        key=lambda n: want["metric"][want["names"].index(n)],
    )
    k = want["names"].index(outsider)
    bad_member = {g: v for g, v in good.items() if g != g0}
    bad_member[outsider] = (int(want["count"][k]), float(want["metric"][k]))
    short = {g: v for g, v in good.items() if g != g0}
    # each fault is refused by `check` and read over its limit by the number that is its own
    for name, bad, number in (
        ("count", bad_count, "count_gap"), ("value", bad_value, "value_gap_tol"),
        ("member", bad_member, "top_gap_tol"), ("short TOP", short, "groups_gap"),
    ):
        expect(dataset.check(q, bad, want) is not None, f"a wrong {name} passed")
        expect(number in over_limit(q, bad, want), f"a wrong {name}: {number} within its limit")
    reply = {"groups": [["r0"], ["r1"]], "values": {"count": [3, 4], "sum": [1.5, 2.5]}}
    expect(dataset.answer_of(reply) == {"r0": (3, 1.5), "r1": (4, 2.5)}, "answer_of")


def check_traffic() -> None:
    ds = dataset.Dataset(SMALL, seed=1)
    for path in sorted(glob.glob(os.path.join(HERE, "traffic", "*.json"))):
        mix = load(path)
        a = traffic.stream(mix, ds, 3000000019, 0)
        b = traffic.stream(mix, ds, 3000000019, 0)
        qa = [next(a) for _ in range(12)]
        expect(qa == [next(b) for _ in range(12)], f"{path}: the same seed, other queries")
        warm = traffic.draws(3000000019, 0, warm=True)
        for q in qa:
            panel = mix["panels"][q["panel"]]
            expect(
                traffic.repeats(panel) or q != traffic.spec(q["panel"], panel, ds, warm),
                f"{path}: a warm-up draw equals the window's",
            )
        for q in qa:
            expect((q["lo"] - ds.t0) % ds.bucket_ms != 0, "a range starts on a bucket edge")
            ql = traffic.ql_of(q, "g", "m")
            expect(ql.startswith("SELECT ") and f"BETWEEN {q['lo']} AND {q['hi']}" in ql, ql)
    pctl = {"agg": "percentile", "field": "value", "quantiles": [0.5, 0.99], "group_by": "svc",
            "lo": 1, "hi": 2}
    expect(
        traffic.ql_of(pctl, "g", "m")
        == "SELECT PERCENTILE(value, 0.5, 0.99) FROM MEASURE m IN g "
        "TIME BETWEEN 1 AND 2 GROUP BY svc",
        "percentile text",
    )
    q = {"agg": "sum", "field": "hits", "group_by": "svc", "top": 10, "lo": 1, "hi": 2,
         "where": {"tag": "region", "op": "!=", "value": "r3"}}
    expect(
        traffic.ql_of(q, "g", "m")
        == "SELECT sum(hits) FROM MEASURE m IN g TIME BETWEEN 1 AND 2 "
        "WHERE region != 'r3' GROUP BY svc TOP 10 BY hits",
        "topn text",
    )
    expect(
        traffic.ql_of(dict(pctl, limit=1000), "g", "m")
        == "SELECT PERCENTILE(value, 0.5, 0.99) FROM MEASURE m IN g "
        "TIME BETWEEN 1 AND 2 GROUP BY svc LIMIT 1000",
        "limit text",
    )
    panel = {"agg": "sum", "field": "hits", "group_by": "svc", "top": 10, "limit": 20,
             "range_ms": 60000, "lo": "last"}
    q = traffic.spec("p", panel, ds, traffic.draws(1, 0))
    expect(q["limit"] == 20, "spec copies a panel's limit")
    expect(
        traffic.ql_of(q, "g", "m").endswith(" GROUP BY svc TOP 10 BY hits LIMIT 20"),
        "the limit follows TOP",
    )


# -- readers ---------------------------------------------------------------------------------


def check_readers() -> None:
    def tree(gather_ms: float) -> dict:
        return {"name": "standalone:measure", "duration_ms": 100.0, "tags": {}, "children": [
            {"name": "part_gather", "duration_ms": 30.0, "tags": {"rows": 10}, "children": []},
            {"name": "execute", "duration_ms": 60.0, "tags": {}, "children": [
                {"name": "gather", "duration_ms": gather_ms, "tags": {}, "children": []},
                {"name": "reduce", "duration_ms": 35.0, "children": [
                    {"name": "decode", "duration_ms": 5.0, "children": [],
                     "tags": {"host_ms": 4.0, "shipped_bytes": 2_000_000}},
                ], "tags": {"device_ms": 20.0, "host_ms": 9.0, "dispatches": 1, "path": "fused"}},
            ]},
        ]}

    rec = {
        "queries": [
            {"latency_ms": 103.0, "root_ms": 100.0, "served": "scan", "tree": tree(10.0)},
            {"latency_ms": 105.0, "root_ms": 100.0, "served": "scan", "tree": tree(20.0)},
            {"latency_ms": 104.0, "root_ms": 100.0, "served": "scan", "tree": tree(14.0)},
            {"latency_ms": 2.0, "root_ms": 1.0, "served": "replay",
             "tree": {"name": "standalone:measure", "duration_ms": 1.0, "children": []}},
        ],
        "setup": {"load_points_per_s": 190000.0},
        "prom": {"before": {"compile_cache_misses": 7.0}, "after": {"compile_cache_misses": 9.0}},
        "xplane": {"busy_s": 2.0, "idle_share": 0.8, "queries_finished": 4},
    }
    cases = [
        ({"kind": "span_self_ms", "span": "gather"}, 14.0),
        ({"kind": "span_self_ms", "span": "reduce"}, 30.0),
        ({"kind": "span_self_ms", "span": "execute"}, 11.0),  # 60 - 14 - 35
        ({"kind": "span_self_ms", "span": "nothing"}, None),
        ({"kind": "span_tag", "span": "reduce", "tag": "device_ms"}, 20.0),
        ({"kind": "span_tag", "span": "decode", "tag": "shipped_bytes", "scale": 1e-6}, 2.0),
        ({"kind": "span_tag", "span": "reduce", "tag": "path"}, None),
        ({"kind": "reply_share", "field": "served", "value": "scan"}, 75.0),
        ({"kind": "prom_delta", "metric": "compile_cache_misses"}, 2.0),
        ({"kind": "prom_delta", "metric": "absent"}, None),
        ({"kind": "client", "what": "wire_ms"}, 3.5),
        ({"kind": "client", "what": "wire_ms", "where_served": "replay"}, 1.0),
        ({"kind": "setup", "key": "load_points_per_s"}, 190000.0),
        ({"kind": "setup", "key": "absent"}, None),
        ({"kind": "xplane", "what": "idle_share"}, 80.0),
        ({"kind": "xplane", "what": "busy_ms_per_query"}, 500.0),
    ]
    seen = set()
    for reader, want in cases:
        got = readers.read({"name": "t", "reader": reader}, rec)
        expect(
            got == want or (want is not None and got is not None and abs(got - want) < 1e-9),
            f"reader {reader}: {got}, by hand {want}",
        )
        seen.add(reader["kind"])
    missed = set(readers.READERS) - seen
    expect(not missed, f"reader kinds not exercised: {missed}")
    expect(readers.read({"name": "t", "reader": {"kind": "xplane", "what": "idle_share"}},
                        dict(rec, xplane=None)) is None, "no trace, no reading")


# -- the files ---------------------------------------------------------------------------------


def truncated(cfg: dict, mix: dict) -> list[str]:
    """The panels of `mix` that on deployment `cfg` can hold more
    groups than the server returns: a panel without `top` gets back at
    most its `limit` groups, DEFAULT_LIMIT when it names none, and the
    oracle holds it to every group the data holds."""
    groups = {"svc": cfg["data"]["series"], "region": cfg["data"]["regions"]}
    out = []
    for name in dict.fromkeys(mix["cycle"]):
        panel = mix["panels"][name]
        if panel.get("top"):
            continue
        n, limit = groups[panel["group_by"]], panel.get("limit") or DEFAULT_LIMIT
        if n > limit:
            out.append(f"{name}: {n} {panel['group_by']} groups, LIMIT {limit}")
    return out


def check_files(checkout: str = CHECKOUT) -> None:
    """`checkout` holds BENCHMARK.json and benchmarks/e2e/ (a spoiled
    copy, in the tests)."""
    here = os.path.join(checkout, "benchmarks", "e2e")
    bench = load(checkout, "BENCHMARK.json")
    expect(
        set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                       "end_to_end", "per_layer"},
        f"BENCHMARK.json keys {sorted(bench)}",
    )
    expect(bench["paths"] == ["benchmarks/e2e"], "paths")
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    expect("setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25, "setup_s and its bound")
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    peaks = load(here, "peaks.json")
    expect("TPU v5 lite" in peaks and peaks["source"], "peaks.json")

    deployments = {}
    for c in configs.values():
        expect(NAME.match(c["name"]) is not None, f"config name {c['name']!r}")
        expect(c["file"].startswith("benchmarks/e2e/configs/"), c["file"])
        cfg = deployments[c["name"]] = load(checkout, c["file"])
        expect(
            cfg["name"] == c["name"] and cfg["source"] == c["source"],
            f"{c['file']}: name, source",
        )
        expect(sorted(cfg["reduced"]) == sorted(c["reduced"]), f"{c['file']}: reduced")
        for key in ("stands_for", "assumed", "guarantees", "schema", "data"):
            expect(key in cfg, f"{c['file']}: no {key}")
        expect(
            all(k in cfg["data"] for k in c["reduced"]),
            f"{c['file']}: reduced names a key of data",
        )
        expect(
            any(w["config"] == c["name"] for w in cells.values()),
            f"config {c['name']} has no cell",
        )
        dataset.Dataset(dict(cfg, data=dict(cfg["data"], series=16, buckets=2)), 0)
    for w in cells.values():
        for key in ("name", "config", "traffic"):
            expect(NAME.match(w[key]) is not None, f"cell {key} {w[key]!r}")
        expect(w["config"] in configs, f"cell {w['name']}: no config {w['config']}")
        expect(w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200, f"cell {w['name']}")
        mix = load(here, "traffic", w["traffic"] + ".json")
        expect(mix["name"] == w["traffic"] and mix["loop"] == "closed", f"traffic {w['traffic']}")
        expect(all(p in mix["panels"] for p in mix["cycle"]), f"traffic {w['traffic']}: cycle")
        cut = truncated(deployments[w["config"]], mix)
        expect(not cut, f"cell {w['name']}: the server would truncate {cut}")
    for path in glob.glob(os.path.join(here, "traffic", "*.json")):
        expect(NAME.match(os.path.basename(path)[:-5]) is not None, path)

    def cells_of(m: dict) -> set:
        return set(m.get("workloads") or cells)

    for m in bench["end_to_end"] + bench["per_layer"]:
        expect(NAME.match(m["name"]) is not None, f"metric name {m['name']!r}")
        expect(UNIT.match(m["unit"]) is not None, f"unit {m['unit']!r}")
        expect(m["better"] in ("lower", "higher") and m["source"] in SOURCES, m["name"])
        expect(cells_of(m) <= set(cells), f"{m['name']}: unknown cell")
    for m in bench["end_to_end"]:
        expect(
            m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25,
            m["name"],
        )
    files = {}
    for path in glob.glob(os.path.join(here, "metrics", "*.json")):
        m = load(path)
        expect(os.path.basename(path) == m["name"] + ".json", path)
        expect(m["reader"]["kind"] in readers.READERS, f"{path}: reader kind")
        files[m["name"]] = m
    layers = set()
    for m in bench["per_layer"]:
        f = files.get(m["name"])
        expect(f is not None, f"per-layer metric {m['name']} has no file under metrics/")
        for key in ("unit", "better", "layer", "moves", "source"):
            expect(f[key] == m[key], f"metrics/{m['name']}.json: {key} differs from BENCHMARK.json")
        expect(set(f.get("cells") or cells) == cells_of(m), f"{m['name']}: cells differ")
        expect(m["moves"] in e2e, f"{m['name']} moves {m['moves']!r}")
        expect(
            cells_of(m) <= cells_of(e2e[m["moves"]]),
            f"{m['name']}: moves a metric its cells lack",
        )
        layers.add(m["layer"])
    expect(
        set(files) == {m["name"] for m in bench["per_layer"]},
        "a metric file BENCHMARK.json lacks",
    )
    for w in cells:
        here = [m["name"] for m in bench["end_to_end"] if w in cells_of(m)]
        expect("setup_s" in here and len(here) >= 2, f"cell {w}: end-to-end metrics {here}")
        expect(any(w in cells_of(m) for m in bench["per_layer"]), f"cell {w}: no per-layer metric")


def main() -> int:
    checks = [
        check_trace_by_hand, check_trace_fixture, check_oracle, check_traffic,
        check_readers, check_files,
    ]
    for c in checks:
        c()
        print(f"ok  {c.__name__}")
    print(f"selfcheck passed: {len(checks)} checks")
    return 0


if __name__ == "__main__":
    sys.exit(main())
