"""The yardstick's own fast tests, as cases of tier-1.

`benchmarks/e2e/tests/` is not under `tests/`, so the driver's tier-1 run
never collected it: a PR could add a configuration, a traffic mix or a
metric file that `selfcheck.py` refuses (an unresolved name, a panel its
`LIMIT` would truncate, a metric file that disagrees with BENCHMARK.json)
and learn it only on the chip.  This file loads every `test_*.py` there
but `test_faults.py` (which boots a server on the default ports for
minutes) and hands pytest their tests and fixtures under this module's
name, so each case, parametrised ones included, is a tier-1 case:
selfcheck.py's six checks, its `main`, the generator's LIMIT against the
program's grammar, `truncated`, `warm_at`, and the controls of `correct`
at each cell's own size (NumPy, seconds).

Those files say `from conftest import E2E`, meaning their own conftest.py,
while `conftest` is tests/conftest.py here: theirs stands in under that
name for as long as their modules are being executed, and no longer.
"""

import glob
import importlib.util
import json
import os
import sys

import pytest
from _pytest.fixtures import FixtureFunctionDefinition

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_TESTS = os.path.join(CHECKOUT, "benchmarks", "e2e", "tests")
SLOW = {"test_faults.py"}


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _adopt() -> list[str]:
    """Every test and fixture of the benchmark's fast test files into
    this module's namespace -> the files adopted.  A test goes behind
    its file's name (`test_control__test_control_…`); a fixture keeps
    its own, because tests ask for it by argument name, so two files
    may not both define one of a name: that is an error here, not a
    silent swap."""
    ours = sys.modules.get("conftest")
    sys.modules["conftest"] = _load(
        os.path.join(BENCH_TESTS, "conftest.py"), "bench_e2e_conftest"
    )
    adopted = []
    try:
        for path in sorted(glob.glob(os.path.join(BENCH_TESTS, "test_*.py"))):
            base = os.path.basename(path)
            if base in SLOW:
                continue
            mod = _load(path, "bench_e2e_" + base[:-3])
            for attr, obj in vars(mod).items():
                if attr.startswith("test_") and callable(obj):
                    globals()[f"{base[:-3]}__{attr}"] = obj
                elif isinstance(obj, FixtureFunctionDefinition):
                    if attr in globals():
                        raise ImportError(f"{base}: a second fixture named {attr!r}")
                    globals()[attr] = obj
            adopted.append(base)
    finally:
        if ours is not None:
            sys.modules["conftest"] = ours
        else:
            del sys.modules["conftest"]
    return adopted


ADOPTED = _adopt()


def test_the_fast_files_were_adopted():
    assert {
        "test_selfcheck.py", "test_control.py", "test_control_ep9k.py", "test_control_ep400k.py",
        "test_control_r1ep9k.py", "test_control_ep400k_pctl.py",
    } <= set(ADOPTED)
    assert not SLOW & set(ADOPTED)
    assert any(k.startswith("test_selfcheck__test_selfcheck") for k in globals())


# -- metric files a program PR added as data alone ------------------------------------

def _bench_json(*parts: str):
    with open(os.path.join(CHECKOUT, *parts)) as f:
        return json.load(f)


def _reduce_tree(
    tags: dict, gather: dict | None = None, qos: dict | None = None, decode: dict | None = None,
) -> dict:
    return {"name": "measure-query", "children": [
        {"name": "qos", "tags": dict({"tenant": "default", "queued_ms": 0.004}, **(qos or {}))},
        {"name": "execute", "children": [
            {"name": "gather", "tags": dict({"rows": 3240000}, **(gather or {}))},
            {"name": "reduce", "tags": tags, "children": [{"name": "decode", "tags": decode or {}}]},
        ]},
    ]}


@pytest.mark.parametrize(
    "tags, want",
    [
        # 4 real chunks the planner's hint rounds up to the 8-bucket
        pytest.param({"chunks": 4, "chunks_skipped": 4, "dispatches": 1}, 4.0, id="tagged"),
        pytest.param({"chunks": 1, "chunks_skipped": 0, "dispatches": 1}, 0.0, id="nothing-skipped"),
        # a program from before the tag (the parent of ISSUE 31): left out
        pytest.param({"chunks": 4, "dispatches": 1}, None, id="no-tag-left-out"),
    ],
)
def test_skipped_chunks_per_query_reads_the_reduce_span(tags, want):
    """`metrics/skipped_chunks_per_query.json` is data for the `span_tag`
    reader that is there: it reads the `reduce` span's `chunks_skipped`,
    and where the program has no such tag it returns nothing, so the
    line leaves the metric out and does not raise."""
    readers = _load(os.path.join(CHECKOUT, "benchmarks", "e2e", "readers.py"), "bench_e2e_readers")
    metric = _bench_json("benchmarks", "e2e", "metrics", "skipped_chunks_per_query.json")
    assert metric["reader"] == {"kind": "span_tag", "span": "reduce", "tag": "chunks_skipped"}
    rec = {"queries": [{"served": "scan", "tree": _reduce_tree(tags)} for _ in range(3)]}
    assert readers.read(metric, rec) == want


# ISSUE 33: four files for the tags `ep400k.topn-7d` is read by,
# ISSUE 35: four for what ran beside a query (`r1ep9k.topn-15m-c50`), and
# ISSUE 36: whether the plan's program tracked scan order,
# ISSUE 37: six for where a request waited (the bus pool's queue; whether
# the gather's and the pad thunks' threads ran or waited; their page
# faults); name ->
# (the reader the file must hold, [(case, {span: tags}, what it reads)])
SPAN_TAG_FILES = {
    "gather_lut_ms": (
        {"kind": "span_tag", "span": "gather", "tag": "lut_ms"},
        [("tagged", {"gather": {"select_ms": 2266.1, "lut_ms": 2235.4}}, 2235.4),
         ("no-tag", {"gather": {"select_ms": 2266.1}}, None)],
    ),
    "lut_entries_per_query": (
        {"kind": "span_tag", "span": "gather", "tag": "lut_entries"},
        # 28 sources x (400,000 names + 8 regions); 0 under the cap
        [("tagged", {"gather": {"lut_entries": 28 * 400008, "dict_reset": True}}, 11200224.0),
         ("under-the-cap", {"gather": {"lut_entries": 0, "dict_reset": False}}, 0.0),
         ("no-tag", {}, None)],
    ),
    "partials_mb_per_query": (
        {"kind": "span_tag", "span": "reduce", "tag": "partials_bytes", "scale": 1e-06},
        # the 4-bucket x G = 400,000 x 16 B
        [("tagged", {"reduce": {"partials_bytes": 4 * 400000 * 16, "get_ms": 264.0}}, 25.6),
         ("no-tag", {"reduce": {"get_ms": 264.0}}, None)],
    ),
    "absorb_ms": (
        {"kind": "span_tag", "span": "reduce", "tag": "absorb_ms"},
        [("tagged", {"reduce": {"absorb_ms": 12.532, "host_ms": 139.172}}, 12.532),
         ("no-tag", {"reduce": {"host_ms": 139.172}}, None)],
    ),
    "inflight_per_query": (
        {"kind": "span_tag", "span": "qos", "tag": "inflight"},
        # the eight bus workers' queries; 1 for a query alone
        [("tagged", {"qos": {"inflight": 8, "rpc_busy": 8}}, 8.0),
         ("alone", {"qos": {"inflight": 1, "rpc_busy": 1}}, 1.0),
         ("no-tag", {}, None)],
    ),
    "rpc_busy_per_query": (
        {"kind": "span_tag", "span": "qos", "tag": "rpc_busy"},
        [("tagged", {"qos": {"inflight": 7, "rpc_busy": 8}}, 8.0),
         ("no-tag", {}, None)],
    ),
    "dispatches_ahead_per_query": (
        {"kind": "span_tag", "span": "reduce", "tag": "dispatches_ahead"},
        [("tagged", {"reduce": {"dispatches_ahead": 3, "get_ms": 40.0}}, 3.0),
         ("alone", {"reduce": {"dispatches_ahead": 0, "get_ms": 10.0}}, 0.0),
         ("no-tag", {"reduce": {"get_ms": 10.0}}, None)],
    ),
    "dict_lock_wait_ms": (
        {"kind": "span_tag", "span": "gather", "tag": "dict_lock_wait_ms"},
        [("tagged", {"gather": {"dict_lock_wait_ms": 9.317}}, 9.317),
         ("no-tag", {"gather": {"select_ms": 4.2}}, None)],
    ),
    "scan_order_tracked_per_query": (
        {"kind": "span_tag", "span": "reduce", "tag": "scan_order_tracked"},
        # a listing (`svc1k.pctl-6h`) tracks; a TOP 10 that projects no tag does not
        [("listing", {"reduce": {"scan_order_tracked": 1, "groups": 1000}}, 1.0),
         ("topn", {"reduce": {"scan_order_tracked": 0, "groups": 9000}}, 0.0),
         ("no-tag", {"reduce": {"groups": 9000}}, None)],
    ),
    "pool_wait_ms": (
        {"kind": "span_tag", "span": "qos", "tag": "pool_wait_ms"},
        # 42 of 50 clients wait in front of 8 workers; a request alone finds one free
        [("queued", {"qos": {"rpc_busy": 8, "pool_wait_ms": 652.318}}, 652.318),
         ("alone", {"qos": {"rpc_busy": 1, "pool_wait_ms": 0.041}}, 0.041),
         ("no-tag", {"qos": {"rpc_busy": 8}}, None)],
    ),
    "gather_off_cpu_ms": (
        {"kind": "span_tag", "span": "gather", "tag": "off_cpu_ms"},
        [("waited", {"gather": {"off_cpu_ms": 38.2, "minflt": 12, "tid": 4711}}, 38.2),
         ("ran", {"gather": {"off_cpu_ms": 0.0, "minflt": 22100, "tid": 4711}}, 0.0),
         ("no-tag", {"gather": {"select_ms": 4.2}}, None)],
    ),
    "gather_minor_faults_per_query": (
        {"kind": "span_tag", "span": "gather", "tag": "minflt"},
        # a first touch of 91 MB is ~22,000 faults of 4,096 B; pages handed back none
        [("first-touch", {"gather": {"off_cpu_ms": 0.3, "minflt": 22216}}, 22216.0),
         ("reused", {"gather": {"off_cpu_ms": 0.3, "minflt": 0}}, 0.0),
         ("no-tag", {"gather": {"select_ms": 4.2}}, None)],
    ),
    "pack_off_cpu_ms": (
        {"kind": "span_tag", "span": "decode", "tag": "pack_off_cpu_ms"},
        [("waited", {"decode": {"pack_ms": 17.7, "pack_off_cpu_ms": 11.4, "pack_minflt": 480}}, 11.4),
         ("ran", {"decode": {"pack_ms": 6.0, "pack_off_cpu_ms": 0.0, "pack_minflt": 480}}, 0.0),
         ("no-tag", {"decode": {"pack_ms": 6.0}}, None)],
    ),
    "pack_minor_faults_per_query": (
        {"kind": "span_tag", "span": "decode", "tag": "pack_minflt"},
        [("tagged", {"decode": {"pack_ms": 61.6, "pack_minflt": 14400}}, 14400.0),
         ("reused", {"decode": {"pack_ms": 27.5, "pack_minflt": 0}}, 0.0),
         ("no-tag", {"decode": {"pack_ms": 27.5}}, None)],
    ),
    "packed_mb_per_query": (
        {"kind": "span_tag", "span": "decode", "tag": "packed_bytes", "scale": 1e-06},
        # a Top-N over [8, 1M]: the tag and field columns + the valid mask;
        # a listing over [1, 1M] ships ts and row besides
        [("topn", {"decode": {"shipped_bytes": 58982400, "packed_bytes": 58982400 + (8 << 20)}},
          67.371008),
         ("listing", {"decode": {"shipped_bytes": 4194304, "packed_bytes": 4194304 + (9 << 20)}},
          13.631488),
         ("no-tag", {"decode": {"shipped_bytes": 58982400}}, None)],
    ),
}
# the count of requests the server works on at once is better higher; a
# wait, and everything of ISSUE 33's, lower
BETTER_HIGHER = {"inflight_per_query", "rpc_busy_per_query"}


@pytest.mark.parametrize(
    "name, tags, want",
    [
        pytest.param(name, tags, want, id=f"{name}-{case}")
        for name, (_, cases) in SPAN_TAG_FILES.items()
        for case, tags, want in cases
    ],
)
def test_a_metric_file_added_as_data_reads_its_span_tag(name, tags, want):
    """Each file is data for the `span_tag` reader that is there; where
    the program has no such tag (the parent of ISSUE 33, 35, 36 or 37) it
    returns nothing and does not raise, and BENCHMARK.json's entry agrees
    with the file and names no `workloads`: every cell reports it."""
    readers = _load(os.path.join(CHECKOUT, "benchmarks", "e2e", "readers.py"), "bench_e2e_readers")
    metric = _bench_json("benchmarks", "e2e", "metrics", name + ".json")
    assert metric["reader"] == SPAN_TAG_FILES[name][0]
    assert "cells" not in metric
    assert metric["better"] == ("higher" if name in BETTER_HIGHER else "lower")
    (entry,) = [m for m in _bench_json("BENCHMARK.json")["per_layer"] if m["name"] == name]
    assert entry == {k: metric[k] for k in ("name", "unit", "better", "source", "layer", "moves")}
    assert entry["moves"] == "query_p50_ms"
    tree = _reduce_tree(
        tags.get("reduce", {}), tags.get("gather"), tags.get("qos"), tags.get("decode"),
    )
    rec = {"queries": [{"served": "scan", "tree": tree} for _ in range(3)]}
    got = readers.read(metric, rec)
    assert got == (want if want is None else pytest.approx(want))


def _ql_tree(signature: bool) -> dict:
    """A `bydbql` answer's tree as the server builds it; without
    `signature` as the parent of ISSUE 37 does, where the plan signature
    ran inside `execute` under no span."""
    under_execute = [
        {"name": "gather", "start_ms": 3.0, "duration_ms": 40.0, "children": []},
        {"name": "reduce", "start_ms": 50.0, "duration_ms": 30.0,
         "children": [{"name": "decode", "duration_ms": 12.0, "children": []}]},
        {"name": "merge", "start_ms": 80.5, "duration_ms": 1.0, "children": []},
    ]
    if signature:
        under_execute.insert(
            1, {"name": "signature", "start_ms": 43.0, "duration_ms": 6.5, "children": []}
        )
    return {"name": "standalone:measure", "start_ms": 0.0, "duration_ms": 85.0, "children": [
        {"name": "parse", "duration_ms": 0.1, "children": []},
        {"name": "qos", "duration_ms": 0.02, "children": []},
        {"name": "analyze", "duration_ms": 0.05, "children": []},
        {"name": "planner", "duration_ms": 0.4, "children": []},
        {"name": "part_gather", "duration_ms": 1.2, "children": []},
        {"name": "execute", "start_ms": 2.5, "duration_ms": 82.0, "children": under_execute},
    ]}


# ISSUE 37: the milliseconds that lay in no span; name -> (the span whose
# self time the file reads, what it reads with the `signature` span, and
# on the parent's tree)
SPAN_SELF_FILES = {
    "signature_ms": ("signature", 6.5, None),
    # 82 - (40 + 6.5 + 30 + 1): the parent's holds the signature's 6.5 too
    "execute_self_ms": ("execute", 4.5, 11.0),
    # 85 - (0.1 + 0.02 + 0.05 + 0.4 + 1.2 + 82): what no child of the handler covers
    "root_self_ms": ("standalone:measure", 1.23, 1.23),
}


@pytest.mark.parametrize("signature", [True, False], ids=["with-signature", "parent"])
@pytest.mark.parametrize("name", sorted(SPAN_SELF_FILES))
def test_a_metric_file_added_as_data_reads_its_span_self_time(name, signature):
    """Each file is data for the `span_self_ms` reader that is there.
    `execute_self_ms` and `root_self_ms` read on the parent of ISSUE 37
    too (its before and after: `execute`'s self time falls by what
    `signature` now covers); `signature_ms` finds no such span there,
    returns nothing and does not raise."""
    readers = _load(os.path.join(CHECKOUT, "benchmarks", "e2e", "readers.py"), "bench_e2e_readers")
    metric = _bench_json("benchmarks", "e2e", "metrics", name + ".json")
    span, with_sig, on_parent = SPAN_SELF_FILES[name]
    assert metric["reader"] == {"kind": "span_self_ms", "span": span}
    assert "cells" not in metric and metric["better"] == "lower" and metric["unit"] == "ms"
    (entry,) = [m for m in _bench_json("BENCHMARK.json")["per_layer"] if m["name"] == name]
    assert entry == {k: metric[k] for k in ("name", "unit", "better", "source", "layer", "moves")}
    assert entry["moves"] == "query_p50_ms" and entry["source"] == "program_span"
    rec = {"queries": [{"served": "scan", "tree": _ql_tree(signature)} for _ in range(3)]}
    want = with_sig if signature else on_parent
    got = readers.read(metric, rec)
    assert got == (want if want is None else pytest.approx(want))


def test_ep400k_is_a_cell_at_issue_33s_size():
    """The deployment as ISSUE 33 names it: one configuration, one cell
    on one chip, `ep9k`'s guarantees word for word, and a size over
    `BYDB_MAX_PERSISTENT_GROUPS`' default, one day a message."""
    bench = _bench_json("BENCHMARK.json")
    (entry,) = [c for c in bench["configs"] if c["name"] == "ep400k"]
    (cell,) = [w for w in bench["workloads"] if w["name"] == "ep400k.topn-7d"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("ep400k", "topn-7d", 1)
    assert all(w["chips"] == 1 for w in bench["workloads"])
    cfg = _bench_json(entry["file"])
    assert cfg["guarantees"] == _bench_json("benchmarks", "e2e", "configs", "ep9k.json")["guarantees"]
    assert cfg["source"] == entry["source"] and len(entry["source"]) <= 200
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"]) == ["buckets", "measures", "series"]
    assert "HIDES" in cfg["reduced"]["series"]
    data = cfg["data"]
    assert data["series"] == 400000 > 1 << 18
    assert (data["batch_rows"], data["snapshot_every_rows"], data["buckets"]) == (400000, 800000, 8)
    assert cfg["schema"]["shards"] == 4 and cfg["schema"]["segment_interval_days"] == 15
    mix = _bench_json("benchmarks", "e2e", "traffic", "topn-7d.json")
    panel = mix["panels"]["topn"]
    assert mix["clients"] == 1 and panel["top"] == 10
    assert panel["range_ms"] == 7 * data["bucket_ms"]


def test_r1ep9k_is_a_cell_at_issue_35s_size():
    """The deployment as ISSUE 35 names it: upstream's published query
    condition on `ep9k`'s estate: `ep9k`'s schema, distributions and
    guarantees word for word, 6 h held in quarter-hour messages, and
    fifty closed-loop clients over drawn quarter hours of `topn-6h`'s
    text."""
    bench = _bench_json("BENCHMARK.json")
    (entry,) = [c for c in bench["configs"] if c["name"] == "r1ep9k"]
    (cell,) = [w for w in bench["workloads"] if w["config"] == "r1ep9k"]
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        "r1ep9k.topn-15m-c50", "topn-15m-c50", 1,
    )
    cfg = _bench_json(entry["file"])
    ep9k = _bench_json("benchmarks", "e2e", "configs", "ep9k.json")
    assert cfg["schema"] == ep9k["schema"]
    assert cfg["guarantees"][:7] == ep9k["guarantees"] and len(cfg["guarantees"]) == 8
    assert cfg["source"] == entry["source"] and len(entry["source"]) <= 200
    assert "50 concurrent" in entry["source"] and "15 min" in entry["source"]
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"]) == ["buckets", "measures"]
    assert "HIDES" in cfg["reduced"]["buckets"]
    data = cfg["data"]
    same = ("series", "regions", "bucket_ms", "t0_ms", "order", "hits", "value")
    assert {k: data[k] for k in same} == {k: ep9k["data"][k] for k in same}
    assert (data["series"], data["regions"], data["buckets"]) == (9000, 8, 360)
    assert data["batch_rows"] == data["snapshot_every_rows"] == 15 * data["series"]
    mix = _bench_json("benchmarks", "e2e", "traffic", "topn-15m-c50.json")
    panel = dict(mix["panels"]["topn"])
    assert (mix["loop"], mix["clients"], mix["cycle"]) == ("closed", 50, ["topn"])
    assert mix["warm_spread"] >= 12
    assert panel.pop("range_ms") == 15 * data["bucket_ms"]
    # any quarter hour of the six held, and the text of `topn-6h` but for the range
    assert panel.pop("lo") == {"draw_ms": [0, (data["buckets"] - 16) * data["bucket_ms"]]}
    other = dict(_bench_json("benchmarks", "e2e", "traffic", "topn-6h.json")["panels"]["topn"])
    del other["range_ms"], other["lo"]
    assert panel == other
    # the four cells it joined are still there, and still one client each
    older = ("topn-24h", "pctl-6h", "topn-6h", "topn-7d")
    assert [w["traffic"] for w in bench["workloads"][:4]] == list(older)
    for name in older:
        assert _bench_json("benchmarks", "e2e", "traffic", name + ".json")["clients"] == 1


# the two metrics of the percentile histogram's layers, read in the two
# cells that run a percentile plan: name -> (the reader the file must
# hold, [(case, tree, what it reads)])
PERCENTILE_CELLS = ["svc1k.pctl-6h", "ep400k.pctl-7d"]


def _merge_tree(invert_ms: float | None) -> dict:
    """A percentile answer's tree; without `invert` as the parent of the
    span builds it, where the inversion ran inside `merge`."""
    merge = {"name": "merge", "duration_ms": 9.0, "tags": {"groups": 1000}, "children": []}
    if invert_ms is not None:
        merge["children"].append(
            {"name": "invert", "duration_ms": invert_ms, "tags": {"groups": 1000},
             "children": []}
        )
    return {"name": "standalone:measure", "duration_ms": 60.0, "children": [
        {"name": "execute", "duration_ms": 50.0, "children": [merge]},
    ]}


PERCENTILE_FILES = {
    "invert_ms": (
        {"kind": "span_self_ms", "span": "invert"},
        [("inverted", _merge_tree(0.812), 0.812), ("parent", _merge_tree(None), None)],
    ),
    "hist_fetched_mb_per_query": (
        {"kind": "span_tag", "span": "reduce", "tag": "hist_fetched_bytes", "scale": 1e-06},
        # where partials combine the int32 [G, 512] crosses once; the
        # standalone path inverts on the device and fetches none of it
        [("combined", _reduce_tree({"hist_fetched_bytes": 400000 * 512 * 4}), 819.2),
         ("inverted", _reduce_tree({"hist_fetched_bytes": 0, "hist_groups": 400000}), 0.0),
         ("parent", _reduce_tree({"partials_bytes": 2486400000}), None)],
    ),
}


@pytest.mark.parametrize(
    "name, tree, want",
    [
        pytest.param(name, tree, want, id=f"{name}-{case}")
        for name, (_, cases) in PERCENTILE_FILES.items()
        for case, tree, want in cases
    ],
)
def test_a_percentile_metric_file_reads_what_the_program_records(name, tree, want):
    """Each file is data for a reader that is there, names the two cells
    that run a percentile plan (`cells` and BENCHMARK.json's `workloads`
    agree), and on the parent's tree, which has no such span or tag,
    returns nothing and does not raise."""
    readers = _load(os.path.join(CHECKOUT, "benchmarks", "e2e", "readers.py"), "bench_e2e_readers")
    metric = _bench_json("benchmarks", "e2e", "metrics", name + ".json")
    assert metric["reader"] == PERCENTILE_FILES[name][0]
    assert metric["cells"] == PERCENTILE_CELLS and metric["better"] == "lower"
    (entry,) = [m for m in _bench_json("BENCHMARK.json")["per_layer"] if m["name"] == name]
    keys = ("name", "unit", "better", "source", "layer", "moves")
    assert entry == dict({k: metric[k] for k in keys}, workloads=PERCENTILE_CELLS)
    assert entry["moves"] == "query_p50_ms"
    rec = {"queries": [{"served": "scan", "tree": tree} for _ in range(3)]}
    got = readers.read(metric, rec)
    assert got == (want if want is None else pytest.approx(want))


def test_ep400k_pctl_7d_is_the_percentile_half_of_the_node():
    """The cell: `ep400k`'s node under its percentile source, asked p50
    and p99 of every endpoint of a drawn zone over 7 days, one client, one
    chip, the three end-to-end metrics the benchmark has; the five cells
    before it are there as they were."""
    bench = _bench_json("BENCHMARK.json")
    (cell,) = [w for w in bench["workloads"] if w["traffic"] == "pctl-7d"]
    assert (cell["name"], cell["config"], cell["chips"]) == ("ep400k.pctl-7d", "ep400k-pctl", 1)
    assert bench["workloads"][-1] == cell and len(cell["why"]) <= 200
    assert [w["traffic"] for w in bench["workloads"][:5]] == [
        "topn-24h", "pctl-6h", "topn-6h", "topn-7d", "topn-15m-c50",
    ]
    mix = _bench_json("benchmarks", "e2e", "traffic", "pctl-7d.json")
    panel = mix["panels"]["pctl"]
    assert (mix["clients"], mix["warm_spread"], mix["cycle"]) == (1, 1, ["pctl"])
    assert panel["quantiles"] == [0.5, 0.99] and panel["group_by"] == "svc"
    assert panel["limit"] == 400000 == _bench_json(
        "benchmarks", "e2e", "configs", "ep400k-pctl.json"
    )["data"]["series"]
    topn = _bench_json("benchmarks", "e2e", "traffic", "topn-7d.json")["panels"]["topn"]
    assert (panel["range_ms"], panel["lo"]) == (topn["range_ms"], topn["lo"])
    assert all("workloads" not in m for m in bench["end_to_end"])


def test_ep400k_pctl_is_ep400k_s_node_under_the_percentile_source():
    """`ep400k-pctl` is the last configuration, with a file of its own: the
    node `ep400k` holds (schema, data and cuts the same, so the same seed
    loads the same points), under BASELINE.json's percentile fan-out line
    (configs[4]) instead of the TopN/percentile one, with the guarantees a
    percentile answer is held to and no TopN one."""
    bench = _bench_json("BENCHMARK.json")
    entry, old = bench["configs"][-1], [c for c in bench["configs"] if c["name"] == "ep400k"][0]
    assert [c["name"] for c in bench["configs"]] == [
        "topn100k", "svc1k", "ep9k", "ep400k", "r1ep9k", "ep400k-pctl",
    ]
    assert entry["file"] == "benchmarks/e2e/configs/ep400k-pctl.json" != old["file"]
    assert entry["reduced"] == old["reduced"] and entry["source"] != old["source"]
    assert "BASELINE.json configs[4]" in entry["source"] and "percentile" in entry["source"]
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    cfg = _bench_json(*entry["file"].split("/"))
    node = _bench_json(*old["file"].split("/"))
    assert (cfg["name"], cfg["source"]) == (entry["name"], entry["source"])
    assert (cfg["schema"], cfg["data"], cfg["reduced"]) == (
        node["schema"], node["data"], node["reduced"]
    )
    assert [g for g in node["guarantees"] if g not in cfg["guarantees"]] == [
        g for g in node["guarantees"] if g.startswith("TopN")
    ]
    assert any("percentile" in g for g in cfg["guarantees"])
    assert [w["name"] for w in bench["workloads"] if w["config"] == "ep400k-pctl"] == [
        "ep400k.pctl-7d"
    ]
