"""One span system on the profiler's clock (docs/observability.md):
span start / identity keys, the annotation hook, spans open while their
work runs, phase tags that sum to their span, compile events, the named
scopes and jit names in the lowered plans, and the device-trace
reduction (obs/devtrace).  No server subprocess; CPU only."""

import json
import os
import threading

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
from banyandb_tpu.obs import devtrace, tracer
from banyandb_tpu.obs.tracer import Span, Tracer, find_span, iter_spans
from banyandb_tpu.query import fused_exec, measure_exec
from banyandb_tpu.storage.part import ColumnData
from banyandb_tpu.utils import compile_cache

T0 = 1_700_000_000_000
HERE = os.path.dirname(os.path.abspath(__file__))


class FakeAnnotations:
    """Stands in for jax.profiler.TraceAnnotation: records enter/leave."""

    def __init__(self):
        self.log: list[tuple[str, str]] = []  # list.append is GIL-atomic
        self.kwargs: list[dict] = []

    def __call__(self, name, **kw):
        hook, self_kw = self, kw

        class _Ann:
            def __enter__(self):
                hook.log.append(("enter", name))
                hook.kwargs.append(self_kw)
                return self

            def __exit__(self, *exc):
                hook.log.append(("leave", name))

        return _Ann()

    def count(self, what: str, name: str) -> int:
        return self.log.count((what, name))

    def index(self, what: str, name: str, last: bool = False) -> int:
        hits = [i for i, e in enumerate(self.log) if e == (what, name)]
        return hits[-1] if last else hits[0]


@pytest.fixture()
def annotations():
    fake = FakeAnnotations()
    tracer.set_annotation_hook(fake)
    try:
        yield fake
    finally:
        tracer.set_annotation_hook(None)


# -- a tiny query through compute_partials -------------------------------------


def _query(kind: str, n: int = 4096, sources: int = 2):
    rng = np.random.default_rng(11)
    m = Measure(
        group="g", name="m",
        tags=(TagSpec("svc", TagType.STRING), TagSpec("region", TagType.STRING)),
        fields=(FieldSpec("hits", FieldType.INT), FieldSpec("value", FieldType.FLOAT)),
        entity=Entity(("svc",)),
    )
    svc = [b"s%03d" % i for i in range(32)]
    region = [b"r%d" % i for i in range(4)]
    per = n // sources
    srcs = [
        ColumnData(
            ts=T0 + (k * per + np.arange(per, dtype=np.int64)),
            series=np.arange(per, dtype=np.int64) % 32,
            version=np.ones(per, dtype=np.int64),
            tags={
                "svc": rng.integers(0, 32, per).astype(np.int32),
                "region": rng.integers(0, 4, per).astype(np.int32),
            },
            fields={
                "hits": rng.integers(0, 100, per).astype(np.float64),
                "value": rng.random(per) * 100,
            },
            dicts={"svc": svc, "region": region},
        )
        for k in range(sources)
    ]
    rng_t = TimeRange(T0, T0 + n + 1)
    # a predicate and a two-tag key, so every stage of the plan has
    # operations of its own (one int32 key column alone lowers to none)
    criteria = Condition("region", "ne", "r1")
    group_by = GroupBy(("svc", "region"))
    if kind == "topn":
        req = QueryRequest(
            ("g",), "m", rng_t, criteria=criteria, group_by=group_by,
            agg=Aggregation("sum", "hits"), top=Top(5, "hits"),
        )
    else:
        req = QueryRequest(
            ("g",), "m", rng_t, criteria=criteria, group_by=group_by,
            agg=Aggregation("percentile", "value", quantiles=(0.5, 0.99)),
        )
    return m, req, srcs


def _run(kind: str, **kw) -> dict:
    m, req, srcs = _query(kind, **kw)
    root = Span("execute")
    part = measure_exec.compute_partials(m, req, srcs, span=root)
    measure_exec.finalize_partials(m, req, [part], span=root)
    return root.to_dict()


# -- A: start, identity, the annotation hook -----------------------------------


def test_span_tree_carries_start_and_identity():
    tr = Tracer("root")
    with tr.span("a"):
        with tr.span("a1"):
            pass
    with tr.span("b"):
        pass
    tree = tr.finish()
    assert tree["start_ms"] == 0.0
    assert tree["start_unix_ms"] > 1.6e12
    assert len(tree["trace_id"]) == 16
    assert tr.root.children[0].trace_id == tree["trace_id"]

    def check(node):
        prev = node["start_ms"]
        for c in node["children"]:
            assert c["start_ms"] >= prev  # ordered, not before the parent
            assert (
                c["start_ms"] + c["duration_ms"]
                <= node["start_ms"] + node["duration_ms"] + 0.01
            )
            prev = c["start_ms"]
            check(c)

    check(tree)
    assert all("start_ms" in s for s in iter_spans(tree))
    # one id per request
    assert Tracer("other").finish()["trace_id"] != tree["trace_id"]


def test_graft_is_tagged_with_the_request_trace_id():
    tr = Tracer("liaison:measure")
    with tr.span("scatter:n0") as sp:
        sp.attach({"name": "data:n0", "duration_ms": 1.0, "tags": {}, "children": []})
    tree = tr.finish()
    assert tree["children"][0]["tags"]["trace_id"] == tree["trace_id"]


@pytest.mark.parametrize("fail", [False, True], ids=["ok", "error"])
def test_annotation_entered_and_left_once_per_span(annotations, fail):
    tr = Tracer("root")
    try:
        with tr.span("work") as sp:
            sp.child("leaf").finish()
            if fail:
                raise ValueError("boom")
    except ValueError:
        pass
    tree = tr.finish()
    tr.finish()  # serialising twice leaves nothing twice
    for name in ("bydb:root", "bydb:work", "bydb:leaf"):
        assert annotations.count("enter", name) == 1
        assert annotations.count("leave", name) == 1
    assert annotations.kwargs[0] == {"trace_id": tree["trace_id"]}
    if fail:
        assert tree["children"][0]["error"] == "ValueError: boom"
    with tracer.annotate("gather.dedup"):
        pass
    assert annotations.count("leave", "bydb:gather.dedup") == 1


def test_no_hook_no_annotation():
    assert tracer._annotate is None
    tr = Tracer("root")
    with tr.span("x"):
        pass
    assert tr.root._ann is None and tr.root.children[0]._ann is None
    assert tracer.annotate("anything") is tracer._NULL_CTX


# -- ran or waited: off_cpu_ms, minflt, tid (ISSUE 37) ---------------------------


def _busy(seconds: float) -> None:
    import time

    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        pass


def test_a_sleeping_span_was_off_the_cpu_and_a_spinning_one_was_not():
    import time

    tr = Tracer("root", usage=True)  # the request asked for its tree
    with tr.span("sleeps"):
        time.sleep(0.05)
    with tr.span("spins"):
        _busy(0.05)
    tree = tr.finish()
    sleeps, spins = find_span(tree, "sleeps"), find_span(tree, "spins")
    assert 40.0 <= sleeps["tags"]["off_cpu_ms"] <= sleeps["duration_ms"]
    # it ran, but for what the machine's other threads took from it; the
    # CPU clock moves a tick at a time, so the reading may fall below 0
    assert -20.0 <= spins["tags"]["off_cpu_ms"] < 0.5 * spins["duration_ms"]
    for span in iter_spans(tree):
        assert span["tags"]["tid"] == threading.get_native_id()  # no call made for it
        assert isinstance(span["tags"]["minflt"], int)
    # the root holds both: it waited what its sleeping child waited (to
    # the microseconds by which two clocks of one interval differ)
    assert tree["tags"]["off_cpu_ms"] >= sleeps["tags"]["off_cpu_ms"] - 0.1


def test_a_request_that_did_not_ask_pays_for_the_roots_reading_alone(monkeypatch):
    """Each reading is a system call (~6 us under the sandboxed kernel
    of the benchmark's machine): a tracer whose request did not ask for
    its tree reads the root's thread only, which is what a slow query's
    tree in the recorder has to go on."""
    calls: list = []
    real = tracer.resource.getrusage
    monkeypatch.setattr(
        tracer.resource, "getrusage", lambda who: calls.append(who) or real(who)
    )
    tr = Tracer("root")
    with tr.span("a") as a:
        a.child("a1").finish()
    with tr.span("b"):
        pass
    tree = tr.finish()
    assert len(calls) == 2
    assert {"off_cpu_ms", "minflt", "tid"} <= set(tree["tags"])
    assert all(s["tags"] == {} for s in iter_spans(tree) if s is not tree)
    asked = Tracer("root", usage=True)
    with asked.span("a") as a:
        a.child("a1").finish()
    assert all("off_cpu_ms" in s["tags"] for s in iter_spans(asked.finish()))
    assert len(calls) == 2 + 2 * 3


def test_the_pad_thunks_read_their_threads_clock_only_under_a_span_that_does():
    m, req, srcs = _query("topn")
    root = Span("execute", usage=False)
    measure_exec.compute_partials(m, req, srcs, span=root)
    decode = find_span(root.to_dict(), "decode")["tags"]
    assert "pack_ms" in decode
    assert "pack_off_cpu_ms" not in decode and "pack_minflt" not in decode


def test_a_span_finished_on_another_thread_says_nothing_of_its_thread():
    """No one thread's clock covers it: the three tags are left out,
    the span is otherwise whole."""
    sp = Span("handed-over").tag("rows", 7)
    t = threading.Thread(target=sp.finish)
    t.start()
    t.join(10)
    d = sp.to_dict()
    assert d["tags"] == {"rows": 7} and d["duration_ms"] > 0
    here = Span("kept").finish().to_dict()
    assert {"off_cpu_ms", "minflt", "tid"} <= set(here["tags"])


def test_noop_tracer_reads_no_clock_of_the_thread(monkeypatch):
    calls: list = []
    real = tracer.resource.getrusage

    def counted(who):
        calls.append(who)
        return real(who)

    monkeypatch.setattr(tracer.resource, "getrusage", counted)
    t = tracer.NOOP_TRACER
    with t.span("a") as sp:
        sp.tag("k", 1).child("b").finish()
    t.current().finish()
    assert t.finish() == {} and calls == []
    Span("real").finish()  # one at open, one at finish, of this thread
    assert calls == [tracer.resource.RUSAGE_THREAD] * 2


def test_minflt_counts_the_first_touch_of_fresh_pages():
    import mmap

    # 8 MB no one has touched: an anonymous map, so that the allocator
    # cannot hand back pages an earlier test freed
    fresh = mmap.mmap(-1, 8 << 20)
    a = np.frombuffer(fresh, dtype=np.uint8)
    with Span("fills") as sp:
        a[:] = 1
    assert a[-1] == 1 and sp.tags["minflt"] > 0
    with Span("idles") as quiet:
        pass
    assert quiet.tags["minflt"] <= sp.tags["minflt"]


def test_serialized_span_keeps_its_five_keys():
    tr = Tracer("root")
    with tr.span("a"):
        pass
    tree = tr.finish()
    five = {"name", "start_ms", "duration_ms", "tags", "children"}
    assert set(tree) == five | {"start_unix_ms", "trace_id"}
    assert set(tree["children"][0]) == five  # the new readings are tags


# -- the plan signature is a span (ISSUE 37) -------------------------------------


@pytest.mark.parametrize("kind", ["topn", "percentile"])
def test_execute_holds_gather_signature_reduce_release_merge_in_that_order(kind):
    from banyandb_tpu.server import _served_class

    tree = _run(kind)
    assert [c["name"] for c in tree["children"]] == [
        "gather", "signature", "reduce", "release", "merge",
    ]
    gather, sig, reduce_, release = tree["children"][:4]
    # the gathered rows are handed back inside a span, not between two
    assert release["children"] == [] and release["tags"].keys() <= {"off_cpu_ms", "minflt", "tid"}
    assert sig["children"] == []  # its self time is signature_ms
    assert sig["tags"]["epoch_ms"] >= 0.0 and sig["tags"]["preds_ms"] > 0.0
    assert sig["tags"]["epoch_ms"] + sig["tags"]["preds_ms"] <= sig["duration_ms"] + 0.01
    # opened where the gather closes, closed where the reduce opens
    assert sig["start_ms"] >= gather["start_ms"] + gather["duration_ms"] - 0.01
    assert sig["start_ms"] + sig["duration_ms"] <= reduce_["start_ms"] + 0.01
    assert "dict_lock_wait_ms" in gather["tags"]  # tagged after its finish, as before
    assert _served_class(tree) == "scan"
    decode = find_span(tree, "decode")["tags"]
    assert -20.0 <= decode["pack_off_cpu_ms"] <= decode["pack_ms"] + 0.01
    assert isinstance(decode["pack_minflt"], int) and decode["pack_minflt"] >= 0


# -- gather / decode are open while their work runs ----------------------------


def _route(monkeypatch, batches: int, scan_chunk: int) -> None:
    """``batches`` 1: the whole scan in one dispatch.  4: over the
    device budget, in four one-chunk batches of ``scan_chunk`` rows."""
    if batches > 1:
        monkeypatch.setenv("BYDB_FUSED_MAX_MB", "0")
        monkeypatch.setattr(measure_exec, "SCAN_CHUNK", scan_chunk)


ROUTES = pytest.mark.parametrize(
    "batches", [1, 4], ids=["one-batch", "chunk-batches"]
)


@ROUTES
def test_gather_and_decode_open_while_their_work_runs(
    annotations, monkeypatch, batches
):
    _route(monkeypatch, batches, 1024)  # of 4,096 rows
    _run("topn")
    a = annotations
    for phase in ("select", "concat", "dedup", "take"):
        name = f"bydb:gather.{phase}"
        assert a.index("enter", "bydb:gather") < a.index("enter", name)
        assert a.index("leave", name) < a.index("leave", "bydb:gather")
    work = "bydb:decode.pack"
    assert a.index("enter", "bydb:decode") < a.index("enter", work)
    assert a.index("leave", work, last=True) < a.index("leave", "bydb:decode")
    # every span of the tree was left exactly once
    for what, name in set(a.log):
        assert a.count("enter", name) == a.count("leave", name)


# -- B: phase tags --------------------------------------------------------------


@ROUTES
@pytest.mark.parametrize("kind", ["topn", "percentile"])
def test_phase_tags_sum_to_their_span(monkeypatch, batches, kind):
    _route(monkeypatch, batches, 65536)  # of 200,000 rows
    tree = _run(kind, n=200_000, sources=4)
    g = find_span(tree, "gather")["tags"]
    phases = [g[f"{p}_ms"] for p in ("select", "concat", "dedup", "take")]
    dur = find_span(tree, "gather")["duration_ms"]
    assert all(p >= 0 for p in phases)
    assert 0.6 * dur <= sum(phases) <= dur + 0.05
    assert g["sources"] == 4 and g["rows"] == 200_000
    d = find_span(tree, "decode")["tags"]
    assert d["pack_ms"] > 0 and d["h2d_ms"] > 0
    assert d["pack_ms"] + d["h2d_ms"] == pytest.approx(d["host_ms"], abs=0.01)
    assert d["packed_bytes"] >= d["shipped_bytes"] > 0
    r = find_span(tree, "reduce")["tags"]
    assert r["path"] == "fused"
    assert r["dispatches"] == batches and r["chunks"] == batches
    assert r["dispatch_ms"] > 0 and r["get_ms"] > 0
    assert r["dispatch_ms"] + r["get_ms"] == pytest.approx(
        r["device_ms"], abs=0.01
    )
    assert "pad_ship_ms" not in r  # was a copy of decode.host_ms
    decode = find_span(tree, "decode")
    assert decode["duration_ms"] > 0  # a real span, not created-and-closed


def test_gather_part_gather_merge_have_no_children(tmp_path):
    """Their self time is a benchmark metric: phases are tags only."""
    from test_admin import _engine

    eng = _engine(tmp_path)
    r = eng.query(QueryRequest(
        ("g",), "m", TimeRange(T0, T0 + 1000),
        group_by=GroupBy(("svc",)), agg=Aggregation("sum", "v"), trace=True,
    ))
    tree = r.trace["span_tree"]
    assert set(r.trace) == {"plan", "span_tree"}  # one trace format
    for name in ("gather", "part_gather", "merge"):
        span = find_span(tree, name)
        assert span is not None, name
        assert span["children"] == [], name
    assert "select_ms" in find_span(tree, "gather")["tags"]


# -- C: compile events ---------------------------------------------------------


def test_jit_traces_counts_programs_not_dispatches():
    import jax
    import jax.numpy as jnp

    compile_cache._install_listener()

    @jax.jit
    def bydb_test_probe(x):
        return jnp.take(x, jnp.arange(2)) + 1  # holds a nested jit

    x7, y7, x9 = np.zeros(7, np.float32), np.ones(7, np.float32), np.zeros(9, np.float32)
    before = compile_cache.stats()
    with compile_cache.watch() as paid:
        bydb_test_probe(x7)
    mid = compile_cache.stats()
    assert mid["traces"] - before["traces"] == 1
    assert mid["compile_seconds"] > before["compile_seconds"]
    assert paid.compiled == 1 and paid.seconds > 0
    assert paid.program == "jit_bydb_test_probe"
    with compile_cache.watch() as again:
        bydb_test_probe(y7)  # same shape: no trace
    assert compile_cache.stats()["traces"] == mid["traces"]
    assert again.compiled == 0
    bydb_test_probe(x9)  # a new shape is a new program
    assert compile_cache.stats()["traces"] == mid["traces"] + 1


def test_the_paying_query_carries_the_compile(caplog):
    import logging

    compile_cache._install_listener()
    # a row count no other test uses: a program of its own
    with caplog.at_level(logging.INFO, logger="banyandb.compile"):
        first = find_span(_run("topn", n=4096 + 64), "reduce")["tags"]
    assert first["compiled"] >= 1 and first["compile_ms"] > 0
    assert first["program"] == "jit_bydb_fused_plan"
    assert first["dispatch_ms"] >= first["compile_ms"] * 0.5
    lines = [r.getMessage() for r in caplog.records]
    assert any(
        ln.startswith("compile program=jit_bydb_fused_plan ms=")
        and ln.endswith(("cache=hit", "cache=miss"))
        for ln in lines
    )
    second = find_span(_run("topn", n=4096 + 64), "reduce")["tags"]
    assert "compiled" not in second and "compile_ms" not in second


# -- D: names on the device ------------------------------------------------------


def _lowered_text(kind: str) -> str:
    from banyandb_tpu.query import precompile

    m, req, srcs = _query(kind)
    root = Span("execute")
    measure_exec.compute_partials(m, req, srcs, span=root)
    fspec = next(
        f for f in reversed(list(fused_exec._KERNEL_CACHE))
        if bool(f.plan.hist_field) == (kind == "percentile")
    )
    args = precompile.fused_warm_args(fspec)
    kernel = fused_exec._build_kernel(fspec)
    return kernel.lower(*args).as_text(debug_info=True)


@pytest.mark.parametrize(
    "kind,scopes",
    [
        ("topn", ("bydb.decode", "bydb.filter", "bydb.group_key",
                  "bydb.group_reduce.", "bydb.fused_scan")),
        ("percentile", ("bydb.decode", "bydb.filter", "bydb.group_key",
                        "bydb.group_reduce.", "bydb.histogram", "bydb.rep",
                        "bydb.fused_scan")),
    ],
)
def test_lowered_plan_names_every_stage(kind, scopes):
    text = _lowered_text(kind)
    assert "jit_bydb_fused_plan" in text
    for scope in scopes:
        assert scope in text, scope
    # scan order is tracked for the listing, which emits its groups in
    # first-appearance order; a TOP n that projects no tag reads none
    assert ("bydb.rep" in text) == (kind == "percentile")
    # the method that ran is part of the name
    m, req, srcs = _query(kind)
    from banyandb_tpu.ops.groupby import select_group_method

    method = select_group_method(4096, 128)
    assert f"bydb.group_reduce.{method}" in text


def test_plan_and_pallas_names():
    import jax
    import jax.numpy as jnp

    from banyandb_tpu.ops import pallas_kernels
    from banyandb_tpu.query import precompile

    fspec = next(iter(reversed(list(fused_exec._KERNEL_CACHE))))
    plan = fused_exec._build_kernel(fspec)
    text = plan.lower(*precompile.fused_warm_args(fspec)).as_text(debug_info=True)
    assert "jit_bydb_fused_plan" in text and "bydb.filter" in text
    n = pallas_kernels.TILE
    low = jax.jit(
        lambda c, v: pallas_kernels.fused_group_multi(
            c, jnp.ones_like(c, dtype=bool), v, jnp.ones_like(c, dtype=bool),
            num_groups=8, interpret=True,
        )
    ).lower(jnp.zeros(n, jnp.int32), jnp.zeros((1, n), jnp.float32))
    assert "bydb_group_multi" in low.as_text(debug_info=True)


def test_dist_step_names_collective_and_topk():
    import jax

    from banyandb_tpu.parallel import dist_exec
    from banyandb_tpu.parallel import mesh as pmesh

    plan = dist_exec.DistPlan(
        tags_code=("svc",), fields=("v",), group_tags=("svc",),
        radices=(16,), num_groups=16, topn=4,
    )
    mesh = pmesh.make_mesh(1)
    n = 1024
    rows = [{
        "tags": {"svc": np.zeros(n, np.int32)},
        "fields": {"v": np.ones(n, np.float32)},
    }]
    chunks = dist_exec.stack_shard_chunks(mesh, rows, ("svc",), ("v",), n)
    step = fused_exec.build_fused_dist_step(mesh, plan, 2)
    import jax.numpy as jnp

    text = step.lower(
        chunks, {}, jnp.float32(0.0), jnp.float32(1.0)
    ).as_text(debug_info=True)
    for name in ("jit_bydb_fused_dist_step", "bydb.fused_scan",
                 "bydb.collective", "bydb.topk", "bydb.group_reduce."):
        assert name in text, name
    assert jax.default_backend() == "cpu"


# -- E: the device-trace reduction ----------------------------------------------


def _op(start, dur, op_name="", name="%op", device="/device:TPU:0"):
    return {"device": device, "name": name, "op_name": op_name,
            "start_ns": start, "dur_ns": dur}


def test_devtrace_reduction_hand_computed():
    ms = 1_000_000
    events = {
        "ops": [
            # a while of 40 ms holding two body ops (self time 40-10-20)
            _op(10 * ms, 40 * ms, "jit(f)/bydb.fused_scan/while"),
            _op(12 * ms, 10 * ms, "jit(f)/bydb.fused_scan/while/body/bydb.filter/and:"),
            _op(25 * ms, 20 * ms,
                "jit(f)/bydb.fused_scan/while/body/bydb.group_reduce.sort/sort:"),
            _op(90 * ms, 10 * ms, ""),  # compiler-made copy: no op name
        ],
        "modules": [
            {"device": "/device:TPU:0", "name": "jit_bydb_fused_plan(123)",
             "start_ns": 10 * ms, "dur_ns": 40 * ms},
            {"device": "/device:TPU:0", "name": "jit_convert_element_type(9)",
             "start_ns": 90 * ms, "dur_ns": 10 * ms},
        ],
        "host": [
            {"name": "bydb:standalone:measure", "start_ns": 0, "dur_ns": 70 * ms},
            {"name": "bydb:gather#trace_id=ab#", "start_ns": 2 * ms, "dur_ns": 6 * ms},
            {"name": "bydb:merge", "start_ns": 60 * ms, "dur_ns": 5 * ms},
        ],
    }
    red = devtrace.reduce_events(events, 100 * ms)
    assert red["window_s"] == pytest.approx(0.100)
    dev = red["devices"]["/device:TPU:0"]
    assert dev["busy_s"] == pytest.approx(0.050)
    assert dev["idle_s"] == pytest.approx(0.050)
    assert red["by_program_s"] == {
        "jit_bydb_fused_plan": pytest.approx(0.040),
        "jit_convert_element_type": pytest.approx(0.010),
    }
    assert red["by_scope_s"] == {
        "bydb.group_reduce.sort": pytest.approx(0.020),
        "bydb.fused_scan": pytest.approx(0.010),
        "bydb.filter": pytest.approx(0.010),
        "(unscoped)": pytest.approx(0.010),
    }
    assert red["unscoped_share"] == pytest.approx(0.2)
    # gap [0, 10): root 0-2, gather 2-8, root 8-10  -> split by overlap;
    # gap [50, 90): root 50-60, merge 60-65, root 65-70, nobody 70-90
    assert red["idle_by_span_s"] == {
        "between queries": pytest.approx(0.020),
        "standalone:measure": pytest.approx(0.019),
        "gather": pytest.approx(0.006),
        "merge": pytest.approx(0.005),
    }
    assert sum(red["idle_by_span_s"].values()) == pytest.approx(dev["idle_s"])
    json.dumps(red)  # JSON-safe


def test_devtrace_reduction_against_the_chip_fixture():
    """Events cut from a trace of topn100k.topn-24h on a TPU v5 lite
    (tests/fixtures/devtrace_chip.json; expected values beside it)."""
    with open(os.path.join(HERE, "fixtures", "devtrace_chip.json")) as f:
        fixture = json.load(f)
    red = devtrace.reduce_events(fixture["events"], fixture["window_ns"])
    want = fixture["expected"]
    assert red["devices"].keys() == want["devices"].keys()
    for dev, w in want["devices"].items():
        assert red["devices"][dev]["busy_s"] == pytest.approx(w["busy_s"])
        assert red["devices"][dev]["idle_s"] == pytest.approx(w["idle_s"])
    for key in ("by_program_s", "by_scope_s", "idle_by_span_s"):
        assert red[key].keys() == want[key].keys(), key
        for name, seconds in want[key].items():
            assert red[key][name] == pytest.approx(seconds), (key, name)
    # what the fixture shows of the real thing
    assert "jit_bydb_fused_plan" in red["by_program_s"]
    assert any(k.startswith("bydb.group_reduce.") for k in red["by_scope_s"])
    assert red["unscoped_share"] < 0.05
    idle = sum(v["idle_s"] for v in red["devices"].values())
    assert sum(red["idle_by_span_s"].values()) <= idle + 1e-9


def test_xplane_reader_round_trip(tmp_path):
    """load_xplane reads tsl's XSpace wire format with protobuf alone."""
    XSpace = devtrace._xspace()
    space = XSpace()
    plane = space.planes.add(name="/device:TPU:0")
    sm = plane.stat_metadata.add(key=1)
    sm.value.name = "tf_op"
    em = plane.event_metadata.add(key=7)
    em.value.name = "%fusion.1 = f32[8] fusion(...)"
    em.value.stats.add(metadata_id=1, str_value="jit(f)/bydb.filter/and:")
    mm = plane.event_metadata.add(key=8)
    mm.value.name = "jit_bydb_fused_plan(42)"
    ops = plane.lines.add(name="XLA Ops", timestamp_ns=5)
    ops.events.add(metadata_id=7, offset_ps=2_000_000, duration_ps=3_000_000)
    mods = plane.lines.add(name="XLA Modules")
    mods.events.add(metadata_id=8, offset_ps=1_000_000, duration_ps=9_000_000)
    plane.lines.add(name="Async XLA Ops").events.add(metadata_id=7)
    host = space.planes.add(name="/host:CPU")
    host.event_metadata.add(key=1).value.name = "bydb:gather"
    host.event_metadata.add(key=2).value.name = "PjitFunction(f)"
    line = host.lines.add(name="python", timestamp_ns=100)
    line.events.add(metadata_id=1, offset_ps=4_000_000, duration_ps=6_000_000)
    line.events.add(metadata_id=2, offset_ps=0, duration_ps=1_000)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    ev = devtrace.load_xplane(str(path))
    assert ev["ops"] == [{
        "name": "%fusion.1 = f32[8] fusion(...)", "start_ns": 2005,
        "dur_ns": 3000, "device": "/device:TPU:0",
        "op_name": "jit(f)/bydb.filter/and:",
    }]
    assert ev["modules"][0]["name"] == "jit_bydb_fused_plan(42)"
    assert ev["host"] == [{"name": "bydb:gather", "start_ns": 4100, "dur_ns": 6000}]


# -- the server's side: parse span, queued_ms, the devtrace topic ----------------


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    from test_obs import _seed_measure

    from banyandb_tpu.server import StandaloneServer

    srv = StandaloneServer(tmp_path_factory.mktemp("srv"), port=0)
    srv.start()
    _seed_measure(srv)
    try:
        yield srv
    finally:
        srv.stop()


def test_ql_root_covers_the_parse_and_queued_ms_is_a_number(server):
    ql = (
        f"SELECT sum(v) FROM MEASURE m IN g TIME BETWEEN {T0} AND "
        f"{T0 + 100} GROUP BY svc"
    )
    reply = server.bus.handle("bydbql", {"ql": ql, "trace": True})
    tree = reply["result"]["trace"]["span_tree"]
    assert tree["name"] == "standalone:measure"
    assert [c["name"] for c in tree["children"]][:2] == ["parse", "qos"]
    parse = tree["children"][0]
    assert parse["start_ms"] >= 0 and parse["duration_ms"] > 0
    qos = find_span(tree, "qos")["tags"]
    assert isinstance(qos["queued_ms"], float) and qos["queued_ms"] >= 0.0
    assert set(reply["result"]["trace"]) == {"plan", "span_tree"}
    text = server.bus.handle("metrics", {})["prometheus"]
    assert "banyandb_jit_traces " in text
    assert "banyandb_jit_compile_seconds " in text


@pytest.mark.parametrize("surface", ["bus", "http"])
def test_second_concurrent_devtrace_is_refused(server, surface):
    """One capture at a time: a profiler session is process-wide."""
    assert devtrace._capture_lock.acquire(blocking=False)
    try:
        if surface == "bus":
            with pytest.raises(devtrace.CaptureBusy):
                server.bus.handle("devtrace", {"seconds": 0.1})
        else:
            import urllib.error
            import urllib.request

            from banyandb_tpu.admin.profiling import ProfilingServer

            prof = ProfilingServer(port=0).start()
            try:
                with pytest.raises(urllib.error.HTTPError) as err:
                    urllib.request.urlopen(
                        f"http://127.0.0.1:{prof.port}/debug/device?seconds=0.1",
                        timeout=10,
                    )
                assert err.value.code == 500
                assert "already being captured" in err.value.reason
            finally:
                prof.stop()
    finally:
        devtrace._capture_lock.release()


def test_devtrace_capture_runs_on_this_backend():
    """capture() end to end on the CPU backend: no device plane there,
    but the profiler starts, stops and its file is read and deleted."""
    done = []

    def work():
        with Tracer("probe").span("gather"):
            done.append(1)

    tracer_hook_before = tracer._annotate
    import jax

    tracer.set_annotation_hook(jax.profiler.TraceAnnotation)
    try:
        t = threading.Timer(0.15, work)
        t.start()
        red = devtrace.capture(0.4)
        t.join()
    finally:
        tracer.set_annotation_hook(tracer_hook_before)
    assert done and red["window_s"] >= 0.4
    assert red["host_annotations"] >= 2  # bydb:probe and bydb:gather
    assert red["devices"] == {} and red["by_scope_s"] == {}
    assert not devtrace._capture_lock.locked()


# -- wire: common/v1 Span.start_time / end_time, Trace.trace_id -------------------


def test_fill_trace_sets_start_end_and_trace_id():
    from banyandb_tpu.api import pb, wire
    from banyandb_tpu.api.model import QueryResult

    tr = Tracer("standalone:measure")
    with tr.span("execute"):
        pass
    tree = tr.finish()
    res = QueryResult()
    res.trace = {"span_tree": tree, "plan": "Limit(100)"}
    out = pb.measure_query_pb2.QueryResponse()
    wire.fill_trace(out, res)
    assert out.trace.trace_id == tree["trace_id"]
    root = next(s for s in out.trace.spans if s.message == "standalone:measure")
    assert root.start_time.ToNanoseconds() == int(tree["start_unix_ms"] * 1e6)
    assert (
        root.end_time.ToNanoseconds() - root.start_time.ToNanoseconds()
        == root.duration
    )
    child = root.children[0]
    assert child.message == "execute"
    assert child.start_time.ToNanoseconds() >= root.start_time.ToNanoseconds()
    assert child.end_time.ToNanoseconds() <= root.end_time.ToNanoseconds() + 1000
    assert any(s.message == "plan: Limit(100)" for s in out.trace.spans)
