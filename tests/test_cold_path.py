"""Cold-path latency machinery (ISSUE 3): persistent compile cache,
plan precompile registry, and the gather/compute pipeline.

Covers:
- PrefetchIterator/prefetched/parallel_map semantics (order, mid-stream
  error propagation, early close);
- pipelined vs strict-serial execution byte-identical on a multi-part
  store (partials arrays AND final JSON results), incl. the stream scan;
- mid-stream part decode errors propagating through the pipeline;
- precompile registry: recording, JSON round-trip, store persistence,
  warming into the process kernel caches, registry<->plan-audit
  agreement (the meta-test the lint satellite pins);
- a subprocess pair proving the persistent XLA compile cache makes the
  second process's first-plan compile a cache hit;
- serving/device/compile cache counters readable from a RUNNING server
  over the bus (/metrics), not process-local globals.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from banyandb_tpu.storage.chunk_stream import (
    PrefetchIterator,
    parallel_map,
    pipeline_enabled,
    prefetched,
)

T0 = 1_700_000_000_000


# -- chunk_stream primitives -------------------------------------------------


def test_prefetch_preserves_order():
    thunks = [lambda i=i: i * i for i in range(50)]
    assert list(prefetched(thunks, enabled=True)) == [i * i for i in range(50)]
    assert list(prefetched(thunks, enabled=False)) == [i * i for i in range(50)]


def test_prefetch_midstream_error_propagates():
    seen = []

    def ok(i):
        seen.append(i)
        return i

    def boom():
        raise RuntimeError("decode failed mid-stream")

    thunks = [lambda: ok(0), lambda: ok(1), boom, lambda: ok(3)]
    got = []
    with pytest.raises(RuntimeError, match="decode failed mid-stream"):
        for v in prefetched(thunks, enabled=True):
            got.append(v)
    # items before the failure were delivered in order; the failure
    # surfaced at its position, exactly like the serial loop
    assert got == [0, 1]


def test_prefetch_early_close_stops_worker():
    import threading

    produced = []

    def make(i):
        def t():
            produced.append(i)
            time.sleep(0.01)
            return i

        return t

    it = PrefetchIterator([make(i) for i in range(100)], depth=2)
    assert next(it) == 0
    it.close()
    assert not it._thread.is_alive()
    # bounded depth: the worker cannot have raced far ahead
    assert len(produced) < 100
    assert threading.active_count() < 50  # no thread leak


def test_pipeline_flag(monkeypatch):
    monkeypatch.setenv("BYDB_PIPELINE", "0")
    assert not pipeline_enabled()
    calls = []
    list(prefetched([lambda: calls.append(1)]))
    monkeypatch.setenv("BYDB_PIPELINE", "1")
    assert pipeline_enabled()


def test_parallel_map_order_and_error():
    thunks = [lambda i=i: (time.sleep(0.002 * (5 - i)), i)[1] for i in range(5)]
    assert parallel_map(thunks, enabled=True) == list(range(5))

    def boom():
        raise ValueError("node gather failed")

    with pytest.raises(ValueError, match="node gather failed"):
        parallel_map([lambda: 1, boom, lambda: 3], enabled=True)


# -- multi-part store fixture ------------------------------------------------


@pytest.fixture()
def store(tmp_path):
    """2-shard store with two flushed parts per shard + memtable rows."""
    from banyandb_tpu.api import (
        Catalog,
        Entity,
        FieldSpec,
        FieldType,
        Group,
        Measure,
        ResourceOpts,
        SchemaRegistry,
        TagSpec,
        TagType,
    )
    from banyandb_tpu.models.measure import DictColumn, MeasureEngine

    reg = SchemaRegistry(tmp_path)
    reg.create_group(Group("g", Catalog.MEASURE, ResourceOpts(shard_num=2)))
    reg.create_measure(
        Measure(
            group="g",
            name="m",
            tags=(
                TagSpec("svc", TagType.STRING),
                TagSpec("region", TagType.STRING),
            ),
            fields=(FieldSpec("value", FieldType.FLOAT),),
            entity=Entity(("svc",)),
        )
    )
    eng = MeasureEngine(reg, tmp_path / "data")
    rng = np.random.default_rng(7)
    for b in range(3):
        n = 15_000
        eng.write_columns(
            "g",
            "m",
            ts_millis=T0 + b * n + np.arange(n, dtype=np.int64),
            tags={
                "svc": DictColumn(
                    [b"s%02d" % i for i in range(30)],
                    rng.integers(0, 30, n).astype(np.int32),
                ),
                "region": DictColumn(
                    [b"r%d" % i for i in range(4)],
                    rng.integers(0, 4, n).astype(np.int32),
                ),
            },
            fields={"value": rng.gamma(2.0, 40.0, n)},
            versions=np.ones(n, dtype=np.int64),
        )
        if b < 2:
            eng.flush()
    return reg, eng


QUERIES = (
    "SELECT sum(value) FROM MEASURE m IN g TIME BETWEEN {b} AND {e} "
    "WHERE region != 'r3' GROUP BY svc TOP 7 BY value",
    "SELECT percentile(value, 0.5, 0.99) FROM MEASURE m IN g "
    "TIME BETWEEN {b} AND {e} GROUP BY region",
    "SELECT count(value) FROM MEASURE m IN g TIME BETWEEN {b} AND {e} "
    "WHERE region = 'r1' OR svc = 's05' GROUP BY svc, region",
)


def _partials_bytes(p):
    out = [p.count.tobytes()]
    for f in sorted(p.sums):
        out += [p.sums[f].tobytes(), p.mins[f].tobytes(), p.maxs[f].tobytes()]
    if p.hist is not None:
        out.append(p.hist.tobytes())
    if p.codes is not None:
        out.append(p.codes.tobytes())
    if p.rep_key is not None:
        out.append(p.rep_key.tobytes())
    return b"".join(out)


def test_pipelined_vs_serial_byte_identical(store, monkeypatch):
    from banyandb_tpu import bydbql
    from banyandb_tpu.query import measure_exec
    from banyandb_tpu.server import result_to_json

    reg, eng = store
    m = reg.get_measure("g", "m")
    for ql in QUERIES:
        req = bydbql.parse(ql.format(b=T0, e=T0 + 50_000))
        sources = eng.gather_query_sources(req)
        monkeypatch.setenv("BYDB_PIPELINE", "1")
        p1 = measure_exec.compute_partials(m, req, sources, dict_state=None)
        r1 = result_to_json(measure_exec.finalize_partials(m, req, [p1]))
        monkeypatch.setenv("BYDB_PIPELINE", "0")
        p0 = measure_exec.compute_partials(m, req, sources, dict_state=None)
        r0 = result_to_json(measure_exec.finalize_partials(m, req, [p0]))
        assert _partials_bytes(p1) == _partials_bytes(p0)
        assert json.dumps(r1) == json.dumps(r0)


def test_pipelined_vs_serial_gather_identical(store, monkeypatch):
    """The storage-side prefetch (part iteration) must yield the same
    source list (same order, same rows) as the serial loop."""
    from banyandb_tpu import bydbql

    reg, eng = store
    req = bydbql.parse(
        QUERIES[0].format(b=T0, e=T0 + 50_000)
    )
    monkeypatch.setenv("BYDB_PIPELINE", "1")
    s1 = eng.gather_query_sources(req)
    monkeypatch.setenv("BYDB_PIPELINE", "0")
    s0 = eng.gather_query_sources(req)
    assert len(s1) == len(s0)
    for a, b in zip(s1, s0):
        assert a.ts.tobytes() == b.ts.tobytes()
        assert a.series.tobytes() == b.series.tobytes()


def test_midstream_decode_error_propagates_from_gather(store, monkeypatch):
    from banyandb_tpu import bydbql
    from banyandb_tpu.storage.part import Part

    reg, eng = store
    req = bydbql.parse(QUERIES[0].format(b=T0, e=T0 + 50_000))
    calls = {"n": 0}
    real_read = Part.read

    def flaky_read(self, *a, **kw):
        calls["n"] += 1
        if calls["n"] >= 2:
            raise RuntimeError("disk decode exploded")
        return real_read(self, *a, **kw)

    monkeypatch.setenv("BYDB_PIPELINE", "1")
    monkeypatch.setattr(Part, "read", flaky_read)
    from banyandb_tpu.storage.cache import reset_global_cache

    reset_global_cache()  # decoded blocks of this store may be cached
    with pytest.raises(RuntimeError, match="disk decode exploded"):
        eng.query(req)
    assert calls["n"] >= 2


def test_stream_scan_pipelined_vs_serial(tmp_path, monkeypatch):
    from banyandb_tpu.api import Catalog, Group, ResourceOpts, SchemaRegistry
    from banyandb_tpu.api.model import QueryRequest, TimeRange
    from banyandb_tpu.api.schema import TagSpec, TagType
    from banyandb_tpu.models.stream import ElementValue, Stream, StreamEngine
    from banyandb_tpu.server import result_to_json

    reg = SchemaRegistry(tmp_path)
    reg.create_group(Group("sg", Catalog.STREAM, ResourceOpts(shard_num=1)))
    eng = StreamEngine(reg, tmp_path / "data")
    eng.create_stream(
        Stream(
            group="sg",
            name="logs",
            tags=(TagSpec("svc", TagType.STRING),),
            entity=("svc",),
        )
    )
    for b in range(2):
        eng.write(
            "sg",
            "logs",
            [
                ElementValue(
                    element_id=f"e{b}-{i}",
                    ts_millis=T0 + b * 1000 + i,
                    tags={"svc": f"s{i % 5}"},
                    body=b"x" * 8,
                )
                for i in range(200)
            ],
        )
        if b == 0:
            eng.flush()
    req = QueryRequest(
        groups=("sg",),
        name="logs",
        time_range=TimeRange(T0, T0 + 10_000),
        limit=500,
    )
    monkeypatch.setenv("BYDB_PIPELINE", "1")
    r1 = result_to_json(eng.query(req))
    monkeypatch.setenv("BYDB_PIPELINE", "0")
    r0 = result_to_json(eng.query(req))
    assert json.dumps(r1) == json.dumps(r0)
    assert len(r1["data_points"]) == 400


def test_multisegment_series_pruning_per_segment(tmp_path, monkeypatch):
    """Deferred decode thunks must filter with THEIR segment's series
    candidate set, not the last segment's (regression: the pruning
    closure used to share one cell across segment iterations)."""
    from banyandb_tpu import bydbql
    from banyandb_tpu.api import (
        Catalog,
        Entity,
        FieldSpec,
        FieldType,
        Group,
        Measure,
        ResourceOpts,
        SchemaRegistry,
        TagSpec,
        TagType,
    )
    from banyandb_tpu.models.measure import DictColumn, MeasureEngine

    reg = SchemaRegistry(tmp_path)
    reg.create_group(Group("g", Catalog.MEASURE, ResourceOpts(shard_num=1)))
    reg.create_measure(
        Measure(
            group="g",
            name="m",
            tags=(TagSpec("svc", TagType.STRING),),
            fields=(FieldSpec("v", FieldType.FLOAT),),
            entity=Entity(("svc",)),
        )
    )
    eng = MeasureEngine(reg, tmp_path / "data")
    day = 24 * 3600 * 1000
    # day 1: 'lone' + 'both'; day 2: only 'both' — the two segments'
    # series indexes resolve DIFFERENT candidate sets for svc='lone'
    n = 64
    eng.write_columns(
        "g",
        "m",
        ts_millis=T0 + np.arange(n, dtype=np.int64),
        tags={
            "svc": DictColumn(
                [b"lone", b"both"],
                np.asarray([0, 1] * (n // 2), dtype=np.int32),
            )
        },
        fields={"v": np.ones(n, dtype=np.float64)},
        versions=np.ones(n, dtype=np.int64),
    )
    eng.flush()
    eng.write_columns(
        "g",
        "m",
        ts_millis=T0 + day + np.arange(n, dtype=np.int64),
        tags={"svc": DictColumn([b"both"], np.zeros(n, dtype=np.int32))},
        fields={"v": np.ones(n, dtype=np.float64)},
        versions=np.ones(n, dtype=np.int64),
    )
    eng.flush()
    req = bydbql.parse(
        f"SELECT count(v) FROM MEASURE m IN g TIME BETWEEN {T0} AND "
        f"{T0 + 2 * day} WHERE svc = 'lone' GROUP BY svc"
    )
    for flag in ("1", "0"):
        monkeypatch.setenv("BYDB_PIPELINE", flag)
        res = eng.query(req)
        assert res.values["count"] == [n // 2], (flag, res.values)


# -- precompile registry -----------------------------------------------------


def test_registry_records_and_roundtrips(store, monkeypatch, tmp_path):
    from banyandb_tpu import bydbql
    from banyandb_tpu.query import precompile

    monkeypatch.setenv("BYDB_PRECOMPILE", "1")
    reg_schema, eng = store
    r = precompile.PrecompileRegistry()
    monkeypatch.setattr(precompile, "_registry", r)
    for ql in QUERIES:
        eng.query(bydbql.parse(ql.format(b=T0, e=T0 + 50_000)))
    st = r.stats()
    assert st["recorded"] >= 2, st

    # JSON round-trip preserves signature equality (incl. expr trees)
    for kind, spec in r.signatures():
        kind2, spec2 = precompile.spec_from_json(
            json.loads(json.dumps(precompile.spec_to_json(kind, spec)))
        )
        assert kind2 == kind and spec2 == spec and hash(spec2) == hash(spec)

    # store persistence + reload into a fresh registry
    store_path = tmp_path / "plan-registry.json"
    r.attach_store(store_path)
    assert store_path.exists()
    r2 = precompile.PrecompileRegistry()
    r2.attach_store(store_path)
    assert set(r2.signatures()) == set(r.signatures())


def test_registry_warm_populates_kernel_cache(monkeypatch):
    from banyandb_tpu.query import fused_exec, precompile, stream_exec

    monkeypatch.setenv("BYDB_PRECOMPILE", "1")
    r = precompile.PrecompileRegistry()
    sigs = [
        ("fused", precompile.builtin_fused()[0][1]),
        ("stream_mask", precompile.builtin_masks()[0][1]),
    ]
    done = r.warm(sigs=sigs)
    assert done == 2 and r.errors == 0
    assert sigs[0][1] in fused_exec._KERNEL_CACHE
    assert sigs[1][1] in stream_exec._KERNEL_CACHE


def test_registry_disabled_records_nothing(monkeypatch):
    from banyandb_tpu.query import precompile

    monkeypatch.setenv("BYDB_PRECOMPILE", "0")
    r = precompile.PrecompileRegistry()
    r.record("measure", precompile.builtin_plans()[0][1])
    assert r.stats()["recorded"] == 0
    assert r.warm_async() is None


def test_warm_async_queues_round_for_midwarm_signatures(monkeypatch):
    """Plans recorded while a warm round is compiling (e.g. queries
    landing during the boot warm, then note_flush) must be warmed by a
    follow-up round, not silently dropped."""
    import threading

    from banyandb_tpu.query import precompile

    monkeypatch.setenv("BYDB_PRECOMPILE", "1")
    r = precompile.PrecompileRegistry()
    started, release = threading.Event(), threading.Event()
    compiled = []

    def fake_compile(kind, spec):
        started.set()
        release.wait(10)
        compiled.append(spec)

    monkeypatch.setattr(r, "_compile_one", fake_compile)
    spec0, spec1 = (
        precompile.builtin_fused()[0][1],
        precompile.builtin_fused()[1][1],
    )
    r.record("fused", spec0)
    t1 = r.warm_async(include_builtin=False)
    assert started.wait(10)
    r.record("fused", spec1)  # lands mid-round
    assert r.warm_async(include_builtin=False) is t1  # queued, not dropped
    release.set()
    t1.join(15)
    assert not t1.is_alive()
    assert spec1 in compiled, "mid-warm signature never compiled"


def test_shutdown_stops_warm_at_kernel_boundary(monkeypatch):
    import dataclasses
    import threading

    from banyandb_tpu.query import precompile

    monkeypatch.setenv("BYDB_PRECOMPILE", "1")
    r = precompile.PrecompileRegistry()
    from banyandb_tpu.query.fused_exec import FusedSpec

    base = precompile.builtin_plans()[0][1]
    for i in range(50):
        plan = dataclasses.replace(base, num_groups=i + 2)
        r._recorded[("fused", FusedSpec(plan=plan, num_chunks=1))] = 1
    started = threading.Event()

    def slow_compile(kind, spec):
        started.set()
        time.sleep(0.02)

    monkeypatch.setattr(r, "_compile_one", slow_compile)
    t = r.warm_async(include_builtin=False)
    assert started.wait(10)
    r.shutdown(timeout=30)
    assert not t.is_alive()
    assert r.compiled < 50, "shutdown did not cancel the warm round"


def test_record_save_is_debounced_off_hot_path(tmp_path, monkeypatch):
    from banyandb_tpu.query import precompile

    monkeypatch.setenv("BYDB_PRECOMPILE", "1")
    r = precompile.PrecompileRegistry()
    store = tmp_path / "plan-registry.json"
    r.attach_store(store)
    assert not store.exists()  # nothing recorded yet, nothing to save
    r.record("measure", precompile.builtin_plans()[0][1])
    assert not store.exists()  # record() itself never writes inline
    deadline = time.time() + 10
    while time.time() < deadline and not store.exists():
        time.sleep(0.05)
    assert store.exists(), "debounced save never fired"
    r.shutdown()


def test_registry_and_plan_audit_agree():
    """The lint satellite's meta-test: the plan auditor's kernel matrix
    IS the precompile registry's builtin signature set — a signature
    warmed is a signature contract-audited, and vice versa."""
    from banyandb_tpu.lint.whole_program.plan_audit import default_entries
    from banyandb_tpu.query import precompile

    audit_names = {e.name for e in default_entries()}
    builtin_names = (
        {n for n, _ in precompile.builtin_fused()}
        | {n for n, _ in precompile.builtin_fused_decode()}
        | {n for n, _ in precompile.builtin_masks()}
    )
    missing = builtin_names - audit_names
    assert not missing, f"registry signatures not audited: {missing}"
    # audit may only add the shared-ops entries on top of the registry set
    extras = audit_names - builtin_names
    assert all(n.startswith("ops/") for n in extras), extras


def test_audit_cache_keys_match_builtin_specs():
    """Every builtin signature is used as a jit cache key somewhere, so
    the audit's immutability/value-hash checks must cover it."""
    from banyandb_tpu.lint.whole_program.plan_audit import default_entries

    keyed = [e for e in default_entries() if e.cache_key is not None]
    assert len(keyed) >= 6  # 5 measure plans + 1 stream mask


# -- persistent compile cache ------------------------------------------------

_CHILD = """
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["BYDB_PRECOMPILE"] = "1"
import jax
updates = []
_real_update = jax.config.update
def _spy(name, value):
    updates.append(name)
    return _real_update(name, value)
jax.config.update = _spy
from banyandb_tpu.utils import compile_cache
active = compile_cache.enable()
from banyandb_tpu.query.precompile import builtin_fused, PrecompileRegistry
name, fspec = builtin_fused()[0]  # fused/flat-count: the smallest plan
r = PrecompileRegistry()
assert r.warm(sigs=[("fused", fspec)]) == 1 and r.errors == 0
print(json.dumps({
    **compile_cache.stats(), "active": active, "updates": updates,
    "jax_dir": jax.config.jax_compilation_cache_dir,
}))
"""


def _run_cache_child(env):
    out = subprocess.run(
        [sys.executable, "-c", _CHILD],
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_persistent_cache_env_dir_wins_and_hits_across_processes(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: entries land there, this package
    never config.update()s the directory, and the second process's
    first-plan compile is a persistent-cache hit — 'compile once per
    machine'."""
    env = dict(os.environ)
    cc = tmp_path / "cc"
    env["JAX_COMPILATION_CACHE_DIR"] = str(cc)
    env["JAX_ENABLE_COMPILATION_CACHE"] = "true"  # conftest pins it off

    first = _run_cache_child(env)
    assert first["enabled"] and first["active"] == str(cc)
    assert first["dir"] == first["jax_dir"] == str(cc)
    assert "jax_compilation_cache_dir" not in first["updates"]
    assert first["entries"] > 0 and any(cc.iterdir())
    assert first["hits"] == 0  # fresh dir: everything compiles
    second = _run_cache_child(env)
    assert second["hits"] > 0, second  # the same plan loads, not compiles
    assert second["misses"] < first["misses"] + first["hits"] + 1


def test_persistent_cache_unset_resolves_to_the_fixed_path(monkeypatch):
    """Unset: ONE fixed path under the checkout — the same for every
    process and every data root (a per-run directory never hits)."""
    from banyandb_tpu.utils import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.resolve_dir() == (
        os.path.join(repo, ".compile-cache"), False,
    )
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache.resolve_dir() == ("/some/dir", True)
    # the fixed path is git-ignored (a cache is never committed)
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".compile-cache/" in f.read().split()


# -- counters end-to-end over the bus ---------------------------------------


def test_cache_counters_via_running_server(tmp_path, monkeypatch):
    from banyandb_tpu.cluster.rpc import GrpcTransport
    from banyandb_tpu.models.measure import DictColumn
    from banyandb_tpu.server import TOPIC_METRICS, TOPIC_QL, StandaloneServer

    monkeypatch.setenv("BYDB_PRECOMPILE", "1")
    srv = StandaloneServer(tmp_path, port=0)
    reg = srv.registry
    from banyandb_tpu.api import (
        Catalog,
        Entity,
        FieldSpec,
        FieldType,
        Group,
        Measure,
        ResourceOpts,
        TagSpec,
        TagType,
    )

    reg.create_group(Group("g", Catalog.MEASURE, ResourceOpts(shard_num=1)))
    reg.create_measure(
        Measure(
            group="g",
            name="m",
            tags=(TagSpec("svc", TagType.STRING),),
            fields=(FieldSpec("v", FieldType.FLOAT),),
            entity=Entity(("svc",)),
        )
    )
    n = 5000
    rng = np.random.default_rng(1)
    srv.measure.write_columns(
        "g",
        "m",
        ts_millis=T0 + np.arange(n, dtype=np.int64),
        tags={
            "svc": DictColumn(
                [b"a", b"b", b"c"], rng.integers(0, 3, n).astype(np.int32)
            )
        },
        fields={"v": rng.random(n)},
        versions=np.ones(n, dtype=np.int64),
    )
    srv.measure.flush()
    srv.start()
    tr = GrpcTransport()
    try:
        ql = (
            f"SELECT sum(v) FROM MEASURE m IN g TIME BETWEEN {T0} AND "
            f"{T0 + n + 1} GROUP BY svc"
        )
        for _ in range(3):  # repeats hit the serving cache
            tr.call(srv.addr, TOPIC_QL, {"ql": ql}, timeout=120.0)
        txt = tr.call(srv.addr, TOPIC_METRICS, {}, timeout=60.0)["prometheus"]
    finally:
        tr.close()
        srv.stop()
    metrics = {}
    for line in txt.splitlines():
        name, _, value = line.rpartition(" ")
        metrics[name] = float(value)
    assert metrics["banyandb_serving_cache_hits"] > 0
    assert metrics["banyandb_serving_cache_misses"] > 0
    assert "banyandb_serving_cache_evictions" in metrics
    assert "banyandb_device_cache_hits" in metrics
    assert "banyandb_compile_cache_enabled" in metrics
    assert metrics["banyandb_precompile_recorded"] >= 1
    # the query's span tree says in-band which cache answered it
    import dataclasses

    from banyandb_tpu import bydbql
    from banyandb_tpu.obs.tracer import find_span

    req = dataclasses.replace(bydbql.parse(ql), trace=True)
    res = srv.measure.query(req)
    gather = find_span(res.trace["span_tree"], "gather")
    assert gather["tags"]["serving_cache"] in ("hit", "miss")
    assert "serving_cache" not in res.trace  # the flat stats copy is gone


def test_serving_cache_eviction_counter():
    from banyandb_tpu.storage.cache import ServingCache

    c = ServingCache(budget_bytes=100)
    c.get_or_load(("a",), lambda: np.zeros(10, dtype=np.float64))  # 80 B
    c.get_or_load(("b",), lambda: np.zeros(10, dtype=np.float64))  # evicts a
    st = c.stats()
    assert st["evictions"] >= 1
    assert st["misses"] == 2
