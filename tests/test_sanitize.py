"""bdsan runtime sanitizers (docs/sanitizers.md).

Three layers under test:

- seeded-violation proofs: the lock wrapper catches an out-of-order
  acquisition against a declared graph; the leak tracker catches a
  seeded leaked thread and a seeded leaked fd;
- identity mapping: package-created locks carry their static
  declaration ids (the lockorder/lockwatch shared scheme);
- the capstone one-shard concurrency stress: concurrent writes +
  queries + flush/merge/retention loops + TopN accumulation, with the
  dynamic lock-order witness log required to be CONSISTENT with the
  declared static graph and zero leaked threads/fds afterwards.  The
  tier-1 smoke runs seconds; `-m slow` runs minutes
  (BYDB_STRESS_SECONDS overrides).
"""

from __future__ import annotations

import os
import threading
import time

import pytest

from banyandb_tpu import sanitize
from banyandb_tpu.sanitize import leaks, lockwatch

# -- gate ---------------------------------------------------------------


def test_enabled_gate(monkeypatch):
    monkeypatch.setenv("BYDB_SANITIZE", "0")
    assert not sanitize.enabled()
    monkeypatch.setenv("BYDB_SANITIZE", "1")
    assert sanitize.enabled()
    monkeypatch.setenv("BYDB_SANITIZE", "yes")
    assert sanitize.enabled()
    monkeypatch.delenv("BYDB_SANITIZE")
    assert not sanitize.enabled()


# -- lock wrapper -------------------------------------------------------


def _traced_pair(declared):
    w = lockwatch.LockWatch(declared=declared)
    a = lockwatch.TracedLock(lockwatch._REAL_LOCK(), "A", w)
    b = lockwatch.TracedLock(lockwatch._REAL_LOCK(), "B", w)
    return w, a, b


def test_traced_lock_behaves_like_a_lock():
    w, a, _b = _traced_pair(declared=None)
    assert not a.locked()
    with a:
        assert a.locked()
        # non-blocking re-acquire of a plain lock fails, like the real one
        assert a.acquire(blocking=False) is False
    assert not a.locked()
    assert a.acquire(timeout=0.1) is True
    a.release()
    assert ("A", "A") not in w.snapshot_edges()


def test_declared_order_records_edge_without_violation():
    w, a, b = _traced_pair(declared=frozenset({("A", "B")}))
    with a:
        with b:
            pass
    assert ("A", "B") in w.snapshot_edges()
    assert w.snapshot_violations() == []


def test_seeded_out_of_order_acquisition_flagged():
    w, a, b = _traced_pair(declared=frozenset({("A", "B")}))
    with b:
        with a:  # inverted: B held while acquiring A
            pass
    vs = w.snapshot_violations()
    assert [(v.held, v.acquired) for v in vs] == [("B", "A")]
    assert vs[0].thread and vs[0].site  # a witness, not just a boolean


def test_same_declaration_reacquire_records_no_edge():
    # two instances of one class share a declaration id: their nesting is
    # the static self-edge rule's business, not a runtime order edge
    w = lockwatch.LockWatch(declared=frozenset())
    a1 = lockwatch.TracedLock(lockwatch._REAL_LOCK(), "X", w)
    a2 = lockwatch.TracedLock(lockwatch._REAL_LOCK(), "X", w)
    with a1:
        with a2:
            pass
    assert w.snapshot_edges() == {}
    assert w.snapshot_violations() == []


def test_fallback_ids_are_exempt_from_validation():
    # unmapped (test-created, "path:line"-identified) locks record edges
    # but never violations: the declared graph knows nothing about them
    w = lockwatch.LockWatch(declared=frozenset())
    a = lockwatch.TracedLock(lockwatch._REAL_LOCK(), "tests/x.py:1", w)
    b = lockwatch.TracedLock(lockwatch._REAL_LOCK(), "tests/x.py:2", w)
    with a:
        with b:
            pass
    assert len(w.snapshot_edges()) == 1
    assert w.snapshot_violations() == []


@pytest.mark.skipif(not sanitize.installed(), reason="sanitizers off")
def test_package_locks_carry_declaration_ids(tmp_path):
    from banyandb_tpu.cluster.handoff import HandoffController

    h = HandoffController(tmp_path)
    assert isinstance(h._lock, lockwatch.TracedLock)
    assert h._lock.lock_id == (
        "banyandb_tpu.cluster.handoff.HandoffController._lock"
    )
    from banyandb_tpu.storage.memtable import MemTable

    mt = MemTable(["t"], ["f"])
    assert isinstance(mt._lock, lockwatch.TracedLock)
    assert mt._lock.lock_id == (
        "banyandb_tpu.storage.memtable.MemTable._lock"
    )


def test_static_model_covers_known_declarations():
    m = lockwatch.load_static()
    ids = set(m.decl_sites.values())
    for want in (
        "banyandb_tpu.cluster.wqueue.WriteQueue._lock",
        "banyandb_tpu.cluster.handoff.HandoffController._lock",
        "banyandb_tpu.storage.memtable.MemTable._lock",
        "banyandb_tpu.models.topn.TopNProcessorManager._obs_lock",
        "banyandb_tpu.cluster.liaison.Liaison._alive_lock",
    ):
        assert want in ids, want
    # the TopN observation lock is an RLock (reentrant by design)
    assert "banyandb_tpu.models.topn.TopNProcessorManager._obs_lock" in (
        m.reentrant
    )


def test_declared_graph_with_extras_is_acyclic():
    """DECLARED_EXTRA_EDGES are reviewed additions to the static graph:
    the union must stay free of deadlock cycles or the declaration is
    self-contradictory."""
    from banyandb_tpu.lint.whole_program.lockorder import _cycles

    m = lockwatch.load_static()
    adj: dict = {}
    for a, b in m.declared:
        if a != b:
            adj.setdefault(a, set()).add(b)
    assert _cycles(adj) == []


# -- leak tracker -------------------------------------------------------


def test_leak_tracker_catches_seeded_thread():
    tr = leaks.LeakTracker(track_fds=False).snapshot()
    stop = threading.Event()
    t = threading.Thread(target=stop.wait, name="bdsan-seeded-leak")
    t.start()
    try:
        rep = tr.check(grace_s=0.2)
        assert [x.name for x in rep.threads] == ["bdsan-seeded-leak"]
        assert not rep.clean() and "bdsan-seeded-leak" in rep.render()
    finally:
        stop.set()
        t.join()
    assert tr.check(grace_s=2.0).clean()


def test_leak_tracker_allowlist_spares_named_daemons():
    tr = leaks.LeakTracker(
        thread_allowlist=(r"^spared-",), track_fds=False
    ).snapshot()
    stop = threading.Event()
    t = threading.Thread(target=stop.wait, name="spared-daemon")
    t.start()
    try:
        assert tr.check(grace_s=0.2).clean()
    finally:
        stop.set()
        t.join()


@pytest.mark.skipif(
    not os.path.isdir("/proc/self/fd"), reason="no /proc fd table"
)
def test_leak_tracker_catches_seeded_fd(tmp_path):
    tr = leaks.LeakTracker().snapshot()
    fd = os.open(tmp_path / "leak.bin", os.O_CREAT | os.O_WRONLY)
    # fd numbers recycle: if the number was open at snapshot time (and
    # closed since), evict it from the baseline so the leak is visible
    tr._fds.discard(fd)
    try:
        rep = tr.check(grace_s=0.2)
        assert any(f == fd for f, _target in rep.fds), rep.render()
    finally:
        os.close(fd)
    assert tr.check(grace_s=1.0).clean()


def test_thread_grace_window_tolerates_finishing_threads():
    before = leaks.thread_snapshot()
    t = threading.Thread(target=lambda: time.sleep(0.3), name="short-lived")
    t.start()
    # the thread outlives the check start but dies inside the grace
    assert leaks.leaked_threads(before, grace_s=2.0) == []
    t.join()


# -- the per-phase watchdog ---------------------------------------------

_HANGS = {
    # a test blocked in its call
    "call": """
def test_blocked():
    _ran()
    threading.Event().wait()
""",
    # a test blocked in a module-scoped fixture's setup
    "setup": """
@pytest.fixture(scope="module")
def stuck():
    _ran()
    threading.Event().wait()
    yield


def test_blocked(stuck):
    pass
""",
}


@pytest.mark.parametrize("phase", sorted(_HANGS))
def test_watchdog_fails_a_hung_test_and_the_run_goes_on(tmp_path, phase):
    """Under xdist, a test that hangs past BYDB_SANITIZE_WATCHDOG_S fails
    once, in its own worker, with every thread's stack on the
    controller's stderr, and the tests beside it still run and pass."""
    import subprocess
    import sys
    import xml.etree.ElementTree as ET
    from pathlib import Path

    repo = Path(__file__).resolve().parent.parent
    mark = tmp_path / "ran"
    (tmp_path / "test_hang.py").write_text(
        "import threading\n\nimport pytest\n\n\n"
        f"def _ran():\n    with open({str(mark)!r}, 'a') as f:\n"
        "        f.write('ran\\n')\n\n\n"
        "def test_before():\n    pass\n\n"
        + _HANGS[phase]
        + "\n\ndef test_after():\n    pass\n"
    )
    xml = tmp_path / "junit.xml"
    env = dict(os.environ, BYDB_SANITIZE="1", BYDB_SANITIZE_WATCHDOG_S="2",
               PYTHONPATH=str(repo))
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", str(tmp_path / "test_hang.py"),
         "-p", "tests.conftest", "-p", "xdist", "-n", "2", "--dist",
         "loadfile", "-p", "no:cacheprovider", "-p", "no:randomly", "-q",
         f"--junitxml={xml}", f"--rootdir={tmp_path}"],
        cwd=repo, env=env, capture_output=True, text=True, timeout=120,
    )
    out = proc.stdout + proc.stderr
    assert proc.returncode == 1, out
    cases = {c.get("name"): c for c in ET.parse(xml).getroot().iter("testcase")}
    assert sorted(cases) == ["test_after", "test_before", "test_blocked"], out
    for name in ("test_before", "test_after"):
        assert len(cases[name]) == 0, out  # passed: no failure/error child
    (verdict,) = cases["test_blocked"]
    assert verdict.tag == ("failure" if phase == "call" else "error"), out
    assert "WatchdogExpired" in verdict.get("message", ""), out
    assert float(cases["test_blocked"].get("time")) < 2 + 30
    assert mark.read_text() == "ran\n"  # run once: xdist did not re-run it
    blocked_fn = "test_blocked" if phase == "call" else "stuck"
    assert f"+++ bdsan watchdog: test_hang.py::test_blocked ({phase})" in out
    assert f"test_hang.py\", line" in out and f"in {blocked_fn}" in out, out
    assert "node down" not in out, out
    assert time.monotonic() - t0 < 100


# -- the capstone stress ------------------------------------------------


def _build_stress_engine(root):
    from banyandb_tpu.api import (
        Catalog,
        Entity,
        FieldSpec,
        FieldType,
        Group,
        IntervalRule,
        Measure,
        ResourceOpts,
        SchemaRegistry,
        TagSpec,
        TagType,
    )
    from banyandb_tpu.api.schema import TopNAggregation
    from banyandb_tpu.models.measure import MeasureEngine

    reg = SchemaRegistry(root)
    reg.create_group(
        Group(
            "stress",
            Catalog.MEASURE,
            ResourceOpts(
                shard_num=1,
                segment_interval=IntervalRule(1, "hour"),
                ttl=IntervalRule(2, "hour"),
            ),
        )
    )
    reg.create_measure(
        Measure(
            group="stress",
            name="cpm",
            tags=(
                TagSpec("svc", TagType.STRING),
                TagSpec("region", TagType.STRING),
            ),
            fields=(FieldSpec("value", FieldType.FLOAT),),
            entity=Entity(("svc",)),
        )
    )
    # a TopN rule so ingest drives the TopNProcessorManager concurrently
    reg.create_topn(
        TopNAggregation(
            group="stress",
            name="top-cpm",
            source_measure="cpm",
            field_name="value",
            group_by_tag_names=(),
            counters_number=50,
            lru_size=4,
        )
    )
    return MeasureEngine(reg, root / "data")


def _run_stress(tmp_path, seconds: float, writers: int = 2, queriers: int = 2):
    """One-shard concurrency stress: N writer threads (row ingest with
    advancing event time, feeding flush/merge and a TopN rule), M query
    threads over the trailing window, while the real lifecycle loops
    flush/merge/retire underneath.  Returns collected worker errors plus
    the lock-order witness delta observed during the run."""
    import numpy as np

    from banyandb_tpu.api import (
        Aggregation,
        DataPointValue,
        GroupBy,
        QueryRequest,
        TimeRange,
        WriteRequest,
    )

    engine = _build_stress_engine(tmp_path)
    HOUR = 3_600_000
    now_ms = int(time.time() * 1000)
    t_start = now_ms - 5 * HOUR  # old enough that retention retires tails

    edges_before = (
        set(lockwatch.watch().snapshot_edges())
        if sanitize.installed()
        else set()
    )
    tracker = leaks.LeakTracker().snapshot()

    # warmup before the clock starts: the first query pays XLA compile,
    # which on a slow host could eat the whole smoke window
    engine.write(
        WriteRequest(
            "stress",
            "cpm",
            (
                DataPointValue(
                    ts_millis=t_start,
                    tags={"svc": "svc-0", "region": "r0"},
                    fields={"value": 1.0},
                    version=1,
                ),
            ),
        )
    )
    engine.query(
        QueryRequest(
            groups=("stress",),
            name="cpm",
            time_range=TimeRange(t_start - HOUR, t_start + HOUR),
            agg=Aggregation("sum", "value"),
            group_by=GroupBy(("svc",)),
        )
    )

    engine.start_lifecycle(
        flush_interval_s=0.05,
        flush_min_rows=1,
        retention_interval_s=0.3,
        merge_sweep_interval_s=0.2,
        idle_timeout_s=600.0,
    )
    stop = threading.Event()
    errors: list = []
    written = [0] * writers
    queried = [0] * queriers
    # event-time high-water mark shared with queriers (GIL-atomic list)
    hw = [t_start]

    def writer(wid: int):
        rng = np.random.default_rng(100 + wid)
        batch = 200
        try:
            while not stop.is_set():
                base = hw[0]
                points = tuple(
                    DataPointValue(
                        ts_millis=int(
                            base + (i * writers + wid) * 20
                        ),
                        tags={
                            "svc": f"svc-{int(rng.integers(0, 8))}",
                            "region": f"r{int(rng.integers(0, 3))}",
                        },
                        fields={"value": float(rng.integers(0, 1000))},
                        version=1,
                    )
                    for i in range(batch)
                )
                engine.write(WriteRequest("stress", "cpm", points))
                written[wid] += batch
                if wid == 0:
                    # advance event time ~4 minutes per batch so the run
                    # crosses hourly segment boundaries and TTL horizons
                    hw[0] = base + 240_000
        except Exception as e:  # noqa: BLE001 - surfaced by the test
            errors.append(("writer", wid, repr(e)))

    def querier(qid: int):
        rng = np.random.default_rng(900 + qid)
        try:
            while not stop.is_set():
                end = hw[0]
                req = QueryRequest(
                    groups=("stress",),
                    name="cpm",
                    time_range=TimeRange(end - HOUR, end + HOUR),
                    agg=Aggregation(
                        ("sum", "mean", "count", "max")[
                            int(rng.integers(0, 4))
                        ],
                        "value",
                    ),
                    group_by=(
                        GroupBy(("svc",)) if rng.integers(0, 2) else None
                    ),
                )
                engine.query(req)
                queried[qid] += 1
        except Exception as e:  # noqa: BLE001 - surfaced by the test
            errors.append(("querier", qid, repr(e)))

    threads = [
        threading.Thread(target=writer, args=(w,), name=f"stress-writer-{w}")
        for w in range(writers)
    ] + [
        threading.Thread(target=querier, args=(q,), name=f"stress-querier-{q}")
        for q in range(queriers)
    ]
    for t in threads:
        t.start()
    time.sleep(seconds)
    stop.set()
    for t in threads:
        t.join(timeout=60)
    # one forced synchronous tick proves the loops' stage bodies still
    # run clean after the storm, then stop everything
    engine._loops.tick()
    engine.topn.flush_all_windows()
    engine.close()

    new_edges = {}
    if sanitize.installed():
        all_edges = lockwatch.watch().snapshot_edges()
        new_edges = {
            e: w for e, w in all_edges.items() if e not in edges_before
        }
    report = tracker.check(grace_s=5.0)
    return {
        "errors": errors,
        "written": sum(written),
        "queried": sum(queried),
        "new_edges": new_edges,
        "leaks": report,
    }


def _assert_stress_clean(res):
    assert res["errors"] == [], res["errors"]
    assert res["written"] > 0 and res["queried"] > 0
    # acceptance: every runtime-observed edge between declared locks is
    # present in the static lock-order graph (+ reviewed extras)
    undeclared = lockwatch.undeclared_edges(res["new_edges"])
    assert undeclared == [], "\n".join(
        f"{w.held} -> {w.acquired} at {w.site} [{w.thread}]"
        for w in undeclared
    )
    assert res["leaks"].clean(), res["leaks"].render()


def test_stress_smoke_one_shard(tmp_path):
    """Tier-1 slice of the capstone stress (~4s wall)."""
    _assert_stress_clean(_run_stress(tmp_path, seconds=3.0))


@pytest.mark.slow
def test_stress_one_shard_sustained(tmp_path):
    """Minutes-long stress (BYDB_STRESS_SECONDS overrides, default 120:
    its call phase must end inside BYDB_SANITIZE_WATCHDOG_S, 180 s)."""
    seconds = float(os.environ.get("BYDB_STRESS_SECONDS", "120"))
    _assert_stress_clean(
        _run_stress(tmp_path, seconds=seconds, writers=3, queriers=3)
    )
