"""Serving cache (banyand/internal/storage/cache.go:125 analog):
repeat queries must skip disk reads, decode, dict building, and the
host gather entirely (VERDICT r1 next #3)."""

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
    Measure,
    QueryRequest,
    ResourceOpts,
    SchemaRegistry,
    TagSpec,
    TagType,
    TimeRange,
    Top,
    WriteRequest,
)
from banyandb_tpu.models.measure import MeasureEngine
from banyandb_tpu.storage import part as part_mod
from banyandb_tpu.storage.cache import (
    ServingCache,
    global_cache,
    reset_global_cache,
)

T0 = 1_700_000_000_000


@pytest.fixture()
def engine(tmp_path):
    reset_global_cache()
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
            fields=(FieldSpec("lat", FieldType.FLOAT),),
            entity=Entity(("svc",)),
        )
    )
    eng = MeasureEngine(reg, tmp_path / "data")
    rng = np.random.default_rng(0)
    pts = tuple(
        DataPointValue(
            ts_millis=T0 + i,
            tags={"svc": f"s{rng.integers(0, 8)}", "region": "eu"},
            fields={"lat": float(rng.gamma(2.0, 40.0))},
            version=1,
        )
        for i in range(4000)
    )
    eng.write(WriteRequest("g", "m", pts))
    eng.flush()
    return eng


def _req(**kw):
    defaults = dict(
        groups=("g",),
        name="m",
        time_range=TimeRange(T0, T0 + 10_000_000),
        group_by=GroupBy(("svc",)),
        agg=Aggregation("sum", "lat"),
        criteria=Condition("region", "eq", "eu"),
    )
    defaults.update(kw)
    return QueryRequest(**defaults)


def test_repeat_query_skips_part_reads_and_gather(engine, monkeypatch):
    decodes = []
    orig = part_mod.Part._read_uncached

    def counting(self, *a, **kw):
        decodes.append(self.dir)
        return orig(self, *a, **kw)

    monkeypatch.setattr(part_mod.Part, "_read_uncached", counting)

    r1 = engine.query(_req())
    first_decodes = len(decodes)
    assert first_decodes > 0  # cold: parts actually decoded

    before = global_cache().stats()
    r2 = engine.query(_req())
    after = global_cache().stats()

    assert len(decodes) == first_decodes  # warm: zero part decodes
    assert after["hits"] > before["hits"]
    assert r1.groups == r2.groups
    assert r1.values["sum(lat)"] == r2.values["sum(lat)"]


def test_gather_cache_not_poisoned_by_memtable(engine):
    r1 = engine.query(_req())
    # New unflushed write must be visible: memtable sources carry no
    # cache identity, so the gather cache is bypassed.
    engine.write(
        WriteRequest(
            "g",
            "m",
            (
                DataPointValue(
                    ts_millis=T0 + 50_000,
                    tags={"svc": "s0", "region": "eu"},
                    fields={"lat": 10_000.0},
                    version=1,
                ),
            ),
        )
    )
    r2 = engine.query(_req())
    s1 = dict(zip([g[0] for g in r1.groups], r1.values["sum(lat)"]))
    s2 = dict(zip([g[0] for g in r2.groups], r2.values["sum(lat)"]))
    # tolerance: f32 kernel output granularity at ~5e4 magnitude
    assert abs(s2["s0"] - s1["s0"] - 10_000.0) < 0.1


def test_different_time_ranges_are_distinct_entries(engine):
    r_all = engine.query(_req())
    r_half = engine.query(
        _req(time_range=TimeRange(T0, T0 + 2000))
    )
    total = sum(r_all.values["count"])
    half = sum(r_half.values["count"])
    assert total == 4000 and half == 2000


def test_lru_eviction_respects_budget():
    c = ServingCache(budget_bytes=10_000)
    for i in range(20):
        c.get_or_load(("k", i), lambda: np.zeros(1000, np.int8))
    st = c.stats()
    assert st["bytes"] <= 10_000
    assert st["entries"] < 20  # older entries evicted


def test_entry_cap_evicts_beyond_capacity():
    """BYDB_SERVING_CACHE_CAP (ISSUE 10 satellite): an explicit entry
    capacity bounds the population independently of the byte budget —
    the r06 load run's 916-entry squeeze becomes an operator knob."""
    c = ServingCache(budget_bytes=1 << 30, max_entries=5)
    for i in range(12):
        c.get_or_load(("k", i), lambda: np.zeros(10, np.int8))
    st = c.stats()
    assert st["entries"] == 5
    assert st["cap"] == 5
    assert st["evictions"] == 7
    # LRU: the newest entries survive
    hits_before = c.stats()["hits"]
    c.get_or_load(("k", 11), lambda: (_ for _ in ()).throw(AssertionError))
    assert c.stats()["hits"] == hits_before + 1


def test_entry_cap_env_default(monkeypatch):
    """BYDB_SERVING_CACHE_CAP is read at CONSTRUCTION time (ISSUE 15
    satellite): a post-import env change — or a late server flag — must
    take effect on the next ServingCache() without re-importing the
    module (the old import-time read froze the value forever)."""
    monkeypatch.setenv("BYDB_SERVING_CACHE_CAP", "3")
    c = ServingCache(budget_bytes=1 << 30)
    assert c.cap == 3
    for i in range(6):
        c.get_or_load(("e", i), lambda: np.zeros(1, np.int8))
    assert c.stats()["entries"] == 3
    # the knob stays live: a second post-import change is honored too
    monkeypatch.setenv("BYDB_SERVING_CACHE_CAP", "5")
    assert ServingCache(budget_bytes=1 << 30).cap == 5
    # explicit max_entries still wins over the env
    assert ServingCache(budget_bytes=1 << 30, max_entries=2).cap == 2


def test_set_cap_live_shrinks_and_churn_reported():
    c = ServingCache(budget_bytes=1 << 30)
    for i in range(10):
        c.get_or_load(("k", i), lambda: np.zeros(1, np.int8))
    assert c.stats()["entries"] == 10
    c.set_cap(4)
    st = c.stats()
    assert st["entries"] == 4 and st["evictions"] == 6
    # eviction-churn gauge input: evictions per lookup
    assert st["churn"] == pytest.approx(6 / 10, abs=1e-4)


def test_oversized_value_served_uncached():
    c = ServingCache(budget_bytes=100)
    v = c.get_or_load(("big",), lambda: np.zeros(1000, np.int8))
    assert v.nbytes == 1000
    assert c.stats()["entries"] == 0


def test_concurrent_queries_with_dict_growth(engine):
    """Concurrent queries share one DictState while flushes grow the
    dictionaries — no 'dict changed size during iteration', no wrong
    decodes (VERDICT r1: concurrency under-tested)."""
    import threading

    errors: list[Exception] = []
    stop = threading.Event()

    def reader():
        try:
            while not stop.is_set():
                r = engine.query(_req())
                names = {g[0] for g in r.groups}
                assert all(n.startswith("s") for n in names)
        except Exception as e:  # propagated to the main thread below
            errors.append(e)

    threads = [threading.Thread(target=reader) for _ in range(4)]
    for t in threads:
        t.start()
    try:
        for i in range(10):
            engine.write(
                WriteRequest(
                    "g",
                    "m",
                    (
                        DataPointValue(
                            ts_millis=T0 + 70_000 + i,
                            tags={"svc": f"sX{i}", "region": "eu"},
                            fields={"lat": 1.0},
                            version=1,
                        ),
                    ),
                )
            )
            engine.flush()
    finally:
        stop.set()
        for t in threads:
            t.join()
    assert not errors, errors[0]


def _resets() -> float:
    from banyandb_tpu.obs import metrics as obs_metrics

    counters = obs_metrics.global_meter().snapshot()["counters"]
    return sum(v for k, v in counters.items() if k[0] == "dict_state_resets")


def _flush_part(engine, names, at):
    """One more flush: a point a name from T0 + `at` on, so the parts it
    makes hold `names` alone in their dictionaries."""
    engine.write(
        WriteRequest(
            "g",
            "m",
            tuple(
                DataPointValue(
                    ts_millis=T0 + at + i,
                    tags={"svc": n, "region": "eu"},
                    fields={"lat": float(i + 1)},
                    version=1,
                )
                for i, n in enumerate(names)
            ),
        )
    )
    engine.flush()


def _rows(r):
    return list(zip(r.groups, r.values["count"], r.values["sum(lat)"]))


def test_persistent_group_cap_resets_state(engine, monkeypatch):
    """Over the bound a state is reset for the dead values it holds: 8
    live svc values over a bound of 2 stay, 9 dead ones beside them (more
    than half of the group space) go, in one reset."""
    from banyandb_tpu.query import measure_exec

    monkeypatch.setattr(measure_exec, "_MAX_PERSISTENT_GROUPS", 2)
    st = engine._dict_state("g", "m")
    engine.query(_req())
    token_before, resets = st.token, _resets()
    engine.query(_req(time_range=TimeRange(T0, T0 + 9_000_000)))
    assert st.token == token_before and st.live_seen["svc"] == 8
    with st.lock:  # values no live part holds any more
        st.dicts.add_source("svc", [b"gone%d" % i for i in range(9)])
    r = engine.query(_req(time_range=TimeRange(T0, T0 + 8_000_000)))
    assert st.token != token_before and _resets() - resets == 1
    assert len(st.dicts.maps["svc"]) == 8
    assert sum(r.values["count"]) == 4000  # results still correct


def test_a_narrow_query_between_wide_ones_resets_nothing(engine, monkeypatch):
    """`live_seen` keeps the largest reading since the last reset, not
    the latest: a query over one part (8 of 48 values) must not make the
    state look bloated and set off a reset the next wide query undoes."""
    from banyandb_tpu.query import measure_exec

    monkeypatch.setattr(measure_exec, "_MAX_PERSISTENT_GROUPS", 16)
    _flush_part(engine, [f"w{i}" for i in range(40)], at=100_000)
    st = engine._dict_state("g", "m")
    wide = engine.query(_req())
    token, resets = st.token, _resets()
    assert st.live_seen["svc"] == 48
    narrow = engine.query(_req(time_range=TimeRange(T0, T0 + 50_000)))
    assert len(narrow.groups) == 8 and st.live_memo["svc"][1] == 8
    assert st.live_seen["svc"] == 48
    again = engine.query(_req(time_range=TimeRange(T0, T0 + 9_000_000)))
    assert st.token == token and _resets() == resets
    assert _rows(again) == _rows(wide)


def test_answers_do_not_depend_on_the_states_history(engine, monkeypatch):
    """Global codes are first-seen order, so a kept state's differ from a
    fresh one's; the rows that come back do not: the same query against
    a fresh state and against one that first served a wider and a
    differently-ordered set of sources."""
    from banyandb_tpu.query import measure_exec

    monkeypatch.setattr(measure_exec, "_MAX_PERSISTENT_GROUPS", 16)
    _flush_part(engine, [f"w{i}" for i in reversed(range(40))], at=100_000)
    _flush_part(engine, [f"x{i}" for i in range(30)], at=200_000)
    st = engine._dict_state("g", "m")
    two_parts = TimeRange(T0, T0 + 150_000)
    top5 = _req(time_range=two_parts, top=Top(5, "lat"))
    fresh = engine.query(_req(time_range=two_parts))
    fresh_top = engine.query(top5)
    fresh_codes = dict(st.dicts.maps["svc"])
    st.reset()
    engine.query(_req(time_range=TimeRange(T0 + 200_000, T0 + 300_000)))
    engine.query(_req(time_range=TimeRange(T0 + 100_000, T0 + 300_000)))
    engine.query(_req())  # the wider set: 78 values
    token = st.token
    kept = engine.query(_req(time_range=two_parts))
    kept_top = engine.query(top5)
    assert st.token == token and len(st.dicts.maps["svc"]) == 78
    assert {v: st.dicts.maps["svc"][v] for v in fresh_codes} != fresh_codes
    assert len(fresh.groups) == 48 and _rows(kept) == _rows(fresh)
    assert len(fresh_top.groups) == 5 and _rows(kept_top) == _rows(fresh_top)


@pytest.mark.parametrize("when", ["before_gather", "after_gather"])
def test_a_query_across_a_reset_leaves_the_new_generation_clean(
    engine, monkeypatch, when
):
    """A query holding a pre-reset `gd` writes nothing into the new
    generation: no remap table, no `live_seen`, no memo, though the
    group space it gathered is over the bound."""
    from banyandb_tpu.query import measure_exec

    monkeypatch.setattr(measure_exec, "_MAX_PERSISTENT_GROUPS", 2)
    st = engine._dict_state("g", "m")
    gather = measure_exec._gather_rows

    def racing(*args, **kw):
        if when == "before_gather":
            st.reset()  # another query's reset, while this one is in flight
        out = gather(*args, **kw)
        if when == "after_gather":
            st.reset()
        return out

    monkeypatch.setattr(measure_exec, "_gather_rows", racing)
    r = engine.query(_req())
    assert sum(r.values["count"]) == 4000 and len(r.groups) == 8
    assert st.live_seen == {} and st.live_memo == {} and st.remaps == {}
    assert all(len(m) == 0 for m in st.dicts.maps.values())


def test_concurrent_queries_over_the_bound_while_values_churn(engine, monkeypatch):
    """More readers than cores share one DictState over the bound while
    dead values pile into it and set off resets under their feet: every
    answer is right, nothing raises, and once the churn stops the state
    is the live size again."""
    import sys
    import threading

    from banyandb_tpu.query import measure_exec

    monkeypatch.setattr(measure_exec, "_MAX_PERSISTENT_GROUPS", 2)
    st = engine._dict_state("g", "m")
    errors: list[Exception] = []
    stop = threading.Event()

    def reader(k: int):
        try:
            n = 0
            while not stop.is_set():
                n += 1
                r = engine.query(
                    _req(time_range=TimeRange(T0, T0 + 5_000_000 + 16 * n + k))
                )
                assert sum(r.values["count"]) == 4000
                assert sorted(g[0] for g in r.groups) == [f"s{i}" for i in range(8)]
        except Exception as e:  # propagated to the main thread below
            errors.append(e)

    threads = [threading.Thread(target=reader, args=(k,)) for k in range(12)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for round_ in range(10):
            with st.lock:
                st.dicts.ensure("svc")
                st.dicts.add_source(
                    "svc", [b"gone%d_%d" % (round_, i) for i in range(20)]
                )
            stop.wait(0.05)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=60)
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors[0]
    engine.query(_req())
    engine.query(_req(time_range=TimeRange(T0, T0 + 4_999_999)))
    assert len(st.dicts.maps["svc"]) == 8 and st.live_seen["svc"] == 8


def test_dict_codes_stable_across_queries(engine):
    """Persistent DictState: group decode stays correct as dicts grow."""
    r1 = engine.query(_req())
    # flush a new part with a brand-new tag value -> dictionary grows
    engine.write(
        WriteRequest(
            "g",
            "m",
            (
                DataPointValue(
                    ts_millis=T0 + 60_000,
                    tags={"svc": "s_new", "region": "eu"},
                    fields={"lat": 5.0},
                    version=1,
                ),
            ),
        )
    )
    engine.flush()
    r2 = engine.query(_req())
    names = {g[0] for g in r2.groups}
    assert "s_new" in names
    s1 = dict(zip([g[0] for g in r1.groups], r1.values["sum(lat)"]))
    s2 = dict(zip([g[0] for g in r2.groups], r2.values["sum(lat)"]))
    for k, v in s1.items():
        assert abs(s2[k] - v) <= abs(v) * 1e-5 + 1e-3


def test_partials_cache_keyed_by_rep_tags(engine):
    """ADVICE r5: two queries with identical plan + predicate values but
    different projected-not-grouped tag sets must NOT share a partials
    cache entry — the projecting query would be served rep_vals=None
    (its projected tag silently missing from every group row)."""
    # warm the cache with the projection-free shape
    r1 = engine.query(_req())
    assert not r1.rep_tags

    # same filter/group/agg, now projecting a non-grouped tag: the
    # representative values must materialize, not come back empty from
    # the projection-free entry
    r2 = engine.query(_req(tag_projection=("svc", "region")))
    assert "region" in r2.rep_tags
    assert len(r2.rep_tags["region"]) == len(r2.groups)
    assert all(v == "eu" for v in r2.rep_tags["region"])

    # and the reverse order on a fresh filter value: projection first,
    # then projection-free — the latter must not inherit rep state
    crit = Condition("region", "in", ("eu", "nowhere"))
    r3 = engine.query(_req(criteria=crit, tag_projection=("svc", "region")))
    assert "region" in r3.rep_tags
    r4 = engine.query(_req(criteria=crit))
    assert not r4.rep_tags
    assert r3.groups == r4.groups


# -- admission and victim choice by observed reuse (ISSUE 29) --------------


def _load(c: ServingCache, key, nbytes: int):
    """Ask `c` for `key`, a value of `nbytes`; -> (outcome, loader ran)."""
    ran: list = []

    def loader():
        ran.append(1)
        return np.zeros(nbytes, np.int8)

    return c.fetch((key,), loader)[1], bool(ran)


def _resident(c: ServingCache, key) -> bool:
    """Is `key` held, read without touching recency or the counters."""
    with c._lock:
        return (key,) in c._proven or (key,) in c._unproven


def test_proven_working_set_survives_one_shot_stream():
    """The ep9k case in small: three parts asked for by every query fill
    most of the budget, and each query brings an entry of its own that
    nobody asks for again and that needs a part's room."""
    c = ServingCache(budget_bytes=10_000)
    for _ in range(2):  # second pass: hits, so the parts are proven
        for part in "abc":
            _load(c, part, 3_000)
    for q in range(50):
        outcome, ran = _load(c, ("gather", q), 2_500)
        assert (outcome, ran) == ("refused", True)
        assert c.stats()["bytes"] <= 10_000
        for part in "abc":
            assert _load(c, part, 3_000) == ("hit", False)
    st = c.stats()
    assert st["refused"] == 50
    assert st["evictions"] == 0
    assert st["entries"] == 3


def test_refused_key_is_admitted_on_second_request():
    c = ServingCache(budget_bytes=10_000)
    for _ in range(2):
        for part in "abc":
            _load(c, part, 3_000)
    assert _load(c, "new", 3_000) == ("refused", True)
    assert not _resident(c, "new")
    # the second request is the evidence: admitted, at the cost of the
    # least recently used proven entry
    assert _load(c, "new", 3_000) == ("miss", True)
    assert _resident(c, "new") and not _resident(c, "a")
    assert _load(c, "new", 3_000) == ("hit", False)
    st = c.stats()
    assert (st["refused"], st["evictions"]) == (1, 1)
    assert st["bytes"] <= 10_000


def test_evicted_proven_key_comes_back_proven():
    """The trap of "evict never-hit entries first" alone: a part that was
    evicted and decoded again has no hit yet when the next one-shot entry
    arrives, and would be evicted for it again."""
    c = ServingCache(budget_bytes=10_000)
    for _ in range(2):
        for part in "abcd":  # four parts of 3,000: one is always out
            _load(c, part, 3_000)
    assert not _resident(c, "a")  # pushed out by "d"'s second request
    assert _load(c, "a", 3_000) == ("miss", True)  # back, and still proven
    assert _load(c, ("gather", 0), 2_500) == ("refused", True)
    assert _resident(c, "a")
    assert c.stats()["bytes"] <= 10_000


def test_unproven_entries_evict_in_plain_lru_order():
    c = ServingCache(budget_bytes=10_000)
    for i in range(25):
        assert _load(c, i, 1_000) == ("miss", True)
        # exactly the last ten, as one LRU keeps them
        assert [k for k in range(i + 1) if _resident(c, k)] == list(
            range(max(0, i - 9), i + 1)
        )
    st = c.stats()
    assert (st["refused"], st["evictions"], st["bytes"]) == (0, 15, 10_000)


def test_entry_cap_evicts_unproven_first_and_never_refuses():
    c = ServingCache(budget_bytes=1 << 30, max_entries=4)
    for _ in range(2):
        for part in "abc":
            _load(c, part, 10)
    for q in range(10):  # one-shot entries take turns in the fourth slot
        assert _load(c, ("gather", q), 10) == ("miss", True)
    assert all(_resident(c, part) for part in "abc")
    assert _resident(c, ("gather", 9)) and not _resident(c, ("gather", 8))
    c.set_cap(2)  # the unproven entry goes first, then the LRU proven one
    assert [p for p in "abc" if _resident(c, p)] == ["b", "c"]
    # at the cap with proven entries only, a new key still gets in
    assert _load(c, "new", 10) == ("miss", True)
    assert _resident(c, "new") and not _resident(c, "b")
    st = c.stats()
    assert st["refused"] == 0 and st["entries"] == 2


def test_ghost_list_is_bounded():
    from banyandb_tpu.storage import cache as cache_mod

    c = ServingCache(budget_bytes=100)
    for part in "ab":
        _load(c, part, 50)
        _load(c, part, 50)
    for q in range(cache_mod._GHOST_KEYS + 100):
        assert _load(c, ("gather", q), 10)[0] == "refused"
    assert len(c._ghosts) == cache_mod._GHOST_KEYS
    # the oldest refusal is forgotten: first sight again
    assert _load(c, ("gather", 0), 10)[0] == "refused"
    assert _load(c, ("gather", cache_mod._GHOST_KEYS + 99), 10)[0] == "miss"


def test_oversized_value_counts_as_refused():
    c = ServingCache(budget_bytes=100)
    assert _load(c, "big", 1_000) == ("refused", True)
    assert _load(c, "big", 1_000) == ("refused", True)  # never admissible
    st = c.stats()
    assert (st["entries"], st["bytes"], st["refused"]) == (0, 0, 2)


def test_distinct_ranges_stop_decoding_parts_again(tmp_path, monkeypatch):
    """Engine level, the ep9k case: decoded parts that nearly fill the
    budget, queried over ranges that never repeat.  One LRU decodes parts
    again for every query, because each query's gather (never asked for
    twice) evicts them; now the parts stay and the gather is refused."""
    from banyandb_tpu.obs.tracer import Tracer

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
            fields=(FieldSpec("lat", FieldType.FLOAT),),
            entity=Entity(("svc",)),
        )
    )
    eng = MeasureEngine(reg, tmp_path / "data")
    rng = np.random.default_rng(0)
    for batch in range(4):  # four parts a shard, 4,000 ms each
        eng.write(
            WriteRequest(
                "g",
                "m",
                tuple(
                    DataPointValue(
                        ts_millis=T0 + batch * 4000 + i,
                        tags={"svc": f"s{rng.integers(0, 8)}", "region": "eu"},
                        fields={"lat": float(rng.gamma(2.0, 40.0))},
                        version=1,
                    )
                    for i in range(4000)
                ),
            )
        )
        eng.flush()

    decodes = []
    orig = part_mod.Part._read_uncached

    def counting(self, *a, **kw):
        decodes.append(self.dir)
        return orig(self, *a, **kw)

    monkeypatch.setattr(part_mod.Part, "_read_uncached", counting)

    def ask(i: int):
        """Query i: every part in range, an end no other query has."""
        tracer = Tracer("test")
        res = eng.query(
            _req(time_range=TimeRange(T0, T0 + 16_000 - i)), tracer=tracer
        )
        spans = {}
        for s in tracer.finish()["children"]:
            spans[s["name"]] = s
            for c in s.get("children") or ():
                spans[c["name"]] = c
        return res, spans["part_gather"]["tags"], spans["gather"]["tags"]

    # eight decoded parts ~560 kB, a gather of all rows ~450 kB
    reset_global_cache(700_000)
    _, pg, g = ask(0)
    assert (pg["cache_hits"], pg["cache_misses"]) == (0, 8)
    assert pg["decoded_bytes"] > 500_000 and len(decodes) == 8
    assert g["serving_cache"] == "miss"  # room at unproven entries' cost
    ask(1)  # the parts evicted for query 0's gather come back, proven
    warm = len(decodes)

    got = []
    for i in range(2, 10):
        res, pg, g = ask(i)
        got.append(res)
        assert (pg["cache_hits"], pg["cache_misses"]) == (8, 0)
        assert pg["decoded_bytes"] == 0
        assert g["serving_cache"] == "refused"
        assert global_cache().stats()["bytes"] <= 700_000
    assert len(decodes) == warm  # one LRU (the parent): 8 decodes a query
    assert global_cache().stats()["refused"] >= 8

    for i, res in zip(range(2, 10), got):
        reset_global_cache()
        cold, pg, g = ask(i)
        assert pg["cache_misses"] == 8 and g["serving_cache"] == "miss"
        assert cold.groups == res.groups
        assert cold.values == res.values
    eng.close()


def test_concurrent_fetches_keep_the_byte_accounting():
    """More threads than cores over a small key space, so hits, ghost
    re-admissions, refusals, evictions and racing loads of one key
    interleave; the budget and the two byte counts must hold."""
    import sys
    import threading

    c = ServingCache(budget_bytes=20_000)
    stop = threading.Event()
    errors: list = []

    def worker(seed: int):
        rng = np.random.default_rng(seed)
        try:
            while not stop.is_set():
                k = int(rng.integers(0, 40))
                size = 500 + 100 * (k % 17)
                v, how = c.fetch(("k", k), lambda: np.zeros(size, np.int8))
                assert v.nbytes == size and how in ("hit", "miss", "refused")
                assert c.stats()["bytes"] <= 20_000
        except Exception as e:  # propagated to the main thread below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    threads = [threading.Thread(target=worker, args=(i,)) for i in range(16)]
    try:
        for t in threads:
            t.start()
        stop.wait(1.0)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors[0]
    st = c.stats()
    assert st["hits"] and st["refused"] and st["evictions"]
    with c._lock:
        assert not set(c._proven) & set(c._unproven)
        unproven = sum(s for _, s in c._unproven.values())
        proven = sum(s for _, s in c._proven.values())
        assert (c._unproven_bytes, c.bytes) == (unproven, unproven + proven)
