"""selfcheck.py's checks as pytest cases, and the generator's LIMIT held
to the program's grammar and to the parent's query text."""

import hashlib
import itertools
import json
import os
import shutil

import pytest

import dataset
import selfcheck
import traffic
from conftest import CHECKOUT, E2E
from selfcheck import load

CHECKS = [
    selfcheck.check_trace_by_hand, selfcheck.check_trace_fixture, selfcheck.check_oracle,
    selfcheck.check_traffic, selfcheck.check_readers, selfcheck.check_files,
]
PCTL = {"agg": "percentile", "field": "value", "quantiles": [0.5, 0.99], "group_by": "svc",
        "lo": 1, "hi": 2}
# sha256 of the first 200 query texts of topn-24h, seed 2147483659, client 0, joined by
# "\n", as the parent of PR 27 (d285738) wrote them: the limit must not change a text
# that names none
TOPN_200_SHA256 = "b95ee656cdb30d0065640c46cb33aaa81bbb6672a89b0d9c37e7e6b7e2c4fcb7"


@pytest.mark.parametrize("check", CHECKS, ids=lambda c: c.__name__)
def test_selfcheck(check):
    check()


def test_main_runs_every_check(capsys):
    assert selfcheck.main() == 0
    assert f"{len(CHECKS)} checks" in capsys.readouterr().out


@pytest.mark.parametrize("limit, tail", [(None, "GROUP BY svc"), (1000, "GROUP BY svc LIMIT 1000")])
def test_ql_of_limit(limit, tail):
    q = dict(PCTL, limit=limit) if limit else PCTL
    assert traffic.ql_of(q, "g", "m") == (
        "SELECT PERCENTILE(value, 0.5, 0.99) FROM MEASURE m IN g TIME BETWEEN 1 AND 2 " + tail
    )


@pytest.mark.parametrize("limit, parsed", [(1000, 1000), (None, selfcheck.DEFAULT_LIMIT)])
def test_limit_in_the_programs_grammar(limit, parsed):
    """The text the generator writes means to the server what the panel
    asked for, and without it the server's default is the one
    selfcheck.py holds cells to."""
    from banyandb_tpu.bydbql import parse_with_catalog

    q = dict(PCTL, limit=limit) if limit else PCTL
    catalog, req = parse_with_catalog(traffic.ql_of(q, "g", "m"))
    assert catalog == "measure" and req.limit == parsed


def test_topn_text_is_the_parents():
    cfg = load(E2E, "configs", "topn100k.json")
    ds = dataset.Dataset(dict(cfg, data=dict(cfg["data"], series=16)), 0)
    mix = load(E2E, "traffic", "topn-24h.json")
    texts = [
        traffic.ql_of(q, "g", "m")
        for q in itertools.islice(traffic.stream(mix, ds, 2147483659, 0), 200)
    ]
    assert len(set(texts)) == 200 and not any("LIMIT" in t for t in texts)
    assert hashlib.sha256("\n".join(texts).encode()).hexdigest() == TOPN_200_SHA256


def test_every_panel_over_100_groups_names_its_limit():
    """Pending mixes too: a by-svc panel of svc1k without TOP asks for 1,000."""
    cfg = load(E2E, "configs", "svc1k.json")
    for name in ("pctl-6h", "dash-mix"):
        assert selfcheck.truncated(cfg, load(E2E, "traffic", name + ".json")) == []


def test_warm_at_places_meet_the_one_part_shape():
    """pctl-6h's `warm_at` starts lie less than a bucket before a part
    boundary (a batch of 360 buckets is a part), so the range holds one
    whole part per shard: the shape an even spread of starts misses and
    one window query in 360 meets."""
    cfg, mix = load(E2E, "configs", "svc1k.json"), load(E2E, "traffic", "pctl-6h.json")
    ds = dataset.Dataset(dict(cfg, data=dict(cfg["data"], series=16)), 0)
    per_part = cfg["data"]["batch_rows"] // cfg["data"]["series"]
    assert cfg["data"]["snapshot_every_rows"] == cfg["data"]["batch_rows"] and per_part == 360
    firsts = []
    for at in mix["warm_at"]:
        q = traffic.spec("pctl", mix["panels"]["pctl"], ds, None, at)
        b0, b1 = ds.bucket_range(q["lo"], q["hi"])
        assert b0 % per_part == 0 and b1 - b0 == per_part, (at, b0, b1)
        firsts.append(b0 // per_part)
    assert firsts == [1, 2, 3]


def test_check_files_refuses_a_cell_the_default_would_truncate(tmp_path):
    shutil.copy(os.path.join(CHECKOUT, "BENCHMARK.json"), tmp_path)
    there = tmp_path / "benchmarks" / "e2e"
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(E2E, sub), there / sub)
    shutil.copy(os.path.join(E2E, "peaks.json"), there)
    selfcheck.check_files(str(tmp_path))  # the copy as it stands passes
    mix = load(there, "traffic", "pctl-6h.json")
    del mix["panels"]["pctl"]["limit"]
    (there / "traffic" / "pctl-6h.json").write_text(json.dumps(mix))
    with pytest.raises(AssertionError, match=r"svc1k\.pctl-6h.*1000 svc groups, LIMIT 100"):
        selfcheck.check_files(str(tmp_path))
