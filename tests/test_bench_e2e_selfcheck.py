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
    assert {"test_selfcheck.py", "test_control.py", "test_control_ep9k.py"} <= set(ADOPTED)
    assert not SLOW & set(ADOPTED)
    assert any(k.startswith("test_selfcheck__test_selfcheck") for k in globals())


# -- metric files a program PR added as data alone ------------------------------------

def _reduce_tree(tags: dict) -> dict:
    return {"name": "measure-query", "children": [{"name": "execute", "children": [
        {"name": "gather", "tags": {"rows": 3240000}},
        {"name": "reduce", "tags": tags, "children": [{"name": "decode", "tags": {}}]},
    ]}]}


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
    with open(os.path.join(CHECKOUT, "benchmarks", "e2e", "metrics", "skipped_chunks_per_query.json")) as f:
        metric = json.load(f)
    assert metric["reader"] == {"kind": "span_tag", "span": "reduce", "tag": "chunks_skipped"}
    rec = {"queries": [{"served": "scan", "tree": _reduce_tree(tags)} for _ in range(3)]}
    assert readers.read(metric, rec) == want
