"""Fast CPU-only cold-path smoke (scripts/check.sh --fast + CI).

Proves, on a tiny store in seconds, the three cold-path invariants PR 3
introduced (docs/performance.md):

1. pipelined (BYDB_PIPELINE=1) and strict-serial (=0) execution produce
   byte-identical partials AND identical JSON results on a multi-part
   store with memtable rows;
2. the plan precompile registry records live signatures, persists them
   to the root's plan-registry.json, and warms them back into the
   process kernel cache;
3. the persistent XLA compile cache wiring is active and holds entries.

Exit 0 on success; any assertion prints a diagnostic and exits 1.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["BYDB_PRECOMPILE"] = "1"

# runnable as `python scripts/cold_smoke.py` from the repo root or CI
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def main() -> int:
    from pathlib import Path

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
    from banyandb_tpu.query import measure_exec
    from banyandb_tpu.query.precompile import default_registry
    from banyandb_tpu.server import result_to_json
    from banyandb_tpu.utils import compile_cache

    root = Path(tempfile.mkdtemp(prefix="bydb-cold-smoke-"))
    try:
        assert compile_cache.enable(), "cache wiring"
        reg = SchemaRegistry(root)
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
        eng = MeasureEngine(reg, root / "data")
        rng = np.random.default_rng(3)
        T0 = 1_700_000_000_000
        for b in range(3):  # 2 flushed parts per shard + memtable rows
            n = 20_000
            eng.write_columns(
                "g",
                "m",
                ts_millis=T0 + b * n + np.arange(n, dtype=np.int64),
                tags={
                    "svc": DictColumn(
                        [b"s%02d" % i for i in range(20)],
                        rng.integers(0, 20, n).astype(np.int32),
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

        m = reg.get_measure("g", "m")
        queries = [
            bydbql.parse(
                f"SELECT sum(value) FROM MEASURE m IN g TIME BETWEEN {T0} "
                f"AND {T0 + 100000} WHERE region != 'r3' GROUP BY svc "
                f"TOP 5 BY value"
            ),
            bydbql.parse(
                f"SELECT percentile(value, 0.5, 0.99) FROM MEASURE m IN g "
                f"TIME BETWEEN {T0} AND {T0 + 100000} GROUP BY region"
            ),
        ]

        # 1. pipelined vs strict-serial: byte-identical partials + results
        for req in queries:
            sources = eng.gather_query_sources(req)
            os.environ["BYDB_PIPELINE"] = "1"
            p1 = measure_exec.compute_partials(m, req, sources, dict_state=None)
            r1 = result_to_json(
                measure_exec.finalize_partials(m, req, [p1])
            )
            os.environ["BYDB_PIPELINE"] = "0"
            p0 = measure_exec.compute_partials(m, req, sources, dict_state=None)
            r0 = result_to_json(
                measure_exec.finalize_partials(m, req, [p0])
            )
            os.environ["BYDB_PIPELINE"] = "1"
            assert p1.count.tobytes() == p0.count.tobytes(), "count drifted"
            for f in p1.sums:
                assert p1.sums[f].tobytes() == p0.sums[f].tobytes(), (
                    f"sums[{f}] drifted"
                )
            assert (p1.hist is None) == (p0.hist is None), "hist presence drifted"
            if p1.hist is not None:
                assert p1.hist.tobytes() == p0.hist.tobytes(), "hist drifted"
            assert json.dumps(r1) == json.dumps(r0), "result drifted"

        # 2. precompile registry recorded the live plans; store + warm work
        r = default_registry()
        r.attach_store(root / "plan-registry.json")
        assert r.stats()["recorded"] >= 2, f"registry empty: {r.stats()}"
        # attaching a store with unsaved signatures persists immediately
        # (record()-driven saves are debounced off the hot path)
        assert (root / "plan-registry.json").exists(), "store not persisted"
        warmed = r.warm(include_builtin=False)
        assert warmed >= 2, f"warm compiled only {warmed}"
        assert r.stats()["errors"] == 0, f"warm errors: {r.stats()}"

        # 3. the persistent compile cache holds the kernels just built
        cc = compile_cache.stats()
        assert cc["enabled"] and cc["entries"] > 0, f"compile cache: {cc}"

        print(
            "cold-path smoke: OK "
            + json.dumps(
                {
                    "recorded": r.stats()["recorded"],
                    "warmed": warmed,
                    "compile_cache_entries": cc["entries"],
                }
            )
        )
        return 0
    finally:
        shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    try:
        sys.exit(main())
    except AssertionError as e:
        print(f"cold-path smoke: FAILED — {e}", file=sys.stderr)
        sys.exit(1)
