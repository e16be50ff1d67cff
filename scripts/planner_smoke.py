"""Adaptive-planner smoke (~3s): the self-driving loop end-to-end on a
real standalone server (docs/performance.md "Adaptive planner").

Asserts:

  1. a hot dashboard pattern (repeated streamagg-eligible QL queries,
     NO manual registration) is auto-registered by the bydb-autoreg
     loop and subsequent queries serve class `materialized`;
  2. `cli.py explain` output is sane: plan tree, chosen path, estimated
     vs actual rows (the golden-pinned renderer);
  3. `BYDB_PLANNER` A/B: result JSON byte-identical with the planner
     on/off across the mixed-selectivity probe set;
  4. the planner span + `planner_decisions_total{path}` /
     `autoreg_signatures{source}` instruments move.

Wired into scripts/check.sh (both modes) and
.github/workflows/check.yml.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("BYDB_PRECOMPILE", "0")
# the loop is driven EXPLICITLY below (deterministic smoke): keep the
# background thread off, tick by hand
os.environ["BYDB_AUTOREG"] = "0"
os.environ.setdefault("BYDB_PLANNER", "1")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

T0 = 1_700_000_000_000
GROUP, MEASURE = "pg", "m"


def main() -> int:
    import base64

    from banyandb_tpu.cli import render_explain
    from banyandb_tpu.cluster.bus import Topic
    from banyandb_tpu.cluster.rpc import GrpcTransport
    from banyandb_tpu.server import (
        TOPIC_METRICS,
        TOPIC_QL,
        TOPIC_REGISTRY,
        StandaloneServer,
    )

    t_start = time.perf_counter()
    root = tempfile.mkdtemp(prefix="bydb-planner-smoke-")
    srv = StandaloneServer(root, port=0, workers=0)
    srv.start()
    tr = GrpcTransport()

    def call(topic, env, timeout=60.0):
        return tr.call(srv.addr, topic, env, timeout=timeout)

    try:
        call(TOPIC_REGISTRY, {"op": "create", "kind": "group", "item": {
            "name": GROUP, "catalog": "measure",
            "resource_opts": {
                "shard_num": 2, "replicas": 0,
                "segment_interval": {"num": 1, "unit": "day"},
                "ttl": {"num": 7, "unit": "day"}, "stages": [],
            },
        }})
        call(TOPIC_REGISTRY, {"op": "create", "kind": "measure", "item": {
            "group": GROUP, "name": MEASURE,
            "tags": [{"name": "svc", "type": "string"},
                     {"name": "region", "type": "string"}],
            "fields": [{"name": "v", "type": "int"}],
            "entity": {"tag_names": ["svc"]}, "interval": "",
            "index_mode": False,
        }})
        rng = np.random.default_rng(5)
        n = 6000
        ts = T0 + np.arange(n, dtype=np.int64) * 60  # ~6 min: several 60s windows
        call(Topic.MEASURE_WRITE_COLUMNS.value, {
            "group": GROUP, "name": MEASURE,
            "ts": base64.b64encode(ts.astype("<i8").tobytes()).decode(),
            "versions": base64.b64encode(
                np.ones(n, dtype="<i8").tobytes()
            ).decode(),
            "tags": {
                "svc": {
                    "dict": [f"s{i}" for i in range(8)],
                    "codes": base64.b64encode(
                        rng.integers(0, 8, n, dtype=np.int32)
                        .astype("<i4").tobytes()
                    ).decode(),
                },
                "region": {
                    "dict": ["east", "west"],
                    "codes": base64.b64encode(
                        rng.integers(0, 2, n, dtype=np.int32)
                        .astype("<i4").tobytes()
                    ).decode(),
                },
            },
            "fields": {
                "v": base64.b64encode(
                    rng.integers(0, 100, n).astype("<f8").tobytes()
                ).decode(),
            },
        })
        call(Topic.HEALTH.value, {})  # settle
        lo, hi = T0, T0 + n * 60

        dash = (
            f"SELECT sum(v) FROM MEASURE {MEASURE} IN {GROUP} TIME "
            f"BETWEEN {lo} AND {hi} WHERE region = 'east' GROUP BY svc"
        )
        probes = [
            dash,
            f"SELECT count(v) FROM MEASURE {MEASURE} IN {GROUP} TIME "
            f"BETWEEN {lo} AND {hi} GROUP BY region",
            f"SELECT mean(v) FROM MEASURE {MEASURE} IN {GROUP} TIME "
            f"BETWEEN {lo} AND {hi} WHERE svc IN ('s1','s2') "
            f"GROUP BY svc",
        ]

        # -- 1: hot pattern -> auto-registration -> materialized ------
        for _ in range(4):
            call(TOPIC_QL, {"ql": dash})
        made = 0
        for _ in range(5):
            made += srv.autoreg.tick()
            if made:
                break
        assert made >= 1, "autoreg never registered the hot signature"
        rows = srv._streamagg_signature_rows()
        assert rows and rows[0]["origin"] == "auto", rows
        served = call(TOPIC_QL, {"ql": dash}).get("served")
        assert served == "materialized", f"served={served!r}"
        print(f"# auto-registered: {rows[0]['signature']} -> materialized")

        # -- 2: explain output sane ----------------------------------
        reply = call(TOPIC_QL, {"ql": dash, "trace": True})
        text = render_explain(reply)
        assert "plan:" in text and "path: materialized" in text, text
        scan_ql = probes[1]
        os.environ["BYDB_STREAMAGG"] = "0"  # force the scan path
        reply = call(TOPIC_QL, {"ql": scan_ql, "trace": True})
        os.environ["BYDB_STREAMAGG"] = "1"
        text = render_explain(reply)
        assert "estimated rows:" in text and "actual rows:" in text, text
        assert "path: fused" in text, text
        print("# explain renders plan + est-vs-actual rows")

        # -- 3: BYDB_PLANNER A/B byte parity --------------------------
        for ql in probes:
            os.environ["BYDB_PLANNER"] = "1"
            on = json.dumps(
                call(TOPIC_QL, {"ql": ql})["result"], sort_keys=True
            )
            os.environ["BYDB_PLANNER"] = "0"
            off = json.dumps(
                call(TOPIC_QL, {"ql": ql})["result"], sort_keys=True
            )
            os.environ["BYDB_PLANNER"] = "1"
            assert on == off, f"planner parity broke on: {ql}"
        print("# BYDB_PLANNER=0/1 result JSON byte-identical")

        # -- 4: instruments -------------------------------------------
        text = call(TOPIC_METRICS, {})["prometheus"]
        assert 'banyandb_planner_decisions_total{path="materialized"}' in text
        assert 'banyandb_autoreg_signatures{source="auto"}' in text, text
        assert "banyandb_autoreg_registered_total" in text
        print("# planner_decisions_total / autoreg_signatures exported")
    finally:
        tr.close()
        srv.stop()
        import shutil

        shutil.rmtree(root, ignore_errors=True)
    print(f"planner smoke OK in {time.perf_counter() - t_start:.1f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
