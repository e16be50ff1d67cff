"""Fast CPU-only device-decode + zone-map smoke (scripts/check.sh, both
modes + CI).

Proves, in seconds on a REAL multi-block on-disk part, the device-side
decode contract (docs/performance.md "Device-side decode & zone maps"):

1. zone-map block skipping: a selective eq predicate over the part
   skips >= 1 block (``blocks_skipped_total{reason=zone}`` grows) with
   results identical to a full scan;
2. a query over the part ships its batch compressed (the ``decode``
   span's ``shipped_bytes``, the ``decode_ship_bytes_total`` counter),
   and the ``fused/*`` kernel-budget rows agree with the runtime (1
   dispatch/part-batch).

Exit 0 on success; any assertion prints a diagnostic and exits 1.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("BYDB_PRECOMPILE", "0")

# runnable as `python scripts/decode_smoke.py` from the repo root or CI
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

T0 = 1_700_000_000_000


def _span_named(tree: dict, name: str):
    if tree.get("name") == name:
        return tree
    for c in tree.get("children", ()):
        hit = _span_named(c, name)
        if hit is not None:
            return hit
    return None


def main() -> int:
    import numpy as np

    from banyandb_tpu.api.model import (
        Aggregation,
        Condition,
        GroupBy,
        QueryRequest,
        TimeRange,
    )
    from banyandb_tpu.api.schema import (
        Entity,
        FieldSpec,
        FieldType,
        Measure,
        TagSpec,
        TagType,
    )
    from banyandb_tpu.obs.metrics import global_meter
    from banyandb_tpu.obs.tracer import Tracer
    from banyandb_tpu.query.measure_exec import (
        compute_partials,
        finalize_partials,
    )
    from banyandb_tpu.server import result_to_json
    from banyandb_tpu.storage.part import Part, PartWriter

    n = 20_000  # 3 storage blocks (8192-row cap)
    rng = np.random.default_rng(23)
    m = Measure(
        group="g",
        name="m",
        tags=(TagSpec("svc", TagType.STRING),),
        fields=(FieldSpec("v", FieldType.INT),),
        entity=Entity(("svc",)),
    )
    # 'rare' appears ONLY in early rows -> only block 0's zone covers it
    codes = np.zeros(n, dtype=np.int32)
    codes[:64] = 1
    with tempfile.TemporaryDirectory() as root:
        part_dir = os.path.join(root, "part-1")
        PartWriter.write(
            part_dir,
            ts=T0 + np.arange(n, dtype=np.int64),
            series=np.zeros(n, dtype=np.int64),
            version=np.ones(n, dtype=np.int64),
            tag_codes={"svc": codes},
            tag_dicts={"svc": [b"common", b"rare"]},
            fields={"v": rng.integers(-100, 30_000, n).astype(np.float64)},
            extra_meta={"measure": "m"},
        )
        part = Part(part_dir)
        assert part.has_zone_maps(), "freshly written part must carry zones"
        assert len(part.blocks) == 3, len(part.blocks)

        req = QueryRequest(
            ("g",),
            "m",
            TimeRange(T0, T0 + n),
            group_by=GroupBy(("svc",)),
            agg=Aggregation("sum", "v"),
        )

        # 1. zone-map skipping: selective eq -> >=1 block skipped, results
        # identical to the full scan
        sel_req = QueryRequest(
            ("g",),
            "m",
            TimeRange(T0, T0 + n),
            criteria=Condition("svc", "eq", "rare"),
            agg=Aggregation("count", "v"),
        )
        lut = {v: i for i, v in enumerate(part.dict_for("svc"))}
        zone_preds = [("tag_svc", np.asarray([lut[b"rare"]], dtype=np.int64))]

        def count_result(blocks):
            src = part.read(blocks, tags=["svc"], fields=["v"])
            p = compute_partials(m, sel_req, [src])
            return json.dumps(
                result_to_json(finalize_partials(m, sel_req, [p])),
                sort_keys=True,
            )

        before = (
            global_meter()
            .snapshot()["counters"]
            .get(("blocks_skipped", (("reason", "zone"),)), 0.0)
        )
        pruned = part.select_blocks(T0, T0 + n, zone_preds=zone_preds)
        full = part.select_blocks(T0, T0 + n)
        after = (
            global_meter()
            .snapshot()["counters"]
            .get(("blocks_skipped", (("reason", "zone"),)), 0.0)
        )
        assert len(pruned) < len(full), (len(pruned), len(full))
        assert after > before, "blocks_skipped_total did not grow"
        assert count_result(pruned) == count_result(full), "zone skip changed results"
        print(
            f"# zone maps: {len(full) - len(pruned)} of {len(full)} blocks "
            f"skipped, results identical (blocks_skipped_total {after:.0f})"
        )

        # 2. the compressed batch, and budget agreement: the fused
        # programs are ratcheted at one dispatch per part-batch, and the
        # runtime saw exactly that
        from banyandb_tpu.lint.kernel.kernel_budgets import BUDGETS

        src = part.read(
            part.select_blocks(T0, T0 + n),
            tags=["svc"],
            fields=["v"],
            narrow_codes=True,
        )
        shipped_before = global_meter().snapshot()["counters"].get(
            ("decode_ship_bytes", ()), 0.0
        )
        tr = Tracer("decode-smoke")
        with tr.span("q") as sp:
            compute_partials(m, req, [src], span=sp)
        tree = tr.finish()
        dspan = _span_named(tree, "decode")
        assert dspan is not None, "no decode span in the reduce tree"
        shipped = dspan["tags"]["shipped_bytes"]
        shipped_c = global_meter().snapshot()["counters"].get(
            ("decode_ship_bytes", ()), 0.0
        )
        assert 0 < shipped == shipped_c - shipped_before, (shipped, shipped_c)
        rows = {k: v for k, v in BUDGETS.items() if k.startswith("fused/")}
        assert len(rows) >= 5, sorted(rows)
        assert all(
            r.dispatches == 1 and r.gets == 1
            for r in rows.values()
            if r.dispatches is not None
        ), rows
        rspan = _span_named(tree, "reduce")
        assert rspan is not None and rspan["tags"]["dispatches"] == 1, rspan
        print(
            f"# decode span: shipped {shipped} bytes; budgets: {len(rows)} "
            "fused rows, runtime dispatches=1"
        )

    print("decode_smoke: OK")
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except AssertionError as e:
        print(f"decode_smoke: FAIL: {e}", file=sys.stderr)
        sys.exit(1)
