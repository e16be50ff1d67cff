#!/usr/bin/env bash
# One-stop pre-PR gate: ruff (generic lint) + bdlint (project-native
# invariants, docs/linting.md) + the tier-1 test command from ROADMAP.md.
# Run from the repo root:  ./scripts/check.sh [--fast]
#   --fast  skip the tier-1 pytest run (lint-only, seconds not minutes)
set -u -o pipefail

cd "$(dirname "$0")/.."
fail=0

echo "== ruff =="
if command -v ruff >/dev/null 2>&1; then
    ruff check banyandb_tpu tests scripts || fail=1
else
    # the container this repo grows in does not ship ruff; the config
    # (ruff.toml) still pins the style for environments that do
    echo "ruff not installed; skipping (config: ruff.toml)"
fi

echo "== bdlint =="
# --fast skips the kernel lowering-audit (XLA compiles); the jaxpr,
# dispatch and budget halves of the kernel audit still run in both modes
if [ "${1:-}" = "--fast" ]; then
    python -m banyandb_tpu.lint --check --fast banyandb_tpu || fail=1
else
    python -m banyandb_tpu.lint --check banyandb_tpu || fail=1
fi

echo "== kernel smoke (bdjit) =="
# budget-table agreement with the plan-audit matrix + obs-plane export
# (docs/linting.md "Kernel audit").  --no-audit: the jaxpr/dispatch
# audit itself just ran inside bdlint --check above — no double work
env JAX_PLATFORMS=cpu python scripts/kernel_smoke.py --no-audit || fail=1

echo "== wire smoke (bdwire) =="
# role/topic matrix == golden, every wire analyzer fires on its seeded
# violation (docs/linting.md "Wire-contract audit").  --no-audit: the
# real-tree wire audit just ran inside bdlint --check above
env JAX_PLATFORMS=cpu python scripts/wire_smoke.py --no-audit || fail=1

echo "== cold-path smoke =="
# tiny store: pipelined == serial byte-identical, precompile registry
# populated + persisted, compile cache active (docs/performance.md)
env JAX_PLATFORMS=cpu python scripts/cold_smoke.py || fail=1

echo "== device-decode smoke =="
# zone-map block skipping with identical results on a real multi-block
# part, decode span + shipped-bytes counter, fused budget agreement
# (docs/performance.md "Device-side decode & zone maps")
env JAX_PLATFORMS=cpu python scripts/decode_smoke.py || fail=1

echo "== streamagg smoke =="
# materialized rolling windows: registration backfill, ingest across a
# window rotation, BYDB_STREAMAGG=0 A/B byte parity (covered, partial,
# evicted-horizon), streamagg span + counters, registry store
# round-trip (docs/performance.md "Continuous streaming aggregation")
env JAX_PLATFORMS=cpu python scripts/streamagg_smoke.py || fail=1

echo "== planner smoke =="
# self-driving materialization: hot QL pattern -> bydb-autoreg
# registers a window -> served=materialized; explain renders est-vs-
# actual; BYDB_PLANNER=0/1 byte parity; planner/autoreg instruments
# (docs/performance.md "Adaptive planner")
env JAX_PLATFORMS=cpu python scripts/planner_smoke.py || fail=1

echo "== qos smoke =="
# multi-tenant QoS: abuser tenant shed with the retryable kind=shed
# wire rejection + per-tenant counters, compliant tenant served,
# serving-cache partition isolation, single-tenant parity
# (docs/robustness.md "Multi-tenant QoS")
env JAX_PLATFORMS=cpu python scripts/qos_smoke.py || fail=1

echo "== sanitize smoke (bdsan) =="
# live-engine stress slice under BYDB_SANITIZE=1: lock-order witnesses
# consistent with the declared graph, zero leaked threads/fds, seeded
# leak caught (docs/sanitizers.md)
env JAX_PLATFORMS=cpu BYDB_SANITIZE=1 python scripts/sanitize_smoke.py || fail=1

echo "== obs smoke =="
# 2-node traced distributed query: ONE merged span tree with per-node
# subtrees + device/host attribution, trace on/off result parity,
# bucketed stage histograms on /metrics (docs/observability.md)
env JAX_PLATFORMS=cpu python scripts/obs_smoke.py || fail=1

echo "== trace smoke =="
# trace query surface + dogfood loop: bloom/zone block pruning with
# BYDB_ZONE_SKIP=0 byte parity, distributed trace=true query parity +
# merged scatter/merge span tree, BYDB_SELF_TRACE round-trip — the
# in-band span tree read back from _monitoring.self_query via bydbql
# (docs/observability.md "Self-trace")
env JAX_PLATFORMS=cpu python scripts/trace_smoke.py || fail=1

echo "== workers smoke =="
# multi-process data plane: BYDB_WORKERS=2 vs 0 scatter BYTE parity,
# per-worker span graft + labeled /metrics, worker SIGKILL -> restart +
# journal replay with zero acked loss
# (docs/performance.md "Multi-process data plane")
env JAX_PLATFORMS=cpu python scripts/workers_smoke.py || fail=1

echo "== rebalance smoke =="
# elastic cluster: live 3->4 node expansion under sustained ingest —
# zero acked-write loss, pre/post-cutover result byte parity, epoch
# bump observed on every node, stale-epoch write rejected (counter),
# one replica-repair round to convergence
# (docs/robustness.md "Elastic cluster")
env JAX_PLATFORMS=cpu python scripts/rebalance_smoke.py || fail=1

echo "== chaos smoke =="
# 3 in-process data-node kill/restart cycles under the liaison write
# queue + a degradation scenario + a seeded fault schedule + a
# rebalance whose part source is killed mid-move (join/kill schedule,
# holder failover, zero loss): explicit degraded markers, queries
# inside their deadline budget (docs/robustness.md)
env JAX_PLATFORMS=cpu python scripts/chaos.py --smoke || fail=1

if [ "${1:-}" != "--fast" ]; then
    echo "== tier-1 tests (ROADMAP.md, BYDB_SANITIZE=1 via conftest) =="
    rm -f /tmp/_t1.log
    timeout -k 10 870 env JAX_PLATFORMS=cpu BYDB_SANITIZE=1 python -m pytest tests/ -q \
        -m 'not slow' --continue-on-collection-errors \
        -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 \
        | tee /tmp/_t1.log
    rc=${PIPESTATUS[0]}
    echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)"
    [ "$rc" -ne 0 ] && fail=1
fi

if [ "$fail" -ne 0 ]; then
    echo "check.sh: FAILED"
else
    echo "check.sh: all gates green"
fi
exit "$fail"
