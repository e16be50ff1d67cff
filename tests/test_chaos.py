"""Chaos harness gates (scripts/chaos.py, docs/robustness.md).

Tier-1 runs the in-process smoke: three data-node kill/restart cycles
under the liaison write queue, a degradation scenario with explicit
markers, and a seeded fault schedule — all with zero acked-write loss
and every query inside its deadline budget.  The ``-m slow`` tier runs
the real-subprocess soak (SIGKILL cycles under sustained load).
"""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "scripts"))

import chaos  # noqa: E402


def test_chaos_smoke(tmp_path):
    stats = chaos.run_smoke(tmp_path / "chaos", seed=7)
    assert stats["kill_cycles"] >= 3
    assert stats["degraded_seen"] >= 1
    assert stats["faults_injected"] > 0
    assert stats["acked_a"] > 0 and stats["acked_c"] > 0
    # deadline invariant: asserted per-query inside the harness too
    assert stats["max_query_wall_s"] <= 4.0
    # multi-process data plane: worker SIGKILL/restart cycles under
    # ingest (site=worker kill schedule) — zero acked-write loss is
    # asserted inside the phase; the windows stay bounded
    assert stats["worker_kill_cycles"] >= 2
    assert stats["worker_restarts"] >= 2
    assert stats["worker_acked"] > 0
    assert max(stats["worker_degraded_windows_s"]) < 45


def test_chaos_smoke_seed_changes_schedule(tmp_path):
    """Different seeds draw different probabilistic fault sequences —
    the smoke is not accidentally seed-blind."""
    from banyandb_tpu.cluster.faults import FaultPlane

    spec = "seed={};rpc=error:p=0.3"
    a, b = FaultPlane(spec.format(3)), FaultPlane(spec.format(4))
    fired_a = [i for i in range(64) if a.decide("rpc")]
    fired_b = [i for i in range(64) if b.decide("rpc")]
    assert fired_a != fired_b


def test_streamagg_failover_windows_gap_free(tmp_path, monkeypatch):
    """ROADMAP item 4 failover bar: kill a data node mid-load, let the
    liaison wqueue replay drain, and assert the materialized streaming-
    aggregation windows are gap-free and not double-counted vs a full-
    rescan oracle (`BYDB_STREAMAGG=0` byte parity + exact acked total).

    The restart path exercises the deterministic rebuild: the new
    DataNode reloads its persisted streamagg registry and backfills
    from the parts that survived on disk; the wqueue then re-ships the
    outage window and the install-digest dedup keeps re-delivered parts
    (and therefore window updates) single."""
    import json as _json
    import time as _time

    from banyandb_tpu.api import SchemaRegistry, WriteRequest
    from banyandb_tpu.cluster import DataNode, Liaison, NodeInfo
    from banyandb_tpu.cluster.rpc import GrpcTransport
    from banyandb_tpu.server import result_to_json

    reg = SchemaRegistry(tmp_path / "n0" / "schema")
    chaos._schema(reg, shard_num=2)
    dn = DataNode("n0", reg, tmp_path / "n0" / "data")
    # 1s windows so a few hundred points cross a rotation
    dn.measure.streamagg.register(
        "cg", "m", key_tags=("svc",), fields=("v",), window_millis=1000
    )
    srv = chaos._bind_server(dn.bus, 0, sync_install=dn.install_synced_parts)
    port = srv.port
    lreg = SchemaRegistry(tmp_path / "l" / "schema")
    chaos._schema(lreg, shard_num=2)
    transport = GrpcTransport()
    liaison = Liaison(
        lreg, transport, [NodeInfo("n0", srv.addr)], query_budget_s=5.0
    )
    liaison.probe()
    wq = liaison.enable_write_queue(
        tmp_path / "l" / "wqueue", flush_interval_s=30.0, retry_base_s=0.01
    )
    acked = 0

    def write(n):
        nonlocal acked
        acked += liaison.write_measure_queued(
            WriteRequest("cg", "m", chaos._points(acked, n))
        )

    def drain(deadline_s=20.0):
        end = _time.monotonic() + deadline_s
        while _time.monotonic() < end:
            liaison.probe()
            try:
                wq.flush(force=True)
            except Exception:  # noqa: BLE001 - victim still down
                pass
            if wq.pending_parts() == 0:
                return
            _time.sleep(0.05)
        raise AssertionError("wqueue never drained")

    dn2 = None
    try:
        write(1500)  # crosses a window rotation (1ms-spaced points)
        drain()
        # kill mid-load: acked rows pile into the spool while down
        srv.stop(grace=0)
        write(1200)
        try:
            wq.flush(force=True)
        except Exception:  # noqa: BLE001 - expected: node down
            pass
        assert wq.pending_parts() > 0, "outage produced nothing to replay"
        # restart over the SAME root: the fresh engine reloads the
        # persisted streamagg registry and backfills from on-disk parts
        dn.measure.close()
        dn.stream.close()
        dn.trace.close()
        dn2 = DataNode(
            "n0", SchemaRegistry(tmp_path / "n0" / "schema"),
            tmp_path / "n0" / "data",
        )
        st = dn2.measure.streamagg.stats()
        assert len(st["signatures"]) == 1, "registry did not reload"
        assert st["rows"] > 0, "backfill applied nothing"
        srv = chaos._bind_server(
            dn2.bus, port, sync_install=dn2.install_synced_parts
        )
        liaison.probe()
        drain()  # replay: re-ships dedup by part uuid, windows stay single
        req = chaos._count_req()
        monkeypatch.setenv("BYDB_STREAMAGG", "1")
        on = result_to_json(liaison.query_measure(req))
        monkeypatch.setenv("BYDB_STREAMAGG", "0")
        off = result_to_json(liaison.query_measure(req))
        assert _json.dumps(on, sort_keys=True) == _json.dumps(
            off, sort_keys=True
        ), "materialized answer diverged from the rescan oracle"
        # gap-free AND not double-counted: the folded total is exactly
        # the acked row count
        assert sum(on["values"]["count"]) == acked
        assert dn2.measure.streamagg.stats()["rows"] == acked
    finally:
        wq.stop(final_flush=False)
        transport.close()
        srv.stop(grace=0)
        for node in (dn, dn2):
            if node is not None:
                node.measure.close()
                node.stream.close()
                node.trace.close()


@pytest.mark.slow  # real subprocess cluster: boots + kill/restart cycles
def test_chaos_soak(tmp_path):
    import os

    # default 45: the call phase is the soak plus four boots, three
    # restarts and the convergence wait, and must end inside
    # BYDB_SANITIZE_WATCHDOG_S (180 s) on a loaded runner too
    seconds = float(os.environ.get("BYDB_CHAOS_SECONDS", "45"))
    stats = chaos.run_soak(tmp_path / "soak", seconds=seconds)
    assert stats["kill_cycles"] >= 3
    assert stats["degraded_seen"] >= 1
    assert stats["acked"] > 0
    assert stats["max_query_wall_s"] <= 15.0
