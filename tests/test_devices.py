"""One process per chip (utils/devices): `--workers` resolution as a pure
function of (request, platform asked for, cores), and the boot-time
backend claim that refuses an unasked-for CPU."""

import pytest

from banyandb_tpu.utils import devices


@pytest.mark.parametrize(
    "requested,platform,cores,want",
    [
        # auto: a fleet only where the CPU was asked for on purpose
        (-1, "cpu", 8, 4),
        (-1, "cpu", 4, 2),
        (-1, "cpu", 64, 4),
        (-1, "cpu", 2, 0),
        (-1, "cpu,tpu", 8, 4),
        # every chip host: one process, whatever the core count
        (-1, "", 8, 0),
        (-1, "", 224, 0),
        (-1, "tpu", 8, 0),
        (-1, "tpu,cpu", 8, 0),
        # explicit values
        (0, "", 8, 0),
        (0, "cpu", 8, 0),
        (3, "cpu", 2, 3),
    ],
)
def test_resolve_workers(requested, platform, cores, want):
    assert devices.resolve_workers(requested, platform, cores) == want


@pytest.mark.parametrize("platform", ["", "tpu", "tpu,cpu"])
def test_explicit_workers_off_cpu_refuse_and_say_why(platform):
    with pytest.raises(ValueError, match="a chip belongs to one process"):
        devices.resolve_workers(2, platform, 8)


def test_claim_backend_reports_the_device(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rt = devices.claim_backend("test")
    assert rt["backend"] == "cpu" and rt["device_count"] >= 1
    assert rt["device_kind"]


def test_claim_backend_refuses_unasked_cpu(monkeypatch):
    # JAX's answer to "chip busy" with JAX_PLATFORMS unset is the CPU
    # backend; a serving process must not take it silently
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(SystemExit, match="one process per chip"):
        devices.claim_backend("standalone server")


def test_standalone_server_refuses_worker_fleet_off_cpu(tmp_path, monkeypatch):
    from banyandb_tpu.server import StandaloneServer

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with pytest.raises(ValueError, match="--workers 2"):
        StandaloneServer(tmp_path, port=0, workers=2)
