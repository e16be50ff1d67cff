"""Test env: force CPU platform with 8 virtual devices so sharding/mesh
tests run without TPU hardware (matches the driver's dryrun harness).

The whole run executes under the bdsan runtime sanitizers
(BYDB_SANITIZE=1, docs/sanitizers.md): package locks are traced for
lock-order witnesses, faulthandler arms a per-test dump-on-timeout
watchdog, and every test must end with the thread set it started with
(allowlisted process-wide daemons excepted) — the gleak analog."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# keep the kernel-cache population deterministic: no background plan
# warming in the general suite (tests/test_cold_path.py re-enables it
# explicitly to exercise the precompile registry)
os.environ.setdefault("BYDB_PRECOMPILE", "0")
# no background auto-registration in the general suite: a bydb-autoreg
# loop registering streamagg signatures mid-test would make window
# population timing-dependent (tests/test_planner.py builds explicit
# AutoRegistrar instances and drives ticks deterministically)
os.environ.setdefault("BYDB_AUTOREG", "0")
# no shard-worker subprocesses in the general suite (the workers-on /
# workers-off A/B contract is pinned explicitly by tests/test_workers.py,
# which passes workers=N to the server; everything else runs the
# single-process layout it was written against)
os.environ.setdefault("BYDB_WORKERS", "0")
# no persistent XLA compile cache in the general suite: an in-process
# server wires it (utils/compile_cache), and tests must neither write
# into the checkout nor run executables a previous run compiled
# (tests/test_cold_path.py re-enables it in its own subprocesses)
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
# race/leak sanitizers on for the whole suite (BYDB_SANITIZE=0 opts out)
os.environ.setdefault("BYDB_SANITIZE", "1")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()


import pytest  # noqa: E402

from banyandb_tpu import sanitize  # noqa: E402

if sanitize.enabled():
    # before any test module imports the package's threaded classes, so
    # every lock they construct is traced with its declaration identity
    sanitize.install()

# One test may legitimately outlive this only by hanging: the watchdog
# dumps every thread's stack (non-fatal) so a wedged run leaves evidence
# instead of a silent timeout kill.
_TEST_WATCHDOG_S = float(os.environ.get("BYDB_SANITIZE_WATCHDOG_S", "180"))


def pytest_configure(config):
    """Build the native codec once per session (make is incremental, ~2s
    cold) so the C paths are TESTED, never skipped: test_native.py's
    skipif evaluates after this.  A failed build degrades to the old
    skip behavior rather than failing collection."""
    config.addinivalue_line(
        "markers",
        "slow: long-running E2E; tier-1 runs -m 'not slow' (ROADMAP.md), "
        "fast smoke variants keep the coverage",
    )
    from banyandb_tpu.utils import native

    try:
        native.build()
    except Exception as exc:  # noqa: BLE001 — toolchain-less envs skip
        print(f"# native build unavailable ({exc}); native tests will skip")


@pytest.fixture(autouse=True)
def _bdsan_guard(request):
    """Per-test sanitizer envelope: arm the faulthandler watchdog and
    enforce thread-count parity (ROADMAP item 8).  Baseline is captured
    at test start, so a long-lived fixture's threads (set up earlier at
    higher scope) never count; anything the test itself started and
    failed to stop fails the test after a grace window."""
    if not sanitize.enabled():
        yield
        return
    from banyandb_tpu.sanitize import leaks

    sanitize.arm_watchdog(_TEST_WATCHDOG_S)
    before = leaks.thread_snapshot()
    before_procs = leaks.process_snapshot()
    yield
    sanitize.disarm_watchdog()
    leaked = leaks.leaked_threads(before, grace_s=5.0)
    if leaked:
        names = ", ".join(f"{t.name} (ident={t.ident})" for t in leaked)
        pytest.fail(
            f"thread parity: test leaked {len(leaked)} thread(s): {names}; "
            "stop()/close()/join() the owner in teardown (allowlist: "
            "sanitize.leaks.DEFAULT_THREAD_ALLOWLIST)"
        )
    leaked_procs = leaks.leaked_processes(before_procs, grace_s=5.0)
    if leaked_procs:
        names = ", ".join(f"{label} (pid={pid})" for pid, label in leaked_procs)
        pytest.fail(
            f"process parity: test leaked {len(leaked_procs)} worker "
            f"process(es): {names}; stop() the owning pool/server in "
            "teardown (every spawn registers in utils.procreg)"
        )


@pytest.fixture()
def mesh8():
    """4x2 (shard x seg) mesh over the 8 forced host devices."""
    from banyandb_tpu.parallel import make_mesh

    return make_mesh(4, 2)
