"""Which device a serving process runs on: one process per chip, and
never the CPU backend by accident.

A TPU chip belongs to one process at a time.  With ``JAX_PLATFORMS``
unset, JAX answers "chip held by another process" (or "no chip") with a
log line and the CPU backend, so a second JAX process on a chip host
would serve queries from the CPU without anyone noticing.  Every process
that executes queries therefore claims its backend at boot through
``claim_backend`` and refuses to start on an unasked-for CPU.

No jax import at module level: ``resolve_workers`` runs before backend
choice and is a pure function.
"""

from __future__ import annotations

import os


def platform_asked() -> str:
    """The platform list the operator asked JAX for ('' = JAX's choice)."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower()


def cpu_asked(platform: str) -> bool:
    """True when the CPU was asked for on purpose (``JAX_PLATFORMS=cpu``,
    or cpu first in the list)."""
    return platform.split(",")[0].strip() == "cpu"


def resolve_workers(requested: int, platform: str, cores: int) -> int:
    """Shard-worker process count from (``--workers``, the platform
    asked for, host cores).

    Each worker is a full JAX process and nothing assigns a worker a
    chip of its own (ROADMAP D5), so a worker fleet exists only when the
    CPU was asked for on purpose: there ``-1`` keeps the cores rule.
    Anywhere else ``-1`` is the single-process layout and an explicit
    N > 0 raises — the parent and N workers would contend for one chip
    and all but the first would fall back to the CPU."""
    if requested == 0:
        return 0
    if not cpu_asked(platform):
        if requested > 0:
            raise ValueError(
                f"--workers {requested} needs {requested} more JAX "
                f"processes, but JAX_PLATFORMS={platform or '<unset>'} is "
                "not the CPU and a chip belongs to one process: no worker "
                "could get a chip of its own (per-chip workers are ROADMAP "
                "D5).  Use --workers 0, or set JAX_PLATFORMS=cpu to run "
                "the worker fleet on the CPU on purpose."
            )
        return 0
    if requested > 0:
        return requested
    return min(4, cores // 2) if cores >= 4 else 0


def claim_backend(role: str) -> dict:
    """Initialise this process's JAX backend NOW and return what it
    serves from ({backend, device_kind, device_count}).

    Exits with the reason when the backend cannot initialise, or when
    JAX fell back to the CPU although the CPU was not asked for — the
    chip is held by another process (a second ``--role data`` on the
    same chip, a worker under a parent that touched JAX) or absent."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"{role}: no device for this process: {e}") from e
    dev = devs[0]
    if dev.platform == "cpu" and not cpu_asked(platform_asked()):
        raise SystemExit(
            f"{role}: JAX came up on the CPU backend although "
            f"JAX_PLATFORMS={platform_asked() or '<unset>'} did not ask for "
            "it — no accelerator is present, or another process holds the "
            "chip (one process per chip).  Refusing to serve from the CPU "
            "silently; set JAX_PLATFORMS=cpu to do so on purpose."
        )
    # from here on every open span is an event on the profiler's clock
    # (obs/tracer keeps no jax import of its own)
    from banyandb_tpu.obs import tracer

    tracer.set_annotation_hook(jax.profiler.TraceAnnotation)
    return {
        "backend": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(devs),
    }
