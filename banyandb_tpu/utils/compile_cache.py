"""Persistent XLA compilation cache wiring (+ hit/miss counters).

The cold path pays one XLA compile per plan signature per PROCESS; on a
restart every dashboard query recompiles kernels whose HLO has not
changed.  A cache directory that outlives the process makes plan kernels
compile once per machine.

The directory resolves in two steps and nowhere else:

1. ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; this module
   leaves the directory alone (the operator, or the harness that runs
   several processes against one chip, owns the placement).
2. unset: ``FIXED_DIR``, one git-ignored path inside the checkout.  The
   path is part of what XLA keys entries on, so it must not move between
   runs or between a parent and its children — a per-run or per-root
   directory never hits.

Wiring is process-global and idempotent; every entry point that can
dispatch a kernel calls ``enable()``.  ``stats()`` feeds the /metrics
surface.  Hit/miss counts come from jax's own monitoring events
(``/jax/compilation_cache/cache_hits`` and ``.../cache_misses``) so they
reflect what XLA actually did, not what we hoped.
"""

from __future__ import annotations

import os
import threading
from pathlib import Path

FIXED_DIR = Path(__file__).resolve().parents[2] / ".compile-cache"

_lock = threading.Lock()
_state = {
    "enabled": False,
    "dir": None,
    "hits": 0,
    "misses": 0,
    "listener": False,
    "error": None,
}


def resolve_dir() -> tuple[str, bool]:
    """-> (cache directory, whether JAX_COMPILATION_CACHE_DIR named it)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if env:
        return env, True
    return str(FIXED_DIR), False


def _install_listener() -> None:
    """Count persistent-cache hits/misses via jax monitoring events.

    Private-API dependent (jax._src.monitoring); counters degrade to 0
    rather than break wiring if the surface moves."""
    if _state["listener"]:
        return
    try:
        from jax._src import monitoring

        def _on_event(event: str, **kw) -> None:
            # int += under the GIL; counters are best-effort telemetry
            if event.endswith("/cache_hits"):
                _state["hits"] += 1
            elif event.endswith("/cache_misses"):
                _state["misses"] += 1

        monitoring.register_event_listener(_on_event)
        _state["listener"] = True
    except Exception as e:  # noqa: BLE001 — counters are optional
        _state["error"] = f"listener: {type(e).__name__}: {e}"


def enable() -> str | None:
    """Wire the persistent cache; -> the active directory, or None when
    the directory cannot be created (the cache is an optimization)."""
    with _lock:
        if _state["enabled"]:
            return _state["dir"]
        import jax

        target, from_env = resolve_dir()
        try:
            os.makedirs(target, exist_ok=True)
        except OSError as e:
            _state["error"] = f"{type(e).__name__}: {e}"
            return None
        if not from_env:
            jax.config.update("jax_compilation_cache_dir", target)
        # default thresholds skip sub-second compiles — exactly the
        # population a dashboard's plan kernels live in
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        _install_listener()
        _state["enabled"] = True
        _state["dir"] = target
        return target


def stats() -> dict:
    """Telemetry for /metrics and the diagnostics topic."""
    entries = 0
    d = _state["dir"]
    if _state["enabled"] and d and os.path.isdir(d):
        try:
            entries = sum(1 for _ in os.scandir(d))
        except OSError:
            entries = 0
    return {
        "enabled": _state["enabled"],
        "dir": d,
        "hits": _state["hits"],
        "misses": _state["misses"],
        "entries": entries,
        "error": _state["error"],
    }
