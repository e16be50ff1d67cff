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

Compile events: a program that is traced again costs trace + lower +
backend compile (or the load from this cache) whether or not the cache
hits, so jax's time-span events for those three steps are counted too:
``traces`` (programs lowered: one per top-level jit that had to be
traced), ``compile_seconds`` (their summed time), one INFO line per
program (``compile program=<name> ms=<n> cache=hit|miss``), and
``watch()`` hands the calling thread's share to the query that paid.
"""

from __future__ import annotations

import logging
import os
import re
import threading
from pathlib import Path

FIXED_DIR = Path(__file__).resolve().parents[2] / ".compile-cache"

log = logging.getLogger("banyandb.compile")

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"

_lock = threading.Lock()
_state = {
    "enabled": False,
    "dir": None,
    "hits": 0,
    "misses": 0,
    "traces": 0,
    "compile_seconds": 0.0,
    "listener": False,
    "error": None,
}
# per thread: the program being compiled on it (jax compiles on the
# thread that dispatched) and the watch() open on it, if any
_tl = threading.local()


class _Pending:
    """One program's compile as its events arrive on a thread."""

    __slots__ = ("traces", "seconds", "program", "hit")

    def __init__(self):
        self.traces: list = []  # (start, end) of trace events booked
        self.seconds = 0.0
        self.program = ""
        self.hit = False


class watch:
    """Context manager: what the calling thread compiled while it was
    open (``compiled`` programs, ``seconds``, the last ``program``).
    Set around a dispatch so the query that paid for a trace says so."""

    __slots__ = ("compiled", "seconds", "program")

    def __init__(self):
        self.compiled = 0
        self.seconds = 0.0
        self.program = ""

    def __enter__(self) -> "watch":
        _tl.watch = self
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        _tl.watch = None


def _pending() -> _Pending:
    pend = getattr(_tl, "pending", None)
    if pend is None:
        pend = _tl.pending = _Pending()
    return pend


def _book(pend: _Pending, seconds: float) -> None:
    pend.seconds += seconds
    # float += under the GIL; counters are best-effort telemetry
    _state["compile_seconds"] += seconds


def _on_time_span(event: str, start: float, end: float, **kw) -> None:
    """jax's trace / lower / backend-compile events of one program, in
    that order, on the thread that dispatched it."""
    if event == _TRACE_EVENT:
        pend = _pending()
        # the outer jit's trace contains its nested jits' traces, which
        # ended (and were booked) before it: count their time once
        inner = [(s, e) for s, e in pend.traces if s >= start and e <= end]
        for span in inner:
            pend.traces.remove(span)
        pend.traces.append((start, end))
        _book(pend, (end - start) - sum(e - s for s, e in inner))
    elif event == _LOWER_EVENT:
        pend = _pending()
        pend.traces.clear()
        pend.program = str(kw.get("fun_name", ""))
        _state["traces"] += 1
        _book(pend, end - start)
    elif event == _BACKEND_EVENT:
        pend = _pending()
        _book(pend, end - start)
        # `jit(fn)` -> `jit_fn`, the module name the device trace prints
        program = re.sub(
            r"\W+", "_", pend.program or str(kw.get("fun_name", "")) or "?"
        ).strip("_")
        log.info(
            "compile program=%s ms=%.1f cache=%s",
            program, pend.seconds * 1000.0, "hit" if pend.hit else "miss",
        )
        w = getattr(_tl, "watch", None)
        if w is not None:
            w.compiled += 1
            w.seconds += pend.seconds
            w.program = program
        _tl.pending = None


def resolve_dir() -> tuple[str, bool]:
    """-> (cache directory, whether JAX_COMPILATION_CACHE_DIR named it)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if env:
        return env, True
    return str(FIXED_DIR), False


def _install_listener() -> None:
    """Count persistent-cache hits/misses and compile events via jax
    monitoring events.

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
                _pending().hit = True
            elif event.endswith("/cache_misses"):
                _state["misses"] += 1

        monitoring.register_event_listener(_on_event)
        monitoring.register_event_time_span_listener(_on_time_span)
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
        "traces": _state["traces"],
        "compile_seconds": _state["compile_seconds"],
        "entries": entries,
        "error": _state["error"],
    }
