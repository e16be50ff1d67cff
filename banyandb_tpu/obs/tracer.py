"""Hierarchical query tracer (pkg/query/tracer.go:50 analog).

A ``Tracer`` owns one root ``Span``; nested ``tracer.span(...)`` context
managers build the tree.  Spans carry a name, wall duration, a flat tag
map (device_ms/host_ms attribution, cache hit/miss, row counts...) and
child spans.  ``Span.attach`` grafts an already-serialized subtree —
the cluster merge: each data node runs its own tracer and returns
``tracer.finish()`` in the RPC reply, the liaison attaches the subtree
under that node's scatter span, and the response carries ONE tree.

Serialized form (JSON-safe, the ``res.trace["span_tree"]`` payload and
the wire common/v1 Span mapping):

    {"name": str, "start_ms": float, "duration_ms": float,
     "tags": {str: scalar}, "children": [<span>...], "error": str?}

``start_ms`` is the span's offset from its tree's root start (the same
``perf_counter`` as ``duration_ms``); the root also carries
``start_unix_ms`` and ``trace_id`` (one id per request; ``attach`` puts
it on a remote subtree's scatter span, whose own ``start_ms`` restarts
at its node's root).

The profiler's clock: while a span is open it holds one annotation
``bydb:<name>`` made by ``_annotate`` — a module-level hook that
``utils/devices.claim_backend`` points at ``jax.profiler.TraceAnnotation``
at boot, so ``obs/`` imports no jax.  Whenever a device trace is running
(``obs/devtrace.capture`` or anyone else's) every open span is an event
in its host plane; with no hook a span costs what it did before.
``annotate(name)`` is the bare form for work off the owner thread.

Ran or waited: a span that reads its thread's clock (the root of every
tracer, and every span of a tracer made with ``usage=True``: the request
asked for its tree) and is finished on the thread that opened it carries
``off_cpu_ms`` (its wall time less the CPU time that thread was given
between open and finish: a wait for the interpreter lock, a lock, a
queue, a ``device_get``; NumPy's C loops and the kernel's work on a
first touch of fresh pages count as ON the CPU), ``minflt`` (that
thread's minor page faults inside the span) and ``tid`` (its native
id).  One finished on another thread has none of the three: no one
thread's clock covers it.  Each reading is one ``getrusage`` call, which
is why only the root takes it for a request that did not ask: where the
kernel is a sandbox's (the benchmark's machine) a system call costs
~6 µs, fifteen times what it does on Linux itself.

Tracing off must cost nothing: callers thread ``None`` (executors skip
span work on a ``None`` span) or ``NOOP_TRACER`` (handlers keep one
code path); both avoid allocation on the hot path.
"""

from __future__ import annotations

import contextlib
import os
import resource
import threading
import time
from typing import Callable, Optional

# (name, **kwargs) -> context manager; None until a process that owns a
# JAX backend installs jax.profiler.TraceAnnotation (set_annotation_hook)
_annotate: Optional[Callable] = None
_NULL_CTX = contextlib.nullcontext()


def set_annotation_hook(factory: Optional[Callable]) -> None:
    """Install (or with None remove) the annotation factory every span
    enters while it is open."""
    global _annotate
    _annotate = factory


def annotate(name: str):
    """A bare ``bydb:<name>`` annotation for work that is no span of its
    own: phases inside ``gather``, pad thunks on a worker thread."""
    if _annotate is None:
        return _NULL_CTX
    return _annotate("bydb:" + name)


def thread_usage() -> tuple[float, int]:
    """-> (CPU seconds, user + system; minor page faults) of the calling
    thread so far: one ``getrusage(RUSAGE_THREAD)``.  The kernel brings
    the CPU time up to date at its scheduler tick, so a difference of
    two readings is right to a tick (1 - 4 ms on Linux; 10 ms under the
    sandboxed kernel of the benchmark's machine, which also reports no
    page faults at all): unbiased over many spans, coarse for one, and
    never clamped: a span that ran through a tick reads an ``off_cpu_ms``
    below zero by up to that tick."""
    ru = resource.getrusage(resource.RUSAGE_THREAD)
    return ru.ru_utime + ru.ru_stime, ru.ru_minflt


class Span:
    """One timed node of the trace tree.  Not thread-safe: a span is
    owned by the thread that created it (worker-side timings are
    accumulated into plain tags by the owner, see measure_exec)."""

    __slots__ = (
        "name", "t0", "t1", "tags", "children", "error_msg", "trace_id",
        "_ann", "usage", "_tid", "_cpu0", "_flt0",
    )

    def __init__(self, name: str, trace_id: str = "", usage: bool = True):
        self.name = name
        self.trace_id = trace_id
        self.tags: dict = {}
        self.children: list = []  # Span | dict (attached subtree)
        self.error_msg: Optional[str] = None
        self.t1: Optional[float] = None
        self._ann = None
        if _annotate is not None:
            self._ann = _annotate("bydb:" + name, trace_id=trace_id)
            self._ann.__enter__()
        # usage: the children this span makes read their thread's clock
        # (ran or waited); _tid: this span itself has, on that thread
        self.usage = usage
        self._tid = threading.get_ident() if usage else None
        if usage:
            self._cpu0, self._flt0 = thread_usage()
        self.t0 = time.perf_counter()

    # -- building -----------------------------------------------------------
    def tag(self, key: str, value) -> "Span":
        self.tags[key] = value
        return self

    def error(self, msg: str) -> "Span":
        self.error_msg = str(msg)
        return self

    def child(self, name: str) -> "Span":
        s = Span(name, self.trace_id, self.usage)
        self.children.append(s)
        return s

    def attach(self, subtree: dict) -> None:
        """Graft a serialized span tree (a remote node's subtree); this
        (scatter) span is tagged with the request's trace_id so the
        graft is findable from either side."""
        if subtree:
            self.children.append(subtree)
            if self.trace_id:
                self.tags.setdefault("trace_id", self.trace_id)

    def finish(self) -> "Span":
        if self.t1 is None:
            # bdlint: disable=wp-shared-state -- a Span belongs to ONE
            # query's tracer (constructed per request, never shared
            # across requests); many roots run queries, but no two roots
            # ever hold the same Span instance
            self.t1 = time.perf_counter()
            if self._tid is not None and threading.get_ident() == self._tid:
                # ran or waited: this thread's own clock covers the span
                cpu, flt = thread_usage()
                # not clamped at 0: the CPU clock moves a whole tick at a
                # time, and only the unclamped difference is unbiased
                waited = (self.t1 - self.t0) - (cpu - self._cpu0)
                # bdlint: disable=wp-shared-state -- as t1 above: one
                # query's Span, finished by one thread
                self.tags.update(
                    off_cpu_ms=round(waited * 1000.0, 3),
                    minflt=flt - self._flt0,
                    tid=threading.current_thread().native_id,
                )
            if self._ann is not None:  # left once: t1 guards re-entry
                self._ann.__exit__(None, None, None)
        return self

    # spans double as context managers so executors can scope a leg
    # without holding a Tracer
    def __enter__(self) -> "Span":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc is not None and self.error_msg is None:
            self.error(f"{type(exc).__name__}: {exc}")
        self.finish()

    # -- reading ------------------------------------------------------------
    @property
    def duration_ms(self) -> float:
        end = self.t1 if self.t1 is not None else time.perf_counter()
        return (end - self.t0) * 1000.0

    def to_dict(self, root_t0: Optional[float] = None) -> dict:
        self.finish()
        if root_t0 is None:
            root_t0 = self.t0
        out = {
            "name": self.name,
            "start_ms": round((self.t0 - root_t0) * 1000.0, 3),
            "duration_ms": round(self.duration_ms, 3),
            "tags": dict(self.tags),
            "children": [
                c.to_dict(root_t0) if isinstance(c, Span) else c
                for c in self.children
            ],
        }
        if self.error_msg is not None:
            out["error"] = self.error_msg
        return out


class _SpanCtx:
    """Context manager pushing/popping one span on a tracer's stack."""

    __slots__ = ("_tracer", "_span")

    def __init__(self, tracer: "Tracer", span: Span):
        self._tracer = tracer
        self._span = span

    def __enter__(self) -> Span:
        return self._span

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc is not None and self._span.error_msg is None:
            self._span.error(f"{type(exc).__name__}: {exc}")
        self._span.finish()
        self._tracer._stack.pop()


class Tracer:
    """Span-tree builder for one query.  Single-owner (the query's
    request thread); remote subtrees arrive serialized via attach."""

    __slots__ = ("root", "_stack", "start_unix_ms")

    def __init__(self, name: str, usage: bool = False):
        """``usage``: the request asked for its tree, so every span says
        whether its thread ran or waited; the root says so always (two
        ``getrusage`` calls a request: what a slow query's tree in the
        recorder has to go on)."""
        self.start_unix_ms = time.time() * 1000.0
        self.root = Span(name, os.urandom(8).hex())
        self.root.usage = usage  # of the children; the root itself has read
        self._stack: list[Span] = [self.root]

    def current(self) -> Span:
        return self._stack[-1]

    def span(self, name: str) -> _SpanCtx:
        s = self._stack[-1].child(name)
        self._stack.append(s)
        return _SpanCtx(self, s)

    def finish(self) -> dict:
        """Close the root and return the serialized tree."""
        out = self.root.to_dict()
        out["start_unix_ms"] = round(self.start_unix_ms, 3)
        out["trace_id"] = self.root.trace_id
        return out


class _NoopSpan:
    """Absorbs the whole Span surface at near-zero cost."""

    __slots__ = ()
    usage = False  # reads no clock, nor do the executors under it

    def tag(self, key, value):
        return self

    def error(self, msg):
        return self

    def child(self, name):
        return self

    def attach(self, subtree):
        pass

    def finish(self):
        return self

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        pass

    def to_dict(self) -> dict:
        return {}


class _NoopTracer:
    __slots__ = ()

    root = _NoopSpan()

    def current(self):
        return NOOP_SPAN

    def span(self, name):
        return NOOP_SPAN

    def finish(self) -> dict:
        return {}


NOOP_SPAN = _NoopSpan()
NOOP_TRACER = _NoopTracer()


def attach_tree(res, req, tree: dict):
    """Attach a finished span tree to a QueryResult when the request
    asked for in-band tracing (`res.trace["span_tree"]`) — the one
    response-side attach, shared by every serving surface."""
    if getattr(req, "trace", False):
        res.trace = dict(res.trace or {})
        res.trace["span_tree"] = tree
    return res


def find_span(tree: Optional[dict], name: str) -> Optional[dict]:
    """Depth-first lookup by span name in a serialized tree (tests,
    smoke scripts, slowlog consumers)."""
    if not tree:
        return None
    if tree.get("name") == name:
        return tree
    for c in tree.get("children", ()):
        hit = find_span(c, name)
        if hit is not None:
            return hit
    return None


def iter_spans(tree: Optional[dict]):
    """Yield every span dict of a serialized tree, depth-first."""
    if not tree:
        return
    yield tree
    for c in tree.get("children", ()):
        yield from iter_spans(c)
