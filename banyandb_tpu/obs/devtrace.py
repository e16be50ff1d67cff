"""A device trace the server takes of itself.

``capture(seconds)`` runs ``jax.profiler`` (Python's call tracer off)
for ``seconds``, reduces the ``.xplane.pb`` it leaves, deletes it and
returns JSON-safe numbers: window / busy / idle seconds per device,
device seconds by program (the ``XLA Modules`` line: ``jit_bydb_fused_plan``)
and by innermost ``bydb.`` scope (each ``XLA Ops`` event's op name holds
the ``jax.named_scope`` path; ``(unscoped)`` for the rest), and the idle
gaps split by overlap across the ``bydb:`` host annotations every open
span holds (obs/tracer) — the innermost open annotation wins,
``between queries`` when none is open.

Reached through the bus topic ``devtrace`` and ``GET /debug/device``
(admin/profiling); one capture at a time.  ``reduce_events`` is a pure
function over plain event dicts (tests hold it to a hand-computed case
and a fixture cut from a chip trace); ``load_xplane`` reads the file with
protobuf alone, because jax's own reader leaves out the per-operation
metadata the op names live in.  No jax import outside ``capture``.
"""

from __future__ import annotations

import glob
import os
import shutil
import tempfile
import threading
import time

MAX_SECONDS = 30.0
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
UNSCOPED, BETWEEN = "(unscoped)", "between queries"
MIN_GAP_NS = 100_000  # shorter gaps are the device's own pauses between ops

_capture_lock = threading.Lock()
_xspace_cls = None


class CaptureBusy(RuntimeError):
    """Another capture is running; a profiler session is process-wide."""


def capture(seconds: float) -> dict:
    seconds = min(max(float(seconds), 0.05), MAX_SECONDS)
    if not _capture_lock.acquire(blocking=False):
        raise CaptureBusy("a device trace is already being captured")
    try:
        import jax

        out_dir = tempfile.mkdtemp(prefix="bydb-devtrace-")
        try:
            options = jax.profiler.ProfileOptions()
            # device planes and XLA's host events only: Python's call
            # tracer slows the host it measures
            options.python_tracer_level = 0
            jax.profiler.start_trace(out_dir, profiler_options=options)
            t0 = time.perf_counter()
            try:
                time.sleep(seconds)
            finally:
                window_ns = int((time.perf_counter() - t0) * 1e9)
                jax.profiler.stop_trace()
            paths = glob.glob(
                os.path.join(out_dir, "**", "*.xplane.pb"), recursive=True
            )
            if not paths:
                raise RuntimeError("the profiler left no .xplane.pb")
            events = load_xplane(paths[0])
        finally:
            shutil.rmtree(out_dir, ignore_errors=True)
        return reduce_events(events, window_ns)
    finally:
        _capture_lock.release()


def _xspace():
    """tsl's xplane.proto (the fields read here) as a protobuf class."""
    global _xspace_cls
    if _xspace_cls is not None:
        return _xspace_cls
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="bydb_xplane.proto", package="bydb.xplane", syntax="proto3"
    )
    I, S, U = F.TYPE_INT64, F.TYPE_STRING, F.TYPE_UINT64
    schema = {  # message -> (field, number, scalar type or message, repeated)
        "XStat": [("metadata_id", 1, I, 0), ("str_value", 5, S, 0), ("ref_value", 7, U, 0)],
        "XEvent": [("metadata_id", 1, I, 0), ("offset_ps", 2, I, 0), ("duration_ps", 3, I, 0)],
        "XLine": [("name", 2, S, 0), ("timestamp_ns", 3, I, 0), ("events", 4, "XEvent", 1)],
        "XEventMetadata": [("name", 2, S, 0), ("stats", 5, "XStat", 1)],
        "XStatMetadata": [("name", 2, S, 0)],
        "EventEntry": [("key", 1, I, 0), ("value", 2, "XEventMetadata", 0)],
        "StatEntry": [("key", 1, I, 0), ("value", 2, "XStatMetadata", 0)],
        "XPlane": [("name", 2, S, 0), ("lines", 3, "XLine", 1),
                   ("event_metadata", 4, "EventEntry", 1), ("stat_metadata", 5, "StatEntry", 1)],
        "XSpace": [("planes", 1, "XPlane", 1)],
    }
    for name, fields in schema.items():
        msg = fd.message_type.add(name=name)
        for fname, number, typ, repeated in fields:
            f = msg.field.add(
                name=fname, number=number,
                label=F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL,
            )
            if isinstance(typ, str):
                f.type, f.type_name = F.TYPE_MESSAGE, f".bydb.xplane.{typ}"
            else:
                f.type = typ
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    _xspace_cls = message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bydb.xplane.XSpace")
    )
    return _xspace_cls


def load_xplane(path: str) -> dict:
    """-> {"ops", "modules", "host"}: device operations (with `op_name`,
    the named-scope path), device programs and the host's `bydb:`
    annotations as plain dicts; times in ns from the start of the trace."""
    space = _xspace()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out: dict = {"ops": [], "modules": [], "host": []}
    for plane in space.planes:
        device = plane.name.startswith("/device:")
        if not device and not plane.name.startswith("/host:"):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {}
        for e in plane.event_metadata:
            op_name = ""
            for st in e.value.stats:
                if stat_names.get(st.metadata_id) == "tf_op":
                    op_name = st.str_value or stat_names.get(st.ref_value, "")
            meta[e.key] = (e.value.name, op_name)
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                name, op_name = meta.get(ev.metadata_id, ("", ""))
                if not device and not name.startswith("bydb:"):
                    continue
                rec = {
                    "name": name,
                    "start_ns": line.timestamp_ns + ev.offset_ps // 1000,
                    "dur_ns": ev.duration_ps // 1000,
                }
                if not device:
                    out["host"].append(rec)
                    continue
                rec["device"] = plane.name
                if line.name == OPS_LINE:
                    rec["op_name"] = op_name
                    out["ops"].append(rec)
                else:
                    out["modules"].append(rec)
    return out


def _scope(op_name: str) -> str:
    """`jit(f)/bydb.fused_scan/while/body/bydb.filter/and:` -> `bydb.filter`."""
    for part in reversed(op_name.split("/")):
        if part.startswith("bydb."):
            return part.rstrip(":")
    return UNSCOPED


def _self_ns(ops: list[dict]) -> list[int]:
    """Each operation's time less the operations it contains (a `while`
    holds its body's); `ops` sorted by (start, longest first)."""
    out = [ev["dur_ns"] for ev in ops]
    open_: list[int] = []
    for i, ev in enumerate(ops):
        while open_ and _end(ops[open_[-1]]) <= ev["start_ns"]:
            open_.pop()
        if open_:
            out[open_[-1]] -= ev["dur_ns"]
        open_.append(i)
    return [max(x, 0) for x in out]


def _end(ev: dict) -> int:
    return ev["start_ns"] + ev["dur_ns"]


def _union(events: list[dict]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted((ev["start_ns"], _end(ev)) for ev in events):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _innermost(host: list[dict]) -> list[tuple[int, int, str]]:
    """The host annotations flattened to disjoint (start, end, name): where
    several are open the one opened last wins."""
    edges = sorted({t for ev in host for t in (ev["start_ns"], _end(ev))})
    pending = sorted(host, key=lambda ev: ev["start_ns"])
    active: list[dict] = []
    out, k = [], 0
    for a, b in zip(edges, edges[1:]):
        while k < len(pending) and pending[k]["start_ns"] <= a:
            active.append(pending[k])
            k += 1
        active = [ev for ev in active if _end(ev) > a]
        if active:
            top = max(active, key=lambda ev: (ev["start_ns"], -ev["dur_ns"]))
            out.append((a, b, top["name"].split("#")[0].removeprefix("bydb:")))
    return out


def reduce_events(events: dict, window_ns: int) -> dict:
    """The reduction, pure: see the module docstring."""
    by_dev: dict[str, list[dict]] = {}
    for ev in events["ops"]:
        by_dev.setdefault(ev["device"], []).append(ev)
    last = max((_end(ev) for evs in events.values() for ev in evs), default=0)
    window_ns = max(int(window_ns), last)
    segments = _innermost(events["host"])
    devices, by_scope, idle_by_span = {}, {}, {}
    for dev, ops in sorted(by_dev.items()):
        ops.sort(key=lambda ev: (ev["start_ns"], -ev["dur_ns"]))
        for ev, self_ns in zip(ops, _self_ns(ops)):
            scope = _scope(ev.get("op_name", ""))
            by_scope[scope] = by_scope.get(scope, 0) + self_ns
        busy = _union(ops)
        busy_ns = sum(e - s for s, e in busy)
        devices[dev] = {
            "busy_s": busy_ns / 1e9, "idle_s": (window_ns - busy_ns) / 1e9,
        }
        edges = [0] + [t for iv in busy for t in iv] + [window_ns]
        k = 0
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge - gs < MIN_GAP_NS:
                continue
            covered = 0
            while k < len(segments) and segments[k][1] <= gs:
                k += 1
            j = k
            while j < len(segments) and segments[j][0] < ge:
                a, b, name = segments[j]
                part = min(b, ge) - max(a, gs)
                idle_by_span[name] = idle_by_span.get(name, 0) + part
                covered += part
                j += 1
            rest = ge - gs - covered
            if rest:
                idle_by_span[BETWEEN] = idle_by_span.get(BETWEEN, 0) + rest
    by_program: dict[str, int] = {}
    for ev in events["modules"]:
        name = ev["name"].split("(")[0]
        by_program[name] = by_program.get(name, 0) + ev["dur_ns"]
    scoped_ns = sum(by_scope.values())

    def seconds(d: dict) -> dict:
        return {k: v / 1e9 for k, v in sorted(d.items(), key=lambda kv: -kv[1])}

    return {
        "window_s": window_ns / 1e9,
        "devices": devices,
        "by_program_s": seconds(by_program),
        "by_scope_s": seconds(by_scope),
        "unscoped_share": (
            by_scope.get(UNSCOPED, 0) / scoped_ns if scoped_ns else 0.0
        ),
        "idle_by_span_s": seconds(idle_by_span),
        "host_annotations": len(events["host"]),
    }
