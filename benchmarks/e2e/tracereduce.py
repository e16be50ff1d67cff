"""tracereduce.py - from a profiler trace to device busy time, idle share,
the longest device operations and the idle gaps by what the host did.

The reduction works on plain event dicts
    {"device": plane name, "name": str, "start_ns": int, "dur_ns": int}
so selfcheck.py can hold it to hand-computed values on a recorded
fixture; `load_xplane` is the only part that needs jax (its profiler's
reader, no backend).  Run as a script it prints a trace's reduction, or
cuts a fixture:  python tracereduce.py <file.xplane.pb> [--fixture N]
"""

from __future__ import annotations

import bisect
import json
import re
import sys

OPS_LINE = "XLA Ops"  # the per-op line of a /device:TPU:n plane
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


def load_xplane(path: str) -> list[dict]:
    """Every device operation of every /device: plane that has an
    `XLA Ops` line; times in ns from the start of the trace."""
    from jax.profiler import ProfileData

    events = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                events.append({
                    "device": plane.name,
                    "name": ev.name,
                    "start_ns": int(ev.start_ns),
                    "dur_ns": int(ev.duration_ns),
                })
    return events


def busy_intervals(events: list[dict]) -> list[tuple[int, int]]:
    """Union of [start, end) of the events, as sorted disjoint intervals."""
    out: list[list[int]] = []
    for s, e in sorted((ev["start_ns"], ev["start_ns"] + ev["dur_ns"]) for ev in events):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def short_name(name: str) -> str:
    """`%while.29 = (...) while(...)` -> `%while.29 while`: the name the
    trace prints and the opcode, without the shapes."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:120]
    m = _OPCODE.search(" " + rest)
    return f"{head} {m.group(1)}"[:120] if m else head[:120]


def reduce_events(events: list[dict], window_ns: int) -> dict:
    """-> busy_s (mean over the devices seen), idle_share in [0, 1],
    the ten operations with the most summed time (a `while` contains its
    body's operations, as the trace prints them), and per device the
    idle gaps as (start_ns, end_ns)."""
    by_dev: dict[str, list[dict]] = {}
    for ev in events:
        by_dev.setdefault(ev["device"], []).append(ev)
    busy_ns, gaps = [], {}
    for dev, evs in sorted(by_dev.items()):
        iv = busy_intervals(evs)
        busy_ns.append(sum(e - s for s, e in iv))
        edges = [0] + [x for s, e in iv for x in (s, e)] + [window_ns]
        gaps[dev] = [
            (edges[i], edges[i + 1])
            for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]
        ]
    busy_s = sum(busy_ns) / len(busy_ns) / 1e9 if busy_ns else 0.0
    totals: dict[str, int] = {}
    for ev in events:
        key = short_name(ev["name"])
        totals[key] = totals.get(key, 0) + ev["dur_ns"]
    n = max(len(by_dev), 1)
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / (window_ns / 1e9) if window_ns else None,
        "device_ops": [[k, v / n / 1e9] for k, v in top],
        "gaps": gaps,
        "devices": len(by_dev),
    }


def span_timeline(tree: dict, start_s: float) -> list[tuple[float, float, str]]:
    """An estimate of when each span of a query's tree ran: the tree
    holds durations only, so children are laid end to end from their
    parent's start, and a parent's own time falls after its last child.
    -> [(start_s, end_s, name)] leaves first, so the first hit is the
    deepest span."""
    out: list[tuple[float, float, str]] = []

    def walk(node: dict, t: float) -> None:
        cursor = t
        for c in node.get("children") or ():
            walk(c, cursor)
            cursor += float(c.get("duration_ms") or 0.0) / 1000.0
        out.append((t, t + float(node.get("duration_ms") or 0.0) / 1000.0, node["name"]))

    walk(tree, start_s)
    return out


def attribute_gaps(
    gaps: list[tuple[int, int]], trace_t0_s: float, timelines: list[list],
    min_gap_ns: int = 100_000, look_back: int = 64,
) -> list[list]:
    """Sum the idle gaps by the host span open at the gap's middle
    (`between queries` when none was) -> ten [name, seconds], longest
    first.  `timelines` are span_timeline() lists on the clock of
    `trace_t0_s`, the host time of the trace's zero.  Only the
    `look_back` queries that started last before a gap are looked at:
    a closed loop holds no more open than it has clients."""
    timelines = sorted(timelines, key=lambda tl: tl[-1][0])  # the root is last
    starts = [tl[-1][0] for tl in timelines]
    totals: dict[str, float] = {}
    for s, e in gaps:
        if e - s < min_gap_ns:
            continue
        mid = trace_t0_s + (s + e) / 2e9
        name = "between queries"
        k = bisect.bisect_right(starts, mid)
        for tl in reversed(timelines[max(k - look_back, 0):k]):
            hit = next((n for a, b, n in tl if a <= mid < b), None)
            if hit is not None:
                name = hit
                break
        totals[name] = totals.get(name, 0.0) + (e - s) / 1e9
    return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])[:10]]


def _main(argv: list[str]) -> int:
    events = load_xplane(argv[0])
    if "--fixture" in argv:
        n = int(argv[argv.index("--fixture") + 1])
        cut = sorted(events, key=lambda ev: ev["start_ns"])[:n]
        base = cut[0]["start_ns"]
        for ev in cut:
            ev["start_ns"] -= base
            ev["name"] = ev["name"][:160]
        json.dump(cut, sys.stdout, indent=0)
        return 0
    end = max(ev["start_ns"] + ev["dur_ns"] for ev in events)
    red = reduce_events(events, end)
    red.pop("gaps")
    print(json.dumps(red, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(_main(sys.argv[1:]))
