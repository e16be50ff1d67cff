"""readers.py - the reader kinds a per-layer metric file may name.

A metric file (metrics/<name>.json) holds `name`, `unit`, `better`,
`layer`, `moves`, `source` (as BENCHMARK.json has it), optionally
`cells`, and a `reader`: {"kind": one of READERS, ...its parameters}.
A reader takes the run's record and its own parameters and returns a
number, or None when there is nothing to read (the metric is then left
out of the line).  A later PR that wants another span or counter read
adds a metric file, not code.

The record a reader sees:
    queries   the window's answered queries, each with latency_ms,
              served and, in a traced run, root_ms and tree (span tree)
    setup     the set-up's own readings (load rate, first warm-up query)
    prom      {"before": {...}, "after": {...}} unlabeled /metrics samples
    xplane    the trace reduction, or None
Per-query readers give the median over the queries that have the span.
"""

from __future__ import annotations

import statistics


def iter_spans(tree: dict | None):
    """Every span dict of a serialized tree, depth-first."""
    if not tree:
        return
    yield tree
    for c in tree.get("children") or ():
        yield from iter_spans(c)


def _median(values: list[float]):
    return statistics.median(values) if values else None


def _queries(rec: dict, p: dict) -> list[dict]:
    """Answered queries, optionally only those the reply marks `served`
    as the reader's `where_served`."""
    want = p.get("where_served")
    return [q for q in rec["queries"] if want is None or q.get("served") == want]


def span_self_ms(rec: dict, p: dict):
    """A span's duration less what its children cover, summed over the
    spans of that name in one query."""
    per_query = []
    for q in _queries(rec, p):
        hits = [s for s in iter_spans(q.get("tree")) if s.get("name") == p["span"]]
        if hits:
            per_query.append(sum(
                float(s.get("duration_ms") or 0.0)
                - sum(float(c.get("duration_ms") or 0.0) for c in s.get("children") or ())
                for s in hits
            ))
    return _median(per_query)


def span_tag(rec: dict, p: dict):
    """A numeric tag summed over one query's spans of that name, times
    `scale` (default 1)."""
    per_query = []
    for q in _queries(rec, p):
        vals = [
            float(s["tags"][p["tag"]])
            for s in iter_spans(q.get("tree"))
            if s.get("name") == p["span"]
            and isinstance((s.get("tags") or {}).get(p["tag"]), (int, float))
        ]
        if vals:
            per_query.append(sum(vals) * float(p.get("scale", 1.0)))
    return _median(per_query)


def reply_share(rec: dict, p: dict):
    """Percent of the answered queries whose reply field has the value."""
    qs = rec["queries"]
    if not qs:
        return None
    return 100.0 * sum(1 for q in qs if q.get(p["field"]) == p["value"]) / len(qs)


def prom_delta(rec: dict, p: dict):
    """An unlabeled /metrics sample, after the window less before it."""
    before, after = rec["prom"]["before"], rec["prom"]["after"]
    if p["metric"] not in after:
        return None
    return float(after[p["metric"]]) - float(before.get(p["metric"], 0.0))


def client(rec: dict, p: dict):
    """`wire_ms`: client latency less the server's root span."""
    if p["what"] != "wire_ms":
        raise ValueError(f"client reader: no reading {p['what']!r}")
    return _median([
        q["latency_ms"] - q["root_ms"]
        for q in _queries(rec, p)
        if q.get("root_ms") is not None
    ])


def setup(rec: dict, p: dict):
    return rec["setup"].get(p["key"])


def xplane(rec: dict, p: dict):
    x = rec.get("xplane")
    if not x:
        return None
    if p["what"] == "idle_share":
        return 100.0 * x["idle_share"]
    if p["what"] == "busy_ms_per_query":
        n = x["queries_finished"]
        return x["busy_s"] * 1000.0 / n if n else None
    raise ValueError(f"xplane reader: no reading {p['what']!r}")


READERS = {
    f.__name__: f
    for f in (span_self_ms, span_tag, reply_share, prom_delta, client, setup, xplane)
}


def read(metric: dict, rec: dict):
    p = metric["reader"]
    if p["kind"] not in READERS:
        raise ValueError(f"metric {metric['name']}: no reader kind {p['kind']!r}")
    return READERS[p["kind"]](rec, p)
